"""The reference in the program's place, at a chosen precision.

``RefProgram(dep, cfg, np.float32)`` is the control: the comparison in
:mod:`benchmarks.chip.check` has to find it not correct, because the
configuration states float64 criteria.  It offers the same ``session()``
surface as :class:`benchmarks.chip.program.Program`.
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

if __name__ == "__main__":
    # run as a script: import the benchmark as a package of the checkout
    sys.path[0:1] = [str(Path(__file__).resolve().parents[2])]

import numpy as np  # noqa: E402

from benchmarks.chip import reference  # noqa: E402
from benchmarks.chip.reference import RefState  # noqa: E402


class RefSession:
    def __init__(self, st: RefState, cfg: dict):
        self.st = st
        self.cfg = cfg

    def plan(self, budget):
        t0 = time.perf_counter()
        moves = reference.plan(self.st, budget, **self.cfg)
        return time.perf_counter() - t0, moves

    def grow(self, pool_id: int, user_bytes: float) -> None:
        self.st.grow(pool_id, user_bytes)

    def set_out(self, osd: int, out: bool) -> None:
        self.st.set_out(osd, out)

    def apply(self, row: int, slot: int, src: int, dst: int) -> None:
        self.st.apply(row, slot, src, dst)


class RefProgram:
    def __init__(self, dep, planner_cfg: dict, dtype=np.float32):
        self.base = RefState(dep, dtype)
        self.cfg = planner_cfg

    def session(self) -> RefSession:
        return RefSession(self.base.copy(), self.cfg)


def main(argv=None) -> int:
    """Run the control through a cell's whole harness, on several seeds:
    ``python3 benchmarks/chip/control.py --workload b-converge --seconds 5
    --seeds 1 2 3``.  Prints each run's checks; every run has to read
    not correct."""
    import argparse
    import json

    from benchmarks.chip import harness

    p = argparse.ArgumentParser(description=main.__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    bench = harness.load_benchmark()
    caught = 0
    for seed in args.seeds:
        out = harness.run_cell(
            bench, args.workload, seed, args.seconds, False,
            time.perf_counter(),
            subject=lambda d, c: RefProgram(d, c, np.float32),
            log=lambda d: None)
        caught += not out["correct"]
        print(json.dumps({"control": args.workload, "seed": seed,
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "checks": out["checks"]}), flush=True)
    return 0 if caught == len(args.seeds) else 1


if __name__ == "__main__":
    sys.exit(main())
