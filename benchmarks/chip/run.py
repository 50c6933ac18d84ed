"""Run one cell of the benchmark once, on the chips of this machine.

    python3 benchmarks/chip/run.py --workload b-converge --seed 7 \\
        --seconds 30 --trace 0

The cell, its configuration, traffic mix and metrics are named in
``BENCHMARK.json`` at the root of the checkout.  The run exits non-zero,
with no result, when JAX finds no TPU or fewer chips than the cell asks
for.  It keeps JAX's compilation cache in ``$JAX_COMPILATION_CACHE_DIR``,
or else in ``.cache/jax`` inside the checkout, builds the seeded cluster,
warms up the cell's own programs, measures for ``--seconds``, checks the
window's plans against the plain reference, and prints one JSON object
as its last line (earlier lines: set-up, window and check details; the
numbers compared, beside their limits, last on standard error).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
# import the benchmark as a package from the checkout's root; the
# script's own directory is dropped so its modules shadow nothing
sys.path[0:1] = [str(ROOT)]


def _args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def _chips_or_exit(need: int) -> None:
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        sys.exit(f"no accelerator: {e}")
    if devs[0].platform != "tpu":
        sys.exit(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < need:
        sys.exit(f"the cell needs {need} chips, JAX finds {len(devs)}")


def _compile_cache() -> None:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(ROOT / ".cache" / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    # every program, however quick to compile, comes from the cache in
    # a later run, so no run but a checkout's first compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv=None) -> int:
    args = _args(argv)
    from benchmarks.chip import harness
    bench = harness.load_benchmark()
    cell = harness.find(bench["workloads"], args.workload)
    _chips_or_exit(cell["chips"])
    _compile_cache()
    out = harness.run_cell(bench, args.workload, args.seed, args.seconds,
                           bool(args.trace), T_START)
    harness.print_checks(out)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
