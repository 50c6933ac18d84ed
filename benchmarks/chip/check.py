"""Whether what the timed path produced is correct.

Every plan is compared move for move, ``(pg, slot, src, dst)``, with the
plain reference (:mod:`benchmarks.chip.reference`) replaying the same
inputs, once the window has closed:

* cold plans (``session: fresh``) all start from the seeded cluster, so
  the reference plans it once and every plan of the window is compared
  with that sequence;
* warm requests: every move of every request is checked for legality on
  the benchmark's own model of the cluster as it is applied, and a seeded
  sample of the requests, with the slowest and every outage tick in it,
  is compared with the reference planned from the model's state before
  that request (replayed from the state when the window opened).

``moves_off`` counts the positions at which a compared plan differs from
the reference's (a missing or extra move counts once); ``illegal_moves``
the moves of warm requests that break a rule on the model;
``refused_moves`` the re-placements of an outage that the subject refused
because its own state no longer held the shard where its answers had put
it.  The limit of each number is 0: the configuration states the
faithful plan exactly.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.chip import reference
from benchmarks.chip.reference import RefState

LIMITS = {"moves_off": 0, "illegal_moves": 0, "refused_moves": 0}


def moves_off(got: list, want: list) -> int:
    n = max(len(got), len(want))
    return sum(1 for i in range(n)
               if i >= len(got) or i >= len(want) or got[i] != want[i])


def first_divergence(got: list, want: list) -> dict:
    for i in range(max(len(got), len(want))):
        a = got[i] if i < len(got) else None
        b = want[i] if i < len(want) else None
        if a != b:
            return {"at": i, "got": a, "want": b, "got_len": len(got),
                    "want_len": len(want)}
    return {}


def _sample(reqs, seed: int, params: dict) -> list[int]:
    rng = np.random.default_rng((seed, 0xC4EC))
    n = len(reqs)
    chosen = set(rng.choice(n, size=min(params["sample"], n),
                            replace=False).tolist()) if n else set()
    by_time = sorted(range(n), key=lambda i: -reqs[i].seconds)
    chosen |= set(by_time[:params["slowest"]])
    chosen |= {i for i, r in enumerate(reqs)
               if any(op[0] in ("out", "in") for op in r.ops)}
    return sorted(chosen)


def _replay_ops(st: RefState, ops) -> None:
    for op in ops:
        if op[0] == "grow":
            st.grow(op[1], op[2])
        elif op[0] == "out":
            st.set_out(op[1], True)
            for mv in op[2]:
                st.apply(*mv)
        else:
            st.set_out(op[1], False)


def _apply_legal(st: RefState, moves) -> None:
    for row, slot, src, dst in moves:
        if st.acting[row, slot] == src and st.is_legal(row, slot, dst):
            st.apply(row, slot, src, dst)


def check(workload, reqs, planner_cfg: dict, seed: int) -> dict:
    """Returns {"checks": {name: (value, limit)}, "failed", "compared",
    "reference_s", "divergence"}."""
    t0 = time.perf_counter()
    budget = workload.budget
    failed_idx: set = set()
    off_total = 0
    divergence = None
    compared = 0
    if not workload.warm:
        want = reference.plan(RefState(workload.dep), budget, **planner_cfg)
        for r in reqs:
            off = moves_off(r.moves, want)
            compared += 1
            if off:
                failed_idx.add(r.index)
                divergence = divergence or {
                    "request": r.index, **first_divergence(r.moves, want)}
            off_total += off
        checks = {"moves_off": off_total}
    else:
        sample = set(_sample(reqs, seed, workload.t["check"]))
        st = workload.start_mirror.copy()
        for r in reqs:
            _replay_ops(st, r.ops)
            if r.index in sample:
                want = reference.plan(st.copy(), budget, **planner_cfg)
                off = moves_off(r.moves, want)
                compared += 1
                if off:
                    failed_idx.add(r.index)
                    divergence = divergence or {
                        "request": r.index,
                        **first_divergence(r.moves, want)}
                off_total += off
            _apply_legal(st, r.moves)
            if r.illegal:
                failed_idx.add(r.index)
        illegal = workload.setup_illegal + sum(r.illegal for r in reqs)
        checks = {"moves_off": off_total, "illegal_moves": illegal,
                  "refused_moves": workload.refused}
    return {"checks": {k: (v, LIMITS[k]) for k, v in checks.items()},
            "failed": len(failed_idx), "compared": compared,
            "reference_s": time.perf_counter() - t0,
            "divergence": divergence}


def correct(result: dict) -> bool:
    return all(v <= lim for v, lim in result["checks"].values())
