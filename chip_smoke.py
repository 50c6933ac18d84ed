#!/usr/bin/env python3
"""Run the planner's main path once on a TPU and check it against the host.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded engine on a 4-chip mesh

One chip, through the public entry points:

1. ``b1``: cluster B (``cluster_b()``, 995 OSDs / 8,731 PGs) planned to
   convergence by ``create_planner("equilibrium_batch")`` on the chip.  A
   first plan on a copy of the cluster compiles; the timed plan on a
   fresh planner then reuses the compiled program.  The move sequence
   must equal the host ``equilibrium`` engine's, move for move.
2. ``churn-heavy``: the lifecycle scenario run with ``equilibrium_batch``
   and with ``equilibrium``; the metrics JSON must be byte-identical and
   the batch engine must build its device carry exactly once.
3. ``fleet``: one ``FleetService`` tick over clusters A, C and F must
   equal serial ``equilibrium_batch`` plans of each cluster.

``--chips 4`` runs only the sharded phase: ``equilibrium_batch_sharded``
with ``n_shards=4`` on ``cluster_b(scale=8)`` (7,960 OSDs / 69,848 PGs),
64 moves, against the one-chip ``equilibrium_batch`` on the same cluster.

Each phase prints one line; a failed check raises.  The last line is
``{"ok": true, "device": {...}}``.  Without a TPU the script exits 2 and
prints no result.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))


def _moves(result) -> list[tuple]:
    return [(m.pg, m.slot, m.src_osd, m.dst_osd) for m in result.moves]


def _emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


class _CompileClock:
    """Sums the seconds JAX spends obtaining executables while active:
    backend compiles plus persistent-cache reads."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.active = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if self.active and event in self.EVENTS:
            self.seconds += duration


def _criteria(state, move) -> dict:
    """Host (IEEE float64) criterion values of one move on ``state``."""
    import numpy as np
    from repro.core import legality
    util = state.utilization()
    used = state.used()
    cap = state.capacity_vector()
    pg, slot, src, dst = move
    s, d = state.idx(src), state.idx(dst)
    size = state.shard_sizes[pg]
    n = float(state.n_devices)
    us, usq = float(util.sum()), float((util ** 2).sum())
    v_s = (used[s] - size) / cap[s]
    v_d = (used[d] + size) / cap[d]
    dsum = (v_s - util[s]) + (v_d - util[d])
    dsq = (v_s ** 2 - util[s] ** 2) + (v_d ** 2 - util[d] ** 2)
    dvar = ((usq + dsq) / n - ((us + dsum) / n) ** 2
            - legality.variance_from_moments(us, usq, n))
    return {"move": [list(pg), slot, src, dst], "size": size,
            "util_src": float(util[s]), "util_dst": float(util[d]),
            "variance_delta": float(dvar),
            "legal": bool(state.move_is_legal(pg, slot, dst)),
            "equal_util_devices": int(np.sum(util == util[d]))}


def _check_same(name: str, got: list, want: list, start_state) -> None:
    """Raise with the first diverging move and both moves' criteria."""
    if got == want:
        return
    i = next((j for j, (a, b) in enumerate(zip(got, want)) if a != b),
             min(len(got), len(want)))
    from repro.core.cluster import Movement
    st = start_state.copy()
    for pg, slot, src, dst in want[:i]:
        st.apply(Movement(pg, slot, src, dst, st.shard_sizes[pg]))
    detail = {"first_diverging_move": i, "device_moves": len(got),
              "host_moves": len(want)}
    if i < len(got):
        detail["device"] = _criteria(st, got[i])
    if i < len(want):
        detail["host"] = _criteria(st, want[i])
    raise AssertionError(f"{name}: device plan diverges from the host "
                         f"plan: {json.dumps(detail)}")


def phase_b1(clock) -> None:
    from repro.core.clustergen import cluster_b
    from repro.core.planner import create_planner

    t0 = time.perf_counter()
    state = cluster_b()
    build_s = time.perf_counter() - t0
    start = state.copy()

    # first plan on a copy: compiles every program the plan needs
    clock.active = True
    t0 = time.perf_counter()
    warm = create_planner("equilibrium_batch").plan(start.copy())
    warm_s = time.perf_counter() - t0
    clock.active = False

    planner = create_planner("equilibrium_batch")
    t0 = time.perf_counter()
    res = planner.plan(state)   # every chunk ends in a device_get
    plan_s = time.perf_counter() - t0
    got = _moves(res)

    t0 = time.perf_counter()
    host = create_planner("equilibrium").plan(start.copy())
    host_s = time.perf_counter() - t0
    want = _moves(host)
    _check_same("b1", got, want, start)
    assert _moves(warm) == got, "b1: two device plans of one cluster differ"
    assert len(got) > 0, "b1: no moves planned"
    state.check_valid()
    _emit("b1", osds=state.n_devices, pgs=len(state.acting), moves=len(got),
          identical=True, backend=planner._impl.select_backend,
          build_s=build_s, compile_s=clock.seconds, first_plan_s=warm_s,
          plan_s=plan_s, host_plan_s=host_s,
          jit_recompiles=res.stats["jit_recompiles"],
          host_syncs=res.stats["host_syncs"],
          rebuilds=res.stats["rebuilds"])


def phase_churn() -> None:
    from repro.core.equilibrium_batch import dense_rebuild_count
    from repro.sim.scenarios import run_scenario

    t0 = time.perf_counter()
    r0 = dense_rebuild_count()
    dev = run_scenario("churn-heavy", "equilibrium_batch", seed=0)
    rebuilds = dense_rebuild_count() - r0
    dev_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    host = run_scenario("churn-heavy", "equilibrium", seed=0)
    host_s = time.perf_counter() - t0
    a = json.dumps(dev["metrics"], sort_keys=True)
    b = json.dumps(host["metrics"], sort_keys=True)
    assert a == b, "churn-heavy: metrics differ between equilibrium_batch " \
                   "and equilibrium"
    assert rebuilds == 1, f"churn-heavy: batch.rebuilds == {rebuilds}, not 1"
    _emit("churn-heavy", ticks=dev["ticks"],
          planned_moves=dev["metrics"]["planned_moves"][-1],
          identical=True, rebuilds=rebuilds, device_s=dev_s, host_s=host_s)


def phase_fleet() -> None:
    from repro.core.clustergen import cluster_a, cluster_c, cluster_f
    from repro.core.planner import create_planner
    from repro.fleet import FleetService

    made = {"A": cluster_a(), "C": cluster_c(), "F": cluster_f()}
    svc = FleetService()
    twins = {}
    for key, st in made.items():
        twins[key] = st.copy()
        svc.attach(key, st)
    t0 = time.perf_counter()
    tick = svc.tick()
    tick_s = time.perf_counter() - t0
    moves = {}
    for key, twin in twins.items():
        serial = create_planner("equilibrium_batch").plan(twin)
        got, want = _moves(tick.results[key]), _moves(serial)
        assert got == want, (f"fleet: lane {key} differs from its serial "
                             f"plan ({len(got)} vs {len(want)} moves)")
        moves[key] = len(got)
    _emit("fleet", lanes=len(made), moves=moves, identical=True,
          tick_s=tick_s)


def phase_sharded(n_shards: int, scale: int, max_moves: int) -> None:
    import jax
    from repro.core.clustergen import cluster_b
    from repro.core.equilibrium import EquilibriumConfig
    from repro.core.planner import create_planner

    t0 = time.perf_counter()
    state = cluster_b(scale=scale)
    build_s = time.perf_counter() - t0
    twin = state.copy()
    cfg = EquilibriumConfig(max_moves=max_moves)

    t0 = time.perf_counter()
    sharded = create_planner("equilibrium_batch_sharded", cfg=cfg,
                             n_shards=n_shards)
    res = sharded.plan(state)
    sharded_s = time.perf_counter() - t0
    carry = sharded._impl._dyn
    mesh_devices = sorted(d.id for d in carry[7].sharding.device_set)
    t0 = time.perf_counter()
    one = create_planner("equilibrium_batch", cfg=cfg).plan(twin)
    one_s = time.perf_counter() - t0
    got, want = _moves(res), _moves(one)
    assert len(mesh_devices) == n_shards == len(jax.devices()[:n_shards]), \
        f"sharded: carry spans devices {mesh_devices}"
    assert got == want, (f"sharded: {n_shards}-chip plan differs from the "
                         f"one-chip plan ({len(got)} vs {len(want)} moves)")
    assert len(got) == max_moves, f"sharded: {len(got)} moves planned"
    _emit("sharded", osds=state.n_devices, pgs=len(state.acting),
          shards=n_shards, mesh_devices=mesh_devices, moves=len(got),
          identical=True, build_s=build_s, sharded_s=sharded_s,
          one_chip_s=one_s)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: only the sharded "
                         "phase on a 4-chip mesh")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache
    _emit("setup", compile_cache=enable_compile_cache(),
          jax=jax.__version__)
    if args.chips == 4:
        phase_sharded(n_shards=4, scale=8, max_moves=64)
    else:
        phase_b1(_CompileClock())
        phase_churn()
        phase_fleet()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
