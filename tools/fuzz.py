#!/usr/bin/env python
"""Differential lifecycle fuzzing CLI (see ``src/repro/fuzz``).

Modes:

* **sweep** (default): generate one timeline per seed in ``--seeds A:B``
  and run each through every engine lane under the full oracle set.  On
  the first oracle failure, optionally shrink (``--shrink``) and save
  the minimized reproducer to the corpus (``--save``), then exit 1.
* **replay** (``--replay FILE``): run one serialized timeline (corpus
  file) through the full harness and exit by its verdict.
* **mutation smoke** (``--mutate NAME``): patch one legality predicate
  to its vacuous form (``repro.fuzz.mutate.MUTATIONS``), sweep seeds
  until an oracle catches the broken planner, shrink the reproducer,
  and exit 0 only if it was caught *and* shrank to at most
  ``--expect-max-events`` events — the proof the harness would catch a
  real regression of the same shape.

``--shard-subprocess N`` additionally runs every Nth seed's timeline
through the sharded engine on a ``--shard-devices``-wide mesh and
compares its move stream and metrics hashes against the reference lane.
On a CPU host the mesh is forced in a subprocess
(``tools/shard_check.py --timeline``); on a TPU host the lane runs in
this process on the first ``--shard-devices`` chips.

Examples::

    python tools/fuzz.py --seeds 0:200
    python tools/fuzz.py --seeds 0:25 --engines host --shard-subprocess 8
    python tools/fuzz.py --replay tests/regressions/variance-seed0.json
    python tools/fuzz.py --mutate variance_always_improves --shrink
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

HOST_ENGINES = ("equilibrium", "equilibrium_faithful")


def parse_seeds(spec: str) -> range:
    lo, _, hi = spec.partition(":")
    return range(int(lo or 0), int(hi))


def lane_hashes(lane) -> dict:
    return {
        "moves_sha": hashlib.sha256(
            json.dumps(lane.moves).encode()).hexdigest(),
        "metrics_sha": hashlib.sha256(
            lane.metrics_json.encode()).hexdigest(),
        "n_moves": len(lane.moves),
    }


def resolve_engines(spec: str):
    from repro.core.planner import planners_in_class
    if spec == "class":
        return planners_in_class("equilibrium")
    if spec == "host":
        return HOST_ENGINES
    return tuple(spec.split(","))


def shard_lane_check(tl, ref_lane, devices: int) -> None:
    """Run the timeline's sharded lane on a ``devices``-wide mesh; raise
    OracleFailure("agreement") on hash mismatch.

    On a TPU host the lane runs in this process over
    ``jax.devices()[:devices]`` (a chip belongs to one process, and this
    one already holds it).  Elsewhere a subprocess forces ``devices``
    host devices, since JAX fixes the CPU device count at start-up."""
    import jax
    from repro.fuzz import OracleFailure
    if jax.default_backend() == "tpu":
        from repro.fuzz.harness import run_lane
        got = lane_hashes(run_lane(tl, "equilibrium_batch_sharded",
                                   n_shards=devices))
    else:
        got = _shard_subprocess(tl, devices)
    want = lane_hashes(ref_lane)
    for key in ("moves_sha", "metrics_sha"):
        if got[key] != want[key]:
            raise OracleFailure(
                "agreement",
                f"sharded lane (mesh={devices}) {key} "
                f"mismatch: {got[key]} != {want[key]}")


def _shard_subprocess(tl, devices: int) -> dict:
    """The sharded lane's hashes from ``tools/shard_check.py --timeline``
    on a forced ``devices``-device host mesh."""
    from repro.fuzz import OracleFailure
    script = os.path.join(os.path.dirname(__file__), "shard_check.py")
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                        f"platform_device_count={devices}").strip()
    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as fh:
        json.dump(tl.to_dict(), fh)
        path = fh.name
    try:
        proc = subprocess.run(
            [sys.executable, script, "--timeline", path,
             "--devices", str(devices)],
            env=env, capture_output=True, text=True, timeout=1200)
    finally:
        os.unlink(path)
    if proc.returncode != 0:
        raise OracleFailure(
            "agreement",
            f"sharded subprocess lane (mesh={devices}) failed rc="
            f"{proc.returncode}:\n{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run_one(tl, engines, baselines=True):
    """Full oracle pass on one timeline; returns the reference lane."""
    from repro.fuzz import run_timeline
    from repro.fuzz.harness import BASELINE_LANES
    lanes = run_timeline(tl, engines=engines,
                         baseline_lanes=BASELINE_LANES if baselines else ())
    return lanes[engines[0] if engines else sorted(lanes)[0]]


def make_predicate(engines, oracle: str):
    """Shrink predicate: candidate reproduces iff the same oracle fires
    (other failures — including unrelated crashes on mangled
    candidates — do not count)."""
    from repro.fuzz import OracleFailure
    from repro.sim.generate import timeline_from_dict

    def fails(d: dict) -> bool:
        try:
            run_one(timeline_from_dict(d), engines, baselines=False)
        except OracleFailure as exc:
            return exc.oracle == oracle
        except Exception:
            return False
        return False
    return fails


def shrink_and_save(d, engines, oracle, args):
    from repro.fuzz import save_timeline, shrink_timeline
    small, evals = shrink_timeline(d, make_predicate(engines, oracle),
                                   max_evals=args.max_evals)
    n_events = len(small["events"])
    print(f"shrunk to {n_events} events / {small['sim']['ticks']} ticks "
          f"in {evals} evals")
    if args.save:
        path = save_timeline(small, args.save, args.corpus)
        print(f"saved reproducer: {path}")
    return small, n_events


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--seeds", default="0:50", help="seed range A:B")
    ap.add_argument("--profile", default="quick",
                    help="FuzzProfile name (quick, nightly)")
    ap.add_argument("--engines", default="class",
                    help="'class' (full equivalence class), 'host' "
                         "(numpy engines only), or a comma list")
    ap.add_argument("--no-baselines", action="store_true",
                    help="skip the mgr/none reduced-oracle lanes")
    ap.add_argument("--replay", metavar="FILE",
                    help="replay one serialized timeline and exit")
    ap.add_argument("--shrink", action="store_true",
                    help="shrink the reproducer on failure")
    ap.add_argument("--save", metavar="NAME",
                    help="save the (shrunk) reproducer under this corpus "
                         "name")
    ap.add_argument("--corpus", default=None,
                    help="corpus directory (default tests/regressions)")
    ap.add_argument("--mutate", metavar="NAME",
                    help="mutation smoke: run under a broken legality "
                         "predicate and require the harness to catch it")
    ap.add_argument("--expect-max-events", type=int, default=12,
                    help="mutation smoke: max events in the shrunk "
                         "reproducer")
    ap.add_argument("--max-evals", type=int, default=300,
                    help="shrinker predicate-evaluation budget")
    ap.add_argument("--shard-subprocess", type=int, default=0, metavar="N",
                    help="every Nth seed, also check the sharded-mesh "
                         "lane (0 = off)")
    ap.add_argument("--shard-devices", type=int, default=2,
                    help="mesh size for the sharded-mesh lane")
    args = ap.parse_args()

    from repro.fuzz import OracleFailure, load_timeline, mutated
    from repro.sim.generate import generate_timeline

    engines = resolve_engines(args.engines)

    if args.replay:
        tl = load_timeline(args.replay)
        try:
            ref = run_one(tl, engines, baselines=not args.no_baselines)
        except OracleFailure as exc:
            print(f"REPLAY FAILED {args.replay}: {exc}")
            return 1
        print(f"replay ok: {args.replay} ({len(ref.moves)} moves)")
        return 0

    if args.mutate:
        if args.engines == "class":
            engines = HOST_ENGINES    # jit caches would mask in-proc traces
        with mutated(args.mutate):
            found = None
            for seed in parse_seeds(args.seeds):
                tl = generate_timeline(seed, args.profile)
                try:
                    run_one(tl, engines, baselines=False)
                except OracleFailure as exc:
                    found = (seed, tl, exc)
                    break
            if found is None:
                print(f"mutation {args.mutate!r} NOT caught in seeds "
                      f"{args.seeds} — the harness is blind to it")
                return 1
            seed, tl, exc = found
            print(f"mutation {args.mutate!r} caught at seed {seed}: {exc}")
            d = tl.to_dict()
            d["provenance"]["mutation"] = args.mutate
            d["provenance"]["oracle"] = exc.oracle
            small, n_events = shrink_and_save(d, engines, exc.oracle, args)
            if n_events > args.expect_max_events:
                print(f"shrunk reproducer still has {n_events} events "
                      f"(> {args.expect_max_events})")
                return 1
        return 0

    failures = 0
    t0 = time.time()
    seeds = parse_seeds(args.seeds)
    for i, seed in enumerate(seeds):
        if i and i % 10 == 0:
            # every seed draws fresh cluster shapes, so compiled programs
            # never get cache hits across timelines — without this a long
            # sweep OOMs on accumulated jit executables (~100 timelines
            # exhausts a 128 GB host on the full engine class)
            try:
                import jax
                jax.clear_caches()
            except Exception:
                pass
        tl = generate_timeline(seed, args.profile)
        try:
            ref = run_one(tl, engines, baselines=not args.no_baselines)
            if args.shard_subprocess and i % args.shard_subprocess == 0:
                shard_lane_check(tl, ref, args.shard_devices)
        except OracleFailure as exc:
            failures += 1
            print(f"seed {seed}: {exc}")
            d = tl.to_dict()
            d["provenance"]["oracle"] = exc.oracle
            if args.shrink:
                if not args.save:
                    args.save = f"{exc.oracle}-seed{seed}"
                shrink_and_save(d, engines, exc.oracle, args)
            return 1
        if (i + 1) % 10 == 0 or i + 1 == len(seeds):
            rate = (i + 1) / max(time.time() - t0, 1e-9)
            print(f"[{i + 1}/{len(seeds)}] ok "
                  f"({rate:.2f} timelines/s)", flush=True)
    print(f"sweep ok: {len(seeds)} timelines x {len(engines)} engines, "
          f"0 oracle failures in {time.time() - t0:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
