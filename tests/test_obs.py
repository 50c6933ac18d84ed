"""The telemetry spine (PR 7, ``repro.obs``): the no-op fast path never
perturbs plan bit-identity and costs ≲2% of a plan, every registered
planner emits the full :data:`STATS_SCHEMA` key set, the trace sinks
(JSONL and Chrome/Perfetto) round-trip losslessly, and the counters the
benchmarks and CI assert on actually appear in the footer."""

import json
import time

import pytest

from repro import obs
from repro.core import available_planners, create_planner, small_test_cluster
from repro.core.cluster import PoolGrowthDelta
from repro.obs import (STATS_SCHEMA, MetricsRegistry, read_trace, registry,
                       to_chrome, validate_stats, validate_trace)


def tup(moves):
    return [(m.pg, m.slot, m.src_osd, m.dst_osd) for m in moves]


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    """Tracing is process-global; never leak a tracer across tests."""
    assert not obs.enabled(), "a previous test leaked a live tracer"
    yield
    if obs.enabled():
        obs.stop_tracing()
        pytest.fail("test leaked a live tracer")


# ---------------------------------------------------------------------------
# metrics registry


def test_registry_counters_labels_and_deltas():
    reg = MetricsRegistry()
    reg.inc("a")
    reg.inc("a", 2)
    reg.inc("a", 5, planner="x")
    assert reg.get("a") == 3
    assert reg.get("a", planner="x") == 5
    assert reg.total("a") == 8
    snap = reg.snapshot()
    reg.inc("a")
    reg.inc("b", 4)
    assert reg.deltas_since(snap) == {"a": 1, "b": 4}
    reg.set_gauge("g", 7, pool=1)
    reg.observe("h", 3)
    dump = reg.dump()
    assert dump["gauges"]["g{pool=1}"] == 7
    assert dump["histograms"]["h"] == {"count": 1, "sum": 3,
                                       "min": 3, "max": 3}


def test_label_rendering_is_sorted_and_stable():
    reg = MetricsRegistry()
    reg.inc("n", 1, b=2, a=1)
    reg.inc("n", 1, a=1, b=2)
    assert reg.dump()["counters"] == {"n{a=1,b=2}": 2}


# ---------------------------------------------------------------------------
# no-op fast path


def test_disabled_span_is_shared_singleton():
    assert not obs.enabled()
    s1, s2 = obs.span("x"), obs.span("y", cat="z", counters=True)
    assert s1 is s2                     # no allocation on the disabled path
    with s1 as sp:
        sp.set(anything=1)
    assert sp.wall_s == 0.0 and sp.cpu_s == 0.0 and sp.args == {}
    obs.point("x", cat="z")             # dropped, no error


def test_disabled_overhead_within_two_percent_of_a_plan():
    # proxy for the ≤2% budget: (spans a traced plan emits) × (disabled
    # per-call cost) must be ≲2% of that plan's wall time
    n = 50_000
    t0 = time.perf_counter()
    for _ in range(n):
        with obs.span("overhead.probe"):
            pass
    per_call = (time.perf_counter() - t0) / n

    state = small_test_cluster()
    planner = create_planner("equilibrium")
    t0 = time.perf_counter()
    planner.plan(state.copy())
    plan_wall = time.perf_counter() - t0
    with obs.tracing() as t:
        create_planner("equilibrium").plan(state.copy())
    spans = sum(1 for r in t.records if r.get("ev") == "span")
    assert spans >= 1
    assert spans * per_call <= 0.02 * plan_wall, (
        f"{spans} spans x {per_call * 1e9:.0f}ns = "
        f"{spans * per_call * 1e6:.1f}us vs plan {plan_wall * 1e6:.0f}us")


@pytest.mark.parametrize("name", ["equilibrium", "equilibrium_batch"])
def test_plans_bit_identical_with_tracing_on_and_off(name):
    state = small_test_cluster()
    off = create_planner(name).plan(state.copy())
    with obs.tracing():
        on = create_planner(name).plan(state.copy())
    assert tup(on.moves) == tup(off.moves)
    assert set(on.stats) == set(off.stats)


# ---------------------------------------------------------------------------
# stats schema: one contract for every registered planner


def test_every_registered_planner_emits_the_full_schema():
    for name in available_planners():
        result = create_planner(name).plan(small_test_cluster(), budget=5)
        assert set(result.stats) >= set(STATS_SCHEMA), (
            name, set(STATS_SCHEMA) - set(result.stats))
        problems = validate_stats(result.stats)
        assert not problems, (name, problems)


def test_plan_span_carries_counter_attribution():
    with obs.tracing() as t:
        create_planner("equilibrium_batch").plan(small_test_cluster())
    plan_spans = [r for r in t.records
                  if r.get("ev") == "span" and r["name"] == "planner.plan"]
    assert len(plan_spans) == 1
    counters = plan_spans[0]["args"].get("counters", {})
    assert counters.get("planner.plans{planner=equilibrium_batch}") == 1
    assert counters.get("batch.rebuilds") == 1
    assert "tail.moves" in counters


def test_observe_absorb_counters_per_delta_type():
    from repro.core import TiB
    reg = registry()
    before = reg.snapshot()
    state = small_test_cluster()
    planner = create_planner("equilibrium_batch")
    planner.plan(state)
    state.grow_pool(0, 1.0 * TiB)
    assert planner.observe(PoolGrowthDelta(state.mutation_epoch, 0, 1.0 * TiB))
    planner.plan(state)                 # absorb happens lazily, in plan()
    deltas = reg.deltas_since(before)
    assert deltas.get("absorb.runs", 0) >= 1
    assert deltas.get("absorb.deltas{type=PoolGrowthDelta}", 0) >= 1
    assert deltas.get("batch.rebuilds") == 1


# ---------------------------------------------------------------------------
# trace sinks round-trip


def _traced_quick_plan(path):
    with obs.tracing(str(path)) as t:
        with obs.span("outer", cat="test", counters=True, name="row"):
            create_planner("equilibrium").plan(small_test_cluster())
        obs.point("marker", cat="test", k=1)
    return t.records


def test_jsonl_sink_round_trips_and_validates(tmp_path):
    path = tmp_path / "run.jsonl"
    records = _traced_quick_plan(path)
    assert not validate_trace(records)
    back = read_trace(str(path))
    assert back == json.loads(json.dumps(records))   # number-type neutral
    assert back[0]["ev"] == "meta"
    assert back[-1]["ev"] == "counters"
    names = {r["name"] for r in back if r["ev"] == "span"}
    assert {"outer", "planner.plan"} <= names
    outer = next(r for r in back if r["ev"] == "span"
                 and r["name"] == "outer")
    assert outer["args"]["name"] == "row"
    assert outer["parent"] == 0
    inner = next(r for r in back if r["ev"] == "span"
                 and r["name"] == "planner.plan")
    assert inner["parent"] == outer["id"]


def test_chrome_sink_round_trips_losslessly(tmp_path):
    jsonl = tmp_path / "run.jsonl"
    records = _traced_quick_plan(jsonl)
    chrome_path = tmp_path / "run.trace.json"
    with obs.tracing(str(chrome_path)) as t:
        with obs.span("outer", cat="test"):
            pass
    chrome = json.load(open(chrome_path))
    assert chrome["traceEvents"][0]["ph"] == "M"
    # and the pure-function conversion inverts on the richer trace
    back = read_trace(str(chrome_path))
    assert [r["ev"] for r in back] == [r["ev"] for r in t.records]
    full = to_chrome(records)
    footer = [e for e in full["traceEvents"] if e.get("cat") == "__footer__"]
    assert len(footer) == 1
    assert footer[0]["args"]["values"]     # registry counters survive


def test_start_tracing_twice_raises():
    t = obs.start_tracing()
    try:
        with pytest.raises(RuntimeError):
            obs.start_tracing()
    finally:
        assert obs.stop_tracing() is t.records or True
    assert not obs.enabled()


# ---------------------------------------------------------------------------
# the profiler's clock and the compiled chunk program


def test_span_opens_a_profiler_annotation_only_while_tracing(monkeypatch):
    import jax.profiler
    opened = []

    class Recorder:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(("enter", self.name))

        def __exit__(self, *exc):
            opened.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    off = obs.span("batch.build")
    with off:
        pass
    assert off is obs.span("other")     # still the shared no-op
    assert opened == []
    with obs.tracing():
        with obs.span("planner.plan"):
            with obs.span("batch.build", cat="batch", n=1):
                pass
    assert opened == [("enter", "planner.plan"), ("enter", "batch.build"),
                      ("exit", "batch.build"), ("exit", "planner.plan")]


def test_default_tracing_runs_the_untraced_chunk_program():
    from repro.core.equilibrium_batch import _plan_chunk
    state = small_test_cluster()
    off = create_planner("equilibrium_batch").plan(state.copy())
    compiled = _plan_chunk._cache_size()
    reg = registry()
    snap = reg.snapshot()
    with obs.tracing():
        on = create_planner("equilibrium_batch").plan(state.copy())
    deltas = reg.deltas_since(snap)
    assert deltas.get("batch.jit_recompiles", 0) == 0
    assert _plan_chunk._cache_size() == compiled
    assert "batch.tiles_walked" not in deltas
    assert tup(on.moves) == tup(off.moves)


def test_device_counters_compile_the_tile_counters_in():
    reg = registry()
    snap = reg.snapshot()
    with obs.tracing(device_counters=True):
        assert obs.device_counters()
        create_planner("equilibrium_batch").plan(small_test_cluster())
    assert not obs.device_counters()
    assert reg.deltas_since(snap).get("batch.tiles_walked", 0) > 0


def test_batch_spans_nest_and_every_fetch_names_its_reason():
    with obs.tracing() as t:
        create_planner("equilibrium_batch").plan(small_test_cluster())
    spans = [r for r in t.records if r.get("ev") == "span"]
    by_id = {r["id"]: r for r in spans}
    plan = next(r for r in spans if r["name"] == "planner.plan")
    names = [r["name"] for r in spans]
    assert names.count("batch.build") == 1
    assert names.count("batch.reconcile") == 1
    assert names.count("batch.dispatch") == names.count("batch.chunk") > 0
    build = next(r for r in spans if r["name"] == "batch.build")
    assert build["args"]["n_devices"] == small_test_cluster().n_devices
    reconcile = next(r for r in spans if r["name"] == "batch.reconcile")
    assert reconcile["args"]["moves"] == plan["args"]["moves"]
    for r in spans:
        if r["name"] in ("batch.dispatch", "batch.fetch"):
            if r["args"].get("what") == "pruned":
                assert by_id[r["parent"]]["name"] == "planner.plan"
            else:
                assert by_id[r["parent"]]["name"] == "batch.chunk"
    assert "dispatch_s" not in str([r["args"] for r in spans])
    fetches = [r for r in spans if r["name"] == "batch.fetch"]
    assert {r["args"]["what"] for r in fetches} <= {"chunk", "repad",
                                                    "pruned"}
    assert len(fetches) == plan["args"]["counters"]["batch.host_syncs"]
    points = {r["name"] for r in t.records if r.get("ev") == "point"}
    assert "batch.rebuild" not in points


def test_absorb_is_a_span_with_its_args():
    from repro.core import TiB
    state = small_test_cluster()
    planner = create_planner("equilibrium_batch")
    planner.plan(state)
    state.grow_pool(0, 1.0 * TiB)
    with obs.tracing() as t:
        planner.plan(state)
    absorbs = [r for r in t.records if r.get("name") == "batch.absorb"]
    assert len(absorbs) == 1 and absorbs[0]["ev"] == "span"
    assert absorbs[0]["args"]["deltas"] >= 1
    assert {"structural", "kept_bounds"} <= set(absorbs[0]["args"])
    assert not any(r.get("name") == "batch.build" for r in t.records)
