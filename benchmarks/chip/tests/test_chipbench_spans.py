"""The program's own spans in the benchmark: the gap reduction names each
idle gap piece after the innermost program span covering it, and the
span readings of the build, the reconcile and the chunk loop."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import gaps, trace  # noqa: E402
from benchmarks.chip.plan_spans import chunk_host_ms, plan_span_ms  # noqa: E402


def _spans_timeline():
    # bench.copy [0, 100), then bench.plan [100, 1000) holding one plan:
    # build, two chunks (dispatch, then fetch) and the reconcile; the
    # device runs [450, 580) and [640, 690); one program span lies
    # after the window
    ann = [("bench.copy", 0, 100), ("bench.plan", 100, 900)]
    spans = [("planner.plan", 110, 880), ("batch.build", 120, 280),
             ("batch.chunk", 400, 200), ("batch.dispatch", 400, 20),
             ("batch.fetch", 420, 180), ("batch.chunk", 600, 200),
             ("batch.dispatch", 600, 10), ("batch.fetch", 610, 90),
             ("batch.reconcile", 820, 160), ("batch.fetch", 1000, 100)]
    ops = [("%fusion.1 = f32[8] fusion(f32[8] %p)", 450, 130),
           ("%fusion.2 = f32[8] fusion(f32[8] %p)", 640, 50)]
    return trace.Timeline([ops], [[]], ann), spans


def test_gap_pieces_are_named_after_the_innermost_program_span():
    tl, spans = _spans_timeline()
    r = gaps.reduce(tl, spans, top=20)
    # [0, 110) lies under no program span: named as before, after the
    # annotation overlapping it most
    assert [[n, round(s * 1e9)] for n, s in r["idle_gaps"]] == [
        ["batch.build", 280], ["batch.reconcile", 160],
        ["bench.copy", 110], ["batch.chunk", 100],
        ["batch.fetch", 30], ["batch.fetch", 30],
        ["planner.plan", 20], ["batch.fetch", 20], ["batch.dispatch", 20],
        ["bench.plan", 10], ["planner.plan", 10], ["batch.fetch", 10],
        ["batch.dispatch", 10], ["planner.plan", 10]]
    top = gaps.reduce(tl, spans)["idle_gaps"]
    assert len(top) == 10 and top == r["idle_gaps"][:10]


def test_program_spans_move_nothing_but_the_gap_names():
    tl, spans = _spans_timeline()
    r = gaps.reduce(tl, spans, top=20)
    plain = trace.reduce(tl, top=20)
    assert r["window_s"] == plain["window_s"] == pytest.approx(1000e-9)
    for key in ("busy_s", "idle_share", "device_ops", "modules",
                "kernels"):
        assert r[key] == plain[key]
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(
        sum(s for _, s in plain["idle_gaps"]))
    # without them the three gaps keep the name overlapping them most
    assert [[n, round(s * 1e9)] for n, s in plain["idle_gaps"]] == [
        ["bench.plan", 450], ["bench.plan", 310], ["bench.plan", 60]]


def test_device_zero_events_may_be_read_once():
    # from_xplane hands each device's op events over as a generator
    tl, spans = _spans_timeline()
    once = trace.Timeline([iter(tl.ops[0])], tl.modules, tl.annotations)
    assert gaps.reduce(once, spans, top=20) == gaps.reduce(tl, spans,
                                                          top=20)


def test_program_spans_are_read_from_the_profiler_trace(tmp_path):
    # repro.obs spans reach the profiler's host plane only while a
    # tracer is installed; other names are left out
    import jax
    import jax.numpy as jnp
    from repro import obs

    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.tracing(None):
            with obs.span("planner.plan"):
                with obs.span("batch.build"):
                    jnp.ones(4).sum().block_until_ready()
            with obs.span("other.span"):
                pass
        with obs.span("batch.untraced"):
            pass
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    spans = gaps.program_spans(str(path))
    assert [n for n, _, _ in spans] == ["planner.plan", "batch.build"]
    (_, p0, pd), (_, b0, bd) = spans
    assert p0 <= b0 and b0 + bd <= p0 + pd


# what the two recorded slices of the trace tests reduced to before the
# program's spans were read: a trace without them reduces to exactly this
RECORDED = {
    "trace-b-converge": {
        "window_s": 0.0004, "busy_s": 0.0004, "idle_share": 0.0,
        "device_ops": [
            ["while.122", 0.012734097000000002],
            ["while.123", 0.000281871],
            ["conditional.8", 5.5513e-05],
            ["fusion.681", 7.2340000000000005e-06],
            ["broadcast_compare_fusion.8", 6.9350000000000005e-06],
            ["fusion.646", 6.72e-06],
            ["fusion.647", 6.7040000000000005e-06],
            ["fusion.617", 6.452e-06],
            ["fusion.615", 6.363000000000001e-06],
            ["fusion.614", 6.357e-06]],
        "idle_gaps": [],
        "modules": {},
        "kernels": {"masked_select": {8: (2, 6.860000000000001e-07)}}},
    "trace-e-converge": {
        "window_s": 0.0004, "busy_s": 0.000148965,
        "idle_share": 0.6275875,
        "device_ops": [
            ["while.122", 0.011353691],
            ["while.123", 5.9263000000000005e-05],
            ["conditional.8", 2.6839000000000002e-05],
            ["fusion.652", 5.371000000000001e-06],
            ["fusion.653", 5.269000000000001e-06],
            ["fusion.639", 3.8960000000000005e-06],
            ["fusion.640", 3.883e-06],
            ["and_select_fusion.13", 3.536e-06],
            ["copy.61", 3.5280000000000004e-06],
            ["fusion.220", 2.395e-06]],
        "idle_gaps": [["bench.plan", 0.000251008]]
        + [["bench.plan", 2e-09]] * 9,
        "modules": {},
        "kernels": {"masked_select": {8: (1, 3.42e-07)}}},
}


@pytest.mark.parametrize("name", ["trace-b-converge", "trace-e-converge"])
def test_recorded_trace_reduces_as_it_did(name):
    tl = trace.Timeline.from_json(
        (ROOT / "benchmarks/chip/tests/data" / f"{name}.json").read_text())
    assert trace.reduce(tl) == RECORDED[name]
    # without program spans the named reduction is the same
    tl = trace.Timeline.from_json(
        (ROOT / "benchmarks/chip/tests/data" / f"{name}.json").read_text())
    assert gaps.reduce(tl, []) == RECORDED[name]


def _plan_spans():
    # two plans: the first built, ran two chunks (one dispatch, one fetch
    # each), fetched the pruned count and reconciled; the second only
    # reconciled
    def sp(i, parent, name, dur, **args):
        return {"ev": "span", "name": name, "id": i, "parent": parent,
                "dur": float(dur), "args": args}
    return [
        sp(2, 1, "batch.build", 600),
        sp(4, 3, "batch.dispatch", 10),
        sp(5, 3, "batch.fetch", 250, what="chunk"),
        sp(3, 1, "batch.chunk", 300),
        sp(7, 6, "batch.dispatch", 10),
        sp(8, 6, "batch.fetch", 150, what="chunk"),
        sp(6, 1, "batch.chunk", 200),
        sp(9, 1, "batch.fetch", 40, what="pruned"),
        sp(10, 1, "batch.reconcile", 80),
        sp(1, 0, "planner.plan", 1500),
        {"ev": "point", "name": "batch.stash"},
        sp(12, 11, "batch.reconcile", 20),
        sp(11, 0, "planner.plan", 100),
    ]


def test_build_reconcile_and_chunk_host_readings():
    spans = _plan_spans()
    assert plan_span_ms(spans, "batch.build") == pytest.approx(0.3)
    assert plan_span_ms(spans, "batch.reconcile") == pytest.approx(0.05)
    # (300 - 250) + (200 - 150) in the first plan, nothing in the second;
    # the pruned fetch lies outside every chunk
    assert chunk_host_ms(spans) == pytest.approx(0.05)


def test_readings_are_none_without_their_spans():
    # a program that writes planner.plan and batch.chunk only
    old = [r for r in _plan_spans()
           if r.get("name") in ("planner.plan", "batch.chunk")]
    assert plan_span_ms(old, "batch.build") is None
    assert plan_span_ms(old, "batch.reconcile") is None
    assert chunk_host_ms(old) is None
    assert chunk_host_ms([]) is None
    assert plan_span_ms(None, "batch.build") is None
