"""The trace reduction, the peaks table and the kernel byte count."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import peaks, trace  # noqa: E402


def _hand_timeline():
    # two annotated host spans over [0, 100) ns; device busy [10, 30)
    # and [25, 40) (overlapping) and [70, 80), one op outside the window
    ops = [("%fusion.1 = f32[8] fusion(f32[8] %p)", 10, 20),
           ("%masked_select.1 = (s32[8,1], s32[8,1]) custom-call("
            "s32[8,1024] %a, s32[1,1024] %b)", 25, 15),
           ("%fusion.1 = f32[8] fusion(f32[8] %p)", 70, 10),
           ("late", 150, 5)]
    mods = [("jit__plan_chunk(1)", 10, 30)]
    ann = [("bench.copy", 0, 50), ("bench.plan", 50, 50)]
    return trace.Timeline([ops], [mods], ann)


def test_reduce_hand_timeline():
    r = trace.reduce(_hand_timeline())
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(40e-9)
    assert r["idle_share"] == pytest.approx(0.6)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(30e-9)]
    assert r["modules"] == {"jit__plan_chunk(1)": pytest.approx(30e-9)}
    assert r["kernels"]["masked_select"] == {8: (1, pytest.approx(15e-9))}
    # gaps: [40, 70) mostly under bench.plan, [0, 10) under bench.copy,
    # [80, 100) under bench.plan
    assert r["idle_gaps"] == [["bench.plan", pytest.approx(30e-9)],
                              ["bench.plan", pytest.approx(20e-9)],
                              ["bench.copy", pytest.approx(10e-9)]]


def test_timeline_json_round_trip():
    tl = _hand_timeline()
    assert trace.Timeline.from_json(tl.to_json()) == tl


def test_reduce_refuses_a_trace_without_device():
    with pytest.raises(ValueError):
        trace.reduce(trace.Timeline([], [], [("bench.plan", 0, 5)]))


def test_masked_select_bytes_known_shape():
    # 8 rows x 995 devices: 995 bytes of validity bits, the int32 key row
    # (3,980 bytes) and two int32 outputs per row (64 bytes)
    assert peaks.masked_select_bytes(8, 995) == 995 + 3980 + 64
    assert peaks.masked_select_bytes(3, 5) == 2 + 20 + 24


def test_masked_select_padding_does_not_change_the_count():
    text = ("%masked_select = (s32[8,1]{1,0}, s32[8,1]{1,0}) "
            "custom-call(s32[8,1024]{1,0} %pad.1, s32[1,1024]{1,0} %pad.2)")
    rows = trace.operand_rows(text)
    assert rows == 8
    assert peaks.masked_select_bytes(rows, 995) \
        == peaks.masked_select_bytes(8, 995)


def test_unknown_device_kind_raises():
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary", "hbm_bytes_per_s")


@pytest.mark.parametrize("name", ["trace-b-converge", "trace-e-converge"])
def test_reduce_recorded_trace(name):
    """0.4 ms slices of cold plans of clusters B (inside a chunk) and E
    (the end of one chunk and the host gap after it) on a TPU v5e, one
    chip, recorded from the profiler: busy time agrees with a brute-force
    union of the op intervals, and the select kernel's calls are found
    with their 8-row validity operand."""
    tl = trace.Timeline.from_json(
        (ROOT / "benchmarks/chip/tests/data" / f"{name}.json").read_text())
    r = trace.reduce(tl)
    lo, hi = 0.0, tl.annotations[0][2]
    edges = sorted({lo, hi} | {min(max(x, lo), hi) for _, s, d in tl.ops[0]
                               for x in (s, s + d)})
    busy = sum(b - a for a, b in zip(edges, edges[1:])
               if any(s <= a and b <= s + d for _, s, d in tl.ops[0]))
    assert r["busy_s"] == pytest.approx(busy * 1e-9)
    assert r["window_s"] == pytest.approx(hi * 1e-9)
    assert 0.0 <= r["idle_share"] <= 1.0
    calls = r["kernels"]["masked_select"]
    assert list(calls) == [8] and calls[8][0] == sum(
        1 for op, *_ in tl.ops[0] if op.startswith("%masked_select"))
    assert r["device_ops"][0][0].startswith("while")


def test_program_span_readings():
    from benchmarks.chip.spans import per_plan_counter, plan_self_ms
    spans = [
        {"ev": "span", "name": "batch.chunk", "id": 2, "parent": 1,
         "dur": 300.0, "args": {}},
        {"ev": "span", "name": "batch.chunk", "id": 3, "parent": 1,
         "dur": 200.0, "args": {}},
        {"ev": "span", "name": "planner.plan", "id": 1, "parent": 0,
         "dur": 1500.0, "args": {"counters": {"batch.host_syncs": 2}}},
        {"ev": "span", "name": "planner.plan", "id": 4, "parent": 0,
         "dur": 700.0, "args": {"counters": {"batch.host_syncs": 4}}},
        {"ev": "point", "name": "batch.stash"},
    ]
    assert plan_self_ms(spans) == pytest.approx((1.0 + 0.7) / 2)
    assert per_plan_counter(spans, "batch.host_syncs") == 3
    assert plan_self_ms([]) is None
