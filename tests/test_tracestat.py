"""Golden-file coverage for the tools/tracestat.py CLI.

The fixture trace under ``tests/fixtures/tracestat/`` is a hand-written,
schema-valid JSONL trace exercising every derived view: fleet ticks with
an SLO-expired round, per-cluster plan spans, batch.chunk overlap spans
(each with its batch.dispatch and batch.fetch children), a bench.call
with counters, and a counters footer with sharded tile counters.  Each
CLI view's stdout is compared byte-for-byte against a
committed golden — any change to the derived-metric math (prune rate,
tail share, overlap split, freshness buckets) shows up as a readable
golden diff, not a silent drift.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "tracestat")
TRACE = os.path.join(FIXTURES, "fixture_trace.jsonl")
GOLDEN = os.path.join(FIXTURES, "golden")


def _run(*argv, check=True):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "tracestat.py"), *argv],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    if check:
        assert proc.returncode == 0, proc.stderr[-2000:]
    return proc


@pytest.mark.parametrize("name,flags", [
    ("default", ()),
    ("fleet", ("--fleet",)),
    ("shards", ("--shards",)),
    ("bench", ("--bench",)),
    ("validate", ("--validate",)),
], ids=["default", "fleet", "shards", "bench", "validate"])
def test_golden_stdout(name, flags):
    proc = _run(*flags, TRACE)
    with open(os.path.join(GOLDEN, f"{name}.txt")) as f:
        assert proc.stdout == f.read()


def test_validate_rejects_corrupt_trace(tmp_path):
    """A span whose parent id never opened must fail --validate with
    exit 1 and an INVALID diagnostic on stderr."""
    bad = tmp_path / "bad.jsonl"
    with open(TRACE) as f:
        lines = f.read().splitlines()
    dangling = {"ev": "span", "name": "x", "cat": "t", "ts": 1.0, "dur": 1.0,
                "cpu": 1.0, "id": 99, "parent": 777, "tid": 0, "args": {}}
    bad.write_text("\n".join(lines[:-1] + [json.dumps(dangling), lines[-1]])
                   + "\n")
    proc = _run("--validate", str(bad), check=False)
    assert proc.returncode == 1
    assert "INVALID" in proc.stderr


def test_chrome_conversion_round_trips(tmp_path):
    """--chrome writes a Perfetto-loadable event list covering every
    span/point in the fixture."""
    out = tmp_path / "trace.json"
    proc = _run("--chrome", str(out), TRACE)
    assert f"wrote {out}" in proc.stdout
    doc = json.loads(out.read_text())
    events = doc["traceEvents"]
    assert events
    names = {ev.get("name") for ev in events}
    assert {"fleet.tick", "planner.plan", "batch.chunk",
            "bench.call"} <= names
