"""The harness: discovery by name, the contract of BENCHMARK.json, the
command's refusal without a TPU, and whole runs on the CPU with the
chip check skipped: a sound run is correct, the control and each fault
the cells can have are not."""

import json
import os
import re
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[3]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.chip import harness  # noqa: E402
from benchmarks.chip.control import RefProgram  # noqa: E402

HERE = ROOT / "benchmarks/chip"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _bench_with_small_cells(extra_traffic_dir=None):
    """BENCHMARK.json plus two throwaway cells on the small CPU config."""
    bench = harness.load_benchmark()
    bench["configs"].append({"name": "small", "file":
                             "benchmarks/chip/tests/data/small.json"})
    bench["workloads"] += [
        {"name": "s-converge", "config": "small", "traffic": "converge",
         "chips": 1},
        {"name": "s-tick", "config": "small", "traffic": "tick-fast",
         "chips": 1}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        w = m.get("workloads")
        if w is not None:
            w.append("s-converge" if "b-converge" in w else "s-tick")
    # the tick mix's own end-to-end metrics, whether or not a cell of
    # BENCHMARK.json reports them yet
    names = {m["name"] for m in bench["end_to_end"]}
    for name in ("tick_ms_p50", "tick_ms_p95"):
        if name not in names:
            bench["end_to_end"].append(
                {"name": name, "unit": "ms", "better": "lower",
                 "bound": 0.1, "source": "host_clock",
                 "workloads": ["s-tick"]})
    return bench


@pytest.fixture(scope="module")
def traffic_dir(tmp_path_factory):
    """The real mixes plus ``tick-fast``: the tick mix with an outage
    every 6 ticks, so a short CPU window sees outages."""
    d = tmp_path_factory.mktemp("traffic")
    for f in (HERE / "traffic").glob("*.json"):
        shutil.copy(f, d / f.name)
    tick = json.loads((HERE / "traffic/tick.json").read_text())
    tick["outage"] = {"class": "hdd", "first": 2, "every": 6,
                      "back_after": 3}
    (d / "tick-fast.json").write_text(json.dumps(tick))
    return d


def _run(bench, cell, traffic_dir, subject=None, seconds=1.5, seed=11,
         metrics_dir=HERE / "metrics"):
    lines = []
    out = harness.run_cell(bench, cell, seed, seconds, False,
                           time.perf_counter(), subject=subject,
                           log=lines.append, metrics_dir=metrics_dir,
                           traffic_dir=traffic_dir)
    return out, lines


# -- BENCHMARK.json ----------------------------------------------------------


def test_benchmark_json_names_files_that_exist():
    bench = harness.load_benchmark()
    assert bench["paths"] == ["benchmarks/chip"]
    assert bench["command"] == ["python3", "benchmarks/chip/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    names = set()
    for c in bench["configs"]:
        assert c["file"].startswith("benchmarks/chip/")
        assert harness.load_config(bench, c["name"])
        assert c["reduced"] == [] or all(NAME.match(k) for k in c["reduced"])
        names.add(c["name"])
    metrics = bench["end_to_end"] + bench["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert hasattr(harness.load_metric(m["name"]), "read")
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    moves = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in moves
    for w in bench["workloads"]:
        assert w["config"] in names and w["chips"] in (1, 4)
        assert (HERE / "traffic" / f"{w['traffic']}.json").exists()
        e2e = [m["name"] for m in harness.metrics_for(bench, w["name"], False)]
        per = harness.metrics_for(bench, w["name"], True)
        assert "setup_s" in e2e and len(e2e) >= 2 and per
        for m in per:
            assert m["moves"] in e2e


def test_adding_a_cell_needs_only_new_files_and_entries(tmp_path,
                                                        traffic_dir):
    """A throwaway configuration, traffic mix and metric, each a new file
    found by the name its new entry gives."""
    cfg = json.loads((HERE / "tests/data/small.json").read_text())
    cfg["device_groups"][0]["count"] = 30
    (tmp_path / "throwaway.json").write_text(json.dumps(cfg))
    tdir = tmp_path / "traffic"
    shutil.copytree(traffic_dir, tdir)
    mix = json.loads((HERE / "traffic/converge.json").read_text())
    mix["budget"] = 5
    (tdir / "five-moves.json").write_text(json.dumps(mix))
    mdir = tmp_path / "metrics"
    shutil.copytree(HERE / "metrics", mdir)
    (mdir / "moves_per_plan.py").write_text(
        "def read(ctx):\n"
        "    return sum(len(r.moves) for r in ctx.requests)"
        " / len(ctx.requests)\n")
    bench = harness.load_benchmark()
    bench["configs"].append({"name": "throwaway",
                             "file": str(tmp_path / "throwaway.json")})
    bench["workloads"].append({"name": "t-five", "config": "throwaway",
                               "traffic": "five-moves", "chips": 1})
    bench["end_to_end"].append({"name": "moves_per_plan", "unit": "moves",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock",
                                "workloads": ["t-five"]})
    out, _ = _run(bench, "t-five", tdir, seconds=0.5, metrics_dir=mdir)
    assert out["correct"]
    assert out["metrics"]["moves_per_plan"]["value"] == 5
    assert set(out["metrics"]) == {"setup_s", "moves_per_plan"}


# -- the command ---------------------------------------------------------------


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", "b-converge",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout


# -- whole runs on the CPU ---------------------------------------------------


@pytest.mark.parametrize("cell", ["s-converge", "s-tick"])
def test_sound_run_is_correct(cell, traffic_dir):
    out, lines = _run(_bench_with_small_cells(), cell, traffic_dir)
    assert out["correct"], out
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert all(c["value"] == 0 for c in out["checks"].values())
    window = next(d["window"] for d in lines if "window" in d)
    assert window["jit_recompiles"] == 0
    want = {"s-converge": {"setup_s", "plan_s"},
            "s-tick": {"setup_s", "tick_ms_p50", "tick_ms_p95"}}[cell]
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    if cell == "s-tick":
        assert any(d.get("check", {}).get("compared") for d in lines)


@pytest.mark.parametrize("cell", ["s-converge", "s-tick"])
def test_float32_control_in_the_programs_place_is_not_correct(cell,
                                                             traffic_dir):
    out, _ = _run(_bench_with_small_cells(), cell, traffic_dir,
                  subject=lambda d, c: RefProgram(d, c, np.float32),
                  seconds=3.0)
    assert not out["correct"], out


class _Faulty:
    """The program with its timed path broken underneath."""

    def __init__(self, fault):
        self.fault = fault

    def __call__(self, dep, cfg):
        from benchmarks.chip.program import Program
        self.program = Program(dep, cfg)
        self.n_dev = dep.n_dev
        return self

    def session(self):
        inner = self.program.session()
        fault, n_dev = self.fault, self.n_dev

        class S:
            def plan(self, budget):
                if fault == "state_unchanged":
                    return 0.0, []
                dt, moves = inner.plan(budget)
                if fault == "half_left_out":
                    moves = moves[:len(moves) // 2]
                elif fault == "answer_altered" and moves:
                    r, s, src, dst = moves[0]
                    moves = [(r, s, src, (dst + 1) % n_dev)] + moves[1:]
                return dt, moves

            def __getattr__(self, name):
                return getattr(inner, name)
        return S()


@pytest.mark.parametrize("fault", ["state_unchanged", "half_left_out",
                                   "answer_altered"])
@pytest.mark.parametrize("cell", ["s-converge", "s-tick"])
def test_each_fault_reads_not_correct(cell, fault, traffic_dir):
    out, _ = _run(_bench_with_small_cells(), cell, traffic_dir,
                  subject=_Faulty(fault), seconds=2.0)
    assert not out["correct"], out
    assert out["failed"] > 0
