"""Runs one cell once, driven by ``BENCHMARK.json`` and files found by
name: ``configs/<config>.json``, ``traffic/<traffic>.json`` and
``metrics/<metric>.py``.  Nothing here knows a configuration, a traffic
mix or a metric.

A metric file defines ``read(ctx) -> float | None``: ``ctx`` is the
:class:`Context` of the run; None leaves the metric out of the line.
"""

from __future__ import annotations

import importlib.util
import json
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

from benchmarks.chip import check as _check
from benchmarks.chip import clustergen, trace as _trace
from benchmarks.chip.driver import Workload, load_traffic

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_benchmark(path: Path = ROOT / "BENCHMARK.json") -> dict:
    with open(path) as f:
        return json.load(f)


def find(entries: list, name: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no entry named {name!r}: "
                   f"{[e['name'] for e in entries]}")


def load_config(bench: dict, name: str) -> dict:
    with open(ROOT / find(bench["configs"], name)["file"]) as f:
        return json.load(f)


def metrics_for(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The cell's end-to-end metrics (``trace`` false) or per-layer
    metrics (``trace`` true), in ``BENCHMARK.json`` order."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def load_metric(name: str, directory: Path = HERE / "metrics"):
    path = directory / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Context:
    """What a metric reader may read."""

    n_osds: int                       # devices of the planned cluster
    setup_s: float
    requests: list                    # driver.Request, the whole window
    traced: list = field(default_factory=list)  # requests under the trace
    spans: list | None = None         # program spans + counters (obs)
    reduced: dict | None = None       # trace.reduce of the device trace
    device_kind: str = ""


class _DeviceTrace:
    """The profiler over the first ``seconds`` of the window."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self.start = None
        self.count = None             # requests traced, once stopped

    def begin(self):
        import jax
        # host annotations and device events; no Python call tracing,
        # which would swamp the host plane and slow the run
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.start = time.perf_counter()

    def on_request(self, reqs):
        if self.count is None and \
                time.perf_counter() - self.start >= self.seconds:
            self.stop(len(reqs))

    def stop(self, count):
        import jax
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        self.stop_s = time.perf_counter() - t0
        self.count = count

    def reduce(self) -> dict:
        files = sorted(Path(self.dir).rglob("*.xplane.pb"))
        try:
            return _trace.reduce(_trace.from_xplane(str(files[-1])))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _annotate(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _program(dep, cfg):
    from benchmarks.chip.program import Program
    return Program(dep, cfg)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak() -> int | None:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _counter(name):
    from benchmarks.chip.program import counter
    return counter(name)


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, subject=None, log=None,
             metrics_dir: Path = HERE / "metrics",
             traffic_dir: Path = HERE / "traffic") -> dict:
    """One run of one cell.  Returns the result object; ``log`` (a
    callable taking one dict) receives the earlier lines.  ``subject``
    replaces the program (``subject(dep, planner_cfg)``)."""
    log = log or (lambda d: print(json.dumps(d), flush=True))
    cell = find(bench["workloads"], workload)
    config = load_config(bench, cell["config"])
    traffic = load_traffic(cell["traffic"], traffic_dir)
    dep = clustergen.generate(config, seed)
    subject = (subject or _program)(dep, config["planner"])

    spans_tracer = None
    if trace:
        from repro import obs
        spans_tracer = obs.start_tracing(None)
    wl = Workload(traffic, dep, seed, subject, _annotate if trace else None)
    wl.setup()
    setup_s = time.perf_counter() - t_start
    log({"setup": {"setup_s": setup_s, "osds": dep.n_dev,
                   "pgs": len(dep.pgs),
                   "shards": int((dep.acting >= 0).sum())}})

    recompiles0 = _counter("batch.jit_recompiles")
    compiles = _CompileCount()
    span_mark = len(spans_tracer.records) if spans_tracer else 0
    dtrace = _DeviceTrace(traffic["trace_seconds"]) if trace else None
    if dtrace:
        dtrace.begin()
    t_win = time.perf_counter()
    reqs = wl.window(seconds, dtrace.on_request if dtrace else None)
    window_s = time.perf_counter() - t_win
    if dtrace and dtrace.count is None:
        dtrace.stop(len(reqs))
    compiles.stop()
    log({"window": {"seconds": window_s, "requests": len(reqs),
                    "moves": sum(len(r.moves) for r in reqs),
                    "jit_recompiles": _counter("batch.jit_recompiles")
                    - recompiles0,
                    "compiles": compiles.count}})
    peak_bytes = memory_peak()
    spans = None
    if spans_tracer is not None:
        from repro import obs
        spans = obs.stop_tracing()[span_mark:]
    wl.session = None                   # free the program's state

    result = _check.check(wl, reqs, config["planner"], seed)
    log({"check": {"reference_s": result["reference_s"],
                   "compared": result["compared"],
                   "divergence": result["divergence"]}})

    ctx = Context(n_osds=dep.n_dev, setup_s=setup_s, requests=reqs,
                  spans=spans)
    dev = device_info()
    ctx.device_kind = dev["kind"]
    if dtrace:
        t0 = time.perf_counter()
        ctx.reduced = dtrace.reduce()
        ctx.traced = reqs[:dtrace.count]
        log({"trace": {"requests": dtrace.count, "stop_s": dtrace.stop_s,
                       "reduce_s": time.perf_counter() - t0,
                       "window_s": ctx.reduced["window_s"]}})
    metrics = {}
    for m in metrics_for(bench, workload, trace):
        value = load_metric(m["name"], metrics_dir).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev["memory_peak_bytes"] = peak_bytes
    out = {"correct": _check.correct(result), "attempted": len(reqs),
           "failed": result["failed"], "metrics": metrics, "device": dev}
    if ctx.reduced:
        dev["busy_s"] = ctx.reduced["busy_s"]
        dev["window_s"] = ctx.reduced["window_s"]
        out["breakdown"] = {"device_ops": ctx.reduced["device_ops"],
                            "idle_gaps": ctx.reduced["idle_gaps"]}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in result["checks"].items()}
    return out


class _CompileCount:
    """Programs JAX obtained (compiled, or read from the persistent
    cache) between construction and :meth:`stop`."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring
        self.count = 0
        self.active = True
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if self.active and event in self.EVENTS:
            self.count += 1

    def stop(self):
        self.active = False


def print_checks(out: dict, stream=sys.stderr) -> None:
    for name, c in out["checks"].items():
        print(f"{name} {c['value']} limit {c['limit']}", file=stream)
