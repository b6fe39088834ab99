"""Run one cell of ``BENCHMARK.json`` once and build its result line.

Everything about a cell is found by name, so a later cell, configuration,
traffic mix or per-layer metric is added with files alone:

- the cell is the ``workloads`` entry of ``BENCHMARK.json`` (its config,
  traffic and chips);
- ``bench/configs/<config>.json`` is the deployment (sizes, semantics);
- ``bench/traffic/<traffic>.json`` is the mix, and names its generator,
  ``bench/drivers/<driver>.py``;
- ``bench/metrics/<metric>.py`` reads one per-layer metric from the run's
  record (a ``read(rec)`` that returns a number, or None when the run has
  nothing to read);
- the end-to-end metrics a cell reports are those of ``BENCHMARK.json``
  whose ``workloads`` list holds it (or that have none); the cell's driver
  measures each, and ``setup_s`` is this module's.

A driver module has ``setup(ctx)``, ``window(state, seconds, tracer)``,
``report(state)``, ``release(state)`` and ``check(state)``; ``check``
returns ``(name, number, limit)`` triples, each passing when number <=
limit.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import resource
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoChip(RuntimeError):
    """The machine lacks the accelerator or the chips a cell asks for."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def spec() -> Dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_module(sub: str, name: str):
    """bench/<sub>/<name>.py, imported by path (names may hold dots)."""
    path = os.path.join(BENCH, sub, name + ".py")  # sub "" is bench/
    if not os.path.exists(path):
        raise FileNotFoundError(f"{path} not found")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{sub}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_cell(name: str) -> Dict:
    bench = spec()
    wl = _by_name(bench["workloads"], name, "workload")
    cfg_entry = _by_name(bench["configs"], wl["config"], "config")
    return {
        "name": name,
        "chips": int(wl["chips"]),
        "config": load_json(os.path.join(ROOT, cfg_entry["file"])),
        "traffic": load_json(
            os.path.join(BENCH, "traffic", wl["traffic"] + ".json")),
        "end_to_end": _applies(bench["end_to_end"], name),
        "per_layer": _applies(bench["per_layer"], name),
    }


def _applies(metrics, cell):
    return [m for m in metrics
            if "workloads" not in m or cell in m["workloads"]]


def load_peaks(kind: str) -> Dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def chips_in_use(chips: int):
    """The first ``chips`` accelerator devices; raises NoChip without them."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


@dataclass
class Ctx:
    """What a driver gets: the cell's files, the seed, the window length,
    and a timer for the parts of set-up."""

    name: str
    seed: int
    seconds: float
    chips: int
    config: Dict
    traffic: Dict
    setup_parts: Dict[str, float] = field(default_factory=dict)

    @contextlib.contextmanager
    def phase(self, part: str):
        t = time.perf_counter()
        try:
            yield
        finally:
            self.setup_parts[part] = (self.setup_parts.get(part, 0.0)
                                      + time.perf_counter() - t)


class Tracer:
    """Profiles the first ``seconds`` of a window, at the traffic driver's
    chunk boundaries, under the host span ``bench.trace``; off, it only
    hands out no-op spans."""

    def __init__(self, enabled: bool, seconds: float, directory: str):
        self.enabled = enabled
        self.seconds = float(seconds)
        self.dir = directory
        self.state = "idle"
        self._t0 = 0.0
        self._span = None
        self.path = None

    def tick(self) -> bool:
        """Called before each chunk; starts the profiler on the first call
        and returns True while the chunk about to run is traced."""
        if not self.enabled or self.state == "done":
            return False
        if self.state == "idle":
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation("bench.trace")
            self._span.__enter__()
            self.state, self._t0 = "on", time.perf_counter()
        return not self.over()

    def over(self) -> bool:
        """True once a traced run's traced time is up: the traffic driver
        ends its window there, and only then stops the profiler, whose stop
        takes seconds of host time that must not fall inside the window."""
        return self.state == "on" and (
            time.perf_counter() - self._t0 >= self.seconds)

    def span(self, name: str):
        if self.state != "on":
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def stop(self) -> None:
        if self.state != "on":
            return
        import jax

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"
        found = []
        for dirpath, _, files in os.walk(self.dir):
            found += [os.path.join(dirpath, f) for f in files
                      if f.endswith(".xplane.pb")]
        self.path = found[0] if found else None


_COMPILES = None


def compile_counter() -> Dict[str, float]:
    """Process-wide tallies of JAX's compile events (backend compiles,
    persistent-cache hits and misses, and the seconds of each kind of
    compile work), registered once; snapshot it around a phase."""
    global _COMPILES
    if _COMPILES is None:
        import jax.monitoring as mon

        counts: Dict[str, float] = {}

        def on_event(event, **_):
            counts[event] = counts.get(event, 0) + 1

        def on_duration(event, duration, **_):
            counts[event] = counts.get(event, 0) + 1
            counts[event + " s"] = counts.get(event + " s", 0.0) + duration

        mon.register_event_listener(on_event)
        mon.register_event_duration_secs_listener(on_duration)
        _COMPILES = counts
    return _COMPILES


def _delta(after: Dict[str, float], before: Dict[str, float]):
    return {k: v - before.get(k, 0) for k, v in after.items()
            if "compil" in k and v != before.get(k, 0)}


def _samples(values) -> Dict[str, float]:
    """A list of samples in the record's log line: count, median, p95,
    max, mean and standard deviation."""
    import numpy as np

    a = np.asarray(values, dtype=np.float64)
    if a.size == 0:
        return {"n": 0}
    return {"n": int(a.size), "p50": float(np.median(a)),
            "p95": float(np.percentile(a, 95)), "max": float(a.max()),
            "mean": float(a.mean()), "sd": float(a.std())}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _device_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True,
             peaks: Optional[Dict] = None,
             adjust: Optional[Callable[[Dict], None]] = None
             ) -> Tuple[Dict, List]:
    """One run of one cell; returns (result line, checks).

    ``require_chip=False`` skips the look for a chip (the rehearsal on the
    CPU and the tests); ``adjust`` may edit the loaded cell in place (a
    rehearsal's smaller sizes); ``peaks`` overrides the table's entry."""
    cell = load_cell(name)
    if adjust is not None:
        adjust(cell)
    import jax

    if require_chip:
        devices = chips_in_use(cell["chips"])
    else:
        devices = jax.devices()[:cell["chips"]]
    kind = devices[0].device_kind
    peaks = load_peaks(kind) if peaks is None else peaks
    driver = load_module("drivers", cell["traffic"]["driver"])
    ctx = Ctx(name=name, seed=int(seed), seconds=float(seconds),
              chips=cell["chips"], config=cell["config"],
              traffic=cell["traffic"])
    ctx.setup_parts["start"] = time.perf_counter() - t_start
    compiles = compile_counter()
    at_start = dict(compiles)
    state = driver.setup(ctx)
    setup_s = time.perf_counter() - t_start
    log(f"# setup_s {setup_s} parts {json.dumps(ctx.setup_parts)}")
    log(f"# setup compiles {json.dumps(_delta(compiles, at_start))}")
    at_window = dict(compiles)

    trace_root = os.environ.get("TMPDIR") or tempfile.gettempdir()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_", dir=trace_root)
    tracer = Tracer(trace, float(cell["traffic"].get("trace_seconds", 2.0)),
                    trace_dir)
    try:
        driver.window(state, float(seconds), tracer)
        in_window = _delta(compiles, at_window)
        tracer.stop()
        log(f"# window compiles {json.dumps(in_window)}")
        out = driver.report(state)
        memory_peak = _device_peak(devices)
        rec = dict(out["rec"])
        rec["peaks"] = peaks
        rec["chips"] = cell["chips"]
        rec["window_backend_compiles"] = in_window.get(
            "/jax/core/compile/backend_compile_duration", 0)
        summary = None
        if trace:
            if tracer.path is None:
                raise RuntimeError("the traced run wrote no trace")
            summary = load_module("", "trace").reduce(
                tracer.path, devices=[d.id for d in devices])
            rec["trace"] = summary
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)

    metrics = {}
    if trace:
        for m in cell["per_layer"]:
            value = load_module("metrics", m["name"]).read(rec)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        e2e = dict(out["e2e"], setup_s=setup_s)
        for m in cell["end_to_end"]:
            if m["name"] not in e2e:
                raise KeyError(f"driver reported no {m['name']!r}")
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}
    log("# record " + json.dumps(
        {k: _samples(v) if isinstance(v, list) else v
         for k, v in rec.items() if k != "trace"}, default=str))
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    result = {"attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}

    # The reference runs once the window's numbers are read and the
    # program's state is freed.
    driver.release(state)
    gc.collect()
    t = time.perf_counter()
    checks = driver.check(state)
    log(f"# check_s {time.perf_counter() - t}")
    log(f"# host_peak_rss_bytes {1024 * resource.getrusage(resource.RUSAGE_SELF).ru_maxrss}")
    correct = all(value <= lim for _, value, lim in checks)
    result = {"correct": bool(correct), **result,
              "checks": {n: {"value": v, "limit": lim}
                         for n, v, lim in checks}}
    return result, checks
