"""Plumbing shared by every cell: finding a cell by name, the chip check,
the compile cache, host spans, the profiler window, the per-layer metric
readers and the result line.

Nothing here knows a configuration, a mix or a metric by name: those are
files under ``configs/``, ``mixes/`` and ``metrics/``, found through
``BENCHMARK.json``.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import shutil
import sys
import time
from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from . import devtrace

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# run outputs (trace directories, data files, profiler traces), made anew
# by every run of a cell; and JAX's persistent compilation cache, at a
# fixed path because the path is part of the cache's key
OUT = ROOT / ".bench_out"
CACHE = ROOT / ".bench_cache" / "jax"


def load_json(path: Path) -> Dict[str, Any]:
    return json.loads(Path(path).read_text())


def benchmark(held: bool = False) -> Dict[str, Any]:
    """``BENCHMARK.json``; with ``held`` also the cells of ``held.json``:
    built and rehearsed, not admitted to the benchmark, so its own runs
    never ask for them.  A held metric entry of a name the benchmark
    already has adds its cells to that entry's ``workloads``."""
    bench = load_json(ROOT / "BENCHMARK.json")
    if not held:
        return bench
    for key, entries in load_json(BENCH / "held.json").items():
        have = {e["name"]: e for e in bench[key]}
        for e in entries:
            if e["name"] not in have:
                bench[key].append(e)
            elif "workloads" in have[e["name"]]:
                have[e["name"]]["workloads"].extend(e.get("workloads", []))
    return bench


@dataclass
class Cell:
    """One entry of ``workloads`` with its configuration and mix loaded."""
    name: str
    chips: int
    config_name: str
    traffic: str
    config: Dict[str, Any]
    mix: Dict[str, Any]
    bench: Dict[str, Any]
    root: Path = OUT

    @property
    def out(self) -> Path:
        return self.root / self.name

    def end_to_end(self) -> List[Dict[str, Any]]:
        """The end-to-end metrics this cell reports."""
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> List[Dict[str, Any]]:
        """The per-layer metrics read in this cell's traced run: those that
        list it, and those without a list that move one of its end-to-end
        metrics."""
        e2e = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]


def find_cell(name: str, bench: Optional[Dict[str, Any]] = None) -> Cell:
    """The cell ``name`` of ``bench`` (by default ``BENCHMARK.json``, then
    the held cells)."""
    if bench is None:
        bench = benchmark()
        if name not in {w["name"] for w in bench["workloads"]}:
            bench = benchmark(held=True)
    for w in bench["workloads"]:
        if w["name"] == name:
            break
    else:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    conf = next((c for c in bench["configs"] if c["name"] == w["config"]),
                None)
    if conf is None:
        raise SystemExit(f"workload {name!r} names configuration "
                         f"{w['config']!r}, which BENCHMARK.json lacks")
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                traffic=w["traffic"], config=load_json(ROOT / conf["file"]),
                mix=load_json(BENCH / "mixes" / f"{w['traffic']}.json"),
                bench=bench)


def fresh_dir(path: Path) -> Path:
    shutil.rmtree(path, ignore_errors=True)
    path.mkdir(parents=True)
    return path


def dir_bytes(path: Path) -> int:
    """All bytes the files under ``path`` hold."""
    return sum(p.stat().st_size for p in path.rglob("*") if p.is_file())


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the chip, the compile cache, compilations
# ---------------------------------------------------------------------------


def device_line(chips: int, rehearse: bool = False) -> Dict[str, Any]:
    """The device as JAX reports it; without ``rehearse`` anything but a
    TPU with at least ``chips`` devices ends the run with no result."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if not rehearse:
        if d.platform != "tpu":
            raise SystemExit(f"no TPU: JAX's first device is {d.platform!r}"
                             f" ({d.device_kind}); this benchmark runs on "
                             f"the chip only")
        if len(devs) < chips:
            raise SystemExit(f"the cell needs {chips} chips, JAX sees "
                             f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> Optional[int]:
    """Peak bytes in use on the fullest local device, where reported."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def enable_compile_cache(rehearse: bool = False) -> None:
    """JAX's persistent compilation cache in the checkout, for every
    program however small, so only a checkout's first run compiles.  A
    rehearsal on the CPU keeps no cache."""
    if rehearse:
        return
    import jax
    CACHE.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts the backend compilations JAX makes (a persistent-cache hit
    makes none); ``window()`` marks the measured window."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax.monitoring
        self.in_window = 0
        self._open = False
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT and self._open:
            self.in_window += 1

    @contextlib.contextmanager
    def window(self) -> Iterator[None]:
        self._open = True
        try:
            yield
        finally:
            self._open = False


# ---------------------------------------------------------------------------
# host spans and the profiler window
# ---------------------------------------------------------------------------


class Spans:
    """Durations (s) of host spans by name.  With ``annotate`` each span is
    also a ``jax.profiler.TraceAnnotation`` named ``bench.<name>``, so the
    profiler's trace can say what the host did in a device gap."""

    def __init__(self, annotate: bool = False) -> None:
        self.annotate = annotate
        self.durations: Dict[str, List[float]] = defaultdict(list)

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
        else:
            ann = contextlib.nullcontext()
        t0 = time.perf_counter()
        with ann:
            try:
                yield
            finally:
                self.durations[name].append(time.perf_counter() - t0)

    def add(self, name: str, seconds: float) -> None:
        self.durations[name].append(seconds)


def instrument(rec, ticks, spans: Optional[Spans] = None,
               span_each_record: bool = False) -> None:
    """Wrap a Recorder's bound methods on the instance: every tick its
    clock returns is appended to ``ticks`` (the plain log the read-back is
    checked against); with ``spans`` a record call in which the epoch
    count moved, the call that flushed, is timed as span ``flush``, and
    with ``span_each_record`` every record call is also span ``record``
    (an annotation the trace names idle gaps by)."""
    now = rec.now

    def logged_now() -> int:
        t = now()
        ticks.append(t)
        return t

    rec.now = logged_now
    if spans is None:
        return
    record = rec.record

    def timed_record(*args):
        e = rec.epoch
        t0 = time.perf_counter()
        if span_each_record:
            with spans.span("record"):
                record(*args)
        else:
            record(*args)
        if rec.epoch != e:
            spans.add("flush", time.perf_counter() - t0)

    rec.record = timed_record


@contextlib.contextmanager
def profiled(enabled: bool, out_dir: Path) -> Iterator[Dict[str, Any]]:
    """Trace the enclosed window with JAX's profiler when ``enabled``; the
    yielded dict gets the path of the written ``.xplane.pb``.  The window
    is the host span ``bench.window``, which the reduction reads."""
    found: Dict[str, Any] = {"xplane": None, "dir": out_dir}
    if not enabled:
        yield found
        return
    import jax
    fresh_dir(out_dir)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(devtrace.WINDOW):
            yield found
    finally:
        jax.profiler.stop_trace()
        pbs = sorted(out_dir.glob("plugins/profile/*/*.xplane.pb"))
        found["xplane"] = pbs[-1] if pbs else None


def device_summary(found: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The reduced trace of a ``profiled`` window (None when none was
    written); the trace file, tens of MB, is removed once read."""
    if found["xplane"] is None:
        return None
    try:
        return devtrace.read(found["xplane"])
    finally:
        shutil.rmtree(found["dir"], ignore_errors=True)


# ---------------------------------------------------------------------------
# per-layer metric readers
# ---------------------------------------------------------------------------


def read_metric(name: str, ctx: Dict[str, Any]) -> Optional[float]:
    """Run ``metrics/<name>.py``'s ``read(ctx)``; None when it finds
    nothing to read (the metric is then left out of the line)."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    value = mod.read(ctx)
    return None if value is None else float(value)


# ---------------------------------------------------------------------------
# the result line
# ---------------------------------------------------------------------------


@dataclass
class Check:
    """One number compared, with its limit (the run is correct only when
    every ``value <= limit``)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Outcome:
    """What a kind's module hands back: the device line, the compared numbers,
    the work attempted and failed, the end-to-end metrics by name, and
    what the per-layer readers read (spans, counters, the device trace
    summary)."""
    device: Optional[Dict[str, Any]]
    checks: List[Check]
    attempted: int
    failed: int
    e2e: Dict[str, float]
    spans: Spans
    counters: Dict[str, Any]
    devtrace: Optional[Dict[str, Any]] = None


def emit(*, checks: Sequence[Check], attempted: int, failed: int,
         metrics: Dict[str, Tuple[float, str]], device: Dict[str, Any],
         breakdown: Optional[Dict[str, Any]] = None) -> bool:
    """Print each compared number beside its limit as the last lines of
    standard error, and the result as the last line of standard output;
    returns ``correct``."""
    correct = bool(checks) and all(c.ok for c in checks)
    line: Dict[str, Any] = {
        "correct": correct,
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {k: {"value": v, "unit": u}
                    for k, (v, u) in metrics.items()},
        "device": device,
    }
    if breakdown:
        line["breakdown"] = breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in checks}
    sys.stdout.flush()
    for c in checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r})"
              f"{'' if c.ok else ' FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return correct
