"""From a JAX profiler trace to device metrics.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it into planes, lines and events (start
and duration in nanoseconds, one clock for host and device planes).  The
harness marks the measured window with the host span ``bench.window`` and
what the host does inside it with ``bench.<name>`` spans.

- busy: the union of the intervals in which an operation runs on a
  device, inside the window, averaged over the devices traced;
- idle share: 1 - busy / window;
- collective time: the union of the collective operations' intervals;
- breakdown: the device operations that took most time (self time, the
  operations nested in a loop taken out of it, summed by kind: the HLO
  name without its ``%`` and trailing ``.N``), and the longest idle gaps,
  each named by the innermost host span that covers its middle.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_ns, end_ns)
Event = Tuple[str, float, float]        # (name, start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW = "bench.window"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|ppermute|"
                        r"\bsend\b|\brecv\b")
TOP = 10


def _events(line) -> List[Event]:
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def device_ops(pd, plane: "re.Pattern[str]" = DEVICE_PLANE,
               line: str = OP_LINE) -> Dict[str, List[Event]]:
    """Per device plane, the events of its operations line."""
    out: Dict[str, List[Event]] = {}
    for p in pd.planes:
        if plane.match(p.name):
            for ln in p.lines:
                if ln.name == line:
                    out[p.name] = _events(ln)
    return out


def host_spans(pd, prefix: str = SPAN_PREFIX) -> List[Event]:
    """The harness's ``bench.*`` host spans, from every host line."""
    out: List[Event] = []
    for p in pd.planes:
        if p.name == HOST_PLANE:
            for ln in p.lines:
                out.extend(e for e in _events(ln) if e[0].startswith(prefix))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def clip_events(evs: Iterable[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in evs
            if e > lo and s < hi]


def covered_ns(intervals: Iterable[Interval], lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(clip(intervals, lo, hi)))


def idle_gaps(intervals: Iterable[Interval], lo: float, hi: float
              ) -> List[Interval]:
    gaps, t = [], lo
    for s, e in union(clip(intervals, lo, hi)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def gap_label(gap: Interval, spans: Sequence[Event]) -> str:
    """The innermost (shortest) host span covering the gap's middle."""
    mid = 0.5 * (gap[0] + gap[1])
    inside = [(e - s, n) for n, s, e in spans
              if s <= mid <= e and n != WINDOW]
    return min(inside)[1] if inside else "host:other"


def op_kind(name: str) -> str:
    """``%fusion.728 = bf16[...] fusion(...)`` -> ``fusion``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def self_times(evs: Sequence[Event]) -> List[Tuple[str, float]]:
    """Each event's time less that of the events nested in it (a loop
    operation encloses its body's operations on the same line)."""
    order = sorted(range(len(evs)), key=lambda i: (evs[i][1], -evs[i][2]))
    child = [0.0] * len(evs)
    stack: List[int] = []
    for i in order:
        _, s, e = evs[i]
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            child[stack[-1]] += e - s
        stack.append(i)
    return [(n, (e - s) - c) for (n, s, e), c in zip(evs, child)]


def summarize(ops: Dict[str, List[Event]], spans: Sequence[Event],
              window: Optional[Interval] = None,
              is_collective: Callable[[str], bool] =
              lambda n: bool(COLLECTIVE.search(n))) -> Optional[Dict]:
    """Device busy, window, collective time and the breakdown.  None when
    no device operation ran in the window (nothing to read)."""
    if window is None:
        wins = [(s, e) for n, s, e in spans if n == WINDOW]
        if not wins:
            return None
        window = max(wins, key=lambda w: w[1] - w[0])
    lo, hi = window
    per_dev = {d: [(s, e) for _, s, e in evs] for d, evs in ops.items()}
    busy = [covered_ns(iv, lo, hi) for iv in per_dev.values()]
    if not busy or max(busy) <= 0:
        return None
    n_dev = len(busy)
    coll = [covered_ns([(s, e) for n, s, e in evs if is_collective(n)],
                       lo, hi) for evs in ops.values()]
    by_kind: Dict[str, float] = defaultdict(float)
    for evs in ops.values():
        for n, t in self_times(clip_events(evs, lo, hi)):
            by_kind[op_kind(n)] += t / n_dev
    longest = sorted((g for iv in per_dev.values()
                      for g in idle_gaps(iv, lo, hi)),
                     key=lambda g: g[0] - g[1])[:TOP]
    gaps = [(g[1] - g[0], gap_label(g, spans)) for g in longest]
    ns = 1e-9
    return {
        "busy_s": sum(busy) / n_dev * ns,
        "window_s": (hi - lo) * ns,
        "collective_s": sum(coll) / n_dev * ns,
        "devices": n_dev,
        "breakdown": {
            "device_ops": [[k, v * ns] for k, v in sorted(
                by_kind.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": [[name, g * ns] for g, name in gaps[:TOP]],
        },
    }


def read(xplane_path) -> Optional[Dict]:
    """Summary of one trace file written by ``harness.profiled``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(xplane_path))
    return summarize(device_ops(pd), host_spans(pd))


def merge(summaries: Sequence[Optional[Dict]]) -> Optional[Dict]:
    """Average per-process summaries (one chip each) into one."""
    got = [s for s in summaries if s]
    if not got:
        return None
    n = len(got)
    by_kind: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[float, str]] = []
    for s in got:
        for k, v in s["breakdown"]["device_ops"]:
            by_kind[k] += v / n
        gaps.extend((v, k) for k, v in s["breakdown"]["idle_gaps"])
    gaps.sort(reverse=True)
    return {
        "busy_s": sum(s["busy_s"] for s in got) / n,
        "window_s": max(s["window_s"] for s in got),
        "collective_s": sum(s["collective_s"] for s in got) / n,
        "devices": sum(s["devices"] for s in got),
        "breakdown": {
            "device_ops": [[k, v] for k, v in sorted(
                by_kind.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": [[k, v] for v, k in gaps[:TOP]],
        },
    }
