"""The Recorder's own spans and counters (process-wide, off by default).

The tracer measures where its own time goes: the record path's stages,
the phases of a flush and of finalize, and the encode ops with the
backend each ran on.  Turned on with :func:`enable`, it keeps

* spans -- name, ``perf_counter_ns`` start and end, the thread, and the
  id of the span that was open on the same thread when it began (its
  parent).  Spans live in memory, at most ``MAX_SPANS`` of them; later
  ones are counted in the counter ``selftrace.spans_dropped``.  Each span
  is also a ``jax.profiler.TraceAnnotation`` named ``recorder.<name>``,
  so a JAX profiler trace shows it on the host plane, on the device
  trace's clock.
* counters -- integers by name (:func:`count`).

:func:`snapshot` returns both as plain JSON-able data.  Turned off (the
default) a span is one flag test returning a shared null context, and a
count is one flag test: nothing is allocated and nothing is kept.  jax is
imported only by :func:`enable`.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

MAX_SPANS = 1 << 16

# read as ``selftrace.on`` at each instrumented point; set by enable()
on = False

_NULL = contextlib.nullcontext()
_lock = threading.Lock()
_ids = itertools.count()
_tls = threading.local()
# (name, id, parent id or None, start ns, end ns, thread name)
_spans: List[Tuple[str, int, Optional[int], int, int, str]] = []
_counters: Dict[str, int] = {}
_annotation: Any = None


def enable() -> None:
    """Start keeping spans and counters (what was kept stays)."""
    global on, _annotation
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:     # no jax: spans stay off the profiler's clock
        TraceAnnotation = None
    _annotation = TraceAnnotation
    on = True


def disable() -> None:
    """Stop keeping spans and counters; what was kept stays readable."""
    global on
    on = False


def reset() -> None:
    """Forget every span and counter kept so far."""
    with _lock:
        _spans.clear()
        _counters.clear()


def snapshot() -> Dict[str, Any]:
    """``{"spans": [...], "counters": {...}}``: each span a dict with
    ``name``, ``id``, ``parent``, ``start_ns``, ``end_ns`` and
    ``thread``, in the order the spans ended."""
    with _lock:
        spans = list(_spans)
        counters = dict(_counters)
    keys = ("name", "id", "parent", "start_ns", "end_ns", "thread")
    return {"spans": [dict(zip(keys, s)) for s in spans],
            "counters": counters}


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` (nothing while off)."""
    if not on:
        return
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def span(name: str):
    """Context manager timing the enclosed block as span ``name``."""
    if not on:
        return _NULL
    return _Span(name)


class _Span:
    __slots__ = ("name", "id", "parent", "t0", "ann")

    def __init__(self, name: str) -> None:
        self.name = name

    def __enter__(self) -> "_Span":
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        self.parent = stack[-1] if stack else None
        self.id = next(_ids)
        stack.append(self.id)
        self.ann = (_annotation(f"recorder.{self.name}")
                    if _annotation is not None else None)
        if self.ann is not None:
            self.ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        _tls.stack.pop()
        rec = (self.name, self.id, self.parent, self.t0, t1,
               threading.current_thread().name)
        with _lock:
            if len(_spans) < MAX_SPANS:
                _spans.append(rec)
            else:
                _counters["selftrace.spans_dropped"] = \
                    _counters.get("selftrace.spans_dropped", 0) + 1
