"""Intra-process I/O pattern recognition (paper Section 3.2.1).

Offsets of repeated calls often follow ``offset_i = i*a + b``.  Recorder
checks, at interception time, whether the current offset extends the active
arithmetic run for this call's *pattern key* (function, thread, handle, and
all non-offset arguments).  If it does, the offset is encoded as the pair
``(a, b)`` so that every call of the run shares one call signature; otherwise
the concrete offset is stored and a new run begins.

Encoding protocol (mirrored exactly by the trace reader):

  i == 0           -> concrete offset ``b`` (starts a run)
  i >= 1, matches  -> ``IterPattern(a, b)`` with ``a = off_1 - off_0``
  mismatch         -> concrete offset, run restarts at i == 0

Calls with multiple OFFSET-role arguments are tracked jointly (a shared run
index with per-component strides), so e.g. ``(offset, whence)`` pairs or
framework step counters compress with the same machinery.

Paths are patterns too (paper Section 3.2.1 applied to PATH-role
arguments): :func:`split_path` splits a path into its literal template and
its decimal fields.  The template joins the pattern key and the fields join
the call's run values, after its OFFSET-role values, so ``open(f"file.{i}")``
for ``i = 0, 1, 2, ...`` is one run and its calls share one signature, whose
path is a ``PathPattern`` of ``IterPattern`` fields.  A path with no field
stays a literal string.  Where a call's only run values are its paths'
fields, a call that repeats its run's first values (the same path again)
is stored concretely and starts no stride (``repeat_concrete``): reopening
one file costs one signature, as a literal path does.

``IntraPatternTracker.encode_many`` is the batched entry point: it encodes a
whole sequence of calls for one key at once, finding arithmetic runs with
the segmentation helper :func:`arith_segments`, and is result- and
state-equivalent to calling :meth:`encode` per call.  The benchmark
drivers use it to synthesize large simulated-rank streams.

The record path keeps, in each run, the CST terminal of the signature
that every call continuing the run shares (and the call depth it was
interned at).  Its generated record code (``recorder.record_code``) looks
the run up in ``IntraPatternTracker.runs`` and, on a call that continues
it at that depth, only advances the run and reuses the terminal; any
other call goes through ``IntraPatternTracker.encode_run``.  A broken
run is a new ``_RunState``, so the terminal cannot outlive its run.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from .encoding import IterPattern

# a path's digit runs (ASCII only: a run renders back from its int)
_DIGIT_RUNS = re.compile(r"([0-9]+)")
# the longest digit run that is a field: every such int fits in int64
MAX_FIELD_DIGITS = 18


def split_path(path: str) -> Tuple[Any, Tuple[int, ...]]:
    """``(template, fields)`` of a path.  A field is a maximal run of
    decimal digits with no leading zero (or exactly ``0``) of at most
    :data:`MAX_FIELD_DIGITS` digits; any other run stays literal, so
    ``render_path(*split_path(p)) == p`` for every string.  The template
    is the tuple of ``len(fields) + 1`` literal pieces around the fields,
    or, for a path with no field, the path itself."""
    parts = _DIGIT_RUNS.split(path)
    if len(parts) == 1:
        return path, ()
    pieces = [parts[0]]
    fields = []
    for k in range(1, len(parts), 2):
        run = parts[k]
        if len(run) <= MAX_FIELD_DIGITS and (run[0] != "0" or len(run) == 1):
            fields.append(int(run))
            pieces.append(parts[k + 1])
        else:
            pieces[-1] += run + parts[k + 1]
    if not fields:
        return path, ()
    return tuple(pieces), tuple(fields)


def render_path(template: Any, fields: Sequence[int]) -> str:
    """The literal path of ``template`` with int ``fields`` (the inverse
    of :func:`split_path`)."""
    if not fields:
        return template
    out = [template[0]]
    for f, piece in zip(fields, template[1:]):
        out.append(str(f))
        out.append(piece)
    return "".join(out)


@dataclass
class _RunState:
    index: int                      # how many calls matched this run so far
    base: Tuple[int, ...]           # offsets of call 0
    stride: Optional[Tuple[int, ...]]  # set at call 1
    # the record path's memo: the CST terminal of the run's IterPattern
    # signature, interned at call depth ``depth``
    terminal: Optional[int] = None
    depth: Optional[int] = None


Encoded = Union[int, IterPattern]


def continues(run: _RunState, vals: Sequence[int]) -> bool:
    """Whether ``vals`` are ``run``'s next values (``base + index *
    stride``); ``run.stride`` must be set."""
    if len(vals) != len(run.base):
        return False
    i = run.index
    for v, b, s in zip(vals, run.base, run.stride):
        if v != b + i * s:
            return False
    return True


class IntraPatternTracker:
    """Per-process tracker; keys must be hashable and derivable by the reader
    from decoded records (it uses the same key function on decoded args)."""

    def __init__(self, enabled: bool = True) -> None:
        self.enabled = enabled
        # pattern key -> its active run
        self.runs: Dict[Any, _RunState] = {}

    def encode(self, key: Any, offsets: Sequence[int],
               repeat_concrete: bool = False) -> List[Encoded]:
        """Encode the run values of one call: its OFFSET-role values, then
        its paths' fields.  With ``repeat_concrete`` (the values are all
        path fields) a call whose values equal its run's first starts no
        stride and is encoded concretely."""
        vals = tuple(int(v) for v in offsets)
        if not self.enabled or not vals:
            return list(vals)
        return self._encode(key, self.runs.get(key), vals, repeat_concrete)

    def encode_run(self, key: Any, run: Optional[_RunState],
                   offsets: Sequence[int], keep: bool,
                   repeat_concrete: bool = False
                   ) -> Tuple[Optional[_RunState], List[Encoded]]:
        """:meth:`encode` for the record path, given ``key``'s run as it
        looked it up (``runs.get(key)``): one call's run values (ints),
        whose signature the caller keeps in the run if ``keep``.

        Returns what :meth:`encode` returns, after the run it belongs to:
        the run whose ``terminal`` and ``depth`` the caller sets once it
        has interned the signature, or None where the values are concrete
        or ``keep`` is false."""
        vals = tuple(int(v) for v in offsets)
        if not self.enabled or not vals:
            return None, list(vals)
        encoded = self._encode(key, run, vals, repeat_concrete)
        if not keep or not isinstance(encoded[0], IterPattern):
            return None, encoded
        return run, encoded

    def _encode(self, key: Any, run: Optional[_RunState],
                vals: Tuple[int, ...],
                repeat_concrete: bool = False) -> List[Encoded]:
        """:meth:`encode` given ``key``'s run as looked up."""
        if run is None:
            self.runs[key] = _RunState(index=1, base=vals, stride=None)
            return list(vals)
        if run.stride is None:
            if repeat_concrete and vals == run.base:
                # the same path again: the run stays at its first call
                return list(vals)
            stride = tuple(v - b for v, b in zip(vals, run.base))
            run.stride = stride
            run.index = 2
            return [IterPattern(a, b) for a, b in zip(stride, run.base)]
        expected = tuple(b + run.index * a for a, b in zip(run.stride, run.base))
        if vals == expected:
            run.index += 1
            return [IterPattern(a, b) for a, b in zip(run.stride, run.base)]
        # run broken: restart
        self.runs[key] = _RunState(index=1, base=vals, stride=None)
        return list(vals)

    def encode_many(self, key: Any, rows: Sequence[Sequence[int]],
                    backend: Optional[str] = None) -> List[List[Encoded]]:
        """Batched :meth:`encode`: one call per row, vectorized.

        Equivalent (outputs and final run state) to
        ``[self.encode(key, r) for r in rows]``, but arithmetic runs are
        found with one segmentation pass (``backend`` dispatches it:
        NumPy or the grammar_stats boundary kernel) instead of per-call
        Python work.  Falls back to the scalar loop for ragged/empty
        arities or values outside the int64-safe range.
        """
        rows = [tuple(int(v) for v in r) for r in rows]
        if not self.enabled or not rows:
            return [list(r) for r in rows]
        k = len(rows[0])
        if k == 0 or any(len(r) != k for r in rows):
            return [self.encode(key, r) for r in rows]
        try:
            V = np.asarray(rows, dtype=np.int64)
        except (OverflowError, ValueError):
            return [self.encode(key, r) for r in rows]
        if np.abs(V).max(initial=0) >= (1 << 62):
            return [self.encode(key, r) for r in rows]

        out: List[List[Encoded]] = []
        n = len(rows)
        p = 0  # rows consumed by continuing a pre-existing run
        run = self.runs.get(key)
        if run is not None and len(run.base) == k:
            if run.stride is None:
                # second element of the active run: always matches and
                # fixes the stride
                stride = tuple(v - b for v, b in zip(rows[0], run.base))
                run.stride = stride
                run.index = 2
                out.append([IterPattern(a, b)
                            for a, b in zip(stride, run.base)])
                p = 1
            if p < n and run.stride is not None:
                # keep b + i*a exact in int64 (else defer to Python ints)
                bound = (max(abs(v) for v in run.base)
                         + (run.index + n) * max(
                             (abs(a) for a in run.stride), default=0))
                if bound >= (1 << 62):
                    return out + [self.encode(key, r) for r in rows[p:]]
                base = np.asarray(run.base, dtype=np.int64)
                stride = np.asarray(run.stride, dtype=np.int64)
                idx = run.index + np.arange(n - p, dtype=np.int64)
                expected = base[None, :] + idx[:, None] * stride[None, :]
                bad = (V[p:] != expected).any(axis=1)
                m = int(np.argmax(bad)) if bad.any() else n - p
                if m:
                    pat = [IterPattern(a, b)
                           for a, b in zip(run.stride, run.base)]
                    out.extend(list(pat) for _ in range(m))
                    run.index += m
                    p += m
                if p < n:
                    run = None  # run broken: remaining rows start fresh
        elif run is not None:
            # arity changed mid-stream: defer to the scalar protocol
            return out + [self.encode(key, r) for r in rows]

        if p < n:
            W = V[p:]
            segs = arith_segments(W, backend=backend)
            for s, e in segs:
                base = tuple(int(v) for v in W[s])
                out.append(list(base))
                if e - s >= 2:
                    stride = tuple(int(v) for v in (W[s + 1] - W[s]))
                    pat = [IterPattern(a, b) for a, b in zip(stride, base)]
                    out.extend(list(pat) for _ in range(e - s - 1))
                    self.runs[key] = _RunState(index=e - s, base=base,
                                               stride=stride)
                else:
                    self.runs[key] = _RunState(index=1, base=base,
                                               stride=None)
        return out


def arith_segments(V: np.ndarray,
                   backend: Optional[str] = None) -> List[Tuple[int, int]]:
    """Greedy arithmetic-run segmentation of a (n, k) value matrix.

    Returns half-open ``(start, end)`` element segments such that within a
    segment every consecutive row difference equals the segment's first
    difference (the run stride), mirroring the streaming protocol of
    ``IntraPatternTracker``: a run's stride is set by its second element and
    the run breaks at the first non-matching row.  ``backend`` dispatches
    the change-point scan (``encode_backend.run_boundaries`` over the diff
    rows); segmentation is identical across backends.
    """
    n = len(V)
    if n == 0:
        return []
    if n == 1:
        return [(0, 1)]
    d = V[1:] - V[:-1]
    if d.ndim == 1:
        d = d[:, None]
    # cp[j] for j >= 1: diff j differs from diff j-1
    from . import encode_backend as _eb
    mask = _eb.run_boundaries(d, backend)
    mask[0] = False  # position 0 is forced True by the boundary op
    cp = np.flatnonzero(mask)
    segs: List[Tuple[int, int]] = []
    s = 0
    while s < n:
        if s >= n - 1:
            segs.append((s, n))
            break
        # largest run of equal diffs starting at diff index s
        k = int(np.searchsorted(cp, s, side="right"))
        c = int(cp[k]) if k < len(cp) else n - 1
        segs.append((s, c + 1))
        s = c + 1
    return segs


class IntraPatternDecoder:
    """Reader-side inverse of :class:`IntraPatternTracker`.

    The decoder tracks, per pattern key, the occurrence index of the active
    run and materializes concrete offsets from ``IterPattern`` values.
    """

    def __init__(self) -> None:
        self._runs: Dict[Any, Tuple[int, Tuple]] = {}  # key -> (index, pattern sig)

    def decode(self, key: Any, encoded: Sequence[Encoded]) -> List[int]:
        if not encoded:
            return []
        if not any(isinstance(v, IterPattern) for v in encoded):
            # concrete call: (re)starts a run at index 0
            self._runs[key] = (1, None)
            return [int(v) for v in encoded]  # type: ignore[arg-type]
        patsig = tuple((v.a, v.b) if isinstance(v, IterPattern) else v
                       for v in encoded)
        idx, prev_sig = self._runs.get(key, (1, None))
        if prev_sig is not None and prev_sig == patsig:
            idx += 1
        # else: this is the first encoded call of the run (index 1)
        out: List[int] = []
        for v in encoded:
            if isinstance(v, IterPattern):
                out.append(int(v.b) + idx * int(v.a))
            else:
                out.append(int(v))
        self._runs[key] = (idx, patsig)
        return out


def pattern_key(func_id: int, thread_id: int, handle_ids: Tuple, other_args: Tuple) -> Tuple:
    """The pattern key shared by tracker and decoder."""
    return (func_id, thread_id, handle_ids, other_args)
