"""Backend dispatch for the batched encode hot paths (ROADMAP dir. 2).

The arithmetic-dense stages of the tracing pipeline -- timestamp
delta+zigzag, varint packing, arithmetic-run boundary detection and the
symbol-stream histograms -- exist in three interchangeable
implementations:

``python``
    The scalar reference loops.  Slowest, but trivially auditable; the
    property suite (``tests/test_encode_kernels.py``) pins every other
    backend byte-identical to them.

``numpy``
    Vectorized host implementations (this module).  The fastest choice on
    CPU-only hosts for any non-trivial batch.

``pallas``
    The TPU kernels under ``repro.kernels`` (``delta_encode``,
    ``grammar_stats``), compiled on the accelerator.  Where JAX's backend
    is the CPU (the test suite) they run under the Pallas interpreter, so
    CPU-only CI still exercises the kernel arithmetic.

``auto`` (the default) crosses over by batch size: tiny batches stay on
the Python loop (below NumPy's fixed per-call overhead), everything else
runs NumPy, and batches of ``PALLAS_MIN_BATCH``+ move to the kernels when
a non-CPU device is present.  Every backend produces byte-identical
output.  A caller picks one per call (``backend=``; ``None`` means
``auto``), and the Recorder passes ``RecorderConfig.encode_backend``.

jax is imported lazily: the ``python`` and ``numpy`` paths must work (and
the core package must import) on hosts without a usable jax install.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from . import selftrace
from .encoding import VarintRangeError, write_uvarint

BACKENDS = ("auto", "python", "numpy", "pallas")

# crossover points for the "auto" backend (see benchmarks/kernel_bench.py;
# the measured sweep lands in artifacts/bench/encode_kernels.json)
NUMPY_MIN_BATCH = 64         # below: NumPy call overhead beats the loop win
PALLAS_MIN_BATCH = 1 << 16   # below: kernel launch + transfer dominates

_I32_SAFE = 1 << 31

_accel: Optional[bool] = None


def has_accelerator() -> bool:
    """True when JAX's default backend is not the CPU (memoized).  False
    only when jax is not installed, so ``auto`` then stays on numpy; a
    JAX runtime that fails to come up raises here."""
    global _accel
    if _accel is None:
        try:
            import jax
        except ImportError:
            _accel = False
        else:
            _accel = jax.default_backend() != "cpu"
    return _accel


def interpret_mode() -> bool:
    """Kernels run under the Pallas interpreter only where JAX's backend
    is the CPU (the test suite); on an accelerator they are compiled."""
    return not has_accelerator()


def resolve(backend: Optional[str], n: int) -> str:
    """Effective backend for a batch of ``n`` elements: explicit choices
    win; ``auto`` (or ``None``) applies the size crossover."""
    b = backend if backend is not None else "auto"
    if b not in BACKENDS:
        raise ValueError(f"encode backend must be one of {BACKENDS}, "
                         f"got {b!r}")
    if b != "auto":
        return b
    if n < NUMPY_MIN_BATCH:
        return "python"
    if n >= PALLAS_MIN_BATCH and has_accelerator():
        return "pallas"
    return "numpy"


def _op(op: str, backend: str, n: int):
    """Span ``encode.<op>`` around one dispatched call; with selftrace on
    it also counts the call and its ``n`` elements under the backend that
    runs it (``encode.<op>.<backend>.calls`` / ``.elements``), which says
    what ``auto`` chose."""
    if selftrace.on:
        selftrace.count(f"encode.{op}.{backend}.calls")
        selftrace.count(f"encode.{op}.{backend}.elements", n)
    return selftrace.span(f"encode.{op}")


# ---------------------------------------------------------------------------
# delta + zigzag (timestamp pipeline stage)
# ---------------------------------------------------------------------------


def _delta_zigzag_py(flat: np.ndarray) -> np.ndarray:
    """Scalar reference: first-order delta wrapped mod 2^32 -> zigzag u32."""
    out = np.empty(len(flat), np.uint32)
    prev = 0
    for i, v in enumerate(flat.tolist()):
        d = v if i == 0 else v - prev
        prev = v
        d = ((d + (1 << 31)) % (1 << 32)) - (1 << 31)
        out[i] = ((d << 1) ^ (d >> 63)) & 0xFFFFFFFF
    return out


def _delta_zigzag_np(flat: np.ndarray) -> np.ndarray:
    flat = flat.astype(np.int64)        # wrap arithmetic needs headroom
    deltas = np.empty_like(flat)
    deltas[0] = flat[0]
    deltas[1:] = flat[1:] - flat[:-1]
    deltas = ((deltas + (1 << 31)) % (1 << 32)) - (1 << 31)
    zz = (deltas << 1) ^ (deltas >> 63)
    return (zz & 0xFFFFFFFF).astype(np.uint32)


def _delta_zigzag_pallas(flat: np.ndarray) -> np.ndarray:
    import jax.numpy as jnp
    from ..kernels.delta_encode.ops import delta_zigzag
    zz = delta_zigzag(jnp.asarray(flat.astype(np.uint32)),
                      interpret=interpret_mode())
    return np.asarray(zz, dtype=np.uint32)


def delta_zigzag(flat: np.ndarray, backend: Optional[str] = None
                 ) -> np.ndarray:
    """Flat int64 tick stream -> zigzag'd u32 deltas, backend-dispatched.
    All backends are bit-identical (the kernel's int32 two's-complement
    arithmetic matches the mod-2^32 wrap of the reference)."""
    if flat.size == 0:
        return np.empty((0,), np.uint32)
    eff = resolve(backend, flat.size)
    with _op("delta_zigzag", eff, flat.size):
        if eff == "python":
            return _delta_zigzag_py(flat)
        if eff == "pallas":
            return _delta_zigzag_pallas(flat)
        return _delta_zigzag_np(flat)


# ---------------------------------------------------------------------------
# varint packing (u64-guarded; see encoding.pack_uvarints)
# ---------------------------------------------------------------------------


def _emit_varint_bytes(lens: np.ndarray, planes: np.ndarray) -> bytes:
    """Scatter per-element byte planes into the packed varint stream.

    ``planes`` is (n_planes, n): plane j holds byte j of every element with
    its continuation bit already set; ``lens`` the per-element byte counts.
    The exclusive-scan offsets + masked scatter are the host half of the
    two-pass byte-emit (the kernels produce lens/planes, shapes static)."""
    lens = np.asarray(lens, np.int64)
    n = len(lens)
    n_planes = planes.shape[0]
    offs = np.zeros(n + 1, np.int64)
    np.cumsum(lens, out=offs[1:])
    out = np.zeros(int(offs[-1]), np.uint8)
    starts = offs[:-1]
    for j in range(n_planes):       # plane-major: <= 10 vector scatters
        sel = lens > j
        if not sel.any():
            break
        out[starts[sel] + j] = planes[j][sel].astype(np.uint8, copy=False)
    return out.tobytes()


def _uvarint_planes_np(v: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(lens, planes) of a u64 value array -- the NumPy mirror of the
    kernel's per-element varint pass."""
    n = v.size
    lens = np.ones(n, np.int64)
    for k in range(1, 10):
        lens += (v >= np.uint64(1 << (7 * k))).astype(np.int64)
    shifts = np.uint64(7) * np.arange(10, dtype=np.uint64)
    b = ((v[None, :] >> shifts[:, None]) & np.uint64(0x7F)).astype(np.uint8)
    cont = np.arange(10, dtype=np.int64)[:, None] < (lens - 1)[None, :]
    return lens, np.where(cont, b | 0x80, b)


def _to_u64(values: Sequence[int]) -> np.ndarray:
    try:
        return np.asarray(values, dtype=np.uint64)
    except (OverflowError, ValueError, TypeError) as e:
        raise VarintRangeError(
            f"uvarint batch contains a value outside [0, 2^64): {e}"
        ) from None


def pack_uvarints_batch(values: Sequence[int], backend: str) -> bytes:
    """Batched uvarint packing, byte-identical to the ``write_uvarint``
    loop; values outside u64 raise :class:`encoding.VarintRangeError` (the
    kernels assume u64 -- arbitrary-precision ints keep their own tagged
    path through ``encode_value``)."""
    v = _to_u64(values)
    if v.size == 0:
        return b""
    if backend == "pallas":
        lens, planes = _uvarint_planes_pallas(v)
    else:
        lens, planes = _uvarint_planes_np(v)
    return _emit_varint_bytes(lens, planes)


def _uvarint_planes_pallas(v: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    import jax.numpy as jnp
    from ..kernels.delta_encode.ops import uvarint_encode64
    lo = (v & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (v >> np.uint64(32)).astype(np.uint32)
    lens, planes = uvarint_encode64(jnp.asarray(lo), jnp.asarray(hi),
                                    interpret=interpret_mode())
    return np.asarray(lens, np.int64), np.asarray(planes)


# ---------------------------------------------------------------------------
# fused tick encode: delta -> zigzag -> varint bytes
# ---------------------------------------------------------------------------


def _encode_ticks_varint_py(flat: np.ndarray) -> bytes:
    out = bytearray()
    prev = 0
    for i, t in enumerate(flat.tolist()):
        d = t if i == 0 else t - prev
        prev = t
        d = ((d + (1 << 31)) % (1 << 32)) - (1 << 31)
        write_uvarint(out, ((d << 1) ^ (d >> 63)) & 0xFFFFFFFF)
    return bytes(out)


def encode_ticks_varint(ticks: np.ndarray, backend: Optional[str] = None
                        ) -> bytes:
    """Fused delta -> zigzag -> varint byte-emit over a tick array.

    The variable-length stream is ~35-45% smaller than the fixed ``<u4``
    layout before zlib; the trace format keeps the fixed layout for
    byte-compat, so this op serves the benchmark sweep and future compact
    segment layouts.  All backends are byte-identical."""
    flat = np.asarray(ticks).reshape(-1).astype(np.int64)
    if flat.size == 0:
        return b""
    eff = resolve(backend, flat.size)
    with _op("encode_ticks_varint", eff, flat.size):
        if eff == "python":
            return _encode_ticks_varint_py(flat)
        if eff == "pallas":
            import jax.numpy as jnp
            from ..kernels.delta_encode.ops import delta_zigzag_varint
            _zz, lens, planes = delta_zigzag_varint(
                jnp.asarray(flat.astype(np.uint32)),
                interpret=interpret_mode())
            return _emit_varint_bytes(np.asarray(lens, np.int64),
                                      np.asarray(planes))
        zz = _delta_zigzag_np(flat).astype(np.uint64)
        lens, planes = _uvarint_planes_np(zz)
        return _emit_varint_bytes(lens, planes[:5])


# ---------------------------------------------------------------------------
# arithmetic-run boundaries (arith_segments / Sequitur RLE pre-tokenization)
# ---------------------------------------------------------------------------


def _run_boundaries_py(V: np.ndarray) -> np.ndarray:
    rows = V.tolist()
    mask = np.zeros(len(rows), bool)
    mask[0] = True
    for i in range(1, len(rows)):
        mask[i] = rows[i] != rows[i - 1]
    return mask


def run_boundaries(V: np.ndarray, backend: Optional[str] = None
                   ) -> np.ndarray:
    """Row-change mask of a (n, k) matrix: ``mask[i]`` iff row i differs
    from row i-1 (``mask[0]`` always True).  The shared building block of
    ``patterns.arith_segments`` (over row diffs) and
    ``Sequitur.push_stream`` (over the raw terminal column)."""
    V = np.asarray(V)
    if V.ndim == 1:
        V = V[:, None]
    n = V.shape[0]
    if n == 0:
        return np.zeros(0, bool)
    eff = resolve(backend, V.size)
    if eff == "pallas" and np.abs(V).max(initial=0) >= _I32_SAFE:
        eff = "numpy"
    with _op("run_boundaries", eff, V.size):
        if eff == "python":
            return _run_boundaries_py(V)
        if eff == "pallas":
            import jax.numpy as jnp
            from ..kernels.grammar_stats.ops import row_boundaries
            out = row_boundaries(jnp.asarray(V.astype(np.int32)),
                                 interpret=interpret_mode())
            return np.asarray(out).astype(bool)
        mask = np.empty(n, bool)
        mask[0] = True
        if n > 1:
            mask[1:] = (V[1:] != V[:-1]).any(axis=1)
        return mask


# ---------------------------------------------------------------------------
# symbol-stream statistics (Sequitur / TraceView digram profiles)
# ---------------------------------------------------------------------------


def terminal_histogram(stream: np.ndarray, n_bins: int,
                       backend: Optional[str] = None) -> np.ndarray:
    """Occurrence counts of terminals ``0..n_bins-1`` over a symbol
    stream, processed in blocks (kernel: one accumulating pallas_call)."""
    stream = np.asarray(stream, np.int64).reshape(-1)
    if stream.size == 0:
        return np.zeros(n_bins, np.int64)
    eff = resolve(backend, stream.size)
    if eff == "pallas" and stream.max(initial=0) >= _I32_SAFE:
        eff = "numpy"
    with _op("terminal_histogram", eff, stream.size):
        if eff == "pallas":
            import jax.numpy as jnp
            from ..kernels.grammar_stats.ops import histogram
            out = histogram(jnp.asarray(stream.astype(np.int32)), n_bins,
                            interpret=interpret_mode())
            return np.asarray(out).astype(np.int64)
        if eff == "python":
            out = np.zeros(n_bins, np.int64)
            for t in stream.tolist():
                if 0 <= t < n_bins:
                    out[t] += 1
            return out
        return np.bincount(stream[(stream >= 0) & (stream < n_bins)],
                           minlength=n_bins)[:n_bins].astype(np.int64)


def digram_histogram(stream: np.ndarray, n_terminals: int,
                     backend: Optional[str] = None) -> Dict[Tuple[int, int],
                                                            int]:
    """Directly-follows (digram) counts over a terminal stream.

    The kernel computes blocked pair codes ``a * n_terminals + b`` with a
    cross-block carry of the previous element; the host bincounts the
    codes.  Backends agree exactly."""
    stream = np.asarray(stream, np.int64).reshape(-1)
    if stream.size < 2:
        return {}
    eff = resolve(backend, stream.size)
    if eff == "pallas" and n_terminals * (n_terminals + 1) >= _I32_SAFE:
        eff = "numpy"
    with _op("digram_histogram", eff, stream.size):
        if eff == "pallas":
            import jax.numpy as jnp
            from ..kernels.grammar_stats.ops import digram_codes
            codes = np.asarray(digram_codes(
                jnp.asarray(stream.astype(np.int32)), n_terminals,
                interpret=interpret_mode())).astype(np.int64)
            codes = codes[codes >= 0]
        elif eff == "python":
            counts: Dict[Tuple[int, int], int] = {}
            prev = None
            for t in stream.tolist():
                if prev is not None:
                    k = (prev, t)
                    counts[k] = counts.get(k, 0) + 1
                prev = t
            return counts
        else:
            codes = stream[:-1] * n_terminals + stream[1:]
        hist = np.bincount(codes)
        nz = np.flatnonzero(hist)
        return {(int(c) // n_terminals, int(c) % n_terminals): int(hist[c])
                for c in nz}
