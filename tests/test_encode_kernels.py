"""Accelerated encode layer vs the pure-Python reference encoders.

Every batched backend ("numpy" vectorized host, "pallas" kernels run in
interpret mode so the suite executes on CPU-only CI) must be BYTE-identical
to the scalar Python path: the backend knob may never change what lands in
a trace file.  Properties cover randomized tick streams (wraps, zero
deltas, max-u32), ragged varint length classes, empty blocks, the u64
batch guard, the run-segmentation dispatcher, the
grammar-stats kernels, and full Recorder round-trips through TraceReader
under every backend.
"""

import hashlib
import os
import shutil
import time
import zlib

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                      # pragma: no cover
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import encode_backend as eb
from repro.core import trace_format
from repro.core.apis import posix
from repro.core.encoding import (VarintRangeError, decode_value,
                                 encode_value, pack_uvarints, read_uvarint,
                                 write_uvarint)
from repro.core.interprocess import tree_finalize_ranks
from repro.core.patterns import IntraPatternTracker, arith_segments
from repro.core.reader import TraceReader
from repro.core.recorder import Recorder, RecorderConfig, attach, detach
from repro.core.sequitur import Sequitur, expand_grammar, parse_grammar
from repro.core.specs import REGISTRY
from repro.core.timestamps import (compress_timestamps,
                                   compress_timestamps_blocked,
                                   decompress_timestamps,
                                   delta_zigzag_encode)
from repro.core.traceview import TraceView, _DATA_FUNCS, _WRITE_FUNCS

rng = np.random.RandomState(11)

BATCH = ["numpy", "pallas"]          # backends that must match "python"


# ---------------------------------------------------------------------------
# tick streams: delta+zigzag and the fused varint emit
# ---------------------------------------------------------------------------

def _tick_stream(n, style):
    """(n, 2) uint32 tick pairs exercising the encoder's edge geometry."""
    if style == "wrap":
        # counters near the 32-bit wrap point: deltas straddle the wrap
        base = (1 << 32) - n - 5
        flat = base + np.sort(rng.randint(0, 2 * n + 9, size=2 * n))
    elif style == "zero":
        # heavy runs of identical ticks (zero deltas)
        flat = np.repeat(rng.randint(0, 1000, size=max(1, n // 4)), 8)[:2 * n]
        flat = np.sort(flat)
    elif style == "extreme":
        # arbitrary u32 values incl. 0 and max-u32: worst-case deltas
        flat = rng.randint(0, 1 << 32, size=2 * n, dtype=np.uint64)
        if n:
            flat[rng.randint(0, 2 * n)] = (1 << 32) - 1
            flat[rng.randint(0, 2 * n)] = 0
    else:
        flat = np.cumsum(rng.randint(0, 100000, size=2 * n))
    return (flat.astype(np.uint64) & 0xFFFFFFFF).astype(
        np.uint32).reshape(-1, 2)


@pytest.mark.parametrize("style", ["mono", "wrap", "zero", "extreme"])
@pytest.mark.parametrize("n", [0, 1, 5, 257, 5000])
def test_delta_zigzag_backends_identical(style, n):
    ticks = _tick_stream(n, style)
    ref = eb.delta_zigzag(ticks.reshape(-1).astype(np.uint32), "python")
    assert ref.dtype == np.uint32
    for b in BATCH:
        out = eb.delta_zigzag(ticks.reshape(-1).astype(np.uint32), b)
        np.testing.assert_array_equal(out, ref, err_msg=b)
    # and the decoder inverts every backend's output (they're equal, but
    # pin the round-trip too so the reference itself can't silently drift)
    blob = compress_timestamps(ticks, backend="numpy")
    np.testing.assert_array_equal(decompress_timestamps(blob), ticks)


@pytest.mark.parametrize("style", ["mono", "wrap", "zero", "extreme"])
def test_compress_timestamps_byte_identical(style):
    ticks = _tick_stream(1000, style)
    ref = compress_timestamps(ticks, backend="python")
    for b in BATCH + ["auto"]:
        assert compress_timestamps(ticks, backend=b) == ref, b


def test_compress_timestamps_blocked_byte_identical():
    ticks = _tick_stream(3000, "mono")
    ref = compress_timestamps_blocked(ticks, block_records=256,
                                      backend="python")
    for b in BATCH + ["auto"]:
        out = compress_timestamps_blocked(ticks, block_records=256,
                                          backend=b)
        assert out == ref, b


@pytest.mark.parametrize("style", ["mono", "wrap", "zero", "extreme"])
@pytest.mark.parametrize("n", [0, 1, 7, 1024])
def test_fused_ticks_varint_matches_python(style, n):
    ticks = _tick_stream(n, style)
    ref = eb.encode_ticks_varint(ticks, "python")
    for b in BATCH:
        assert eb.encode_ticks_varint(ticks, b) == ref, b
    # the stream really is the uvarint coding of the zigzag deltas
    zz = eb.delta_zigzag(ticks.reshape(-1).astype(np.uint32), "python")
    assert ref == pack_uvarints([int(v) for v in zz], backend="python")


# ---------------------------------------------------------------------------
# uvarint batch packing: ragged length classes, u64 edges, range guard
# ---------------------------------------------------------------------------

def _ragged_u64(rng, n):
    """Values spanning every varint length class 1..10 bytes."""
    bits = rng.randint(0, 65, size=n)
    return [int(rng.randint(0, 1 << 32, dtype=np.uint64)
               | (np.uint64(1) << np.uint64(max(0, b - 1))))
            & ((1 << 64) - 1) if b else 0 for b in bits]


@pytest.mark.parametrize("n", [0, 1, 3, 100, 2048])
def test_pack_uvarints_backends_identical(n):
    vals = _ragged_u64(rng, n)
    ref = pack_uvarints(vals, backend="python")
    for b in BATCH + ["auto"]:
        assert pack_uvarints(vals, backend=b) == ref, b
    # decodes back exactly
    pos, out = 0, []
    while pos < len(ref):
        v, pos = read_uvarint(ref, pos)
        out.append(v)
    assert out == vals


def test_pack_uvarints_u64_edges():
    edges = [0, 1, 127, 128, (1 << 14) - 1, 1 << 14, (1 << 21) - 1,
             (1 << 28), (1 << 32) - 1, 1 << 32, (1 << 35) + 7,
             (1 << 56) - 1, 1 << 56, (1 << 63), (1 << 64) - 1]
    ref = pack_uvarints(edges, backend="python")
    for b in BATCH:
        assert pack_uvarints(edges, backend=b) == ref, b


@pytest.mark.parametrize("backend", ["python", "numpy", "pallas"])
@pytest.mark.parametrize("bad", [1 << 64, (1 << 64) + 3, -1, -(1 << 70)])
def test_pack_uvarints_range_guard(backend, bad):
    with pytest.raises(VarintRangeError):
        pack_uvarints([0, 5, bad, 7], backend=backend)


def test_scalar_writers_stay_arbitrary_precision():
    # the u64 guard is a property of the BATCHED packers only: the scalar
    # signature encoder must keep accepting arbitrarily large ints
    buf = bytearray()
    write_uvarint(buf, 1 << 70)
    v, _ = read_uvarint(bytes(buf), 0)
    assert v == 1 << 70
    buf = bytearray()
    encode_value(buf, -(1 << 70))
    v, _ = decode_value(bytes(buf), 0)
    assert v == -(1 << 70)


# ---------------------------------------------------------------------------
# run segmentation dispatchers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 2, 3])
def test_arith_segments_backends_identical(k):
    V = np.concatenate([
        np.arange(50)[:, None] * rng.randint(1, 5, size=k)[None, :] + 7,
        rng.randint(-100, 100, size=(17, k)),
        np.full((31, k), 42),
    ]).astype(np.int64)
    ref = arith_segments(V, backend="python")
    for b in BATCH:
        assert arith_segments(V, backend=b) == ref, b


def test_encode_many_backend_matches_scalar_protocol():
    rows = ([(i * 8, 0) for i in range(60)]
            + [(5, 1), (9, 1), (13, 1)]               # new run, stride 4
            + [(int(v), 2) for v in rng.randint(0, 99, size=20)])
    ref_tr, out_tr = IntraPatternTracker(), {}
    ref = [ref_tr.encode("k", r) for r in rows]
    for b in ["python"] + BATCH:
        tr = IntraPatternTracker()
        got = tr.encode_many("k", rows, backend=b)
        assert got == ref, b
        assert tr.runs.keys() == ref_tr.runs.keys()
        assert all(vars(tr.runs[k]) == vars(ref_tr.runs[k])
                   for k in tr.runs), b


# ---------------------------------------------------------------------------
# grammar_stats kernels vs refs, and their users
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 2, 129, 4096])
@pytest.mark.parametrize("k", [1, 3])
def test_run_boundaries_backends_identical(n, k):
    V = rng.randint(0, 4, size=(n, k)).astype(np.int64)
    ref = eb.run_boundaries(V, "python")
    assert ref[0]
    for b in BATCH:
        np.testing.assert_array_equal(eb.run_boundaries(V, b), ref,
                                      err_msg=b)


@pytest.mark.parametrize("n,n_bins", [(1, 4), (1000, 7), (5000, 64),
                                      (9000, 200)])   # several bin tiles
def test_terminal_histogram_backends_identical(n, n_bins):
    stream = rng.randint(0, n_bins, size=n).astype(np.int64)
    ref = eb.terminal_histogram(stream, n_bins, "python")
    np.testing.assert_array_equal(
        ref, np.bincount(stream, minlength=n_bins))
    for b in BATCH:
        np.testing.assert_array_equal(
            eb.terminal_histogram(stream, n_bins, b), ref, err_msg=b)


@pytest.mark.parametrize("n,T", [(0, 3), (1, 3), (2000, 5), (4097, 40)])
def test_digram_histogram_backends_identical(n, T):
    stream = rng.randint(0, T, size=n).astype(np.int64)
    ref = eb.digram_histogram(stream, T, "python")
    assert sum(ref.values()) == max(0, n - 1)
    for b in BATCH:
        assert eb.digram_histogram(stream, T, b) == ref, b


def test_push_stream_matches_per_terminal_push():
    stream = [int(v) for v in
              np.repeat(rng.randint(0, 6, size=200),
                        rng.randint(1, 9, size=200))]
    # grammar reference: one push(term, run_len) per maximal run (the batch
    # semantics push_stream promises); expansion must also equal the
    # original per-terminal stream
    ref = Sequitur()
    i = 0
    while i < len(stream):
        j = i
        while j < len(stream) and stream[j] == stream[i]:
            j += 1
        ref.push(stream[i], j - i)
        i = j
    for b in ["python"] + BATCH:
        s = Sequitur()
        s.push_stream(stream, backend=b)
        assert s.serialize() == ref.serialize(), b
        assert (list(expand_grammar(parse_grammar(s.serialize())))
                == stream), b


# ---------------------------------------------------------------------------
# full Recorder round-trip: traces byte-identical under every backend
# ---------------------------------------------------------------------------

class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1e-6
        return self.t


def _trace_digest(cfg_backend, base, datadir):
    """Run one deterministic workload under a backend; digest the files.

    ``datadir`` must be IDENTICAL across the runs being compared: the
    open() path string is recorded in the merged CST, so differing data
    directories would (correctly) change the trace bytes."""
    shutil.rmtree(base, ignore_errors=True)
    os.makedirs(base)
    os.makedirs(datadir, exist_ok=True)
    tdir = os.path.join(base, "trace")
    real = time.perf_counter
    time.perf_counter = _FakeClock()
    try:
        rec = Recorder(rank=0, config=RecorderConfig(
            trace_dir=tdir, encode_backend=cfg_backend))
        attach(rec)
        try:
            fd = posix.open(os.path.join(datadir, "f.bin"),
                            os.O_RDWR | os.O_CREAT, 0o644)
            for i in range(300):
                posix.pwrite(fd, b"x" * 512, 512 * i)
            posix.fsync(fd)
            posix.close(fd)
        finally:
            detach()
        rec.finalize()
    finally:
        time.perf_counter = real
    h = hashlib.sha256()
    for name in sorted(os.listdir(tdir)):
        if name.endswith(".json"):
            continue                      # metadata carries no trace bytes
        with open(os.path.join(tdir, name), "rb") as f:
            h.update(name.encode() + b"\0" + f.read())
    return h.hexdigest(), tdir


def test_trace_byte_identical_across_backends(tmp_path):
    datadir = str(tmp_path / "data")
    ref, tdir = _trace_digest("python", str(tmp_path / "python"), datadir)
    r = TraceReader(tdir)
    offs = [rc.arg("offset") for rc in r.iter_records(0)
            if rc.func == "pwrite"]
    assert offs == [512 * i for i in range(300)]
    for b in ["numpy", "pallas", "auto"]:
        got, tdir_b = _trace_digest(b, str(tmp_path / b), datadir)
        assert got == ref, b
        rb = TraceReader(tdir_b)
        assert [rc.arg("offset") for rc in rb.iter_records(0)
                if rc.func == "pwrite"] == offs, b


def test_config_rejects_unknown_backend():
    with pytest.raises(ValueError):
        RecorderConfig(encode_backend="cuda")


def test_resolve_crossover():
    assert eb.resolve("python", 10 ** 9) == "python"     # explicit wins
    assert eb.resolve("auto", 1) == "python"             # tiny -> scalar
    big = eb.resolve("auto", eb.PALLAS_MIN_BATCH)
    assert big == ("pallas" if eb.has_accelerator() else "numpy")
    assert eb.resolve(None, eb.NUMPY_MIN_BATCH) in ("numpy", "pallas")


# ---------------------------------------------------------------------------
# TraceView: memoized walks vs linear references
# ---------------------------------------------------------------------------

def _spmd_trace(base, nranks=3, n=120):
    shutil.rmtree(base, ignore_errors=True)
    os.makedirs(base)
    states = []
    for r in range(nranks):
        rec = Recorder(rank=r, config=RecorderConfig())
        attach(rec)
        try:
            fd = posix.open(os.path.join(base, "a.bin"),
                            os.O_RDWR | os.O_CREAT, 0o644)
            for i in range(n):
                posix.pwrite(fd, b"x" * 64, 64 * (nranks * i + r))
            posix.close(fd)
            fd2 = posix.open(os.path.join(base, "b.bin"),
                             os.O_RDWR | os.O_CREAT, 0o644)
            for j in range(6):
                for i in range(20):
                    posix.pread(fd2, 128, 128 * (j * 20 + i))
                    # every rank writes the SAME extent: cross-rank overlap
                    posix.pwrite(fd2, b"y" * 128, 128 * (j * 20 + i))
                posix.fsync(fd2)
            posix.close(fd2)
        finally:
            detach()
        states.append(rec.local_state())
    merge, cfgs = tree_finalize_ranks([s[0] for s in states],
                                      [s[1] for s in states], REGISTRY)
    tdir = os.path.join(base, "trace")
    trace_format.write_trace(
        tdir, registry=REGISTRY, merged_cst=merge.merged_entries,
        unique_cfgs=cfgs.unique_cfgs, cfg_index=cfgs.cfg_index,
        rank_timestamps=[s[2] for s in states], meta_extra={})
    return tdir


@pytest.fixture(scope="module")
def spmd_view(tmp_path_factory):
    tdir = _spmd_trace(str(tmp_path_factory.mktemp("spmd") / "w"))
    return TraceView(TraceReader(tdir))


def test_per_file_walk_memo_matches_linear(spmd_view):
    tv = spmd_view
    for u in range(len(tv.grammars)):
        assert tv._per_file_walk_memo(u) == tv._per_file_walk_linear(u), u


def _norm_spans(res):
    if res is None:
        return None
    return [(h, list(map(int, cf)), list(map(int, ct)), list(map(int, sz)),
             npc is not None) for h, cf, ct, sz, npc in res]


def test_span_cols_walk_matches_linear(spmd_view):
    tv = spmd_view
    from repro.core.traceview import _SpanBail
    for targets in (_WRITE_FUNCS, _DATA_FUNCS, ("pread",), ("nosuch",)):
        tgt = tuple(targets)
        for u in range(len(tv.grammars)):
            lin = tv._span_cols_linear(u, tgt)
            try:
                walk = tv._span_cols_walk(u, tgt)
            except _SpanBail:
                assert lin is None, (u, tgt)
                continue
            assert _norm_spans(walk) == _norm_spans(lin), (u, tgt)


def test_span_cols_wrapper_caches(spmd_view):
    tv = spmd_view
    tgt = tuple(_WRITE_FUNCS)
    first = tv._span_cols(0, tgt)
    assert (0, tgt) in tv._spancols
    assert tv._span_cols(0, tgt) is first


def test_consistency_pairs_still_overlap(spmd_view):
    pairs = spmd_view.consistency_pairs()
    assert pairs                       # strided writes do interleave
    assert all(p["handle"] is not None for p in pairs)


def test_digram_counts_backends_identical(spmd_view):
    tv = spmd_view
    ref = tv.digram_counts(0, backend="python")
    assert sum(ref.values()) == tv.n_records(0) - 1
    for b in BATCH + ["auto"]:
        assert tv.digram_counts(0, backend=b) == ref, b


# ---------------------------------------------------------------------------
# randomized property sweeps (hypothesis or the seeded fallback)
# ---------------------------------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=(1 << 64) - 1),
                max_size=300))
def test_prop_pack_uvarints(vals):
    ref = pack_uvarints(vals, backend="python")
    for b in BATCH:
        assert pack_uvarints(vals, backend=b) == ref, b


@settings(max_examples=40, deadline=None)
@given(st.lists(st.integers(min_value=0, max_value=(1 << 32) - 1),
                max_size=200))
def test_prop_tick_encode(flat):
    flat = flat + [0] * (len(flat) % 2)     # even count -> (n, 2)
    ticks = np.asarray(flat, np.uint32).reshape(-1, 2)
    ref = compress_timestamps(ticks, backend="python")
    for b in BATCH:
        assert compress_timestamps(ticks, backend=b) == ref, b
    np.testing.assert_array_equal(
        decompress_timestamps(ref), ticks)
