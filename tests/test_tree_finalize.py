"""Tree-reduction finalize: byte-identity with the gather-at-root
reference merge (``_flat_merge_ref``), state serialization, the ThreadComm
collective path, reader round-trips, and the batched intra-pattern
encoding equivalences."""

import os
import random

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # fallback: seeded-random example generation
    from _hypothesis_compat import given, settings, strategies as st

from _flat_merge_ref import finalize_ranks, fit_component
from benchmarks.workloads import synth_rank_states
from repro.core import trace_format
from repro.core.comm import Comm, SoloComm, run_thread_world
from repro.core.encoding import IterPattern, decode_signature, encode_signature
from repro.core.interprocess import (deserialize_rank_state, make_rank_state,
                                     materialize_state, merge_rank_states,
                                     merge_serialized_states,
                                     serialize_rank_state,
                                     tree_finalize_ranks, tree_reduce_states)
from repro.core.patterns import IntraPatternTracker
from repro.core.reader import TraceReader
from repro.core.recorder import Recorder, RecorderConfig
from repro.core.sequitur import Sequitur
from repro.core.specs import REGISTRY
import repro.core.apis  # noqa: F401  (populate registry)

PWRITE = REGISTRY.id_of("pwrite")


def _assert_same_finalize(r1, r2):
    m1, c1 = r1
    m2, c2 = r2
    assert m1.merged_entries == m2.merged_entries
    assert m1.remaps == m2.remaps
    assert m1.n_rank_patterns == m2.n_rank_patterns
    assert c1.unique_cfgs == c2.unique_cfgs
    assert c1.cfg_index == c2.cfg_index


# ---------------------------------------------------------------------------
# tree <-> reference byte-identity
# ---------------------------------------------------------------------------


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 64),
       st.sampled_from(["linear", "constant", "irregular", "mixed"]),
       st.integers(1, 6), st.integers(1, 8), st.integers(0, 2 ** 20))
def test_tree_matches_flat_bytes(nranks, pattern, n_groups, n_calls, seed):
    """tree_finalize_ranks output is identical to the reference merge for
    randomized rank counts (incl. non-powers-of-two) and offset patterns."""
    csts, cfgs = synth_rank_states(nranks, n_groups=n_groups,
                                   n_calls=n_calls, pattern=pattern,
                                   seed=seed)
    for inter in (True, False):
        _assert_same_finalize(
            finalize_ranks(csts, cfgs, REGISTRY, inter_patterns=inter),
            tree_finalize_ranks(csts, cfgs, REGISTRY, inter_patterns=inter))


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 48), st.integers(1, 5), st.integers(0, 2 ** 20))
def test_tree_matches_flat_with_partial_groups(nranks, n_groups, seed):
    """Ranks with missing / extra entries (collective-I/O shape) still
    merge identically."""
    rng = random.Random(seed)
    csts, cfgs = synth_rank_states(nranks, n_groups=n_groups, n_calls=4,
                                   pattern="mixed", seed=seed)
    csts = [list(c) for c in csts]
    cfgs = list(cfgs)
    # drop a suffix of terminals on a few ranks (and shrink their grammar)
    for r in rng.sample(range(nranks), max(1, nranks // 4)):
        keep = rng.randrange(0, len(csts[r]))
        csts[r] = csts[r][:keep]
        g = Sequitur()
        for t in range(keep):
            g.push(t, rng.randrange(1, 4))
        cfgs[r] = g.serialize()
    _assert_same_finalize(
        finalize_ranks(csts, cfgs, REGISTRY),
        tree_finalize_ranks(csts, cfgs, REGISTRY))


def test_tree_reduction_order_invariance():
    """Sequential left-fold and pairwise-tree association produce identical
    states (serialized bytes compared)."""
    csts, cfgs = synth_rank_states(7, n_groups=3, n_calls=5, pattern="mixed",
                                   seed=3)
    leaves = [make_rank_state(r, csts[r], cfgs[r], REGISTRY)
              for r in range(7)]
    tree = tree_reduce_states([make_rank_state(r, csts[r], cfgs[r], REGISTRY)
                               for r in range(7)])
    fold = leaves[0]
    for s in leaves[1:]:
        fold = merge_rank_states(fold, s)
    assert serialize_rank_state(tree) == serialize_rank_state(fold)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 32),
       st.sampled_from(["nested", "multi", "mixed_all"]),
       st.integers(1, 4), st.integers(1, 6), st.integers(0, 2 ** 20))
def test_tree_matches_flat_nested_and_multi_offset(nranks, pattern, n_groups,
                                                   n_calls, seed):
    """The extended synth shapes -- nested IterPattern-of-RankPattern
    offsets (paper Fig 3c) and joint multi-offset lseek runs -- finalize
    as the reference merges them."""
    csts, cfgs = synth_rank_states(nranks, n_groups=n_groups,
                                   n_calls=n_calls, pattern=pattern,
                                   seed=seed)
    _assert_same_finalize(
        finalize_ranks(csts, cfgs, REGISTRY),
        tree_finalize_ranks(csts, cfgs, REGISTRY))


def _column_ranks(columns, nranks):
    """Rank CSTs in which column ``k`` is one pwrite group: rank ``r``
    writes at ``columns[k][r]``, with a size of its own per group.  A
    column of ``IterPattern``s is an intra-rank run whose stride and base
    both vary over the ranks."""
    csts = [[encode_signature(PWRITE, 0, 0, (None, 64 + k, col[r]), 64 + k)
             for k, col in enumerate(columns)] for r in range(nranks)]
    g = Sequitur()
    for t in range(len(columns)):
        g.push(t, 1 + t % 3)
    return csts, [g.serialize()] * nranks


@pytest.mark.parametrize("nranks", [2, 3, 8])
def test_tree_matches_reference_bigint_offsets(nranks):
    """Offsets beyond int64 (every leaf near 2^70) fit and merge as the
    reference merges them."""
    big = 1 << 70
    columns = [
        [big + 5 * r for r in range(nranks)],               # rank-linear
        [big + 7] * nranks,                                 # constant
        [big + r * r for r in range(nranks)],               # broken past 2
        [-big - (1 << 66) * r for r in range(nranks)],      # negative slope
        [IterPattern(big + 3 * r, big - r) for r in range(nranks)],
    ]
    csts, cfgs = _column_ranks(columns, nranks)
    tree = tree_finalize_ranks(csts, cfgs, REGISTRY)
    _assert_same_finalize(finalize_ranks(csts, cfgs, REGISTRY), tree)
    assert tree[0].n_rank_patterns >= 3


# (rank 0, rank 1) values whose difference wraps in int32 arithmetic
_WRAP = [(-((1 << 31) - 1), (1 << 31) - 1), (0, 1 << 30), (5, 5),
         (1 << 30, -(1 << 30))]


@pytest.mark.parametrize("nranks", [2, 3, 8])
def test_tree_matches_reference_int32_wrapping_offsets(nranks):
    """Columns whose rank-to-rank deltas overflow int32, laid out both
    rank-linear and with the last rank off the line, fit and merge as the
    reference merges them."""
    columns = []
    for v0, v1 in _WRAP:
        line = [v0 + r * (v1 - v0) for r in range(nranks)]
        columns.append(line)
        columns.append(line[:-1] + [line[-1] + (1 << 31)])
        columns.append([IterPattern(line[r], v0) for r in range(nranks)])
    csts, cfgs = _column_ranks(columns, nranks)
    tree = tree_finalize_ranks(csts, cfgs, REGISTRY)
    _assert_same_finalize(finalize_ranks(csts, cfgs, REGISTRY), tree)
    assert tree[0].n_rank_patterns >= 6


@settings(max_examples=60, deadline=None)
@given(st.lists(st.lists(st.integers(-2 ** 40, 2 ** 40), min_size=3,
                         max_size=6), min_size=1, max_size=8))
def test_tree_fits_match_reference_fitter(cols):
    """Each column, one group over the ranks, merges to the signature the
    reference's scalar fitter gives it: one shared entry holding the
    constant or the ``RankPattern``, or per-rank entries when the column
    is not rank-linear."""
    cols = [c for c in cols if len(c) == len(cols[0])]
    nranks = len(cols[0])
    csts, cfgs = _column_ranks(cols, nranks)
    merge, _ = tree_finalize_ranks(csts, cfgs, REGISTRY)
    for k, col in enumerate(cols):
        fit = fit_component(col)
        for r in range(nranks):
            sig = merge.merged_entries[merge.remaps[r][k]]
            offset = decode_signature(sig)[3][2]
            assert offset == (col[r] if fit is None else fit), (k, r)


def test_synth_nested_roundtrips_through_reader(tmp_path):
    """Nested offsets (rank-linear base AND stride) and joint lseek
    offset/return runs come back exactly from the merged trace."""
    nprocs, n_groups, n_calls, chunk = 5, 2, 6, 512
    big = 1 << 24
    for pattern in ("nested", "multi"):
        csts, cfgs = synth_rank_states(nprocs, n_groups=n_groups,
                                       n_calls=n_calls, pattern=pattern,
                                       chunk=chunk)
        merge, cfgres = tree_finalize_ranks(csts, cfgs, REGISTRY)
        d = str(tmp_path / pattern)
        trace_format.write_trace(d, registry=REGISTRY,
                                 merged_cst=merge.merged_entries,
                                 unique_cfgs=cfgres.unique_cfgs,
                                 cfg_index=cfgres.cfg_index,
                                 rank_timestamps=[b""] * nprocs,
                                 meta_extra={})
        reader = TraceReader(d)
        for r in range(nprocs):
            base = lambda g: r * chunk + g * big  # noqa: E731
            step = ((nprocs + r) * chunk if pattern == "nested"
                    else nprocs * chunk)
            want = [base(g) + i * step
                    for g in range(n_groups) for i in range(n_calls)]
            recs = list(reader.iter_records(r, timestamps=False))
            assert [rec.arg("offset") for rec in recs] == want, (pattern, r)
            if pattern == "multi":
                assert [rec.ret for rec in recs] == want  # joint OFFSET ret


def test_merge_requires_adjacent_blocks():
    csts, cfgs = synth_rank_states(3, n_groups=1, n_calls=2)
    s0, _, s2 = (make_rank_state(r, csts[r], cfgs[r], REGISTRY)
                 for r in range(3))
    with pytest.raises(ValueError):
        merge_rank_states(s0, s2)


# ---------------------------------------------------------------------------
# state serialization (tree hops)
# ---------------------------------------------------------------------------


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 16),
       st.sampled_from(["linear", "constant", "irregular", "mixed"]),
       st.integers(0, 2 ** 20))
def test_state_serialization_roundtrip(nranks, pattern, seed):
    csts, cfgs = synth_rank_states(nranks, n_groups=3, n_calls=6,
                                   pattern=pattern, seed=seed)
    root = tree_reduce_states([make_rank_state(r, csts[r], cfgs[r], REGISTRY)
                               for r in range(nranks)])
    blob = serialize_rank_state(root)
    back = deserialize_rank_state(blob)
    assert serialize_rank_state(back) == blob           # stable bytes
    _assert_same_finalize(materialize_state(root), materialize_state(back))


def test_merge_serialized_states_matches_object_merge():
    csts, cfgs = synth_rank_states(4, n_groups=2, n_calls=5, seed=1)
    leaves = [make_rank_state(r, csts[r], cfgs[r], REGISTRY)
              for r in range(4)]
    blob = merge_serialized_states(
        merge_serialized_states(serialize_rank_state(leaves[0]),
                                serialize_rank_state(leaves[1])),
        merge_serialized_states(serialize_rank_state(leaves[2]),
                                serialize_rank_state(leaves[3])))
    obj = tree_reduce_states(leaves)
    assert blob == serialize_rank_state(obj)


# ---------------------------------------------------------------------------
# Comm.reduce_tree + the SPMD finalize path (ThreadComm, multi-threaded)
# ---------------------------------------------------------------------------


def test_reduce_tree_solo_and_generic():
    assert SoloComm().reduce_tree(b"x", lambda a, b: a + b) == b"x"

    class ListComm(Comm):
        rank, size = 0, 5

        def gather(self, obj, root=0):
            return [obj * (i + 1) for i in range(5)]

    # fold of ["x","xx","xxx","xxxx","xxxxx"]: association-independent here
    assert ListComm().reduce_tree("x", lambda a, b: a + b) == "x" * 15


def _feed(rec, rank, nprocs, n_calls, chunk):
    """One rank's strided lseek+write loop, fed to ``rec.record``."""
    fid_seek = REGISTRY.id_of("lseek")
    fid_write = REGISTRY.id_of("write")
    fd = object()
    for i in range(n_calls):
        off = rank * chunk + i * nprocs * chunk
        rec.record(fid_seek, (fd, off, 0), off, 0, 2 * i, 2 * i + 1)
        rec.record(fid_write, (fd, b"x" * 64), 64, 0, 2 * i + 1, 2 * i + 2)


def _run_threaded(tmp_path, nprocs=5, n_calls=24, chunk=512):
    """N ranks on N threads; records are fed directly (the wrapper slot is
    a process-global, shared across threads) and finalize runs through the
    real ThreadComm collectives."""
    trace_dir = str(tmp_path / "trace")

    def worker(comm, rank):
        rec = Recorder(rank=rank, config=RecorderConfig())
        _feed(rec, rank, nprocs, n_calls, chunk)
        return rec.finalize(comm, trace_dir=trace_dir)

    stats = run_thread_world(nprocs, worker)
    assert stats[0] is not None
    assert all(s is None for s in stats[1:])
    return trace_dir


@pytest.mark.parametrize("nprocs", [1, 5, 8])
def test_threadcomm_tree_trace_matches_reference(tmp_path, nprocs):
    """A concurrent finalize over ThreadComm writes the trace files that
    the reference merge writes from the same ranks' local states."""
    n_calls, chunk = 24, 512
    d_tree = _run_threaded(tmp_path, nprocs=nprocs, n_calls=n_calls,
                           chunk=chunk)
    states = []
    for r in range(nprocs):
        rec = Recorder(rank=r, config=RecorderConfig())
        _feed(rec, r, nprocs, n_calls, chunk)
        states.append(rec.local_state())
    merge, cfgs = finalize_ranks([s[0] for s in states],
                                 [s[1] for s in states], REGISTRY)
    d_ref = str(tmp_path / "reference")
    trace_format.write_trace(d_ref, registry=REGISTRY,
                             merged_cst=merge.merged_entries,
                             unique_cfgs=cfgs.unique_cfgs,
                             cfg_index=cfgs.cfg_index,
                             rank_timestamps=[s[2] for s in states],
                             meta_extra={})
    for name in ("merged_cst.bin", "unique_cfgs.bin", "cfg_index.bin",
                 "timestamps.bin"):
        with open(os.path.join(d_tree, name), "rb") as f1, \
                open(os.path.join(d_ref, name), "rb") as f2:
            assert f1.read() == f2.read(), name


def test_threadcomm_tree_nonpow2(tmp_path):
    for nprocs in (3, 6, 7):
        d = _run_threaded(tmp_path / str(nprocs), nprocs=nprocs)
        r = TraceReader(d)
        assert r.nranks == nprocs


def test_reader_roundtrip_tree_finalized(tmp_path):
    """TraceReader reconstructs every rank's exact offsets from a trace
    finalized through the tree topology."""
    nprocs, n_calls, chunk = 6, 30, 512
    d = _run_threaded(tmp_path, nprocs=nprocs, n_calls=n_calls, chunk=chunk)
    reader = TraceReader(d)
    assert reader.nranks == nprocs
    assert len(reader.unique_cfgs) == 1    # identical SPMD ranks deduped
    for r in range(nprocs):
        offs = [rec.arg("offset") for rec in reader.iter_records(r)
                if rec.func == "lseek"]
        assert offs == [r * chunk + i * nprocs * chunk
                        for i in range(n_calls)]


# ---------------------------------------------------------------------------
# batched intra-pattern encoding equivalences
# ---------------------------------------------------------------------------


@settings(max_examples=60, deadline=None)
@given(st.lists(st.integers(0, 2 ** 20), max_size=50), st.integers(1, 3),
       st.integers(0, 2 ** 20))
def test_encode_many_matches_sequential(vals, arity, seed):
    rng = random.Random(seed)
    rows = []
    i = 0
    while i < len(vals):
        if rng.random() < 0.5:    # splice in an arithmetic run
            a, n = rng.randrange(0, 4096), rng.randrange(1, 8)
            rows.extend(tuple(vals[i] + j * a + s for s in range(arity))
                        for j in range(n))
        else:
            rows.append(tuple(vals[i] + s for s in range(arity)))
        i += 1
    seq, bat = IntraPatternTracker(), IntraPatternTracker()
    out_seq = [seq.encode("k", r) for r in rows]
    out_bat = bat.encode_many("k", rows)
    assert out_seq == out_bat
    rs, rb = seq.runs.get("k"), bat.runs.get("k")
    assert (rs is None) == (rb is None)
    if rs is not None:
        assert (rs.index, rs.base, rs.stride) == (rb.index, rb.base, rb.stride)


def test_encode_many_continues_existing_run():
    seq, bat = IntraPatternTracker(), IntraPatternTracker()
    head = [(0,), (8,)]
    tail = [(16,), (24,), (99,), (100,)]
    for r in head:
        assert seq.encode("k", r) == bat.encode("k", r)
    assert [seq.encode("k", r) for r in tail] == bat.encode_many("k", tail)


# ---------------------------------------------------------------------------
# scaling sanity: merged state stays O(groups) for SPMD rank blocks
# ---------------------------------------------------------------------------


def test_tree_state_constant_in_ranks():
    small = tree_reduce_states(
        [make_rank_state(r, *rc, REGISTRY) for r, rc in
         enumerate(zip(*synth_rank_states(8, n_groups=4, n_calls=8)))])
    big = tree_reduce_states(
        [make_rank_state(r, *rc, REGISTRY) for r, rc in
         enumerate(zip(*synth_rank_states(128, n_groups=4, n_calls=8)))])
    assert len(big.streams) == len(small.streams) == 1
    assert len(big.groups) == len(small.groups)
    # serialized state grows only by the per-rank stream index varints
    assert len(serialize_rank_state(big)) <= \
        len(serialize_rank_state(small)) + 2 * (128 - 8) + 16


# ---------------------------------------------------------------------------
# near-uniform remap stream cache (materialize_state fast path)
# ---------------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 24),
       st.sampled_from(["linear", "constant", "irregular", "mixed",
                        "mixed_all"]),
       st.integers(1, 5), st.integers(1, 6), st.integers(0, 2 ** 20))
def test_materialize_stream_cache_matches_uncached(nranks, pattern, n_groups,
                                                   n_calls, seed):
    """Near-uniform remap-stream reuse (uniform prefix shared, only the
    irregular rows re-interned per rank) is byte-identical to the
    reference merge, which walks every row of every rank."""
    csts, cfgs = synth_rank_states(nranks, n_groups=n_groups,
                                   n_calls=n_calls, pattern=pattern,
                                   seed=seed)
    state = tree_reduce_states([make_rank_state(r, csts[r], cfgs[r], REGISTRY)
                                for r in range(nranks)])
    for inter in (True, False):
        _assert_same_finalize(
            materialize_state(state, inter_patterns=inter),
            finalize_ranks(csts, cfgs, REGISTRY, inter_patterns=inter))
