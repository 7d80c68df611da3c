"""Compressed-domain TraceView: value-identity with the record-iterator
path over randomized multi-rank traces, grammar-weight helpers, batched
signature decoding, and the exactness fallbacks."""

import random
import shutil
import tempfile
from collections import Counter, defaultdict

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # fallback: seeded-random example generation
    from _hypothesis_compat import given, settings, strategies as st

from benchmarks.reader_scaling import (_size_of, iter_io_summary,
                                       iter_size_histogram)
from repro.core import analysis, trace_format
from repro.core.encoding import (Handle, IterPattern, RankPattern,
                                 decode_signature, decode_signatures_batch,
                                 encode_signature)
from repro.core.interprocess import tree_finalize_ranks
from repro.core.reader import TraceReader
from repro.core.recorder import Recorder, RecorderConfig
from repro.core.sequitur import (Sequitur, expand_grammar,
                                 expand_grammar_reversed, expansion_length,
                                 parse_grammar, rule_weights,
                                 terminal_counts, terminal_positions)
from repro.core.specs import REGISTRY
from repro.core.traceview import _DATA_FUNCS, TraceView, sweep_conflicts
import repro.core.apis  # noqa: F401  (populate registry)


# ---------------------------------------------------------------------------
# the seed per-record reference implementations (the iterator path the view
# must be value-identical to); io_summary / size_histogram live in
# benchmarks.reader_scaling (single source, shared with its value_match)
# ---------------------------------------------------------------------------


def ref_io_summary(reader):
    return iter_io_summary(reader, range(reader.nranks))


def ref_size_histogram(reader, edges=(512, 4096, 65536, 1 << 20)):
    return iter_size_histogram(reader, range(reader.nranks), edges)


def ref_call_chains(reader, rank, targets=_DATA_FUNCS):
    chains = defaultdict(int)
    stack = []
    for rec in reversed(list(reader.iter_records(rank, timestamps=False))):
        del stack[rec.depth:]
        stack.append(rec.func)
        if rec.func in targets:
            chains["->".join(stack)] += 1
    return dict(chains)


def ref_overlap_ratio(reader, rank):
    events = []
    for rec in reader.iter_records(rank):
        if rec.t_entry is None or rec.t_exit is None:
            continue
        events.append((rec.t_entry, 1))
        events.append((rec.t_exit, -1))
    if not events:
        return 0.0
    events.sort()
    busy = overlap = 0
    depth = 0
    last = events[0][0]
    for t, d in events:
        if depth >= 1:
            busy += t - last
        if depth >= 2:
            overlap += t - last
        depth += d
        last = t
    return overlap / busy if busy else 0.0


def ref_consistency_writes(reader, targets=("pwrite", "shard_write_at")):
    """The seed per-record span collection (rank-major, stream order)."""
    writes = defaultdict(list)
    for r, rec in reader.all_records(timestamps=False):
        if rec.func not in targets:
            continue
        off = next((v for v, role in zip(rec.args, rec.roles)
                    if role == "offset" and isinstance(v, int)), None)
        if off is None:
            continue
        hid = next((v.id for v, role in zip(rec.args, rec.roles)
                    if role == "handle" and hasattr(v, "id")), -1)
        writes[hid].append((r, off, off + _size_of(rec)))
    return dict(writes)


# ---------------------------------------------------------------------------
# randomized multi-rank trace generation (direct record feeding: SPMD plan
# with rank-dependent offsets, plus rank-conditional ops so several unique
# CFGs and partially-present groups appear)
# ---------------------------------------------------------------------------

_PATHS = ["/data/a.bin", "/data/b.bin", "/data/c.bin"]


def _gen_plan(rng, nprocs):
    ops = []
    n_slots = rng.randint(1, 3)
    for _ in range(rng.randint(3, 10)):
        cond = rng.choice(["all"] * 4 + ["even", "first"])
        kind = rng.choice(["open", "pwrite_run", "lseek_run", "write",
                           "stat", "close", "pread_run"])
        slot = rng.randrange(n_slots)
        if kind == "open":
            ops.append((cond, kind, slot, rng.randrange(len(_PATHS))))
        elif kind == "close":
            ops.append((cond, kind, slot))
        elif kind == "stat":
            ops.append((cond, kind, rng.randrange(len(_PATHS))))
        elif kind == "write":
            ops.append((cond, kind, slot, rng.choice([17, 600, 5000])))
        else:
            ops.append((cond, kind, slot, rng.randint(1, 6),
                        rng.choice(["linear", "constant", "irregular",
                                    "nested"]),
                        rng.randrange(1 << 20),              # base
                        rng.randrange(4096),                 # rank coef
                        rng.randrange(512),                  # stride
                        rng.choice([0, 0, 8]),               # stride coef
                        [rng.randrange(1 << 20) for _ in range(nprocs)],
                        rng.choice([64, 600, 70000]),        # size
                        rng.randint(0, 2)))                  # depth
    return ops


def _run_plan(rec, ops, rank, nprocs, ts_rng):
    fid = REGISTRY.id_of
    fds = {}

    def t01():
        t0 = ts_rng.randrange(5000)
        return t0, t0 + ts_rng.randrange(100)

    for op in ops:
        cond, kind = op[0], op[1]
        if cond == "even" and rank % 2:
            continue
        if cond == "first" and rank != 0:
            continue
        t0, t1 = t01()
        if kind == "open":
            obj = object()
            fds[op[2]] = obj
            rec.record(fid("open"), (_PATHS[op[3]], 0, 438), obj, 0, t0, t1)
        elif kind == "close":
            obj = fds.pop(op[2], None)
            if obj is not None:
                rec.record(fid("close"), (obj,), 0, 0, t0, t1)
                rec.forget_handle(obj)
        elif kind == "stat":
            rec.record(fid("stat"), (_PATHS[op[2]],), 4096, 0, t0, t1)
        elif kind == "write":
            # a slot never opened exercises the late-registered-handle path
            obj = fds.setdefault(op[2], object())
            rec.record(fid("write"), (obj, b"w" * op[3]), op[3], 0, t0, t1)
        else:
            (_, _, slot, n, bk, base0, coef, stride, scoef, irr, size,
             depth) = op
            obj = fds.setdefault(slot, object())
            if bk == "constant":
                base = base0
            elif bk == "irregular":
                base = irr[rank]
            else:  # linear / nested
                base = base0 + rank * coef
            step = stride + rank * scoef if bk == "nested" else stride
            for i in range(n):
                off = base + i * step
                t0, t1 = t01()
                if kind == "pwrite_run":
                    rec.record(fid("pwrite"), (obj, b"p" * size, off), size,
                               depth, t0, t1)
                elif kind == "pread_run":
                    rec.record(fid("pread"), (obj, size, off), b"r" * 8,
                               depth, t0, t1)
                else:
                    rec.record(fid("lseek"), (obj, off, 0), off, depth,
                               t0, t1)


def _build_random_trace(tmp, seed):
    rng = random.Random(seed)
    nprocs = rng.randint(1, 6)
    ops = _gen_plan(rng, nprocs)
    states = []
    for r in range(nprocs):
        rec = Recorder(rank=r, config=RecorderConfig())
        _run_plan(rec, ops, r, nprocs, random.Random(seed * 1009 + r))
        states.append(rec.local_state())
    merge, cfgs = tree_finalize_ranks([s[0] for s in states],
                                      [s[1] for s in states], REGISTRY)
    d = f"{tmp}/trace"
    trace_format.write_trace(d, registry=REGISTRY,
                             merged_cst=merge.merged_entries,
                             unique_cfgs=cfgs.unique_cfgs,
                             cfg_index=cfgs.cfg_index,
                             rank_timestamps=[s[2] for s in states],
                             meta_extra={})
    return d, nprocs


# ---------------------------------------------------------------------------
# the tentpole property: every analysis is value-identical on both paths
# ---------------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2 ** 20))
def test_view_value_identical_to_iterator(seed):
    tmp = tempfile.mkdtemp(prefix="traceview_")
    try:
        d, nprocs = _build_random_trace(tmp, seed)
        reader = TraceReader(d)
        view = reader.view()
        assert view.io_summary() == ref_io_summary(reader)
        assert analysis.io_summary(reader) == ref_io_summary(reader)
        assert view.size_histogram() == ref_size_histogram(reader)
        assert (analysis.size_histogram(reader, edges=(128, 1024))
                == ref_size_histogram(reader, (128, 1024)))
        for r in range(nprocs):
            assert view.call_chains(rank=r) == ref_call_chains(reader, r)
            assert (view.call_chains(("lseek",), rank=r)
                    == ref_call_chains(reader, r, ("lseek",)))
            assert view.overlap_ratio(r) == ref_overlap_ratio(reader, r)
            assert reader.n_records(r) == sum(
                1 for _ in reader.iter_records(r, timestamps=False))
        assert (view.consistency_pairs()
                == sweep_conflicts(ref_consistency_writes(reader)))
        assert view.total_records() == sum(
            reader.n_records(r) for r in range(nprocs))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def test_view_from_analysis_module(tmp_path):
    """analysis.* accepts both TraceReader and TraceView."""
    d, _ = _build_random_trace(str(tmp_path), 7)
    reader = TraceReader(d)
    assert analysis.io_summary(reader.view()) == analysis.io_summary(reader)
    assert (analysis.consistency_pairs(reader.view())
            == analysis.consistency_pairs(reader))


# ---------------------------------------------------------------------------
# exactness fallbacks
# ---------------------------------------------------------------------------


def test_per_file_fallback_on_handle_reuse(tmp_path):
    """close + reopen under a different path reuses the unified handle id:
    per-file attribution must walk the stream, not trust the weights."""
    states = []
    fid = REGISTRY.id_of
    for rank in range(2):
        rec = Recorder(rank=rank, config=RecorderConfig())
        f1, f2 = object(), object()
        rec.record(fid("open"), ("/data/a.bin", 0, 438), f1, 0, 0, 1)
        rec.record(fid("pwrite"), (f1, b"x" * 100, rank * 100), 100, 0, 1, 2)
        rec.record(fid("close"), (f1,), 0, 0, 2, 3)
        rec.forget_handle(f1)
        rec.record(fid("open"), ("/data/b.bin", 0, 438), f2, 0, 3, 4)
        rec.record(fid("pwrite"), (f2, b"x" * 100, rank * 100), 100, 0, 4, 5)
        rec.record(fid("close"), (f2,), 0, 0, 5, 6)
        rec.forget_handle(f2)
        states.append(rec.local_state())
    merge, cfgs = tree_finalize_ranks([s[0] for s in states],
                                      [s[1] for s in states], REGISTRY)
    d = str(tmp_path / "t")
    trace_format.write_trace(d, registry=REGISTRY,
                             merged_cst=merge.merged_entries,
                             unique_cfgs=cfgs.unique_cfgs,
                             cfg_index=cfgs.cfg_index,
                             rank_timestamps=[s[2] for s in states],
                             meta_extra={})
    reader = TraceReader(d)
    s = analysis.io_summary(reader)
    assert s == ref_io_summary(reader)
    assert s["files"]["/data/a.bin"]["calls"] == 2
    assert s["files"]["/data/b.bin"]["calls"] == 2


def test_span_cols_rank_dependent_guard(tmp_path):
    """Two adjacent pattern signatures with RankPattern components under one
    run key cannot be resolved rank-symbolically: the view must detect the
    case and fall back to the exact per-rank path."""
    pw = REGISTRY.id_of("pwrite")
    sig_a = encode_signature(pw, 0, 0,
                             (Handle(0), 100,
                              IterPattern(4, RankPattern(2, 10))), 100)
    sig_b = encode_signature(pw, 0, 0,
                             (Handle(0), 100,
                              IterPattern(8, RankPattern(2, 10))), 100)
    g = Sequitur()
    g.push(0)
    g.push(1)
    d = str(tmp_path / "t")
    trace_format.write_trace(d, registry=REGISTRY,
                             merged_cst=[sig_a, sig_b],
                             unique_cfgs=[g.serialize()], cfg_index=[0, 0],
                             rank_timestamps=[b"", b""], meta_extra={})
    reader = TraceReader(d)
    view = reader.view()
    assert view._span_cols(0, ("pwrite", "shard_write_at")) is None
    assert (view.consistency_pairs()
            == sweep_conflicts(ref_consistency_writes(reader)))


# ---------------------------------------------------------------------------
# grammar-weight helpers
# ---------------------------------------------------------------------------


@settings(max_examples=50, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 5), st.integers(1, 7)),
                min_size=0, max_size=40))
def test_grammar_weight_helpers_match_expansion(runs):
    g = Sequitur()
    stream = []
    for t, k in runs:
        g.push(t, k)
        stream += [t] * k
    rules = parse_grammar(g.serialize())
    assert list(expand_grammar(rules)) == stream
    assert list(expand_grammar_reversed(rules)) == stream[::-1]
    assert terminal_counts(rules) == dict(Counter(stream))
    assert expansion_length(rules) == len(stream)
    assert rule_weights(rules)[0] == 1
    first, last = terminal_positions(rules)
    assert set(first) == set(last) == set(stream)
    for t in set(stream):
        assert first[t] == stream.index(t)
        assert last[t] == len(stream) - 1 - stream[::-1].index(t)


# ---------------------------------------------------------------------------
# batched signature decoding
# ---------------------------------------------------------------------------


def _rand_value(rng, depth=0):
    kinds = ["int", "big", "str", "bytes", "none", "bool", "float",
             "handle", "rankpat"]
    if depth < 2:
        kinds += ["iterpat", "tuple"]
    k = rng.choice(kinds)
    if k == "int":
        return rng.randrange(-(1 << 20), 1 << 20)
    if k == "big":
        return rng.randrange(-(1 << 70), 1 << 70)
    if k == "str":
        return "".join(rng.choice("abc/xyz.0") for _ in range(rng.randrange(8)))
    if k == "bytes":
        return bytes(rng.randrange(256) for _ in range(rng.randrange(6)))
    if k == "none":
        return None
    if k == "bool":
        return rng.choice([True, False])
    if k == "float":
        return rng.uniform(-1e9, 1e9)
    if k == "handle":
        return Handle(rng.randrange(1 << 16))
    if k == "rankpat":
        return RankPattern(rng.randrange(-(1 << 30), 1 << 30),
                           rng.randrange(-(1 << 30), 1 << 30))
    if k == "iterpat":
        return IterPattern(_rand_value(rng, 2), _rand_value(rng, 2))
    return tuple(_rand_value(rng, depth + 1)
                 for _ in range(rng.randrange(3)))


@settings(max_examples=50, deadline=None)
@given(st.integers(0, 2 ** 20))
def test_decode_signatures_batch_matches_scalar(seed):
    rng = random.Random(seed)
    sigs = []
    for _ in range(rng.randrange(1, 20)):
        args = tuple(_rand_value(rng) for _ in range(rng.randrange(5)))
        sigs.append(encode_signature(rng.randrange(1 << 20),
                                     rng.randrange(1 << 14),
                                     rng.randrange(1 << 7),
                                     args, _rand_value(rng)))
    batch = decode_signatures_batch(sigs)
    assert len(batch) == len(sigs)
    for i, s in enumerate(sigs):
        fid, tid, dep, args, ret = decode_signature(s)
        assert (int(batch.func_id[i]), int(batch.thread[i]),
                int(batch.depth[i])) == (fid, tid, dep)
        assert batch.args[i] == args
        assert batch.ret[i] == ret


def test_decode_signatures_batch_empty():
    batch = decode_signatures_batch([])
    assert len(batch) == 0 and batch.args == [] and batch.ret == []
