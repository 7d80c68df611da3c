"""Intra-process pattern tracker <-> decoder mirror property, and the
inter-process merge invariants."""

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # fallback: seeded-random example generation
    from _hypothesis_compat import given, settings, strategies as st

from repro.core.encoding import IterPattern, RankPattern, decode_signature
from repro.core.interprocess import dedupe_cfgs, tree_finalize_ranks
from repro.core.patterns import IntraPatternDecoder, IntraPatternTracker
from repro.core.specs import REGISTRY
import repro.core.apis  # noqa: F401  (populate registry)


@settings(max_examples=150, deadline=None)
@given(st.lists(st.integers(0, 2**20), min_size=0, max_size=60))
def test_tracker_decoder_mirror(offsets):
    """decode(encode(stream)) == stream for ANY offset sequence."""
    enc = IntraPatternTracker()
    dec = IntraPatternDecoder()
    key = ("f", 0)
    for off in offsets:
        encoded = enc.encode(key, (off,))
        out = dec.decode(key, encoded)
        assert out == [off]


@settings(max_examples=80, deadline=None)
@given(st.integers(0, 1000), st.integers(1, 500), st.integers(2, 40))
def test_arithmetic_run_compresses_to_two(b, a, n):
    """i*a + b runs produce exactly two distinct encodings (concrete head +
    one IterPattern), which is what keeps the CST constant-size."""
    enc = IntraPatternTracker()
    outs = [tuple(enc.encode("k", (b + i * a,))) for i in range(n)]
    assert len(set(outs)) == 2
    assert outs[1] == (IterPattern(a, b),)


def test_multi_offset_joint_run():
    enc = IntraPatternTracker()
    dec = IntraPatternDecoder()
    for i in range(10):
        e = enc.encode("k", (i * 4, i * 100 + 7))
        assert dec.decode("k", e) == [i * 4, i * 100 + 7]


def _offsets_by_rank(column):
    """Rank r writes at ``column[r]``; the offset each rank's call carries
    in the merged CST."""
    fid = REGISTRY.id_of("pwrite")
    csts = [[_sig(fid, (None, 64, v))] for v in column]
    merged = _merge(csts)
    return [decode_signature(merged.merged_entries[m[0]])[3][2]
            for m in merged.remaps]


def test_fit_component():
    assert _offsets_by_rank([5, 5, 5]) == [5] * 3
    assert _offsets_by_rank([3, 7, 11, 15]) == [RankPattern(4, 3)] * 4
    assert _offsets_by_rank([3, 7, 12]) == [3, 7, 12]
    assert _offsets_by_rank([1]) == [1]


def test_dedupe_cfgs():
    res = dedupe_cfgs([b"A", b"B", b"A", b"A"])
    assert res.unique_cfgs == [b"A", b"B"]
    assert res.cfg_index == [0, 1, 0, 0]


def _sig(fid, args, ret=0):
    from repro.core.encoding import encode_signature
    return encode_signature(fid, 0, 0, args, ret)


def _merge(csts, inter_patterns=True):
    """The inter-process pass over per-rank CSTs; each rank's grammar
    calls its terminals once, in order."""
    from repro.core.sequitur import Sequitur
    cfgs = []
    for cst in csts:
        g = Sequitur()
        for t in range(len(cst)):
            g.push(t)
        cfgs.append(g.serialize())
    merged, _ = tree_finalize_ranks(csts, cfgs, REGISTRY,
                                    inter_patterns=inter_patterns)
    return merged


def test_merge_rank_linear():
    """Paper Fig 3(c): per-rank offsets rank*a+b merge to one entry."""
    fid = REGISTRY.id_of("pwrite")
    nranks = 4
    csts = [[_sig(fid, (None, 64, r * 100))] for r in range(nranks)]
    merged = _merge(csts)
    assert len(merged.merged_entries) == 1
    assert merged.n_rank_patterns == 1
    # every rank remaps its terminal 0 to merged terminal 0
    assert all(m[0] == 0 for m in merged.remaps)


def test_merge_respects_occurrence_index():
    """Two occurrences of the same masked signature on each rank must merge
    occurrence-by-occurrence, not cross-match."""
    fid = REGISTRY.id_of("pwrite")
    csts = [[_sig(fid, (None, 64, r * 10)), _sig(fid, (None, 64, 5000 + r * 10))]
            for r in range(3)]
    merged = _merge(csts)
    assert len(merged.merged_entries) == 2


def test_merge_partial_rank_group_not_fitted():
    """Entries missing on some rank (collective-I/O aggregators) are kept
    per-rank rather than wrongly merged."""
    fid = REGISTRY.id_of("pwrite")
    csts = [[_sig(fid, (None, 64, 0))], [_sig(fid, (None, 64, 100))], []]
    merged = _merge(csts)
    assert len(merged.merged_entries) == 2  # no fit without full coverage


def test_merge_no_inter_flag():
    fid = REGISTRY.id_of("pwrite")
    csts = [[_sig(fid, (None, 64, r * 100))] for r in range(4)]
    merged = _merge(csts, inter_patterns=False)
    assert len(merged.merged_entries) == 4
