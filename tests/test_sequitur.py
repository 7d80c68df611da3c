"""Sequitur (exponent-carrying) property + unit tests."""

import random

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # fallback: seeded-random example generation
    from _hypothesis_compat import given, settings, strategies as st

from repro.core.sequitur import (Sequitur, expand_grammar, parse_grammar,
                                 remap_grammar, serialize_grammar)


def build(stream):
    g = Sequitur()
    for t in stream:
        g.push(t)
    return g


@settings(max_examples=200, deadline=None)
@given(st.lists(st.integers(0, 7), max_size=200))
def test_roundtrip_identity(stream):
    g = build(stream)
    assert g.expand() == stream


@settings(max_examples=100, deadline=None)
@given(st.lists(st.integers(0, 3), max_size=120))
def test_serialized_expansion_matches(stream):
    g = build(stream)
    rules = parse_grammar(g.serialize())
    assert list(expand_grammar(rules)) == stream


@settings(max_examples=60, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 4), st.integers(1, 9)),
                max_size=60))
def test_push_with_counts(runs):
    g = Sequitur()
    want = []
    for t, n in runs:
        g.push(t, n)
        want.extend([t] * n)
    assert g.expand() == want


def test_nested_loop_constant_grammar():
    """Paper Listing 2: m x (n writes + fsync) -> grammar size independent
    of m and n (exponents absorb the counts)."""
    def size(m, n):
        g = Sequitur()
        for _ in range(m):
            for _ in range(n):
                g.push(0)
            g.push(1)
        return len(g.serialize())

    s = size(4, 6)
    assert size(40, 6) <= s + 2       # exponent varint may add a byte
    assert size(40, 600) <= s + 4
    assert size(400, 600) <= s + 4


def test_digram_uniqueness_and_utility():
    import itertools
    for stream in itertools.product(range(3), repeat=7):
        g = build(list(stream))
        assert g.expand() == list(stream), stream
        # rule utility: every non-start rule used >= 2 times (or exp >= 2)
        rules = g.rules()
        for r in rules[1:]:
            uses = sum(s.exp for s in _all_refs(g, r))
            assert uses >= 2, (stream, repr(r))


def _all_refs(g, rule):
    out = []
    for r in g.rules():
        for s in r.body():
            if s.rule is rule:
                out.append(s)
    return out


def test_remap_grammar():
    g = build([0, 1, 0, 1, 2])
    remapped = remap_grammar(g.serialize(), {0: 5, 1: 7, 2: 9})
    assert list(expand_grammar(parse_grammar(remapped))) == [5, 7, 5, 7, 9]


def test_serialize_grammar_roundtrip():
    g = build([0, 1, 2, 0, 1, 2, 0, 1, 2])
    rules = parse_grammar(g.serialize())
    assert parse_grammar(serialize_grammar(rules)) == rules


def _runs(seed, n_runs=60, alphabet=4, max_len=7):
    """A random stream as maximal runs ``(terminal, length)``: adjacent
    runs differ in their terminal."""
    rng = random.Random(seed)
    runs = []
    for _ in range(n_runs):
        t = rng.randrange(alphabet)
        while runs and runs[-1][0] == t:
            t = rng.randrange(alphabet)
        runs.append((t, rng.randint(1, max_len)))
    return runs


def _assert_invariants(g):
    """Digram uniqueness and rule utility over a closed grammar."""
    rules = g.rules()
    seen = {}
    for r in rules:
        items = list(r.body())
        for left, right in zip(items, items[1:]):
            key = (left.key(), right.key())
            assert key not in seen, (repr(r), key)
            seen[key] = left
    for r in rules[1:]:
        assert sum(s.exp for s in _all_refs(g, r)) >= 2, repr(r)


@pytest.mark.parametrize("seed", range(8))
def test_per_terminal_pushes_equal_one_push_per_run(seed):
    """The open tail's digram is indexed when its run closes, so pushing
    a run terminal by terminal gives the grammar of one ``push(t, k)``."""
    for k in range(10):
        runs = _runs(seed * 100 + k)
        stream = [t for t, n in runs for _ in range(n)]
        per_run = Sequitur()
        for t, n in runs:
            per_run.push(t, n)
        g = build(stream)
        assert g.serialize() == per_run.serialize()
        assert g.expand() == stream
        assert g.n_pushed == len(stream)
        assert g.n_appended == len(runs)      # one node a run
        _assert_invariants(g)


@pytest.mark.parametrize("seed", range(6))
def test_serialize_mid_run_then_repeat(seed):
    """A read closes the open tail; a repeat after it reopens the tail,
    and the grammar stays lossless with both invariants."""
    rng = random.Random(seed)
    runs = _runs(seed, n_runs=40, alphabet=3, max_len=5)
    stream = [t for t, n in runs for _ in range(n)]
    g = Sequitur()
    for i, t in enumerate(stream):
        g.push(t)
        if rng.random() < 0.3:
            assert list(expand_grammar(parse_grammar(g.serialize()))) \
                == stream[:i + 1]
    assert list(expand_grammar(parse_grammar(g.serialize()))) == stream
    assert g.expand() == stream
    _assert_invariants(g)


def test_run_prefix_makes_no_rule():
    """``a b b a b b b``: the prefix ``a b^2`` of the last run matches the
    first digram, but the run is still open, so no rule forms; the
    closed digram ``a b^3`` is new."""
    g = build([0, 1, 1, 0, 1, 1, 1])
    assert [[(2 * t, n) for t, n in [(0, 1), (1, 2), (0, 1), (1, 3)]]] \
        == parse_grammar(g.serialize())
