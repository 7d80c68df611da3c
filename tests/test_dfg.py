"""Compressed-domain DFG observability: phase segmentation, cross-rank
divergence, and anomaly flagging straight from the grammar.

The properties under test --

  * :func:`dfg.grammar_digrams` (O(|grammar|), zero expansion) is
    edge-count-identical to a per-record directly-follows scan of the
    expanded stream, over random grammars and every ``synth_rank_states``
    shape; first/last boundary terminals are exact,
  * ``TraceView.digram_counts`` serves the grammar walk by default,
    matches the legacy expansion+histogram backends, and the cross-rank
    aggregate costs one walk per UNIQUE CFG (never per rank),
  * ``TraceView.dfg()`` node counts / edge weights equal a label-
    projected scan of the expanded stream,
  * phase boundaries are value-identical between stitched, merged, and
    ``refresh()``-folded reads (the fold walks only the delta-sized
    segment grammar, observable by monkeypatching the dfg walkers),
  * degraded (``ranks_present``-masked) traces still answer DFG/phase
    queries, carrying the PARTIAL-coverage warning and mask,
  * a structurally divergent rank is flagged by ``rank_divergence`` and
    surfaces as a ``dfg_divergent`` straggler reason end-to-end through
    ``TraceService``.
"""

import random
import tempfile
from collections import Counter

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # fallback: seeded-random example generation
    from _hypothesis_compat import given, settings, strategies as st

from benchmarks.workloads import synth_rank_states
from repro.core import dfg, faults, trace_format
from repro.core.comm import run_thread_world
from repro.core.faults import FaultPlan
from repro.core.interprocess import tree_finalize_ranks
from repro.core.reader import TraceReader
from repro.core.recorder import Recorder, RecorderConfig
from repro.core.sequitur import (Sequitur, concat_grammars, expand_grammar,
                                 parse_grammar, serialize_grammar)
from repro.core.specs import REGISTRY
from repro.traceserve import TraceService
import repro.core.apis  # noqa: F401  (populate registry)


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    yield
    faults.uninstall()


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _gen_calls(rng, n_calls, rank, nranks):
    fids = {name: REGISTRY.id_of(name)
            for name in ("open", "close", "pwrite", "lseek", "write")}
    fd = f"fd-{rank}"
    calls = [(fids["open"], ("/data/f.bin", 2, 438), fd)]
    for i in range(n_calls):
        kind = rng.random()
        if kind < 0.6:
            off = rank * 4096 + i * nranks * 4096
            calls.append((fids["pwrite"], (fd, b"x" * 4096, off), 4096))
        elif kind < 0.8:
            calls.append((fids["lseek"], (fd, rank * 256 + i * 256, 0),
                          rank * 256 + i * 256))
        else:
            calls.append((fids["write"], (fd, b"z" * 128), 128))
    calls.append((fids["close"], (fd,), 0))
    return calls


def _feed(rec, calls, tick_start=0):
    t = tick_start
    for fid, args, ret in calls:
        rec.record(fid, args, ret, 0, t, t + 1)
        t += 2
    return t


def _write_plain_trace(d, rank_calls):
    """Per-rank Recorder -> tree_finalize_ranks -> one plain trace dir at
    ``d``."""
    states = []
    for r, calls in enumerate(rank_calls):
        rec = Recorder(rank=r, config=RecorderConfig())
        _feed(rec, calls)
        states.append(rec.local_state())
    merge, cfgs = tree_finalize_ranks([s[0] for s in states],
                                      [s[1] for s in states], REGISTRY)
    trace_format.write_trace(d, registry=REGISTRY,
                             merged_cst=merge.merged_entries,
                             unique_cfgs=cfgs.unique_cfgs,
                             cfg_index=cfgs.cfg_index,
                             rank_timestamps=[s[2] for s in states],
                             meta_extra={})
    return d


def _synth_trace(tmp, nranks, pattern, n_groups=4, n_calls=40, seed=0):
    csts, cfgs = synth_rank_states(nranks, n_groups=n_groups,
                                   n_calls=n_calls, pattern=pattern,
                                   seed=seed)
    merge, cfgres = tree_finalize_ranks(csts, cfgs, REGISTRY)
    d = f"{tmp}/synth_{pattern}"
    trace_format.write_trace(d, registry=REGISTRY,
                             merged_cst=merge.merged_entries,
                             unique_cfgs=cfgres.unique_cfgs,
                             cfg_index=cfgres.cfg_index,
                             rank_timestamps=[b""] * nranks, meta_extra={})
    return d


def _label_graph(g):
    """Order-independent normal form of a ``TraceView.dfg()`` result."""
    nodes = {(n["func"], n["pattern"]): n["count"] for n in g["nodes"]}
    lab = [(n["func"], n["pattern"]) for n in g["nodes"]]
    edges = Counter()
    for e in g["edges"]:
        edges[(lab[e["src"]], lab[e["dst"]])] += e["weight"]
    return nodes, dict(edges), g["n_records"]


# ---------------------------------------------------------------------------
# (a) grammar-derived DFG == brute-force per-record directly-follows scan
# ---------------------------------------------------------------------------


@settings(max_examples=60, deadline=None)
@given(st.integers(0, 2 ** 20))
def test_grammar_digrams_equal_record_scan_random_grammars(seed):
    rng = random.Random(seed)
    stream = []
    for _ in range(rng.randrange(1, 8)):
        block = [rng.randrange(6) for _ in range(rng.randrange(1, 5))]
        stream += block * rng.randrange(1, 12)
    g = Sequitur()
    for t in stream:
        g.push(t)
    rules = parse_grammar(g.serialize())
    expanded = list(expand_grammar(rules))
    assert expanded == stream  # lossless precondition
    edges, first, last = dfg.grammar_digrams(rules)
    assert edges == dfg.stream_digrams(stream)
    assert first == stream[0] and last == stream[-1]
    # episode record accounting covers the stream exactly
    eps = dfg.grammar_episodes(rules, lambda t: f"f{t}")
    assert sum(e[0] for e in eps) == len(stream)
    phases = dfg.phase_segments(eps)
    assert phases[0]["start"] == 0 and phases[-1]["end"] == len(stream)
    assert all(a["end"] == b["start"] for a, b in zip(phases, phases[1:]))


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 2 ** 20))
def test_fold_equals_concatenated_grammar(seed):
    """fold_digrams / fold_phases over two independently induced grammars
    equal one walk of ``concat_grammars`` -- the identity the incremental
    refresh path relies on."""
    rng = random.Random(seed)

    def mk(n):
        s = []
        for _ in range(rng.randrange(1, 5)):
            block = [rng.randrange(5) for _ in range(rng.randrange(1, 4))]
            s += block * rng.randrange(1, 9)
        g = Sequitur()
        for t in s[:n] or [0]:
            g.push(t)
        return parse_grammar(g.serialize())

    r1, r2 = mk(60), mk(60)
    n1 = len(list(expand_grammar(r1)))
    toff = 1000
    cat = parse_grammar(concat_grammars(
        [(serialize_grammar(r1), 0), (serialize_grammar(r2), toff)]))
    assert dfg.grammar_digrams(cat) == dfg.fold_digrams(
        dfg.grammar_digrams(r1), dfg.grammar_digrams(r2), toff)
    name = "f{}".format
    full = dfg.phase_segments(dfg.grammar_episodes(cat, lambda t: name(t)))
    folded = dfg.fold_phases(
        dfg.phase_segments(dfg.grammar_episodes(r1, lambda t: name(t))),
        dfg.phase_segments(dfg.grammar_episodes(
            r2, lambda t: name(t + toff))), n1)
    assert full == folded


@pytest.mark.parametrize("pattern", ["linear", "constant", "irregular",
                                     "nested", "multi", "mixed",
                                     "mixed_all"])
def test_digram_counts_identical_across_paths_synth_shapes(
        tmp_path, pattern):
    """Grammar-walk digram_counts == legacy expansion backend == brute
    scan, per rank AND cross-rank aggregated, for every synth shape."""
    d = _synth_trace(str(tmp_path), 5, pattern, seed=11)
    view = TraceReader(d).view()
    agg = {}
    for r in range(5):
        got = view.digram_counts(r)
        assert got == view.digram_counts(r, backend="numpy")
        brute = dfg.stream_digrams(
            expand_grammar(view.grammars[view.cfg_index[r]]))
        assert got == brute
        for k, c in got.items():
            agg[k] = agg.get(k, 0) + c
    assert view.digram_counts(rank=None) == agg
    assert view.digram_counts(rank=None, backend="numpy") == agg


def test_aggregate_costs_one_walk_per_unique_cfg(tmp_path, monkeypatch):
    """8 SPMD ranks share one unique CFG: the cross-rank aggregate, the
    label DFG, and rank_divergence together walk that grammar ONCE."""
    d = _synth_trace(str(tmp_path), 8, "linear", seed=2)
    view = TraceReader(d).view()
    assert len(view._cfg_mult) == 1  # precondition: CFG is shared
    walks = []
    real = dfg.grammar_digrams
    monkeypatch.setattr(dfg, "grammar_digrams",
                        lambda rules: (walks.append(len(rules)) or
                                      real(rules)))
    view.digram_counts(rank=None)
    view.dfg(rank=None)
    view.rank_divergence()
    assert len(walks) == 1


def test_dfg_nodes_edges_equal_label_projected_scan(tmp_path):
    rng = random.Random(17)
    nranks = 3
    d = _write_plain_trace(str(tmp_path), [
        _gen_calls(rng, 40, r, nranks) for r in range(nranks)])
    view = TraceReader(d).view()
    for r in range(nranks):
        g = view.dfg(rank=r)
        nodes, edges, n_rec = _label_graph(g)
        stream = [dfg.node_label(view._sigs[t]) for t in
                  expand_grammar(view.grammars[view.cfg_index[r]])]
        assert n_rec == len(stream) == view.n_records(r)
        assert nodes == dict(Counter(stream))
        assert edges == dfg.stream_digrams(stream)
    # the aggregate is the node/edge-wise sum over ranks
    tot_nodes, tot_edges = Counter(), Counter()
    for r in range(nranks):
        n, e, _ = _label_graph(view.dfg(rank=r))
        tot_nodes.update(n)
        tot_edges.update(e)
    an, ae, arec = _label_graph(view.dfg())
    assert an == dict(tot_nodes) and ae == dict(tot_edges)
    assert arec == view.total_records()


# ---------------------------------------------------------------------------
# (b) phase boundaries identical across stitched / merged / refresh-folded
# ---------------------------------------------------------------------------


def _drive_stream(sd, calls, bounds, finalize=True):
    rec = Recorder(rank=0, config=RecorderConfig(trace_dir=sd))
    t = 0
    for i in range(len(bounds) - 1):
        t = _feed(rec, calls[bounds[i]:bounds[i + 1]], t)
        if i < len(bounds) - 2 or not finalize:
            rec.flush()
    if finalize:
        rec.finalize()
    return rec, t


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 2 ** 20))
def test_phases_and_dfg_identical_stitched_vs_merged(seed):
    with tempfile.TemporaryDirectory(prefix="dfg_modes") as tmp:
        sd = f"{tmp}/s"
        rng = random.Random(seed)
        calls = _gen_calls(rng, rng.randrange(20, 70), 0, 1)
        k = len(calls)
        bounds = sorted({0, rng.randrange(1, k), rng.randrange(1, k), k})
        _drive_stream(sd, calls, bounds)
        stitched = TraceReader(sd, mode="stitched").view()
        merged = TraceReader(sd, mode="merged").view()
        assert stitched.phases(0) == merged.phases(0)
        assert _label_graph(stitched.dfg(0)) == _label_graph(merged.dfg(0))
        assert (stitched.rank_divergence()["per_rank"]
                == merged.rank_divergence()["per_rank"])


def test_refresh_folded_phases_identical_and_walks_only_delta(
        tmp_path, monkeypatch):
    """A live stitched reader folds committed epochs one at a time: the
    folded view's phases/DFG equal a from-scratch stitched read, and the
    fold walks ONLY the new segment's (delta-sized) grammar -- queries on
    the refreshed view hit the seeded memos with zero further walks."""
    sd = str(tmp_path / "s")
    calls = _gen_calls(random.Random(23), 80, 0, 1)
    bounds = [0, 25, 50, len(calls)]
    rec = Recorder(rank=0, config=RecorderConfig(trace_dir=sd))
    t = _feed(rec, calls[bounds[0]:bounds[1]])
    rec.flush()

    reader = TraceReader(sd, mode="stitched")
    view = reader.view()
    # warm the DFG + phase memos so the fold must carry them forward
    before_phases = view.phases(0)
    view.digram_counts(0)
    full_size = len(reader.unique_cfgs[reader.cfg_index[0]])

    digram_walks, episode_walks = [], []
    real_gd, real_ge = dfg.grammar_digrams, dfg.grammar_episodes
    monkeypatch.setattr(dfg, "grammar_digrams",
                        lambda rules: (digram_walks.append(len(rules)) or
                                      real_gd(rules)))
    monkeypatch.setattr(
        dfg, "grammar_episodes",
        lambda rules, name_of: (episode_walks.append(len(rules)) or
                               real_ge(rules, name_of)))

    for i in range(1, len(bounds) - 1):
        t = _feed(rec, calls[bounds[i]:bounds[i + 1]], t)
        rec.flush()
        digram_walks.clear()
        episode_walks.clear()
        assert reader.refresh() == 1
        # the fold walked exactly one grammar: the new segment's
        assert len(digram_walks) == 1 and len(episode_walks) == 1
        seg_data, err = trace_format.load_segment(
            sd, trace_format.read_manifest(sd)["segments"][i])
        assert err is None
        seg_size = len(parse_grammar(seg_data["unique_cfgs"][0]))
        assert digram_walks == [seg_size] and episode_walks == [seg_size]
        view = reader.view()
        fresh = TraceReader(sd, mode="stitched").view()
        fresh_phases = fresh.phases(0)
        fresh_digrams = fresh.digram_counts(0)
        fresh_graph = _label_graph(fresh.dfg(0))
        digram_walks.clear()
        episode_walks.clear()
        assert view.phases(0) == fresh_phases
        assert view.digram_counts(0) == fresh_digrams
        assert _label_graph(view.dfg(0)) == fresh_graph
        # refreshed-view queries were answered from the seeded memos
        assert digram_walks == [] and episode_walks == []
        assert view.phases(0)[0]["start_record"] == 0
    assert view.phases(0) != before_phases  # history actually grew


def test_phase_segmentation_reads_like_the_program(tmp_path):
    """Deterministic shape: write-loop, then metadata loop, then a read
    loop -- phases cut at the structure shifts with exact record ranges
    and meaningful labels."""
    fids = {n: REGISTRY.id_of(n)
            for n in ("open", "close", "pwrite", "lseek", "pread")}
    fd = "fd-0"
    calls = [(fids["open"], ("/data/a.bin", 2, 438), fd)]
    calls += [(fids["pwrite"], (fd, b"x" * 512, 512 * i), 512)
              for i in range(40)]
    calls += [(fids["lseek"], (fd, 64 * i, 0), 64 * i) for i in range(30)]
    calls += [(fids["pread"], (fd, 512, 512 * i), 512) for i in range(40)]
    calls.append((fids["close"], (fd,), 0))
    d = _write_plain_trace(str(tmp_path), [calls])
    view = TraceReader(d).view()
    ph = view.phases(0)
    assert ph[0]["start_record"] == 0
    assert ph[-1]["end_record"] == len(calls)
    labels = [p["label"] for p in ph]
    doms = [set(p["dominant_funcs"]) for p in ph]
    assert {"pwrite"} in doms and {"lseek"} in doms and {"pread"} in doms
    i_w = doms.index({"pwrite"})
    i_m = doms.index({"lseek"})
    i_r = doms.index({"pread"})
    assert i_w < i_m < i_r  # temporal order preserved
    assert labels[i_w].startswith("write")
    assert labels[i_m].startswith("metadata")
    assert labels[i_r].startswith("read")
    # record accounting: the 40-write run lives inside the write phase
    assert ph[i_w]["end_record"] - ph[i_w]["start_record"] >= 40


# ---------------------------------------------------------------------------
# (c) degraded traces: DFG/phase queries carry the PARTIAL warning
# ---------------------------------------------------------------------------


def test_degraded_trace_dfg_queries_carry_partial_warning(tmp_path):
    root = tmp_path / "runs"
    sd = str(root / "job")
    nranks, dead = 4, 1
    first = [_gen_calls(random.Random(70 + r), 10, r, nranks)
             for r in range(nranks)]
    extra = [_gen_calls(random.Random(80 + r), 6, r, nranks)
             for r in range(nranks)]

    def worker(comm, rank):
        rec = Recorder(rank=rank, config=RecorderConfig(
            trace_dir=sd, flush_timeout_s=2.0))
        t = _feed(rec, first[rank])
        rec.flush(comm)
        comm.barrier()
        if rank == 0:
            faults.install(FaultPlan(dead_ranks=(dead,)))
        comm.barrier()
        _feed(rec, extra[rank], t)
        rec.flush(comm)  # degraded commit: `dead` never shows up
        return None

    run_thread_world(nranks, worker)
    faults.uninstall()

    with pytest.warns(RuntimeWarning, match="PARTIAL"):
        view = TraceReader(sd, mode="stitched").view()
    # the queries still answer, exactly over the records present
    assert view.dfg()["n_records"] == view.total_records()
    assert view.phases(dead)[-1]["end_record"] == view.n_records(dead)
    assert view.rank_divergence()["nranks"] == nranks

    with TraceService(str(root), mode="stitched",
                      max_staleness_s=0.0) as svc:
        for fam in ("dfg", "phases", "anomalies"):
            res = svc.query("job", fam)
            assert res.coverage["complete"] is False, fam
            assert res.coverage["ranks_partial"] == [dead], fam
        rep = svc.stragglers("job")
        assert "partial_coverage" in rep["reasons"][dead]


# ---------------------------------------------------------------------------
# cross-rank divergence: the structurally odd rank is flagged, with reason
# ---------------------------------------------------------------------------


def _divergent_world_calls(nranks, odd, n=40):
    fids = {name: REGISTRY.id_of(name)
            for name in ("open", "close", "pwrite", "lseek")}
    rank_calls = []
    for r in range(nranks):
        fd = f"fd-{r}"
        calls = [(fids["open"], ("/data/f.bin", 2, 438), fd)]
        if r == odd:
            # metadata churn: seek-seek-write where everyone else streams
            for i in range(n):
                calls.append((fids["lseek"], (fd, 64 * i, 0), 64 * i))
                calls.append((fids["lseek"], (fd, 64 * i + 8, 0),
                              64 * i + 8))
                if i % 4 == 0:
                    calls.append((fids["pwrite"],
                                  (fd, b"x" * 64, 64 * i), 64))
        else:
            base = r * 4096
            for i in range(n):
                calls.append((fids["pwrite"],
                              (fd, b"x" * 4096, base + i * nranks * 4096),
                              4096))
        calls.append((fids["close"], (fd,), 0))
        rank_calls.append(calls)
    return rank_calls


def test_divergent_rank_flagged_with_reason(tmp_path):
    root = tmp_path / "runs"
    nranks, odd = 6, 4
    sd = _write_plain_trace(str(root / "job"),
                            _divergent_world_calls(nranks, odd))
    view = TraceReader(sd).view()
    rep = view.rank_divergence(threshold=0.25)
    assert rep["divergent"] == [odd]
    assert rep["majority_size"] == nranks - 1
    assert rep["per_rank"][odd] > 0.25
    assert all(d_ == 0.0 for r, d_ in enumerate(rep["per_rank"])
               if r != odd)

    with TraceService(str(root), max_staleness_s=0.0) as svc:
        anom = svc.query("job", "anomalies")
        assert anom.value["divergent"] == [odd]
        rep = svc.stragglers("job")
        assert odd in rep["stragglers"]
        assert "dfg_divergent" in rep["reasons"][odd]
        assert rep["dfg_divergent"] == [odd]
        # memoized per generation: the repeat is a dictionary hit
        again = svc.query("job", "anomalies")
        assert again.cached and again.value == anom.value
