"""The Recorder's own spans and counters (``core/selftrace.py``): the
module on its own, the record path's stage sums, the span trees of
finalize and flush, the encode counters by backend, the spans on a JAX
profiler trace's host plane, and traces that tracing leaves unchanged."""

import os
import threading

import numpy as np
import pytest

from repro.core import encode_backend as eb
from repro.core import selftrace
from repro.core.apis import posix
from repro.core.comm import run_thread_world
from repro.core.recorder import Recorder, RecorderConfig, attach, detach
from repro.core.specs import REGISTRY

STAGES = ("record.patterns_ns", "record.signature_ns", "record.grammar_ns")


@pytest.fixture
def traced():
    """selftrace on and empty for the test; off and empty after it."""
    selftrace.reset()
    selftrace.enable()
    try:
        yield selftrace
    finally:
        selftrace.disable()
        selftrace.reset()


def _by_name(snap):
    out = {}
    for s in snap["spans"]:
        out.setdefault(s["name"], []).append(s)
    return out


def _children(snap, parent):
    return sorted(s["name"] for s in snap["spans"]
                  if s["parent"] == parent["id"])


def _pwrites(tmp_path, rec, n, block=256):
    """``n`` strided posix pwrites through the traced facade, then fsync
    and close: ``n + 2`` records."""
    fd = os.open(tmp_path / "data.bin", os.O_RDWR | os.O_CREAT, 0o644)
    buf = b"\x5a" * block
    attach(rec)
    try:
        for i in range(n):
            posix.pwrite(fd, buf, i * 4096)
        posix.fsync(fd)
        posix.close(fd)
    finally:
        detach()


# ---------------------------------------------------------------------------
# the module
# ---------------------------------------------------------------------------


def test_off_records_nothing(tmp_path):
    selftrace.disable()
    selftrace.reset()
    a, b = selftrace.span("x"), selftrace.span("y")
    assert a is b                       # one shared null context
    with a:
        selftrace.count("c", 5)
    rec = Recorder(config=RecorderConfig(trace_dir=str(tmp_path / "t")))
    _pwrites(tmp_path, rec, 50)
    rec.finalize()
    assert rec._stage_sums == [0] * len(rec._stage_sums)
    assert selftrace.snapshot() == {"spans": [], "counters": {}}


def test_span_nesting_gives_parent_ids(traced):
    seen = {}

    def other_thread():
        with traced.span("elsewhere") as s:
            seen["thread_span"] = s.id

    with traced.span("outer") as outer:
        with traced.span("inner") as inner:
            with traced.span("leaf"):
                pass
        t = threading.Thread(target=other_thread)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
        with traced.span("sibling"):
            pass
    snap = traced.snapshot()
    by = {s["name"]: s for s in snap["spans"]}
    assert [s["name"] for s in snap["spans"]] == \
        ["leaf", "inner", "elsewhere", "sibling", "outer"]
    assert by["outer"]["parent"] is None
    assert by["inner"]["parent"] == outer.id == by["sibling"]["parent"]
    assert by["leaf"]["parent"] == inner.id
    # another thread's span has its own stack, so no parent here
    assert by["elsewhere"]["parent"] is None
    assert by["elsewhere"]["id"] == seen["thread_span"]
    assert by["elsewhere"]["thread"] != by["outer"]["thread"]
    for s in snap["spans"]:
        assert 0 < s["start_ns"] <= s["end_ns"]
    assert by["outer"]["start_ns"] <= by["leaf"]["start_ns"] <= \
        by["leaf"]["end_ns"] <= by["outer"]["end_ns"]


def test_counters_and_reset(traced):
    traced.count("a")
    traced.count("a", 4)
    traced.count("b", 0)
    with traced.span("s"):
        pass
    snap = traced.snapshot()
    assert snap["counters"] == {"a": 5, "b": 0}
    assert len(snap["spans"]) == 1
    traced.reset()
    assert traced.snapshot() == {"spans": [], "counters": {}}
    assert traced.on                    # reset keeps tracing on
    traced.disable()
    traced.count("a")
    assert traced.snapshot()["counters"] == {}


def test_span_bound_counts_dropped(traced, monkeypatch):
    monkeypatch.setattr(selftrace, "MAX_SPANS", 3)
    for i in range(5):
        with traced.span(f"s{i}"):
            pass
    snap = traced.snapshot()
    assert [s["name"] for s in snap["spans"]] == ["s0", "s1", "s2"]
    assert snap["counters"] == {"selftrace.spans_dropped": 2}


# ---------------------------------------------------------------------------
# the record path
# ---------------------------------------------------------------------------


def test_traced_pwrite_loop_stage_sums(traced, tmp_path):
    n = 300
    rec = Recorder(config=RecorderConfig(trace_dir=str(tmp_path / "t")))
    _pwrites(tmp_path, rec, n)
    rec.finalize()
    c = traced.snapshot()["counters"]
    assert c["record.calls"] == rec.n_records == n + 2
    total = c["record.total_ns"]
    assert all(c[k] > 0 for k in STAGES)
    assert sum(c[k] for k in STAGES) <= total
    # the traced calls' own time, in the recorder's microsecond ticks
    assert 0 <= c["record.wrapped_us"] < 1e6
    # folded once: the recorder's sums restart at zero
    assert rec._stage_sums == [0] * len(rec._stage_sums)


@pytest.mark.parametrize("offsets,on", [("strided", True), ("random", True),
                                        ("strided", False)])
def test_memo_hits_counter(tmp_path, offsets, on):
    """A strided loop reuses its run's terminal from its third call on;
    random offsets never continue a run; with selftrace off nothing is
    counted."""
    n = 1024
    rng = np.random.default_rng(15)
    offs = (np.arange(n) * 4096 if offsets == "strided"
            else rng.integers(0, 1 << 24, n)).tolist()
    rec = Recorder(config=RecorderConfig(trace_dir=str(tmp_path / "t")))
    fd = os.open(tmp_path / "data.bin", os.O_RDWR | os.O_CREAT, 0o644)
    selftrace.reset()
    if on:
        selftrace.enable()
    try:
        attach(rec)
        try:
            for off in offs:
                posix.pwrite(fd, b"\x5a" * 64, off)
        finally:
            detach()
            os.close(fd)
        assert rec._stage_sums[6] == (n - 2 if on and offsets == "strided"
                                      else 0)
        rec.finalize()
        c = selftrace.snapshot()["counters"]
    finally:
        selftrace.disable()
        selftrace.reset()
    if not on:
        assert c == {}
    elif offsets == "strided":
        assert c["record.memo_hits"] == c["record.calls"] - 2 == n - 2
        assert c["record.signature_ns"] > 0      # the two calls that missed
    else:
        assert c["record.calls"] == n and c["record.memo_hits"] == 0


def test_threadcomm_ranks_fold_their_own_sums(traced, tmp_path):
    """Ranks on threads, each with its recorder, fold into one set of
    counters: nothing is lost between them."""
    nranks, n = 4, 40
    fid = REGISTRY.id_of("pwrite")

    def worker(comm, rank):
        rec = Recorder(rank=rank)
        fd = object()
        for i in range(n):
            rec.record(fid, (fd, b"x" * 64, (i * nranks + rank) * 64), 64,
                       0, 2 * i, 2 * i + 1)
        rec.finalize(comm, trace_dir=str(tmp_path / "t"))

    run_thread_world(nranks, worker)
    snap = traced.snapshot()
    c = snap["counters"]
    assert c["record.calls"] == nranks * n
    assert c["record.wrapped_us"] == nranks * n
    fins = _by_name(snap)["finalize"]
    assert len(fins) == nranks
    # each rank's phases hang under its own thread's finalize
    for f in fins:
        kids = [s for s in snap["spans"] if s["parent"] == f["id"]]
        assert kids and all(k["thread"] == f["thread"] for k in kids)


# ---------------------------------------------------------------------------
# span trees of finalize and flush
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("topology", ["tree", "flat"])
def test_finalize_span_tree(traced, tmp_path, topology):
    rec = Recorder(config=RecorderConfig(trace_dir=str(tmp_path / "t"),
                                         finalize_topology=topology,
                                         encode_backend="numpy"))
    _pwrites(tmp_path, rec, 100)
    rec.finalize()
    snap = traced.snapshot()
    by = _by_name(snap)
    (fin,) = by["finalize"]
    assert fin["parent"] is None
    assert _children(snap, fin) == ["encode", "materialize", "reduce",
                                    "write"]
    (enc,) = by["encode"]
    assert _children(snap, enc) == ["encode.delta_zigzag"]
    for s in snap["spans"]:
        if s is not fin:
            assert fin["start_ns"] <= s["start_ns"] <= s["end_ns"] \
                <= fin["end_ns"]
    assert snap["counters"]["encode.delta_zigzag.numpy.elements"] == \
        2 * rec.n_records               # entry and exit ticks per record


@pytest.mark.parametrize("async_flush", [False, True])
def test_flush_span_tree(traced, tmp_path, async_flush):
    rec = Recorder(config=RecorderConfig(trace_dir=str(tmp_path / "t"),
                                         async_flush=async_flush))
    _pwrites(tmp_path, rec, 60)
    rec.flush()
    rec.drain()
    _pwrites(tmp_path, rec, 40)
    rec.finalize()
    snap = traced.snapshot()
    by = _by_name(snap)
    phases = ["commit", "encode", "materialize", "reduce"]
    fin, = by["finalize"]
    flushes = by["flush"]
    assert len(flushes) == 2
    assert [f["parent"] for f in flushes] == [None, fin["id"]]
    assert _children(snap, fin) == ["flush", "write"]
    assert snap["counters"]["flush.records"] == rec.n_records == 104
    for f in flushes:
        kids = _children(snap, f)
        if async_flush and f["parent"] is None:
            # the commit runs on the flush thread, as a tree of its own
            assert kids == ["take_epoch"]
        else:
            assert kids == phases + ["take_epoch"]
    if async_flush:
        roots = [s["name"] for s in snap["spans"]
                 if s["parent"] is None and s["name"] != "finalize"]
        assert sorted(roots) == sorted(["flush"] + phases)


def test_tracing_leaves_the_trace_unchanged(tmp_path):
    """The CST and CFG files are byte-identical with tracing on and off,
    one-shot and streamed."""
    def run(d, on, **kw):
        (tmp_path / d).mkdir()
        rec = Recorder(config=RecorderConfig(trace_dir=str(tmp_path / d / "t"),
                                             **kw))
        if on:
            selftrace.reset()
            selftrace.enable()
        try:
            _pwrites(tmp_path / d, rec, 200)
            rec.finalize()
        finally:
            selftrace.disable()
            selftrace.reset()
        return tmp_path / d / "t"

    for kw, sub in (({}, "."), ({"flush_every_n_records": 64}, "merged")):
        off, on = run(f"off{len(kw)}", False, **kw), run(f"on{len(kw)}",
                                                          True, **kw)
        for name in ("merged_cst.bin", "unique_cfgs.bin"):
            a = (off / sub / name).read_bytes()
            assert a and a == (on / sub / name).read_bytes(), (sub, name)


# ---------------------------------------------------------------------------
# encode ops: the backend each ran on
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["python", "numpy", "pallas"])
def test_encode_counters_by_backend(traced, backend):
    ticks = np.arange(256, dtype=np.int64) * 3
    V = np.stack([np.arange(64), np.arange(64) * 2], axis=1)
    eb.delta_zigzag(ticks, backend)
    eb.delta_zigzag(ticks[:100], backend)
    eb.run_boundaries(V, backend)
    eb.terminal_histogram(ticks % 7, 7, backend)
    eb.digram_histogram(ticks % 5, 5, backend)
    eb.encode_ticks_varint(ticks, backend)
    snap = traced.snapshot()
    c = snap["counters"]
    assert c[f"encode.delta_zigzag.{backend}.calls"] == 2
    assert c[f"encode.delta_zigzag.{backend}.elements"] == 356
    for op, n in (("run_boundaries", V.size), ("terminal_histogram", 256),
                  ("digram_histogram", 256), ("encode_ticks_varint", 256)):
        assert c[f"encode.{op}.{backend}.calls"] == 1
        assert c[f"encode.{op}.{backend}.elements"] == n
    assert sorted({s["name"] for s in snap["spans"]}) == [
        "encode.delta_zigzag", "encode.digram_histogram",
        "encode.encode_ticks_varint", "encode.run_boundaries",
        "encode.terminal_histogram"]


def test_encode_counters_name_what_ran(traced):
    """``auto`` counts under the backend it chose, and a kernel guard that
    falls back counts under numpy."""
    eb.delta_zigzag(np.arange(10), "auto")
    eb.delta_zigzag(np.arange(eb.NUMPY_MIN_BATCH), "auto")
    eb.run_boundaries(np.array([[1 << 40], [0]]), "pallas")
    eb.fit_classify(np.array([[0, 1, 2], [5, 5, 5]]), "pallas")
    eb.fit_classify(np.array([[0, 1 << 40]]), "pallas")
    c = traced.snapshot()["counters"]
    assert c["encode.delta_zigzag.python.calls"] == 1
    assert c["encode.delta_zigzag.numpy.calls"] == 1
    assert c["encode.run_boundaries.numpy.calls"] == 1
    assert c["encode.fit_classify.pallas.calls"] == 1
    assert c["encode.fit_classify.pallas.elements"] == 6
    assert c["encode.fit_classify.numpy.calls"] == 1
    assert not any(k.startswith("encode.run_boundaries.pallas") for k in c)


# ---------------------------------------------------------------------------
# the spans on the profiler's clock
# ---------------------------------------------------------------------------


def test_spans_on_profiler_host_plane(traced, tmp_path):
    import jax
    from jax.profiler import ProfileData

    rec = Recorder(config=RecorderConfig(trace_dir=str(tmp_path / "t")))
    _pwrites(tmp_path, rec, 100)
    prof = tmp_path / "prof"
    jax.profiler.start_trace(str(prof))
    try:
        rec.finalize()
    finally:
        jax.profiler.stop_trace()
    (pb,) = prof.glob("plugins/profile/*/*.xplane.pb")
    pd = ProfileData.from_file(str(pb))
    evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
           for p in pd.planes if p.name == "/host:CPU"
           for ln in p.lines for e in ln.events
           if e.name.startswith("recorder.")]
    names = sorted(n for n, _, _ in evs)
    assert names == ["recorder.encode", "recorder.encode.delta_zigzag",
                     "recorder.finalize", "recorder.materialize",
                     "recorder.reduce", "recorder.write"]
    (_, f0, f1), = [e for e in evs if e[0] == "recorder.finalize"]
    assert all(f0 <= s <= e <= f1 for _, s, e in evs)
    # the same spans, on the same clock length, in the snapshot
    fin = _by_name(traced.snapshot())["finalize"][0]
    assert abs((f1 - f0) - (fin["end_ns"] - fin["start_ns"])) < 5e6
