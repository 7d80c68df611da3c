"""Paths as iteration-symbolic patterns: a path splits into a literal
template and its decimal fields (``patterns.split_path``), the fields
follow runs as offsets do, and every trace reads back to the literal
per-call log.  Field-free paths keep the literal signature, so traces
without fields are byte-identical to those written before path patterns
existed."""

import hashlib
import os
import random

import pytest

from _flat_merge_ref import finalize_ranks
from repro.core import selftrace, trace_format
from repro.core.apis import posix
from repro.core.comm import run_thread_world
from repro.core.encoding import (Handle, IterPattern, PathPattern,
                                 decode_value, encode_value)
from repro.core.patterns import IntraPatternTracker, render_path, split_path
from repro.core.reader import TraceReader
from repro.core.recorder import Recorder, RecorderConfig, attach, detach
from repro.core.specs import REGISTRY
from repro.core.trace_format import make_signature

FID = {n: REGISTRY.id_of(n) for n in (
    "mkdir", "open", "pwrite", "pread", "close", "stat", "unlink", "rmdir",
    "fsync", "rename")}
CREATE, READ = os.O_CREAT | os.O_WRONLY, os.O_RDONLY
FMODE, DMODE, SIZE = 0o664, 0o775, 3901
BINS = ("merged_cst.bin", "unique_cfgs.bin", "cfg_index.bin",
        "timestamps.bin")


# ---------------------------------------------------------------------------
# the split
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("path,fields", [
    ("file.mdtest.0.17", (0, 17)),
    ("/a/b/c.bin", ()),
    ("", ()),
    ("0", (0,)),
    ("00", ()),
    ("x007y", ()),
    ("ckpt_0001/shard_12.bin", (12,)),
    ("run7-0042/step10", (7, 10)),
    ("9" * 18 + "/f", (int("9" * 18),)),
    ("1" + "0" * 18 + "/f.3", (3,)),
    ("layer12norm3.w", (12, 3)),
    ("1a2b3", (1, 2, 3)),
    ("données/é/7/ü", (7,)),
    ("/data/٣٤/5", (5,)),      # Arabic-Indic digits stay literal
    ("-5/+6", (5, 6)),
])
def test_render_split_roundtrip(path, fields):
    template, got = split_path(path)
    assert got == fields
    assert render_path(template, got) == path
    if not fields:
        assert template == path
    else:
        assert len(template) == len(fields) + 1


@pytest.mark.parametrize("value", [
    PathPattern(("/d/file.", ".", ""), (0, 17)),
    PathPattern(("/d/file.", ".", ""), (IterPattern(0, 0),
                                        IterPattern(1, 17))),
    PathPattern(("", ""), (IterPattern(-3, 1 << 40),)),
    PathPattern(("é/", "/ü"), (5,)),
])
def test_path_pattern_value_roundtrip(value):
    out = bytearray()
    encode_value(out, value)
    got, pos = decode_value(bytes(out), 0)
    assert got == value and pos == len(out)


@pytest.mark.parametrize("repeat_concrete,second", [
    (True, [3, 7]),
    (False, [IterPattern(0, 3), IterPattern(0, 7)]),
])
def test_repeated_fields_stay_concrete(repeat_concrete, second):
    """Path fields that repeat their run's first values start no stride
    (one signature for a reopened file); offsets keep the stride-0 run."""
    tr = IntraPatternTracker()
    calls = [[3, 7], [3, 7], [3, 8], [3, 9], [3, 10]]
    got = [tr.encode("k", c, repeat_concrete=repeat_concrete)
           for c in calls]
    assert got[:2] == [[3, 7], second]
    if repeat_concrete:
        assert got[2:] == [[IterPattern(0, 3), IterPattern(1, 7)]] * 3
    else:   # the stride-0 run breaks at [3, 8] and restarts
        assert got[2:] == [[3, 8], [IterPattern(0, 3), IterPattern(1, 8)],
                           [IterPattern(0, 3), IterPattern(1, 8)]]


# ---------------------------------------------------------------------------
# mdtest-hard streams and their literal log
# ---------------------------------------------------------------------------

def plain_log(d, names, rank=0):
    """The literal per-call log of one mdtest-hard job: what a lossless
    trace gives back, handles as unified ids."""
    paths = [os.path.join(d, n) for n in names]
    log = [("mkdir", (d, DMODE), None)]
    for p in paths:
        log += [("open", (p, CREATE, FMODE), 0), ("pwrite", (0, SIZE, 0), SIZE),
                ("close", (0,), None)]
    log += [("stat", (p,), SIZE) for p in paths]
    for p in paths:
        log += [("open", (p, READ, 0), 0), ("pread", (0, SIZE, 0), SIZE),
                ("close", (0,), None)]
    log += [("unlink", (p,), None) for p in paths]
    return log + [("rmdir", (d,), None)]


def names_of(n, rank=0, ids=None):
    return [f"file.mdtest.{rank}.{i}"
            for i in (range(n) if ids is None else ids)]


def facade_job(rec, d, names):
    """One mdtest-hard job through the traced posix facade."""
    paths = [os.path.join(d, n) for n in names]
    buf = b"\x5a" * SIZE
    attach(rec)
    try:
        posix.mkdir(d, DMODE)
        for p in paths:
            fd = posix.open(p, CREATE, FMODE)
            posix.pwrite(fd, buf, 0)
            posix.close(fd)
        for p in paths:
            posix.stat(p)
        for p in paths:
            fd = posix.open(p, READ, 0)
            posix.pread(fd, SIZE, 0)
            posix.close(fd)
        for p in paths:
            posix.unlink(p)
        posix.rmdir(d)
    finally:
        detach()


def fed_job(rec, d, names, raw_fd=7):
    """The same calls fed straight to ``rec.record`` (threads share the
    facades' one active-recorder slot), with a clock of its own."""
    t = [0]

    def call(name, args, ret):
        rec.record(FID[name], args, ret, 0, t[0], t[0] + 1)
        t[0] += 2

    paths = [os.path.join(d, n) for n in names]
    call("mkdir", (d, DMODE), None)
    for p in paths:
        call("open", (p, CREATE, FMODE), raw_fd)
        call("pwrite", (raw_fd, b"\x5a" * SIZE, 0), SIZE)
        call("close", (raw_fd,), None)
        rec.forget_handle(raw_fd)
    for p in paths:
        call("stat", (p,), SIZE)
    for p in paths:
        call("open", (p, READ, 0), raw_fd)
        call("pread", (raw_fd, SIZE, 0), b"r" * SIZE)
        call("close", (raw_fd,), None)
        rec.forget_handle(raw_fd)
    for p in paths:
        call("unlink", (p,), None)
    call("rmdir", (d,), None)


def read_back(trace_dir, rank=0, mode="auto"):
    return [(r.func, tuple(getattr(a, "id", a) for a in r.args),
             getattr(r.ret, "id", r.ret))
            for r in TraceReader(trace_dir, mode=mode).iter_records(rank)]


@pytest.mark.parametrize("n,ids_seed", [(64, None), (1024, None),
                                        (1024, 5)])
def test_mdtest_stream_reads_back_literal_log(tmp_path, n, ids_seed):
    """File ids 0..n-1 (runs), or seeded random ids (no run: every path
    stored concretely)."""
    ids = None
    if ids_seed is not None:
        ids = random.Random(ids_seed).sample(range(10 ** 9), n)
    d, names = str(tmp_path / "mdtest_tree.0"), names_of(n, ids=ids)
    rec = Recorder(config=RecorderConfig(trace_dir=str(tmp_path / "t")))
    facade_job(rec, d, names)
    stats = rec.finalize()
    assert read_back(str(tmp_path / "t")) == plain_log(d, names)
    if ids is None:
        assert stats.cst_entries <= 16
    else:
        assert stats.cst_entries > 4 * n


def _constant_size(tmp_path, n):
    rec = Recorder(config=RecorderConfig(trace_dir=str(tmp_path / f"t{n}")))
    facade_job(rec, str(tmp_path / "mdtest_tree.0"), names_of(n))
    stats = rec.finalize()
    reader = TraceReader(str(tmp_path / f"t{n}"))
    return stats.cst_entries, [len(r) for r in reader.unique_cfgs[0]], \
        len(reader.merged_cst)


def test_cst_and_rules_constant_in_files(tmp_path):
    small, big = _constant_size(tmp_path, 256), _constant_size(tmp_path, 4096)
    assert small == big
    assert small[0] <= 16


def test_calls_after_a_runs_second_reuse_its_terminal(tmp_path):
    """With selftrace on: every call with a path is counted, and every
    call of a run past its second reuses the run's terminal (the closes,
    which have no run values, build their signatures)."""
    n = 300
    rec = Recorder(config=RecorderConfig())
    selftrace.reset()
    selftrace.enable()
    try:
        facade_job(rec, str(tmp_path / "mdtest_tree.0"), names_of(n))
        rec.take_epoch()
        counters = selftrace.snapshot()["counters"]
    finally:
        selftrace.disable()
        selftrace.reset()
    assert counters["record.calls"] == 8 * n + 2
    assert counters["record.path_calls"] == 4 * n + 2
    assert counters["record.path_ns"] > 0
    # create open, pwrite, stat, read open, pread, unlink: n - 2 each
    assert counters["record.memo_hits"] == 6 * (n - 2)


# ---------------------------------------------------------------------------
# byte identity where a path has no field, and the prefix filter
# ---------------------------------------------------------------------------

def _digest(trace_dir):
    h = hashlib.sha256()
    for name in BINS:
        with open(os.path.join(trace_dir, name), "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _listing3(rec):
    for i in range(65536):
        rec.record(FID["pwrite"], (5, b"\x5a" * 256, i * 4096), 256, 0,
                   3 * i, 3 * i + 2)
    rec.record(FID["fsync"], (5,), None, 0, 196608, 196609)
    rec.record(FID["close"], (5,), None, 0, 196610, 196611)


def _literal_paths(rec):
    t = [0]

    def call(name, args, ret):
        rec.record(FID[name], args, ret, 0, t[0], t[0] + 1)
        t[0] += 2

    call("mkdir", ("/data/run/out", 0o755), None)
    for k in range(40):
        call("open", ("/data/run/out/ckpt.bin", CREATE, 0o644), 9)
        call("pwrite", (9, b"x" * 64, k * 64), 64)
        call("close", (9,), None)
        rec.forget_handle(9)
        call("stat", ("/data/run/out/ckpt.bin",), 64 * (k + 1))
    call("rename", ("/data/run/out/ckpt.bin", "/data/run/out/final.bin"), None)
    call("unlink", ("/data/run/out/final.bin",), None)
    call("rmdir", ("/data/run/out",), None)


# sha256 of the four trace files, as written before paths were patterns
@pytest.mark.parametrize("stream,digest", [
    (_listing3,
     "301de70ca8d2b9db78681502fc4b8463135a186f708cb562c7cf51922ea38a6e"),
    (_literal_paths,
     "f8436306dfe8a33c822e83b71bb39eabfcff45737c713b02e5dedad295a9bb02"),
])
def test_field_free_traces_are_unchanged(tmp_path, stream, digest):
    rec = Recorder(config=RecorderConfig(encode_backend="numpy"))
    stream(rec)
    rec.finalize(trace_dir=str(tmp_path / "t"))
    assert _digest(str(tmp_path / "t")) == digest


@pytest.mark.parametrize("path,intra", [("/data/file.bin", True),
                                        ("/data/run7/file.3", False)])
def test_literal_path_signature(path, intra):
    """A path with no field, or any path with patterns off, is the
    literal string in the signature."""
    rec = Recorder(config=RecorderConfig(intra_patterns=intra))
    rec.record(FID["open"], (path, CREATE, FMODE), 11, 0, 0, 1)
    assert rec.cst.entries == [make_signature(
        FID["open"], 0, 0, (path, CREATE, FMODE), Handle(0))]


def test_prefix_filter_acts_on_the_literal_path(tmp_path):
    """``path_prefixes`` compares the literal path: ``run7/`` is kept and
    ``run8/`` skipped though both split to one template."""
    keep = str(tmp_path / "run7")
    rec = Recorder(config=RecorderConfig(path_prefixes=[keep + "/"]))
    for run in (7, 8, 7, 8):
        for i in range(5):
            p = str(tmp_path / f"run{run}" / f"file.{i}")
            rec.record(FID["stat"], (p,), SIZE, 0, 0, 1)
    assert rec.n_records == 10 and rec.n_skipped == 10
    rec.finalize(trace_dir=str(tmp_path / "t"))
    got = [r.args[0] for r in TraceReader(str(tmp_path / "t")).iter_records(0)]
    assert got == [os.path.join(keep, f"file.{i}") for i in range(5)] * 2


# ---------------------------------------------------------------------------
# streaming and several ranks
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["auto", "stitched"])
def test_streamed_job_reads_back(tmp_path, mode):
    """Runs cross epochs: ``take_epoch`` restarts the tracker, so each
    epoch's first call of a run is concrete again."""
    n = 1500
    d, names = str(tmp_path / "mdtest_tree.0"), names_of(n)
    rec = Recorder(config=RecorderConfig(trace_dir=str(tmp_path / "t"),
                                         flush_every_n_records=4096))
    facade_job(rec, d, names)
    rec.finalize()
    assert rec.epoch >= 3
    assert read_back(str(tmp_path / "t"), mode=mode) == plain_log(d, names)


def test_four_ranks_match_reference_merge_and_log(tmp_path):
    """A one-shot finalize of four ranks over ThreadComm: path patterns
    are opaque to the rank fit (each rank's paths keep entries of their
    own), the trace files equal the reference merge's, and each rank
    reads back its literal log."""
    nprocs, n = 4, 200
    d = str(tmp_path / "mdtest_tree.0")
    trace_dir = str(tmp_path / "trace")

    def worker(comm, rank):
        rec = Recorder(rank=rank, config=RecorderConfig())
        fed_job(rec, d, names_of(n, rank))
        return rec.finalize(comm, trace_dir=trace_dir)

    stats = run_thread_world(nprocs, worker)
    assert stats[0] is not None
    states = []
    for r in range(nprocs):
        rec = Recorder(rank=r, config=RecorderConfig())
        fed_job(rec, d, names_of(n, r))
        states.append(rec.local_state())
    merge, cfgs = finalize_ranks([s[0] for s in states],
                                 [s[1] for s in states], REGISTRY)
    ref_dir = str(tmp_path / "reference")
    trace_format.write_trace(ref_dir, registry=REGISTRY,
                             merged_cst=merge.merged_entries,
                             unique_cfgs=cfgs.unique_cfgs,
                             cfg_index=cfgs.cfg_index,
                             rank_timestamps=[s[2] for s in states],
                             meta_extra={})
    for name in BINS:
        with open(os.path.join(trace_dir, name), "rb") as f1, \
                open(os.path.join(ref_dir, name), "rb") as f2:
            assert f1.read() == f2.read(), name
    for r in range(nprocs):
        assert read_back(trace_dir, r) == plain_log(d, names_of(n, r), r)


# ---------------------------------------------------------------------------
# TraceView's per-file queries
# ---------------------------------------------------------------------------

def per_file_from_records(reader):
    """Per-file {bytes, calls} of the data calls, each attributed to the
    path its handle was last opened under: from the expanded records."""
    out = {}
    for rank in range(reader.nranks):
        live = {}
        for r in reader.iter_records(rank, timestamps=False):
            if r.func == "open" and hasattr(r.ret, "id"):
                live[r.ret.id] = r.args[0]
            if r.func in ("pwrite", "pread"):
                key = live.get(r.args[0].id)
                agg = out.setdefault(key, {"bytes": 0, "calls": 0})
                agg["bytes"] += r.args[1] if r.func == "pwrite" \
                    else r.ret
                agg["calls"] += 1
    return out


@pytest.mark.parametrize("ids_seed", [None, 3])
def test_per_file_queries_match_records(tmp_path, ids_seed):
    n = 400
    ids = None if ids_seed is None else \
        random.Random(ids_seed).sample(range(10 ** 6), n)
    d, names = str(tmp_path / "mdtest_tree.0"), names_of(n, ids=ids)
    rec = Recorder(config=RecorderConfig(trace_dir=str(tmp_path / "t")))
    facade_job(rec, d, names)
    rec.finalize()
    reader = TraceReader(str(tmp_path / "t"))
    want = per_file_from_records(reader)
    assert len(want) == n
    view = reader.view()
    assert view.io_summary()["files"] == want
    assert view._per_file_walk_memo(0) == want
    assert view._per_file_walk_linear(0) == want


def test_per_file_refresh_folds_path_patterns(tmp_path):
    """A reader opened on a running stream, its per-file answer memoized,
    then refreshed over new epochs, answers as a fresh reader does."""
    n = 600
    d, names = str(tmp_path / "mdtest_tree.0"), names_of(n)
    trace_dir = str(tmp_path / "t")
    rec = Recorder(config=RecorderConfig(trace_dir=trace_dir))
    facade_job(rec, d, names[:300])
    rec.flush()
    reader = TraceReader(trace_dir)
    before = reader.view().io_summary()["files"]
    assert len(before) == 300
    facade_job(rec, d, names[300:])
    rec.flush()
    assert reader.refresh() == 1
    fresh = TraceReader(trace_dir, mode="stitched")
    assert reader.view().io_summary()["files"] == \
        per_file_from_records(fresh)
    rec.finalize()
