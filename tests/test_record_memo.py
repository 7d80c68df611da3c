"""The record path's generated per-function code (``recorder.record_code``),
which reuses a continuing offset run's terminal: every epoch it leaves --
CST entries, serialized grammar, timestamp rows with their data bytes --
is byte-identical to a scalar reference that interprets each argument's
role and builds every call's signature (``IntraPatternTracker.encode`` +
``make_signature`` + ``CST.intern`` + ``Sequitur.push``), and it reuses a
terminal exactly where the reference says a run's kept one applies."""

import os
import random
import threading

import numpy as np
import pytest

from repro.core import selftrace, streaming, trace_format
from repro.core.apis import framework, posix, shardio
from repro.core.cst import CST
from repro.core.encoding import Handle, IterPattern, PathPattern
from repro.core.patterns import IntraPatternTracker, split_path
from repro.core.recorder import (_KEPT_TYPES, Recorder, RecorderConfig,
                                 attach, detach)
from repro.core.sequitur import Sequitur, concat_grammars
from repro.core.specs import (DATA_FUNCS, REGISTRY, Arg, FnSpec,
                              FunctionRegistry, Role)
from repro.core.trace_format import make_signature

# a private registry for calls no facade makes: two OFFSET-role arguments,
# and a data-moving function whose return is OFFSET-role (its data bytes
# come from the return, an IterPattern inside a run: 0)
TEST_REGISTRY = FunctionRegistry()
COPY_FID = TEST_REGISTRY.register(FnSpec(
    "copy_range", "test", [Arg("fd_in", Role.HANDLE), Arg("off_in", Role.OFFSET),
                           Arg("fd_out", Role.HANDLE), Arg("off_out", Role.OFFSET),
                           Arg("count", Role.SIZE)]))
READ_FID = TEST_REGISTRY.register(FnSpec(
    "read", "test", [Arg("fd", Role.HANDLE), Arg("at", Role.OFFSET)],
    ret_role=Role.OFFSET))


def _data_bytes(spec, norm, nret):
    if spec.name not in DATA_FUNCS:
        return 0
    for a, v in zip(spec.args, norm):
        if a.role in (Role.BUF, Role.SIZE) and isinstance(v, int):
            return v
    return nret if isinstance(nret, int) else 0


class _Reference:
    """The record path without reuse, per call: roles normalised (paths
    filtered by prefix and split into template and fields, handles given
    the smallest free id, closed ones freed), offsets and path fields
    encoded, the signature built and interned, its terminal pushed.  ``hits`` counts the calls whose signature is the
    one kept in their key's run: a call that continues the run (its
    values encode as ``IterPattern``s), at the depth of the last one
    that kept it, with every SIZE/VAL value (and a return that is not
    OFFSET-role) of an exact type."""

    def __init__(self, registry, config):
        self.registry = registry
        self.intra_patterns = config.intra_patterns
        self.prefixes = config.path_prefixes
        self.timestamps = config.timestamps
        self.handles, self.free, self.untracked = {}, set(), set()
        self.next_id = 0
        self.threads = {}
        self.pending = None
        self.recorded = self.hits = 0
        self._new_epoch()

    def _new_epoch(self):
        self.cst = CST()
        self.grammar = Sequitur()
        self.intra = IntraPatternTracker(enabled=self.intra_patterns)
        self.kept = {}          # key -> depth of its run's kept signature
        self.ticks = []

    def _handle(self, raw):
        h = self.handles.get(raw)
        if h is None:
            if self.free:
                hid = min(self.free)
                self.free.discard(hid)
            else:
                hid, self.next_id = self.next_id, self.next_id + 1
            h = self.handles[raw] = Handle(hid)
        return h

    def forget(self, raw):
        h = self.handles.pop(raw, None)
        if h is not None:
            self.free.add(h.id)
        self.untracked.discard(raw)

    def record(self, thread, func_id, raw_args, ret, depth, t0, t1):
        spec = self.registry.spec(func_id)
        tidx = self.threads.setdefault(thread, len(self.threads))
        norm, offsets, slots, hids, parts = [], [], [], [], []
        paths, fields = [], []
        exact = True
        for i, arg in enumerate(raw_args):
            role = spec.args[i].role if i < len(spec.args) else Role.VAL
            if role == Role.PATH:
                p = str(arg)
                if self.prefixes is not None and not any(
                        p.startswith(x) for x in self.prefixes):
                    if spec.ret_role == Role.HANDLE and ret is not None:
                        self.untracked.add(ret)
                    return
                template, fs = (split_path(p) if self.intra_patterns
                                else (p, ()))
                if fs:
                    paths.append((len(norm), template, len(fs)))
                    fields.extend(fs)
                norm.append(p)
                parts.append(template)
            elif role == Role.HANDLE:
                if arg in self.untracked:
                    return
                h = self._handle(arg)
                norm.append(h)
                hids.append(h.id)
            elif role == Role.OFFSET:
                offsets.append(int(arg))
                slots.append(len(norm))
                norm.append(None)
            elif role == Role.BUF:
                v = len(arg) if hasattr(arg, "__len__") else (
                    int(arg) if isinstance(arg, int) else None)
                norm.append(v)
                parts.append(v)
            else:
                exact &= type(arg) in _KEPT_TYPES
                norm.append(arg)
                parts.append(arg)
        err = isinstance(ret, tuple) and len(ret) == 2 and ret[0] == "err"
        nret = ret
        if spec.ret_role == Role.HANDLE and ret is not None and not err:
            nret = self._handle(ret)
        elif spec.ret_role == Role.BUF and hasattr(ret, "__len__"):
            nret = len(ret)
        ret_off = spec.ret_role == Role.OFFSET and isinstance(nret, int)
        if offsets or fields or ret_off:
            key_ret = ("h", nret.id) if isinstance(nret, Handle) else nret
            key = (func_id, tidx, tuple(hids), tuple(parts),
                   None if ret_off else key_ret)
            exact &= ret_off or type(nret) in _KEPT_TYPES
            enc = self.intra.encode(
                key, offsets + fields + ([nret] if ret_off else []),
                repeat_concrete=not offsets and not ret_off)
            for slot, v in zip(slots, enc):
                norm[slot] = v
            k = len(slots)
            for slot, template, n in paths:
                norm[slot] = PathPattern(template, tuple(enc[k:k + n]))
                k += n
            if ret_off:
                nret = enc[-1]
            if enc and not isinstance(enc[0], IterPattern):
                self.kept.pop(key, None)        # the run (re)started
            elif enc and exact:
                if self.kept.get(key) == depth:
                    self.hits += 1
                self.kept[key] = depth
        sig = make_signature(func_id, tidx, depth, tuple(norm), nret)
        self.grammar.push(self.cst.intern(sig))
        self.recorded += 1
        if self.timestamps:
            self.ticks.append((t0, t1, _data_bytes(spec, norm, nret)))

    def take(self):
        entries, cfg = list(self.cst.entries), self.grammar.serialize()
        ticks = np.array(self.ticks, dtype=np.uint32).reshape(-1, 3)
        self._new_epoch()
        if self.pending is not None:
            p_entries, p_cfg, p_ticks = self.pending
            self.pending = None
            cfg = concat_grammars([(p_cfg, 0), (cfg, len(p_entries))])
            entries = p_entries + entries
            ticks = np.concatenate([p_ticks, ticks], axis=0)
        return entries, cfg, ticks


# ---------------------------------------------------------------------------
# call streams
# ---------------------------------------------------------------------------


class _Clock:
    def __init__(self):
        self.t = 0

    def __call__(self):
        self.t += 3
        return self.t - 3, self.t - 1


def _facade(rec, tmp_path, calls):
    """Run ``calls(fds)`` through the traced posix facade on two files."""
    fds = [os.open(tmp_path / f"f{i}.bin", os.O_RDWR | os.O_CREAT, 0o644)
           for i in range(2)]
    attach(rec)
    try:
        calls(fds)
    finally:
        detach()
        for fd in fds:
            os.close(fd)


def _pwrite_run(fd, offsets, block=64):
    buf = b"\x5a" * block
    for off in offsets:
        posix.pwrite(fd, buf, off)


def s_run_breaks_and_restarts(rec, tmp_path, monkeypatch):
    def calls(fds):
        _pwrite_run(fds[0], [i * 4096 for i in range(20)])
        _pwrite_run(fds[0], [7, 99, 5000] + [100 + 3 * i for i in range(15)])
        _pwrite_run(fds[0], [i * 4096 for i in range(10)])
    _facade(rec, tmp_path, calls)


def s_stride_zero(rec, tmp_path, monkeypatch):
    _facade(rec, tmp_path, lambda fds: _pwrite_run(fds[0], [512] * 40))


def s_two_interleaved_handles(rec, tmp_path, monkeypatch):
    def calls(fds):
        buf = b"q" * 32
        for i in range(40):
            posix.pwrite(fds[0], buf, i * 4096)
            posix.pwrite(fds[1], buf, (1 << 20) - i * 512)
            if i % 9 == 8:
                posix.fsync(fds[i % 2])
    _facade(rec, tmp_path, calls)


def s_depths_alternate(rec, tmp_path, monkeypatch):
    """One pattern key at depths 0 and 1 (a layered write and a direct
    one): the runs continue, the signatures differ by depth."""
    fid, clock = REGISTRY.id_of("pwrite"), _Clock()
    for i in range(30):
        depth = (i // 3) % 2 if i < 15 else i % 2
        rec.record(fid, ("fd", b"x" * 64, i * 64), 64, depth, *clock())


def s_lseek_offset_return(rec, tmp_path, monkeypatch):
    def calls(fds):
        for i in range(30):
            posix.lseek(fds[0], 100 + i * 512, os.SEEK_SET)
        for i in range(10):
            posix.lseek(fds[0], 0, os.SEEK_CUR)   # stride 0, return too
        posix.write(fds[0], b"abc")
        for i in range(10):
            posix.lseek(fds[0], 50 * i, os.SEEK_SET)
    _facade(rec, tmp_path, calls)


def s_multi_offset(rec, tmp_path, monkeypatch):
    clock = _Clock()
    for i in range(40):
        src = i * 4096 if i != 25 else 3
        rec.record(COPY_FID, ("in", src, "out", 1000 + i * 100, 4096), 4096,
                   0, *clock())


def s_data_call_offset_return(rec, tmp_path, monkeypatch):
    clock = _Clock()
    for i in range(30):
        rec.record(READ_FID, ("fd", i * 8), 4096 + i * 8, 0, *clock())


def s_intra_patterns_off(rec, tmp_path, monkeypatch):
    _facade(rec, tmp_path,
            lambda fds: _pwrite_run(fds[0], [i * 4096 for i in range(30)]))


def s_flush_mid_run(rec, tmp_path, monkeypatch):
    def calls(fds):
        _pwrite_run(fds[0], [i * 4096 for i in range(25)])
        rec.flush()
        _pwrite_run(fds[0], [i * 4096 for i in range(25, 50)])
    _facade(rec, tmp_path, calls)


def s_failed_commit_restored(rec, tmp_path, monkeypatch):
    """A commit that fails keeps its epoch in ``_pending``; the next
    flush splices it in front of the records after it."""
    real = streaming.trace_format.write_trace

    def boom(*a, **k):
        raise OSError("disk full")

    def calls(fds):
        _pwrite_run(fds[0], [i * 4096 for i in range(20)])
        rec.flush()
        _pwrite_run(fds[0], [i * 4096 for i in range(20, 40)])
        monkeypatch.setattr(streaming.trace_format, "write_trace", boom)
        with pytest.raises(OSError, match="disk full"):
            rec.flush()
        monkeypatch.setattr(streaming.trace_format, "write_trace", real)
        _pwrite_run(fds[0], [i * 4096 for i in range(40, 60)])
        rec.flush()
        _pwrite_run(fds[0], [i * 4096 for i in range(60, 70)])
    _facade(rec, tmp_path, calls)
    assert rec.epochs_restored == 1


def s_two_threads(rec, tmp_path, monkeypatch):
    """Two threads write one file: each thread's calls are a run of its
    own key, interleaved under the recorder's lock."""
    fid = REGISTRY.id_of("pwrite")
    gate = threading.Barrier(2)

    def worker(k):
        gate.wait(timeout=30)
        for i in range(200):
            rec.record(fid, ("fd", b"w" * 128, k * 64 + i * 128), 128, 0,
                       2 * i, 2 * i + 1)

    ts = [threading.Thread(target=worker, args=(k,)) for k in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
        assert not t.is_alive()


def s_equal_values_other_types(rec, tmp_path, monkeypatch):
    """A SIZE-role argument equal across types (1, True, 1.0, numpy 1):
    one pattern key and one run, but four signatures."""
    sizes = [1, 1, 1, True, 1, 1.0, 1.0, 1, np.int64(1), 1, 1, 0.0, -0.0,
             -0.0, 0, 0]
    attach(rec)
    try:
        for i, n in enumerate(sizes):
            framework.fetch_batch(10 * i, n)
    finally:
        detach()


def s_random_runs(rec, tmp_path, monkeypatch):
    """Seeded runs of random length, stride and depth on three keys."""
    rng = random.Random(15)
    fid, clock = REGISTRY.id_of("pwrite"), _Clock()
    for _ in range(60):
        fd = rng.choice(["a", "b", "c"])
        base, stride = rng.randrange(1 << 30), rng.choice([0, 1, 4096, -8])
        depth = rng.choice([0, 0, 1])
        for i in range(rng.randrange(1, 12)):
            rec.record(fid, (fd, b"r" * 16, base + i * stride), 16, depth,
                       *clock())


def s_every_posix_spec(rec, tmp_path, monkeypatch):
    """Every posix function through its wrapper (handles opened, closed
    and their ids reused, failed calls included), then the private
    registry's two calls."""
    d, buf = tmp_path / "d", b"e" * 64
    attach(rec)
    try:
        posix.mkdir(str(d), 0o755)
        with pytest.raises(FileExistsError):
            posix.mkdir(str(d), 0o755)
        posix.makedirs(str(d / "a" / "b"), 0o755)
        f = str(d / "f.bin")
        fd = posix.open(f, os.O_RDWR | os.O_CREAT, 0o644)
        for i in range(20):
            posix.pwrite(fd, buf, i * 128)
        for i in range(10):
            posix.pread(fd, 32, i * 128)
        posix.lseek(fd, 0, os.SEEK_SET)
        for _ in range(5):
            posix.write(fd, buf[:16])
        posix.lseek(fd, 0, os.SEEK_SET)
        for _ in range(5):
            posix.read(fd, 16)
        posix.fsync(fd)
        posix.ftruncate(fd, 100)
        posix.close(fd)
        posix.stat(f)
        posix.access(f, os.R_OK)
        posix.chmod(f, 0o600)
        posix.symlink(f, str(d / "link"))
        posix.readlink(str(d / "link"))
        posix.opendir(str(d))
        posix.rename(f, str(d / "g.bin"))
        fd = posix.open(str(d / "g.bin"), os.O_RDWR, 0)
        for i in range(10):
            posix.pwrite(fd, buf, 4096 + i * 64)
        posix.close(fd)
        posix.unlink(str(d / "link"))
        posix.unlink(str(d / "g.bin"))
        posix.rmdir(str(d / "a" / "b"))
    finally:
        detach()
    s_multi_offset(rec, tmp_path, monkeypatch)
    s_data_call_offset_return(rec, tmp_path, monkeypatch)


def s_error_returns(rec, tmp_path, monkeypatch):
    """Failed calls inside runs and between them, through the wrappers and
    as ("err", name) returns of every return role."""
    buf = b"x" * 64
    rw = os.open(tmp_path / "rw.bin", os.O_RDWR | os.O_CREAT, 0o644)
    ro = os.open(tmp_path / "ro.bin", os.O_RDONLY | os.O_CREAT, 0o644)
    attach(rec)
    try:
        for i in range(16):
            posix.pwrite(rw, buf, i * 64)
            if i % 4 == 3:
                with pytest.raises(OSError):
                    posix.pwrite(ro, buf, i * 64)
        with pytest.raises(FileNotFoundError):
            posix.open(str(tmp_path / "missing" / "x"), os.O_RDONLY, 0)
        with pytest.raises(OSError):
            posix.lseek(rw, -1, os.SEEK_SET)
    finally:
        detach()
        os.close(rw)
        os.close(ro)
    err, clock = ("err", "OSError"), _Clock()
    fids = {n: REGISTRY.id_of(n) for n in ("pwrite", "pread", "lseek", "open")}
    for i in range(12):
        rec.record(fids["pwrite"], ("fd", buf, i * 64),
                   err if i in (5, 6) else 64, 0, *clock())
        rec.record(fids["pread"], ("fd", 8, i * 8),
                   err if i == 7 else b"r" * 8, 0, *clock())
        rec.record(fids["lseek"], ("fd", i * 16, 0),
                   err if i == 4 else i * 16, 0, *clock())
    rec.record(fids["open"], ("/x", 0, 0), err, 0, *clock())


def s_layered_depths(rec, tmp_path, monkeypatch):
    """The shard layer over posix: every shard call records at depth 0
    and its posix call at depth 1, layered opens share one handle id."""
    attach(rec)
    try:
        fh = shardio.shard_open(str(tmp_path / "s.bin"), 1)
        for i in range(20):
            shardio.shard_write_at(fh, b"s" * 128, i * 256)
        for i in range(10):
            shardio.shard_read_at(fh, 64, i * 256)
        shardio.shard_sync(fh)
        shardio.shard_close(fh)
        fh = shardio.shard_open(str(tmp_path / "t.bin"), 1)
        for i in range(10):
            shardio.shard_write_at(fh, b"t" * 64, 4096 + i * 64)
        shardio.shard_close(fh)
        shardio.shard_commit(str(tmp_path / "t.bin"), str(tmp_path / "u.bin"))
    finally:
        detach()


def s_two_threads_facade(rec, tmp_path, monkeypatch):
    """Two threads, each with its own file, through the wrappers."""
    fds = [os.open(tmp_path / f"w{k}.bin", os.O_RDWR | os.O_CREAT, 0o644)
           for k in range(2)]
    gate = threading.Barrier(2)

    def worker(k):
        gate.wait(timeout=30)
        for i in range(100):
            posix.pwrite(fds[k], b"w" * 32, i * 64)
            if i % 10 == 9:
                posix.lseek(fds[k], i * 64, os.SEEK_SET)
                posix.fsync(fds[k])

    attach(rec)
    try:
        ts = [threading.Thread(target=worker, args=(k,)) for k in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
            assert not t.is_alive()
    finally:
        detach()
        for fd in fds:
            os.close(fd)


def s_path_prefixes_untracked(rec, tmp_path, monkeypatch):
    """``path_prefixes`` set on a live recorder: calls on paths outside it
    and on handles opened there are skipped until the handle closes."""
    keep, skip, buf = tmp_path / "keep", tmp_path / "skip", b"p" * 32
    keep.mkdir()
    skip.mkdir()
    rec.config.path_prefixes = [str(keep)]
    attach(rec)
    try:
        a = posix.open(str(skip / "a.bin"), os.O_RDWR | os.O_CREAT, 0o644)
        b = posix.open(str(keep / "b.bin"), os.O_RDWR | os.O_CREAT, 0o644)
        for i in range(15):
            posix.pwrite(a, buf, i * 64)
            posix.pwrite(b, buf, i * 64)
        posix.stat(str(skip / "a.bin"))
        posix.stat(str(keep / "b.bin"))
        posix.close(a)
        posix.close(b)
        posix.rename(str(keep / "b.bin"), str(skip / "b.bin"))
        c = posix.open(str(keep / "c.bin"), os.O_RDWR | os.O_CREAT, 0o644)
        for i in range(5):
            posix.pwrite(c, buf, i * 64)
        posix.close(c)
    finally:
        detach()
    assert rec.n_skipped == 19


def s_value_types(rec, tmp_path, monkeypatch):
    """numpy ints, bools and floats among the SIZE/VAL arguments, the
    SIZE returns and the OFFSET-role returns of runs."""
    clock, n64, n32 = _Clock(), np.int64, np.int32
    pw, pr, ls = (REGISTRY.id_of(n) for n in ("pwrite", "pread", "lseek"))
    for i, r in enumerate([64, 64, n64(64), 64, 64, True, 1, 1, 64.0, 64,
                           64, n32(64), 64, 64]):
        rec.record(pw, ("fd", b"v" * 64, i * 64), r, 0, *clock())
    for i, n in enumerate([8, 8, n64(8), 8, 8.0, 8, True, 1, 1, 8]):
        rec.record(pr, ("fd", n, i * 8), b"r" * 8, 0, *clock())
    for i, w in enumerate([0, 0, False, 0, 0, n64(0), 0.0, 0, 0]):
        rec.record(ls, ("fd", i * 16, w), i * 16, 0, *clock())
    for i, r in enumerate([n64(7), 7, 7, True, 7, 7.0, 7, 7]):
        rec.record(ls, ("fd", 100 + i, 0), r, 0, *clock())
    attach(rec)
    try:
        for i, n in enumerate([4, 4, n64(4), 4, 4, 4.0, 4, 4]):
            framework.fetch_batch(3 * i, n)
    finally:
        detach()


def s_path_runs(rec, tmp_path, monkeypatch):
    """Paths whose decimal fields follow runs, through the wrappers: file
    ids counting up, one path repeated, a run broken, renames with two
    paths of fields each, and failed calls in a run."""
    d = tmp_path / "mdtest_tree.0"
    ids = list(range(12)) + [3, 3, 3] + list(range(20, 30))
    attach(rec)
    try:
        posix.mkdir(str(d), 0o775)
        for i in ids:
            fd = posix.open(str(d / f"file.mdtest.0.{i}"),
                            os.O_CREAT | os.O_WRONLY, 0o664)
            posix.pwrite(fd, b"m" * 64, 0)
            posix.close(fd)
        for i in ids:
            posix.stat(str(d / f"file.mdtest.0.{i}"))
        for i in range(20, 26):
            posix.rename(str(d / f"file.mdtest.0.{i}"),
                         str(d / f"moved.{2 * i}"))
        for i in range(20, 26):
            posix.unlink(str(d / f"moved.{2 * i}"))
        for i in sorted(set(ids) - set(range(20, 26))):
            posix.unlink(str(d / f"file.mdtest.0.{i}"))
        for i in range(4):
            with pytest.raises(FileNotFoundError):
                posix.stat(str(d / f"missing.{i}"))
        posix.rmdir(str(d))
    finally:
        detach()


def s_timestamps_off(rec, tmp_path, monkeypatch):
    def calls(fds):
        _pwrite_run(fds[0], [i * 4096 for i in range(20)])
        for i in range(10):
            posix.lseek(fds[1], i * 8, os.SEEK_SET)
    _facade(rec, tmp_path, calls)


LATE_ARGS = [Arg("fd", Role.HANDLE), Arg("buf", Role.BUF),
             Arg("at", Role.OFFSET)]


def s_spec_registered_late(rec, tmp_path, monkeypatch):
    """A function registered after the recorder was made (and had recorded
    calls of another) is recorded through code generated for it then."""
    clock = _Clock()
    early = rec.registry.id_of("early_write")
    for i in range(10):
        rec.record(early, ("fd", b"e" * 8, i * 8), 8, 0, *clock())
    late = rec.registry.register(FnSpec("late_write", "test", LATE_ARGS,
                                        ret_role=Role.SIZE))
    for i in range(20):
        rec.record(late, ("fd", b"l" * 16, 64 + i * 16), 16, 0, *clock())
        rec.record(early, ("fd", b"e" * 8, (10 + i) * 8), 8, 0, *clock())


def s_memo_hits_counted(rec, tmp_path, monkeypatch):
    """With selftrace on: the stage sums count each reused terminal."""
    s_run_breaks_and_restarts(rec, tmp_path, monkeypatch)
    s_random_runs(rec, tmp_path, monkeypatch)


# name -> (stream, registry (None: a fresh one holding "early_write"),
#          config overrides, does the memo engage)
# name -> (stream, registry, config overrides, does the memo engage)
STREAMS = {
    "run_breaks_and_restarts": (s_run_breaks_and_restarts, REGISTRY, {}, True),
    "stride_zero": (s_stride_zero, REGISTRY, {}, True),
    "two_interleaved_handles": (s_two_interleaved_handles, REGISTRY, {}, True),
    "depths_alternate": (s_depths_alternate, REGISTRY, {}, True),
    "lseek_offset_return": (s_lseek_offset_return, REGISTRY, {}, True),
    "multi_offset": (s_multi_offset, TEST_REGISTRY, {}, True),
    "data_call_offset_return": (s_data_call_offset_return, TEST_REGISTRY, {},
                                True),
    "intra_patterns_off": (s_intra_patterns_off, REGISTRY,
                           {"intra_patterns": False}, False),
    "flush_mid_run": (s_flush_mid_run, REGISTRY, {}, True),
    "failed_commit_restored": (s_failed_commit_restored, REGISTRY, {}, True),
    "two_threads": (s_two_threads, REGISTRY, {}, True),
    "equal_values_other_types": (s_equal_values_other_types, REGISTRY, {},
                                 True),
    "random_runs": (s_random_runs, REGISTRY, {}, True),
    "every_posix_spec": (s_every_posix_spec, REGISTRY, {}, True),
    "error_returns": (s_error_returns, REGISTRY, {}, True),
    "layered_depths": (s_layered_depths, REGISTRY, {}, True),
    "two_threads_facade": (s_two_threads_facade, REGISTRY, {}, True),
    "path_prefixes_untracked": (s_path_prefixes_untracked, REGISTRY, {}, True),
    "value_types": (s_value_types, REGISTRY, {}, True),
    "path_runs": (s_path_runs, REGISTRY, {}, True),
    "path_runs_intra_off": (s_path_runs, REGISTRY,
                            {"intra_patterns": False}, False),
    "timestamps_off": (s_timestamps_off, REGISTRY, {"timestamps": False},
                       True),
    "spec_registered_late": (s_spec_registered_late, None, {}, True),
    "memo_hits_counted": (s_memo_hits_counted, REGISTRY, {}, True),
}

# streams run with selftrace on: record.memo_hits must equal the hits
SELFTRACED = {"memo_hits_counted"}


@pytest.mark.parametrize("name", list(STREAMS))
def test_reused_terminals_leave_the_trace_unchanged(name, tmp_path,
                                                    monkeypatch):
    stream, registry, overrides, engages = STREAMS[name]
    if registry is None:
        registry = FunctionRegistry()
        registry.register(FnSpec("early_write", "test", LATE_ARGS,
                                 ret_role=Role.SIZE))
    rec = Recorder(config=RecorderConfig(trace_dir=str(tmp_path / "t"),
                                         **overrides), registry=registry)
    # what the recorder was given, in the order it took it, and where its
    # epochs were cut and put back
    log, taken, order = [], [], threading.Lock()
    record, forget = rec.record, rec.forget_handle
    take_epoch, restore_epoch = rec.take_epoch, rec._restore_epoch

    def logged_record(*call):
        with order:
            log.append(("call", threading.get_ident()) + call)
            record(*call)

    def logged_forget(raw):
        with order:
            log.append(("forget", raw))
            forget(raw)

    def logged_take():
        epoch = take_epoch()
        log.append(("take",))
        taken.append(epoch[:3])
        return epoch

    def logged_restore(*epoch):
        log.append(("restore",))
        restore_epoch(*epoch)

    rec.record, rec.forget_handle = logged_record, logged_forget
    rec.take_epoch, rec._restore_epoch = logged_take, logged_restore
    built = []
    monkeypatch.setattr(trace_format, "make_signature",
                        lambda *a: built.append(1) or make_signature(*a))

    traced = name in SELFTRACED
    if traced:
        selftrace.reset()
        selftrace.enable()
    try:
        stream(rec, tmp_path, monkeypatch)
        rec.take_epoch()
        counters = selftrace.snapshot()["counters"]
    finally:
        if traced:
            selftrace.disable()
            selftrace.reset()
    assert any(e[0] == "call" for e in log)

    ref = _Reference(registry, rec.config)
    want = []
    for event in log:
        if event[0] == "call":
            ref.record(*event[1:])
        elif event[0] == "forget":
            ref.forget(event[1])
        elif event[0] == "take":
            want.append(ref.take())
        else:
            ref.pending = want[-1]
    assert rec.n_records == ref.recorded
    # every call builds its signature but those that reuse a terminal
    assert len(built) == ref.recorded - ref.hits
    assert (ref.hits > 0) == engages
    if traced:
        assert counters["record.memo_hits"] == ref.hits
    assert len(taken) == len(want)
    for (entries, cfg, ticks), (w_entries, w_cfg, w_ticks) in zip(taken, want):
        assert list(entries) == w_entries
        assert cfg == w_cfg
        assert ticks.dtype == np.uint32
        np.testing.assert_array_equal(ticks, w_ticks)
