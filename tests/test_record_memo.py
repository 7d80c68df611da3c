"""The record path's reuse of a continuing offset run's terminal
(``IntraPatternTracker.advance``): every epoch it leaves -- CST entries,
serialized grammar, timestamp rows with their data bytes -- is
byte-identical to a scalar reference that builds every call's signature
(``IntraPatternTracker.encode`` + ``make_signature`` + ``CST.intern`` +
``Sequitur.push``)."""

import os
import random
import threading

import numpy as np
import pytest

from repro.core import streaming, trace_format
from repro.core.apis import framework, posix
from repro.core.cst import CST
from repro.core.encoding import Handle
from repro.core.patterns import IntraPatternTracker
from repro.core.recorder import Recorder, RecorderConfig, attach, detach
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
    """The record path without reuse, per call: roles normalised (handles
    registered in first-use order; the streams close none), offsets
    encoded, the signature built and interned, its terminal pushed."""

    def __init__(self, registry, intra_patterns):
        self.registry = registry
        self.intra_patterns = intra_patterns
        self.handles = {}
        self.threads = {}
        self.pending = None
        self._new_epoch()

    def _new_epoch(self):
        self.cst = CST()
        self.grammar = Sequitur()
        self.intra = IntraPatternTracker(enabled=self.intra_patterns)
        self.ticks = []

    def record(self, thread, func_id, raw_args, ret, depth, t0, t1):
        spec = self.registry.spec(func_id)
        tidx = self.threads.setdefault(thread, len(self.threads))
        norm, offsets, slots, hids, parts = [], [], [], [], []
        for i, arg in enumerate(raw_args):
            role = spec.args[i].role if i < len(spec.args) else Role.VAL
            if role == Role.HANDLE:
                h = self.handles.setdefault(arg, Handle(len(self.handles)))
                norm.append(h)
                hids.append(h.id)
            elif role == Role.OFFSET:
                offsets.append(int(arg))
                slots.append(len(norm))
                norm.append(None)
            elif role == Role.BUF:
                norm.append(len(arg))
                parts.append(len(arg))
            else:
                norm.append(arg)
                parts.append(arg)
        nret = ret
        if spec.ret_role == Role.BUF and hasattr(ret, "__len__"):
            nret = len(ret)
        ret_off = spec.ret_role == Role.OFFSET and isinstance(nret, int)
        if offsets or ret_off:
            key = (func_id, tidx, tuple(hids), tuple(parts),
                   None if ret_off else nret)
            enc = self.intra.encode(key, offsets + ([nret] if ret_off else []))
            for slot, v in zip(slots, enc):
                norm[slot] = v
            if ret_off:
                nret = enc[-1]
        sig = make_signature(func_id, tidx, depth, tuple(norm), nret)
        self.grammar.push(self.cst.intern(sig))
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
}


@pytest.mark.parametrize("name", list(STREAMS))
def test_reused_terminals_leave_the_trace_unchanged(name, tmp_path,
                                                    monkeypatch):
    stream, registry, overrides, engages = STREAMS[name]
    rec = Recorder(config=RecorderConfig(trace_dir=str(tmp_path / "t"),
                                         **overrides), registry=registry)
    # what the recorder was given, in the order its lock took it, and
    # where its epochs were cut and put back
    log, taken = [], []
    record_locked, take_epoch = rec._record_locked, rec.take_epoch
    restore_epoch = rec._restore_epoch

    def logged_record(spec, *call):
        log.append(("call", threading.get_ident()) + call)
        record_locked(spec, *call)

    def logged_take():
        epoch = take_epoch()
        log.append(("take",))
        taken.append(epoch[:3])
        return epoch

    def logged_restore(*epoch):
        log.append(("restore",))
        restore_epoch(*epoch)

    rec._record_locked = logged_record
    rec.take_epoch = logged_take
    rec._restore_epoch = logged_restore
    built = []
    monkeypatch.setattr(trace_format, "make_signature",
                        lambda *a: built.append(1) or make_signature(*a))

    stream(rec, tmp_path, monkeypatch)
    rec.take_epoch()
    calls = sum(1 for e in log if e[0] == "call")
    assert calls > 0
    if engages:
        assert len(built) < calls
    else:
        assert len(built) == calls

    ref = _Reference(registry, overrides.get("intra_patterns", True))
    want = []
    for event in log:
        if event[0] == "call":
            ref.record(*event[1:])
        elif event[0] == "take":
            want.append(ref.take())
        else:
            ref.pending = want[-1]
    assert len(taken) == len(want)
    for (entries, cfg, ticks), (w_entries, w_cfg, w_ticks) in zip(taken, want):
        assert list(entries) == w_entries
        assert cfg == w_cfg
        assert ticks.dtype == np.uint32
        np.testing.assert_array_equal(ticks, w_ticks)
