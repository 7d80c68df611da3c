"""Async background flush (the non-blocking Recorder.flush): byte-identity
with sync flushes, fault injection into the background committer,
coalescing of overlapping flush requests, drain-on-finalize, the true
point-to-point / collective-exchange reduce transports, and the lockstep
cadence vote."""

import os
import random
import shutil
import tempfile
import threading
import time

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # fallback: seeded-random example generation
    from _hypothesis_compat import given, settings, strategies as st

from test_streaming import _feed, _gen_calls, _split

from repro.core import streaming
from repro.core.comm import (Comm, SoloComm, reduce_rounds,
                             reduce_tree_via_exchange, run_thread_world)
from repro.core.reader import TraceReader
from repro.core.recorder import Recorder, RecorderConfig
from repro.core.specs import REGISTRY
import repro.core.apis  # noqa: F401  (populate registry)


def _dir_snapshot(root):
    """{relative path: bytes} of every file under a trace directory."""
    out = {}
    for dirpath, _dirs, files in os.walk(root):
        for f in files:
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = fh.read()
    return out


# ---------------------------------------------------------------------------
# async == sync byte identity (the tentpole property)
# ---------------------------------------------------------------------------


@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=0, max_value=2 ** 32 - 1),
       st.integers(min_value=1, max_value=3))
def test_async_trace_byte_identical_solo(seed, n_flushes):
    """A drained async run writes the byte-identical trace directory to a
    sync run of the same calls: only WHERE the commit runs moves."""
    rng = random.Random(seed)
    calls = _gen_calls(rng, 40, 0, 1)
    bounds = sorted(rng.sample(range(1, len(calls)), n_flushes))
    tmp = tempfile.mkdtemp(prefix="async_ident_")
    try:
        snaps = {}
        for mode in ("sync", "async"):
            td = os.path.join(tmp, mode)
            rec = Recorder(config=RecorderConfig(
                trace_dir=td, async_flush=(mode == "async")))
            t = 0
            for i, part in enumerate(_split(calls, bounds)):
                t = _feed(rec, part, t)
                if i < n_flushes:
                    rec.flush()
                    rec.drain()  # no coalescing: epochs stay 1:1 with sync
            rec.finalize()
            snaps[mode] = _dir_snapshot(td)
        assert snaps["sync"] == snaps["async"]
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def test_async_trace_byte_identical_threadcomm(tmp_path):
    """Multi-rank async flushes (lockstep vote + dup'd background comm)
    still produce the byte-identical directory to the sync collective."""
    nranks = 4
    rank_calls = [_gen_calls(random.Random(100 + r), 30, r, nranks)
                  for r in range(nranks)]
    snaps = {}
    for mode in ("sync", "async"):
        td = str(tmp_path / mode)

        def worker(comm, rank, td=td, async_=(mode == "async")):
            rec = Recorder(rank=rank, config=RecorderConfig(
                trace_dir=td, async_flush=async_))
            t = 0
            for i, part in enumerate(_split(rank_calls[rank], [10, 20])):
                t = _feed(rec, part, t)
                if i < 2:
                    rec.flush(comm)
                    rec.drain()
            return rec.finalize(comm)

        stats = run_thread_world(nranks, worker)
        assert stats[0] is not None and stats[0].epochs == 3
        snaps[mode] = _dir_snapshot(td)
    assert snaps["sync"] == snaps["async"]


# ---------------------------------------------------------------------------
# fault injection: the background committer fails / stalls
# ---------------------------------------------------------------------------


def test_async_error_surfaces_on_drain_then_recovers(tmp_path, monkeypatch):
    td = str(tmp_path / "t")
    rec = Recorder(config=RecorderConfig(trace_dir=td, async_flush=True))
    _feed(rec, _gen_calls(random.Random(0), 10, 0, 1))
    boom = OSError("trace volume gone")

    def bad_run_flush(*a, **k):
        raise boom

    monkeypatch.setattr(streaming, "run_flush", bad_run_flush)
    rec.flush()  # submits; must NOT raise here
    with pytest.raises(RuntimeError) as ei:
        rec.drain()
    assert ei.value.__cause__ is boom
    # the error is consumed exactly once; the recorder stays usable
    monkeypatch.undo()
    _feed(rec, _gen_calls(random.Random(1), 8, 0, 1), tick_start=10 ** 6)
    rec.flush()
    rec.drain()
    stats = rec.finalize()
    assert stats is not None and stats.epochs >= 1
    assert TraceReader(td, mode="stitched").nranks == 1


def test_async_error_surfaces_on_finalize(tmp_path, monkeypatch):
    """A failed background commit must surface from finalize, not vanish."""
    td = str(tmp_path / "t")
    rec = Recorder(config=RecorderConfig(trace_dir=td, async_flush=True))
    _feed(rec, _gen_calls(random.Random(3), 10, 0, 1))
    monkeypatch.setattr(streaming, "run_flush",
                        lambda *a, **k: (_ for _ in ()).throw(
                            OSError("mid-commit failure")))
    rec.flush()
    with pytest.raises(RuntimeError, match="background epoch commit failed"):
        rec.finalize()


def test_overlapping_flushes_coalesce(tmp_path, monkeypatch):
    """flush() while an epoch is in flight coalesces (at-most-one in
    flight); the coalesced records ride the next committed epoch and no
    record is lost."""
    td = str(tmp_path / "t")
    gate = threading.Event()
    started = threading.Event()
    real = streaming.run_flush

    def slow_run_flush(*a, **k):
        started.set()
        assert gate.wait(30)
        return real(*a, **k)

    monkeypatch.setattr(streaming, "run_flush", slow_run_flush)
    rec = Recorder(config=RecorderConfig(trace_dir=td, async_flush=True))
    calls = _gen_calls(random.Random(2), 30, 0, 1)
    t = _feed(rec, calls[:10])
    rec.flush()
    assert started.wait(30)
    t = _feed(rec, calls[10:20], t)
    rec.flush()  # epoch 0 still committing -> coalesce
    rec.flush()  # still in flight -> coalesce again
    assert rec.epochs_coalesced == 2
    assert rec.epoch == 1  # only one epoch was snapshotted
    gate.set()
    rec.drain()
    _feed(rec, calls[20:], t)
    stats = rec.finalize()  # tail flush carries the coalesced records
    assert stats.n_records == len(calls)
    reader = TraceReader(td, mode="stitched")
    assert reader.n_records(0) == len(calls)


def test_finalize_during_inflight_drains(tmp_path, monkeypatch):
    """finalize() during an in-flight commit waits for it, tail-flushes,
    and the resulting trace is complete."""
    td = str(tmp_path / "t")
    real = streaming.run_flush

    def slow_run_flush(*a, **k):
        time.sleep(0.3)
        return real(*a, **k)

    monkeypatch.setattr(streaming, "run_flush", slow_run_flush)
    rec = Recorder(config=RecorderConfig(trace_dir=td, async_flush=True))
    calls = _gen_calls(random.Random(7), 24, 0, 1)
    t = _feed(rec, calls[:12])
    rec.flush()  # in flight for >= 0.3s
    _feed(rec, calls[12:], t)
    stats = rec.finalize()
    assert stats.epochs == 2
    reader = TraceReader(td, mode="stitched")
    assert reader.n_records(0) == len(calls)
    from repro.core import trace_format
    manifest = trace_format.read_manifest(td)
    assert len(manifest["segments"]) == 2 and "merged" in manifest


# ---------------------------------------------------------------------------
# transports: true p2p schedule, collective exchange, cadence vote
# ---------------------------------------------------------------------------


def _reference_fold(size, fn, leaf):
    items = [leaf(r) for r in range(size)]
    while len(items) > 1:
        items = [fn(items[i], items[i + 1]) if i + 1 < len(items)
                 else items[i] for i in range(0, len(items), 2)]
    return items[0]


def test_threadcomm_p2p_reduce_matches_reference():
    """ThreadComm's send/recv log-round schedule folds in the identical
    association order as the gather fallback (string concat is
    association-sensitive, so any divergence shows)."""
    def worker(comm, rank):
        return comm.reduce_tree(f"[{rank}]", lambda a, b: a + b)

    for size in (2, 3, 5, 8):
        res = run_thread_world(size, worker)
        assert res[0] == _reference_fold(size, lambda a, b: a + b,
                                         lambda r: f"[{r}]")
        assert all(r is None for r in res[1:])


def test_threadcomm_send_recv_fifo():
    def worker(comm, rank):
        if rank == 0:
            comm.send("a", 1)
            comm.send("b", 1)
            return None
        return comm.recv(0), comm.recv(0)

    assert run_thread_world(2, worker)[1] == ("a", "b")


def test_reduce_rounds_cover_all_ranks_once():
    for size in (1, 2, 3, 5, 8, 13, 16):
        rounds = reduce_rounds(size)
        senders = [src for perm in rounds for src, _ in perm]
        assert sorted(senders) == list(range(1, size))  # everyone ships once
        for perm in rounds:
            assert all(dst < src for src, dst in perm)


def test_reduce_tree_via_exchange_matches_reference():
    """The SPMD collective-exchange variant (what JaxComm runs over the
    ppermute byte transport) folds identically to the reference."""
    for size in (1, 2, 3, 5, 8):
        payloads = [None] * size
        barrier = threading.Barrier(size)

        def make_exchange(rank):
            def exchange(payload, perm):
                payloads[rank] = payload
                barrier.wait()
                got = next((payloads[src] for src, dst in perm
                            if dst == rank), None)
                barrier.wait()
                return got
            return exchange

        results = [None] * size

        def worker(r):
            results[r] = reduce_tree_via_exchange(
                r, size, f"[{r}]", lambda a, b: a + b, make_exchange(r))

        threads = [threading.Thread(target=worker, args=(r,))
                   for r in range(size)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert results[0] == _reference_fold(size, lambda a, b: a + b,
                                             lambda r: f"[{r}]")
        assert all(r is None for r in results[1:])


def test_pack_bytes_array_roundtrip():
    from repro.distributed.sharding import (pack_bytes_array,
                                            unpack_bytes_array)
    for payload in (None, b"", b"x", b"hello world" * 10):
        n = 0 if payload is None else len(payload)
        arr = pack_bytes_array(payload, n + 5 + 3)
        assert arr.dtype == np.uint8 and arr.shape == (n + 8,)
        assert unpack_bytes_array(arr) == payload
    with pytest.raises(ValueError):
        pack_bytes_array(b"xxxx", 5)  # cannot hold payload + header


class _BytesOnlyTransport:
    """Stand-in for ``PpermuteByteTransport`` across threads: payloads go
    through the same fixed-size uint8 packing, so anything but bytes
    fails as it would on the chips."""

    def __init__(self, size):
        self.slots = [None] * size
        self.barrier = threading.Barrier(size, timeout=60)

    def exchange_for(self, rank):
        from repro.distributed.sharding import (pack_bytes_array,
                                                unpack_bytes_array)

        def exchange(payload, perm):
            n = 0 if payload is None else len(payload)
            self.slots[rank] = pack_bytes_array(payload, n + 5)
            self.barrier.wait()
            got = next((unpack_bytes_array(self.slots[src])
                        for src, dst in perm if dst == rank), None)
            self.barrier.wait()
            return got
        return exchange


@pytest.mark.parametrize("size", [2, 3, 5])
def test_jaxcomm_finalize_over_byte_transport_matches_threadcomm(size):
    """A tree finalize over JaxComm -- reduce_tree of serialized states and
    gather_tree of timestamp bytes, both on a bytes-only transport --
    writes the same trace files as ThreadComm."""
    from repro.core.comm import JaxComm

    rng = random.Random(size)
    calls = [_gen_calls(rng, 40, r, size) for r in range(size)]
    root = tempfile.mkdtemp()
    xport = _BytesOnlyTransport(size)
    try:
        def run(comm, r, tdir):
            rec = Recorder(rank=r, config=RecorderConfig(trace_dir=tdir))
            _feed(rec, calls[r])
            rec.finalize(comm)

        ref = os.path.join(root, "ref")
        run_thread_world(size, lambda c, r: run(c, r, ref))

        def jax_rank(comm, r):
            jc = JaxComm(transport=type("X", (), {
                "exchange": staticmethod(xport.exchange_for(r))})())
            jc.rank, jc.size = r, size
            jc.barrier = comm.barrier
            run(jc, r, os.path.join(root, "jax"))

        run_thread_world(size, jax_rank)
        snap = {k: v for k, v in _dir_snapshot(os.path.join(root, "jax"))
                .items() if k.endswith(".bin")}
        want = {k: v for k, v in _dir_snapshot(ref).items()
                if k.endswith(".bin")}
        assert snap and snap == want
    finally:
        shutil.rmtree(root, ignore_errors=True)


def test_vote_any_threadcomm():
    def worker(comm, rank):
        return comm.vote_any(rank == 2), comm.vote_any(False)

    assert run_thread_world(4, worker) == [(True, False)] * 4
    assert SoloComm().vote_any(True) is True
    assert SoloComm().vote_any(False) is False


def test_maybe_flush_lockstep(tmp_path):
    """The cadence vote: one rank hitting its flush threshold makes EVERY
    rank flush (non-SPMD record counts stay in lockstep); a vote with
    nobody due is a cheap no-op everywhere."""
    td = str(tmp_path / "t")
    fid = REGISTRY.id_of("write")
    nranks = 3

    def worker(comm, rank):
        rec = Recorder(rank=rank, comm=comm, config=RecorderConfig(
            trace_dir=td, flush_every_n_records=20))
        n = 25 if rank == 0 else 5  # only rank 0 crosses the threshold
        for i in range(n):
            rec.record(fid, (f"fd{rank}", b"x" * 8), 8, 0, 2 * i, 2 * i + 1)
        rec.maybe_flush(comm)
        after_first = rec.epoch
        rec.maybe_flush(comm)  # nobody due now -> no-op on every rank
        assert rec.epoch == after_first
        rec.finalize(comm)
        return after_first

    assert run_thread_world(nranks, worker) == [1] * nranks
    reader = TraceReader(td, mode="stitched")
    assert reader.nranks == nranks
    assert reader.n_records(0) == 25 and reader.n_records(1) == 5
