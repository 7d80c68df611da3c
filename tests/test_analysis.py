"""Paper Section-4 analyses over real traces."""

import os
import sys
import threading

import pytest

sys.path.insert(0, "/root/repo")
from benchmarks.workloads import ior_rank  # noqa: E402
from repro.core import trace_format
from repro.core.analysis import (call_chains, consistency_pairs, io_summary,
                                 overlap_ratio, size_histogram)
from repro.core.apis import posix, shardio
from repro.core.interprocess import tree_finalize_ranks
from repro.core.reader import TraceReader
from repro.core.recorder import Recorder, RecorderConfig, session
from repro.core.specs import REGISTRY


@pytest.fixture
def traced_workload(tmp_path):
    datadir = tmp_path / "data"
    datadir.mkdir()
    tracedir = str(tmp_path / "trace")
    with session(RecorderConfig(trace_dir=tracedir)):
        fh = shardio.shard_open(str(datadir / "big.bin"), 1)
        for i in range(20):
            shardio.shard_write_at(fh, b"x" * 8192, i * 8192)
        shardio.shard_sync(fh)
        shardio.shard_close(fh)
        fd = posix.open(str(datadir / "small.bin"),
                        os.O_RDWR | os.O_CREAT, 0o644)
        for i in range(10):
            posix.pwrite(fd, b"y" * 100, i * 100)
        posix.close(fd)
        posix.stat(str(datadir / "big.bin"))
    return tracedir


def test_io_summary(traced_workload):
    s = io_summary(TraceReader(traced_workload))
    # shardio writes recurse into posix pwrites: both layers counted
    assert s["total_bytes"] == 2 * (20 * 8192) + 10 * 100
    assert s["n_metadata_calls"] > 0
    assert 0 < s["metadata_ratio"] < 0.5
    assert s["aggregate_MBps"] > 0


def test_size_histogram(traced_workload):
    h = size_histogram(TraceReader(traced_workload))
    assert h["<512"] == 10                # the small pwrites
    assert h["<65536"] >= 40              # 8 KiB writes at both layers


def test_call_chains(traced_workload):
    c = call_chains(TraceReader(traced_workload))
    assert c.get("shard_write_at->pwrite") == 20
    assert c.get("pwrite") == 10          # direct application-level writes


def test_overlap_ratio_multithreaded(tmp_path):
    datadir = tmp_path / "d"
    datadir.mkdir()
    tracedir = str(tmp_path / "t")
    with session(RecorderConfig(trace_dir=tracedir)):
        def worker(i):
            fd = posix.open(str(datadir / f"{i}.bin"),
                            os.O_RDWR | os.O_CREAT, 0o644)
            for j in range(200):
                posix.pwrite(fd, b"z" * 1024, j * 1024)
            posix.close(fd)
        ts = [threading.Thread(target=worker, args=(i,)) for i in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    r = overlap_ratio(TraceReader(tracedir))
    assert 0.0 <= r <= 1.0


def _write_span_trace(tmp_path, spans):
    """One pwrite per rank: ``spans[rank] = (offset, size)``."""
    states = []
    fid = REGISTRY.id_of("pwrite")
    for rank, (off, size) in enumerate(spans):
        rec = Recorder(rank=rank, config=RecorderConfig())
        fdobj = object()
        rec.record(fid, (fdobj, b"a" * size, off), size, 0, 0, 1)
        states.append(rec.local_state())
    merge, cfgs = tree_finalize_ranks([s[0] for s in states],
                                      [s[1] for s in states], REGISTRY)
    tdir = str(tmp_path / "trace")
    trace_format.write_trace(tdir, registry=REGISTRY,
                             merged_cst=merge.merged_entries,
                             unique_cfgs=cfgs.unique_cfgs,
                             cfg_index=cfgs.cfg_index,
                             rank_timestamps=[s[2] for s in states])
    return tdir


def test_consistency_pairs(tmp_path):
    """Cross-rank overlapping writes (the [27,28] consistency study)."""
    # both ranks write [0, 100): a genuine conflict
    tdir = _write_span_trace(tmp_path, [(0, 100), (0, 100)])
    conflicts = consistency_pairs(TraceReader(tdir))
    assert len(conflicts) == 1
    assert conflicts[0]["extent"] == (0, 100)


def test_consistency_pairs_non_adjacent_overlap(tmp_path):
    """Regression: a long extent must conflict with every later overlapping
    span, not only the start-adjacent one.  Rank 0 writes [0, 100); rank 1
    writes [10, 20); rank 2 writes [30, 40) -- the seed adjacent-pair scan
    dropped the 0<->2 conflict."""
    tdir = _write_span_trace(tmp_path, [(0, 100), (10, 10), (30, 10)])
    conflicts = consistency_pairs(TraceReader(tdir))
    got = {(c["ranks"], c["extent"]) for c in conflicts}
    assert got == {((0, 1), (10, 20)), ((0, 2), (30, 40))}
