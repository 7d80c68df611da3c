"""mdtest cells: IO500 mdtest-hard jobs traced by the Recorder.

One rank runs whole jobs through the traced posix facade with its
Recorder attached.  A job makes the shared directory, runs the four
phases over ``files_per_job`` files (create: open, pwrite, close; stat;
read: open, pread, close; delete: unlink), removes the directory, then
finalizes into a trace directory of its own (the mix's ``recorder``
knobs; with none, the Recorder's defaults: records held in memory, one
trace written at finalize).  Every file's path differs from the last
call's, so the trace holds as many distinct paths as the job touches.

The window runs jobs back to back until ``--seconds`` have passed.
``io_calls_per_s`` counts every traced call over the time from the first
call to the end of the last finalize.  Set-up runs one job into a scratch
trace, so the shapes its finalize sends to the device are compiled before
the window.  Every record of every job is read back and compared with the
configuration's plain reference.
"""

from __future__ import annotations

import array
import random
import statistics
import time
from pathlib import Path
from typing import Any, Dict, Iterator, List, Tuple

from .. import compare, harness
from ..harness import Check, Outcome
from . import ior
from .train import reference_module, sizes


def plain(recs, ticks: compare.TickMatcher) -> Iterator[Tuple]:
    """``compare.plain``, with a returned handle as its unified id too."""
    for func, args, ret in compare.plain(recs, ticks):
        yield func, args, getattr(ret, "id", ret)


class Job:
    """One traced mdtest-hard job of one rank."""

    def __init__(self, conf, mix, ref, rank: int, out: Path,
                 trace_dir: Path, buf: bytes) -> None:
        from repro.core.recorder import Recorder
        self.conf, self.ref, self.rank = conf, ref, rank
        self.out, self.trace_dir, self.buf = str(out), trace_dir, buf
        self.n_files = mix["files_per_job"]
        self.rec = Recorder(rank=rank, config=ior.recorder_config(
            conf, mix, trace_dir))
        self.ticks = array.array("q")
        harness.instrument(self.rec, self.ticks)
        self.calls = self.failed = 0
        self.stats = None

    def run(self, paths: List[str]) -> None:
        """The job's calls: mkdir, the four phases, rmdir."""
        from repro.core.apis import posix
        from repro.core.recorder import attach, detach
        conf, buf = self.conf, self.buf
        want_w, want_r = conf["write_bytes"], conf["read_bytes"]
        create = self.ref.flags(conf["create_flags"])
        read = self.ref.flags(conf["read_flags"])
        fmode, d = conf["file_mode"], self.ref.directory(conf, self.out)
        failed = 0
        attach(self.rec)
        try:
            posix.mkdir(d, conf["dir_mode"])
            for p in paths:
                fd = posix.open(p, create, fmode)
                failed += posix.pwrite(fd, buf, 0) != want_w
                posix.close(fd)
            for p in paths:
                failed += posix.stat(p) != want_w
            for p in paths:
                fd = posix.open(p, read, 0)
                failed += len(posix.pread(fd, want_r, 0)) != want_r
                posix.close(fd)
            for p in paths:
                posix.unlink(p)
            posix.rmdir(d)
        finally:
            detach()
        self.calls += 2 + 8 * len(paths)
        self.failed += failed

    def finalize(self) -> None:
        self.stats = self.rec.finalize()

    def check(self) -> Dict[str, Any]:
        from repro.core.reader import TraceReader
        matcher = compare.TickMatcher(self.ticks)
        want = self.ref.records(self.conf, self.out, self.rank, self.n_files)
        try:
            bad, n, first = compare.records(
                plain(TraceReader(str(self.trace_dir)).iter_records(
                    self.rank), matcher), want)
        except Exception as exc:    # a trace that cannot be read back
            return {"records": 0, "mismatched": self.calls,
                    "first": f"read-back failed: {exc!r}"}
        if matcher.bad and first is None:
            first = f"{matcher.bad} records' ticks are not the clock's"
        return {"records": n, "mismatched": bad + matcher.bad,
                "first": first}


def _jobs(conf, mix, ref, out: Path, trace_dir: Path, buf: bytes,
          max_jobs, deadline, clock,
          spans: harness.Spans) -> Dict[str, Any]:
    """Whole jobs until ``max_jobs`` or the deadline, each finalized."""
    rank = 0
    paths = ref.file_paths(conf, str(out), rank, mix["files_per_job"])
    jobs: List[Job] = []
    t0 = clock()
    while True:
        job = Job(conf, mix, ref, rank, out, trace_dir / f"job{len(jobs)}",
                  buf)
        with spans.span("unit"):
            job.run(paths)
        with spans.span("finalize"):
            job.finalize()
        jobs.append(job)
        if len(jobs) >= max_jobs if max_jobs else clock() >= deadline:
            break
    return {"jobs": jobs, "t0": t0, "t1": clock()}


def run(cell: harness.Cell, args, t_start: float) -> Outcome:
    conf, mix = sizes(cell, args.rehearse)
    if mix["files_per_job"] != conf["files_per_rank"]:
        raise SystemExit(f"the mix runs {mix['files_per_job']} files a job, "
                         f"the configuration states "
                         f"{conf['files_per_rank']}")
    device = harness.device_line(cell.chips, args.rehearse)
    harness.enable_compile_cache(args.rehearse)
    harness.fresh_dir(cell.out)
    compiles = harness.CompileCounter()
    ref = reference_module(cell.config_name)
    buf = random.Random(args.seed).randbytes(conf["write_bytes"])
    spans = harness.Spans(annotate=bool(args.trace))
    # set-up: one job into a scratch trace
    warm = harness.fresh_dir(cell.out / "warm")
    _jobs(conf, mix, ref, warm, warm / "trace", buf, 1, None,
          time.perf_counter, harness.Spans())
    clock = time.perf_counter
    with compiles.window(), harness.profiled(
            bool(args.trace), cell.out / "profile0") as prof:
        setup_s = clock() - t_start
        w = _jobs(conf, mix, ref, cell.out, cell.out / "trace", buf, None,
                  clock() + args.seconds, clock, spans)
    jobs = w["jobs"]
    elapsed = w["t1"] - w["t0"]
    back = {"records": 0, "mismatched": 0, "first": None}
    for job in jobs:
        got = job.check()
        back["records"] += got["records"]
        back["mismatched"] += got["mismatched"]
        back["first"] = back["first"] or got["first"]
    if back["first"]:
        harness.log(f"trace read-back: {back['first']}")
    calls = sum(j.calls for j in jobs)
    trace_bytes = harness.dir_bytes(cell.out / "trace")
    harness.log(f"window: {len(jobs)} jobs, {calls} calls in {elapsed:.3f} "
                f"s, compilations in the window {compiles.in_window}, trace "
                f"bytes {trace_bytes}, records read back {back['records']}")
    return Outcome(
        device=dict(device, memory_peak_bytes=harness.memory_peak_bytes()),
        checks=[Check("records_mismatched", back["mismatched"],
                      conf["limits"]["records_mismatched"])],
        attempted=calls, failed=sum(j.failed for j in jobs), spans=spans,
        devtrace=harness.device_summary(prof),
        e2e={"io_calls_per_s": calls / elapsed,
             "trace_bytes_per_record": trace_bytes / calls,
             "setup_s": setup_s},
        counters={"calls": calls, "calls_local": calls, "window_s": elapsed,
                  "units": len(jobs),
                  "cst_entries": statistics.mean(
                      j.stats.cst_entries for j in jobs)})
