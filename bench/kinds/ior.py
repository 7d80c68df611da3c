"""IOR cells: Listing-3 write streams traced by the Recorder.

Each rank calls ``repro.core.apis.posix.pwrite`` on a real file with its
Recorder attached, one rank per process (the facades hold one active
recorder).  The mix says how the ranks flush: ``comm: solo`` keeps the
recorder's own cadence (the mix's ``recorder`` knobs; with none, the
Recorder's default: records held in memory, one trace written at
finalize); ``comm: jax`` runs one process per chip and flushes over
``JaxComm`` at every unit's end, the application's sync point, since a
multi-rank comm never auto-flushes.

The window runs whole units of ``unit_segments`` segments (a segment is
``transfers_per_segment`` calls per rank) until ``--seconds`` have
passed.  With ``job_per_unit`` each unit is a traced job of its own:
fsync, close and finalize into a trace directory of its own end it, and
the next unit starts a new job; otherwise the window is one job, closed
and finalized after its last unit.  ``io_calls_per_s`` counts every
traced call over the time from the first call to the end of the last
finalize.  Set-up runs one unit into a scratch trace, so every shape the
window's flushes and finalizes send to the device is compiled before it.
"""

from __future__ import annotations

import array
import json
import os
import socket
import subprocess
import sys
import time
import traceback
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

from .. import compare, harness
from ..harness import Check, Outcome
from .train import reference_module, sizes


def recorder_config(conf, mix, trace_dir):
    from repro.core.recorder import RecorderConfig
    return RecorderConfig(trace_dir=str(trace_dir),
                          encode_backend=conf["encode_backend"],
                          **mix.get("recorder", {}))


class FacadeRank:
    """One rank writing through the traced posix facade."""

    def __init__(self, conf, mix, rank: int, nranks: int, out, trace_dir,
                 comm, spans: harness.Spans):
        from repro.core.recorder import Recorder
        self.conf, self.rank, self.nranks = conf, rank, nranks
        self.rec = Recorder(rank=rank, config=recorder_config(
            conf, mix, trace_dir), comm=comm)
        self.ticks = array.array("q")
        self.calls = 0
        self.failed = 0
        # a million calls a window: flushes are timed, calls are not
        harness.instrument(self.rec, self.ticks,
                           spans if spans.annotate else None)
        self.path = out / "shared.bin"
        self.fd = os.open(self.path, os.O_RDWR | os.O_CREAT, 0o644)
        self.buf = b"\x5a" * conf["bytes_written_per_call"]

    def segment(self) -> None:
        from repro.core.apis import posix
        from repro.core.recorder import attach, detach
        t = self.conf["transfer_bytes"]
        n = self.conf["transfers_per_segment"]
        stride = self.nranks * t
        off = self.rank * t + self.calls * stride
        fd, buf, want = self.fd, self.buf, len(self.buf)
        failed = 0
        attach(self.rec)
        try:
            for _ in range(n):
                failed += posix.pwrite(fd, buf, off) != want
                off += stride
        finally:
            detach()
        self.calls += n
        self.failed += failed
        # the written pages never reach the disk (untraced)
        os.ftruncate(fd, 0)

    def close(self) -> None:
        from repro.core.apis import posix
        from repro.core.recorder import attach, detach
        attach(self.rec)
        try:
            posix.fsync(self.fd)
            posix.close(self.fd)
        finally:
            detach()
        self.calls += 2
        self.path.unlink(missing_ok=True)

    def check(self, ref, trace_dir) -> Dict[str, Any]:
        from repro.core.reader import TraceReader
        matcher = compare.TickMatcher(self.ticks)
        want = ref.records(self.conf, self.rank, self.nranks, self.calls - 2,
                           closed=True)
        try:
            bad, n, first = compare.records(
                compare.plain(TraceReader(str(trace_dir)).iter_records(
                    self.rank), matcher), want)
        except Exception as exc:    # a trace that cannot be read back
            return {"records": 0, "mismatched": self.calls,
                    "first": f"read-back failed: {exc!r}"}
        if matcher.bad and first is None:
            first = f"{matcher.bad} records' ticks are not the clock's"
        return {"records": n, "mismatched": bad + matcher.bad,
                "first": first}


def _warm(cell, conf, mix, comm=None, rank: int = 0) -> None:
    """One unit into a scratch trace: compiles what the window's flushes
    and finalizes send to the device (the persistent cache keeps it)."""
    scratch = cell.out / "warm"
    if rank == 0:
        harness.fresh_dir(scratch)
    if comm is not None:
        comm.barrier()
    _units(conf, mix, scratch, scratch / "trace", 1, None, time.perf_counter,
           comm=comm, rank=rank)


def _units(conf, mix, out, trace_dir, max_units, deadline, clock,
           spans: Optional[harness.Spans] = None, comm=None,
           rank: int = 0) -> Dict[str, Any]:
    """Run whole units until ``max_units`` or the deadline, then close and
    finalize; returns the jobs (one ``FacadeRank`` and trace directory
    each) and the times.  Over a multi-process comm every rank flushes at
    each unit's end and the ranks vote on whether the window has ended,
    so all make the same collective calls."""
    unit, per_job = mix["unit_segments"], mix.get("job_per_unit", False)
    spans = spans or harness.Spans()

    def start(k: int) -> Tuple[FacadeRank, Path]:
        tdir = trace_dir / f"job{k}" if per_job else trace_dir
        return FacadeRank(conf, mix, rank, mix["ranks"], out, tdir,
                          comm=comm, spans=spans), tdir

    def end(ranks: FacadeRank) -> None:
        ranks.close()
        with spans.span("finalize"):
            ranks.rec.finalize(comm)

    jobs = [start(0)]
    t0 = clock()
    units = 0
    while True:
        ranks = jobs[-1][0]
        with spans.span("unit"):
            for _ in range(unit):
                ranks.segment()
            if comm is not None:
                with spans.span("flush"):
                    ranks.rec.flush(comm)
        units += 1
        done = units >= max_units if max_units else clock() >= deadline
        if comm is not None:
            done = comm.vote_any(done)
        if done:
            break
        if per_job:
            end(ranks)
            jobs.append(start(units))
    end(jobs[-1][0])
    return {"jobs": jobs, "t0": t0, "t1": clock(), "units": units}


def _window(cell, args, conf, mix, t_start: float, comm=None,
            rank: int = 0) -> Dict[str, Any]:
    """Set-up, the measured window and the read-back of this process's
    ranks."""
    compiles = harness.CompileCounter()
    ref = reference_module(cell.config_name)
    out, trace_dir = cell.out, cell.out / "trace"
    spans = harness.Spans(annotate=bool(args.trace))
    _warm(cell, conf, mix, comm, rank)
    clock = time.perf_counter
    profile = out / f"profile{rank}"
    with compiles.window(), harness.profiled(bool(args.trace),
                                             profile) as prof:
        setup_s = clock() - t_start
        t_wall = time.time()
        w = _units(conf, mix, out, trace_dir, None, clock() + args.seconds,
                   clock, spans, comm, rank)
    if comm is not None:
        comm.barrier()
    back = {"records": 0, "mismatched": 0, "first": None}
    calls = failed = 0
    for ranks, tdir in w["jobs"]:
        got = ranks.check(ref, tdir)
        back["records"] += got["records"]
        back["mismatched"] += got["mismatched"]
        back["first"] = back["first"] or got["first"]
        calls, failed = calls + ranks.calls, failed + ranks.failed
    if back["first"]:
        harness.log(f"rank {rank} trace read-back: {back['first']}")
    devsum = harness.device_summary(prof)
    return {"setup_s": setup_s, "window_wall": t_wall,
            "elapsed": w["t1"] - w["t0"], "units": w["units"],
            "calls": calls, "failed": failed,
            "back": back, "devtrace": devsum,
            "spans": dict(spans.durations),
            "compiles_in_window": compiles.in_window,
            "memory_peak_bytes": harness.memory_peak_bytes()}


def _outcome(cell, conf, device, parts: List[Dict[str, Any]]) -> Outcome:
    """One result from the processes' parts (one part per process)."""
    from .. import devtrace
    calls = sum(p["calls"] for p in parts)
    elapsed = max(p["elapsed"] for p in parts)
    trace_bytes = harness.dir_bytes(cell.out / "trace")
    mismatched = sum(p["back"]["mismatched"] for p in parts)
    spans = harness.Spans()
    for p in parts:
        for k, v in p["spans"].items():
            spans.durations[k].extend(v)
    units = max(p["units"] for p in parts)
    harness.log(f"window: {units} units, {calls} calls in {elapsed:.3f} s, "
                f"compilations in the window "
                f"{[p['compiles_in_window'] for p in parts]}, trace bytes "
                f"{trace_bytes}, records read back "
                f"{sum(p['back']['records'] for p in parts)}")
    peaks = [p["memory_peak_bytes"] for p in parts
             if p["memory_peak_bytes"] is not None]
    device = dict(device, memory_peak_bytes=max(peaks) if peaks else None)
    return Outcome(
        device=device,
        checks=[Check("records_mismatched", mismatched,
                      conf["limits"]["records_mismatched"])],
        attempted=calls, failed=sum(p["failed"] for p in parts),
        spans=spans, devtrace=devtrace.merge([p["devtrace"] for p in parts]),
        e2e={"io_calls_per_s": calls / elapsed,
             "trace_bytes_per_record": trace_bytes / calls,
             "setup_s": max(p["setup_s"] for p in parts)},
        counters={"calls": calls, "calls_local": calls / len(parts),
                  "window_s": elapsed, "units": units,
                  "flushes": units + 1, "processes": len(parts)})


def run(cell: harness.Cell, args, t_start: float) -> Outcome:
    conf, mix = sizes(cell, args.rehearse)
    if mix["comm"] == "jax":
        return _parent(cell, args, conf, mix, t_start)
    device = harness.device_line(cell.chips, args.rehearse)
    harness.enable_compile_cache(args.rehearse)
    harness.fresh_dir(cell.out)
    part = _window(cell, args, conf, mix, t_start)
    return _outcome(cell, conf, device, [part])


# ---------------------------------------------------------------------------
# one process per chip over JaxComm
# ---------------------------------------------------------------------------


def _free_ports(k: int) -> List[int]:
    socks = [socket.socket() for _ in range(k)]
    for s in socks:
        s.bind(("localhost", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def _parent(cell, args, conf, mix, t_start: float) -> Optional[Outcome]:
    """Start one worker per chip (this process never touches JAX), wait
    for all, and join their parts; a failing worker stops them all."""
    n = cell.chips
    harness.fresh_dir(cell.out)
    coord, *tpu_ports = _free_ports(1 + n)
    t_wall0 = time.time() - (time.perf_counter() - t_start)
    procs = []
    for k in range(n):
        env = dict(os.environ)
        if not args.rehearse:
            env.update({
                "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                "TPU_PROCESS_BOUNDS": "2,2,1",
                "TPU_PROCESS_ADDRESSES": ",".join(
                    f"localhost:{p}" for p in tpu_ports),
                "TPU_PROCESS_PORT": str(tpu_ports[k]),
                "TPU_VISIBLE_CHIPS": str(k),
                "CLOUD_TPU_TASK_ID": str(k),
            })
        cmd = [sys.executable, str(harness.BENCH / "run.py"),
               "--workload", cell.name, "--seed", str(args.seed),
               "--seconds", str(args.seconds), "--trace", str(args.trace),
               "--worker", str(k), "--port", str(coord)]
        if args.rehearse:
            cmd.append("--rehearse")
        if args.out:
            cmd += ["--out", str(args.out)]
        logf = open(cell.out / f"worker{k}.log", "w")
        procs.append((subprocess.Popen(cmd, env=env, stdout=logf,
                                       stderr=subprocess.STDOUT), logf))
    deadline = time.monotonic() + args.seconds + mix["worker_timeout_s"]
    try:
        while True:
            rcs = [p.poll() for p, _ in procs]
            if all(rc == 0 for rc in rcs) or any(rcs):
                break
            if time.monotonic() > deadline:
                harness.log("workers still running at the deadline")
                break
            time.sleep(0.2)
    finally:
        for p, f in procs:
            if p.poll() is None:
                p.kill()
            p.wait()
            f.close()
    rcs = [p.returncode for p, _ in procs]
    if any(rcs):
        for k in range(n):
            text = (cell.out / f"worker{k}.log").read_text()
            harness.log(f"----- worker {k} (exit {rcs[k]}) -----\n"
                        + "\n".join(text.splitlines()[-30:]))
        return None
    parts = [harness.load_json(cell.out / f"part{k}.json") for k in range(n)]
    for p in parts:
        p["setup_s"] = p["window_wall"] - t_wall0
    return _outcome(cell, conf, parts[0]["device"], parts)


def worker(cell: harness.Cell, args) -> int:
    """One chip's process: ``jax.distributed`` on localhost, its rank the
    process index JAX gives it, the window over ``JaxComm``."""
    import jax
    conf, mix = sizes(cell, args.rehearse)
    if args.rehearse:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=f"localhost:{args.port}",
                               num_processes=cell.chips,
                               process_id=args.worker)
    try:
        device = harness.device_line(cell.chips, args.rehearse)
        if len(jax.local_devices()) != 1:
            raise SystemExit(f"local devices {jax.local_devices()}")
        harness.enable_compile_cache(args.rehearse)
        from repro.core.comm import JaxComm
        comm = JaxComm()
        part = _window(cell, args, conf, mix, time.perf_counter(), comm,
                       jax.process_index())
        part["device"] = device
        (cell.out / f"part{args.worker}.json").write_text(json.dumps(part))
        comm.barrier()
    except BaseException:
        traceback.print_exc()
        sys.stdout.flush()
        # leave at once: the others wait in a collective, and
        # jax.distributed's exit barrier would hold this one for minutes
        os._exit(1)
    jax.distributed.shutdown()
    return 0
