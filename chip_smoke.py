#!/usr/bin/env python3
"""Bring-up check: the traced training job and the encode kernels on a TPU.

    python3 chip_smoke.py              # one chip: phases A and B
    python3 chip_smoke.py --chips 4    # four chips: multi-host finalize only
    python3 chip_smoke.py --rehearse   # tiny sizes on the CPU (plumbing only)

Phase A trains qwen1.5-0.5b at its published widths (24 layers, random
weights from ``--seed``) for a few steps inside a streaming Recorder
session, saves the full train state once through the traced checkpoint
engine, resumes it in a second Trainer for one more step, finalizes the
trace and reads it back.  Phase B runs every encode op the tracer
dispatches to the Pallas kernels, compiled on the chip, at real flush
sizes against the numpy backend, then traces a Listing-3 ``pwrite`` loop
under ``auto`` and checks each trace against its numpy twin and the call
log.  ``--chips 4`` starts one worker process per chip; the workers
finalize rank-offset traces over ``JaxComm`` (``lax.ppermute`` between
the chips) and rank 0 checks the trace files byte for byte against a
``ThreadComm`` finalize of the same calls.

Every phase runs even when an earlier one failed; any failure exits
non-zero.  The last line of a passing chip run is one JSON object naming
the device.  Without an accelerator the script fails before any work
(``--rehearse`` excepted, which prints no such line).  Outputs go under
``--out`` (default ``<repo>/.chip_smoke``), made anew on every run.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import math
import os
import shutil
import socket
import subprocess
import sys
import time
import traceback
import warnings
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "qwen1.5-0.5b"
N_CHIPS = 4


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, msg: str) -> None:
    """A check that holds under ``python -O`` too."""
    if not ok:
        raise AssertionError(msg)


# ---------------------------------------------------------------------------
# shared helpers (imported lazily: the four-chip parent never imports JAX)
# ---------------------------------------------------------------------------


def _find_repro() -> None:
    """Put the checkout's ``src`` on the path; fails outside a checkout."""
    if not (ROOT / "src" / "repro" / "core" / "recorder.py").is_file():
        raise SystemExit(f"{ROOT} is not a checkout of this repository")
    sys.path.insert(0, str(ROOT / "src"))


def _device_line(count_expected: int, rehearse: bool) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    if not rehearse and d.platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is {d.platform!r} "
                         f"({d.device_kind}); this check runs on the chip only")
    if not rehearse and len(devs) != count_expected:
        raise SystemExit(f"expected {count_expected} devices, JAX sees "
                         f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def _bin_digests(trace_dir: Path) -> dict:
    """sha256 of every ``.bin`` file under a trace directory (metadata and
    manifests carry pids, hosts and times, so only the binaries compare)."""
    out = {}
    for p in sorted(trace_dir.rglob("*.bin")):
        out[str(p.relative_to(trace_dir))] = hashlib.sha256(
            p.read_bytes()).hexdigest()
    return out


def _fixed_clock(rec) -> None:
    """Deterministic ticks: every ``now()`` advances one microsecond."""
    ticks = iter(range(1 << 62))
    rec.now = lambda: next(ticks)


def _listing3(rank: int, nprocs: int, path: str, calls: range,
              block: int) -> list:
    """Paper Listing 3: rank ``r`` writes ``block`` bytes at
    ``r * block + i * nprocs * block`` of one shared file.  Returns the
    plain call log ``(offset, nbytes)`` of the writes."""
    from repro.core.apis import posix
    buf = b"\x5a" * block
    fd = posix.open(path, os.O_RDWR | os.O_CREAT, 0o644)
    done = []
    for i in calls:
        off = rank * block + i * nprocs * block
        done.append((off, posix.pwrite(fd, buf, off)))
    posix.fsync(fd)
    posix.close(fd)
    return done


class KernelCounter:
    """Counts the calls that reach each Pallas kernel entry point, and
    refuses interpreted ones on the chip.  Wraps the ``ops`` functions
    that ``core.encode_backend`` imports at call time."""

    OPS = {
        "repro.kernels.delta_encode.ops": (
            "delta_zigzag", "delta_zigzag_varint", "uvarint_encode64"),
        "repro.kernels.grammar_stats.ops": (
            "row_boundaries", "histogram", "digram_codes"),
    }

    def __init__(self, allow_interpret: bool):
        import importlib
        self.counts = {}
        self.allow_interpret = allow_interpret
        for mod_name, names in self.OPS.items():
            mod = importlib.import_module(mod_name)
            for name in names:
                self.counts[name] = 0
                setattr(mod, name, self._wrap(name, getattr(mod, name)))

    def _wrap(self, name, fn):
        def counted(*args, **kwargs):
            if kwargs.get("interpret") and not self.allow_interpret:
                raise AssertionError(f"{name} was sent to the Pallas "
                                     f"interpreter on an accelerator")
            self.counts[name] += 1
            return fn(*args, **kwargs)
        return counted

    def snapshot(self) -> dict:
        return dict(self.counts)

    def since(self, before: dict) -> dict:
        return {k: v - before[k] for k, v in self.counts.items()
                if v != before[k]}


# ---------------------------------------------------------------------------
# phase A: traced training at full width, one save, one resume
# ---------------------------------------------------------------------------


def phase_a(out: Path, seed: int, rehearse: bool) -> None:
    import jax
    import numpy as np
    from repro.configs import get_config, get_smoke_config
    from repro.core.reader import TraceReader
    from repro.core.recorder import RecorderConfig, session
    from repro.data import SyntheticConfig, synthetic_batch
    from repro.optim import AdamWConfig
    from repro.train import Trainer, TrainerConfig

    cfg = get_smoke_config(ARCH) if rehearse else get_config(ARCH)
    batch, seq, steps = (2, 32, 3) if rehearse else (4, 512, 6)
    flush_every = 16
    run = out / "train"
    trace_dir, ckpt_dir = run / "trace", run / "ckpt"
    dcfg = SyntheticConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                           batch_size=batch, seed=seed)
    ocfg = AdamWConfig(warmup_steps=1, total_steps=steps + 1)
    log(f"[A] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"vocab {cfg.vocab_size}; batch {batch} x seq {seq}, {steps} steps "
        f"+ 1 after resume")

    attempts: dict = {}
    marks: list = []
    live: list = []

    def hook(step: int) -> None:
        # called before every attempt of a step: count attempts and time
        # the previous step once its state is on the device
        attempts[step] = attempts.get(step, 0) + 1
        jax.block_until_ready(live[-1].state)
        marks.append((step, time.perf_counter()))

    def trainer(num_steps: int) -> Trainer:
        tcfg = TrainerConfig(num_steps=num_steps, ckpt_dir=str(ckpt_dir),
                             ckpt_every=steps, keep=1, seed=seed)
        tr = Trainer(cfg, tcfg, ocfg,
                     data=lambda s: synthetic_batch(dcfg, s), fault_hook=hook)
        save = tr.engine.save

        def timed_save(tree, step, meta=None):
            # the step before a save ends here, not at the next hook
            jax.block_until_ready(tree)
            marks.append(("save", time.perf_counter()))
            save(tree, step, meta)
            log(f"[A] checkpoint save {time.perf_counter() - marks[-1][1]:.1f}s")

        tr.engine.save = timed_save
        live.append(tr)
        return tr

    def finish_marks(tr: Trainer) -> None:
        jax.block_until_ready(tr.state)
        marks.append((None, time.perf_counter()))

    sess = session(RecorderConfig(trace_dir=str(trace_dir),
                                  flush_every_n_records=flush_every))
    with sess as rec:
        t0 = time.perf_counter()
        tr1 = trainer(steps)
        tr1.init_state()
        log(f"[A] init {time.perf_counter() - t0:.1f}s")
        res1 = tr1.run()
        finish_marks(tr1)
        probe = np.asarray(jax.tree.leaves(tr1.state)[-1])
        losses = [m["loss"] for m in tr1.metrics_log]
        tr1.state = None
        live.clear()

        t0 = time.perf_counter()
        tr2 = trainer(steps + 1)
        tr2.init_state()
        log(f"[A] resume {time.perf_counter() - t0:.1f}s")
        resumed_probe = np.asarray(jax.tree.leaves(tr2.state)[-1])
        res2 = tr2.run()
        finish_marks(tr2)
        losses += [m["loss"] for m in tr2.metrics_log]
        mid_run_epochs = rec.epoch
        autoflush_broken = rec._autoflush_broken
        tr2.state = None
        live.clear()
    stats = sess.stats

    times = [b[1] - a[1] for a, b in zip(marks, marks[1:])
             if isinstance(a[0], int)]
    log("[A] step times (s, block_until_ready; each Trainer's first step "
        "compiles): "
        + " ".join(f"{t:.3f}" for t in times))
    log(f"[A] losses: {' '.join(f'{x:.4f}' for x in losses)}")
    mem = jax.devices()[0].memory_stats() or {}
    log(f"[A] peak_bytes_in_use: {mem.get('peak_bytes_in_use', 'n/a')}")

    manifests = sorted(ckpt_dir.glob("step_*/manifest.json"))
    check(len(manifests) == 1, f"expected one checkpoint, got {manifests}")
    manifest = json.loads(manifests[0].read_text())
    written = sorted((e["offset"], e["nbytes"]) for e in manifest["arrays"]
                     if e["nbytes"])
    log(f"[A] checkpoint: step {manifest['step']}, "
        f"{manifest['total_bytes']} bytes in {len(written)} arrays")

    reader = TraceReader(str(trace_dir))
    paths, pwrites, n_read = {}, [], 0
    for _, r in reader.all_records(timestamps=False):
        n_read += 1
        if r.func == "open" and not isinstance(r.ret, tuple):
            paths[r.ret] = r.args[0]
        elif r.func == "pwrite" and str(
                paths.get(r.args[0], "")).endswith("/arrays.bin"):
            check(r.ret == r.args[1], f"short checkpoint write {r}")
            pwrites.append((r.args[2], r.ret))
    trace_bytes = sum(p.stat().st_size for p in trace_dir.rglob("*")
                      if p.is_file())
    log(f"[A] records {stats.n_records} (read back {n_read}), epochs "
        f"{stats.epochs} ({mid_run_epochs} before finalize), trace bytes "
        f"{trace_bytes}")

    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check(len(losses) == steps + 1, f"{len(losses)} steps ran")
    check((res1["final_step"], res2["final_step"]) == (steps, steps + 1),
          f"final steps {res1['final_step']}, {res2['final_step']}")
    check(tr2.start_step == steps,
          f"resume started at step {tr2.start_step}, not {steps}")
    check(np.array_equal(probe, resumed_probe), "resumed state differs")
    retried = {s: n for s, n in attempts.items() if n > 1}
    check(not retried, f"steps retried: {retried}")
    check(not autoflush_broken, "recorder auto-flush broke")
    check(mid_run_epochs >= 1, "no epoch committed before finalize")
    check(n_read == stats.n_records,
          f"read back {n_read} records of {stats.n_records}")
    check(sorted(pwrites) == written,
          f"trace has {len(pwrites)} checkpoint pwrites, engine wrote "
          f"{len(written)}")
    shutil.rmtree(ckpt_dir)
    log("[A] ok")


# ---------------------------------------------------------------------------
# phase B: encode ops at flush sizes, and traced runs under auto
# ---------------------------------------------------------------------------


def _encode_inputs(rng, n: int) -> dict:
    import numpy as np
    # interleaved entry/exit/bytes tick columns, with a clock wrap and a
    # few full-range values
    ticks = np.cumsum(rng.integers(0, 2000, n, dtype=np.int64))
    ticks += (1 << 32) - int(ticks[n // 2])
    ticks[rng.integers(0, n, 16)] = rng.integers(0, 1 << 32, 16)
    ticks = ticks % (1 << 32)
    widths = rng.integers(1, 65, n).astype(np.uint64)
    u64 = rng.integers(0, np.iinfo(np.uint64).max, n, dtype=np.uint64,
                       endpoint=True) >> (np.uint64(64) - widths)
    runs = np.repeat(rng.integers(-5, 5, n // 3 + 1), 3)[:n]
    V = np.stack([runs, np.roll(runs, 7), rng.integers(0, 2, n)], axis=1)
    terms = rng.integers(0, 1000, n)
    terms[: n // 4] = 7
    return {"ticks": ticks, "u64": u64, "V": V, "terms": terms}


def _check_ops(n: int, rng, counter: KernelCounter) -> None:
    import numpy as np
    from repro.core import encode_backend as eb

    x = _encode_inputs(rng, n)
    cases = {
        "delta_zigzag": lambda b: eb.delta_zigzag(x["ticks"], b),
        "encode_ticks_varint": lambda b: eb.encode_ticks_varint(
            x["ticks"], b),
        "pack_uvarints_batch": lambda b: eb.pack_uvarints_batch(x["u64"], b),
        "run_boundaries": lambda b: eb.run_boundaries(x["V"], b),
        "run_boundaries_1col": lambda b: eb.run_boundaries(x["terms"], b),
        "terminal_histogram": lambda b: eb.terminal_histogram(
            x["terms"], 1000, b),
        "terminal_histogram_5000": lambda b: eb.terminal_histogram(
            x["terms"], 5000, b),
        "digram_histogram": lambda b: eb.digram_histogram(
            x["terms"], 1000, b),
    }
    for name, op in cases.items():
        want = op("numpy")
        before = counter.snapshot()
        t0 = time.perf_counter()
        got = op("pallas")
        dt = time.perf_counter() - t0
        hits = counter.since(before)
        check(sum(hits.values()) == 1, f"{name} n={n}: kernel calls {hits}")
        if isinstance(want, np.ndarray):
            same = want.dtype == got.dtype and np.array_equal(want, got)
        else:
            same = want == got
        check(same, f"{name} n={n}: pallas differs from numpy")
        log(f"[B] {name:24s} n={n:8d} kernel={next(iter(hits))} identical "
            f"({dt:.3f}s host wall, first call compiles)")


def _traced_ior(base: Path, backend: str, n_calls: int,
                flush_every) -> tuple:
    from repro.core.recorder import Recorder, RecorderConfig, attach, detach
    trace_dir = base / f"trace_{backend}"
    rec = Recorder(rank=0, config=RecorderConfig(
        trace_dir=str(trace_dir), encode_backend=backend,
        flush_every_n_records=flush_every))
    _fixed_clock(rec)
    attach(rec)
    try:
        log_ = _listing3(0, 4, str(base.parent / "ior_shared.bin"),
                         range(n_calls), 256)
    finally:
        detach()
    stats = rec.finalize()
    return trace_dir, stats, log_


def _check_against_log(trace_dir: Path, call_log: list) -> None:
    """The expanded records equal the plain call log: every write's
    offset and size, in order, with the fixed clock's ticks."""
    from repro.core.reader import TraceReader
    recs = list(TraceReader(str(trace_dir)).iter_records(0))
    ends = [r.func for r in recs[:1] + recs[-2:]]
    check(ends == ["open", "fsync", "close"], f"trace framed by {ends}")
    writes = recs[1:-2]
    check(len(writes) == len(call_log),
          f"{len(writes)} pwrite records, {len(call_log)} calls")
    for i, (r, (off, n)) in enumerate(zip(writes, call_log)):
        t = 2 * (i + 1)
        if (r.func, r.args[2], r.ret, r.t_entry, r.t_exit) != \
                ("pwrite", off, n, t, t + 1):
            raise AssertionError(f"record {i + 1} is {r}, call log says "
                                 f"pwrite at {off} of {n} bytes")


def phase_b(out: Path, seed: int, rehearse: bool) -> None:
    import numpy as np
    from repro.core import encode_backend as eb

    counter = KernelCounter(allow_interpret=rehearse)
    rng = np.random.default_rng(seed)
    sizes = (4096, 4096 + 77, 16384) if rehearse else \
        (65536, 65536 + 77, 1 << 20)
    for n in sizes:
        _check_ops(n, rng, counter)
    log(f"[B] auto: kernels for batches of >= {eb.PALLAS_MIN_BATCH} "
        f"elements, accelerator={eb.has_accelerator()}")

    runs = {"one-shot": (3000 if rehearse else 100_000, None),
            "streamed": (6000 if rehearse else 200_000,
                         2048 if rehearse else 65536)}
    for name, (n_calls, flush_every) in runs.items():
        base = out / "ior" / name
        base.mkdir(parents=True)
        before = counter.snapshot()
        t0 = time.perf_counter()
        t_auto, s_auto, log_auto = _traced_ior(base, "auto", n_calls,
                                               flush_every)
        dt = time.perf_counter() - t0
        hits = counter.since(before)
        if flush_every is None and not rehearse:
            check(hits.get("delta_zigzag"),
                  f"one-shot finalize reached no timestamp kernel: {hits}")
        t_np, s_np, log_np = _traced_ior(base, "numpy", n_calls, flush_every)
        check(log_auto == log_np, f"{name}: the two runs made other calls")
        check(s_auto.n_records == n_calls + 3, f"{name}: {s_auto}")
        if flush_every:
            check(s_auto.epochs >= n_calls // flush_every, f"{name}: {s_auto}")
        d_auto, d_np = _bin_digests(t_auto), _bin_digests(t_np)
        check(d_auto and d_auto == d_np,
              f"{name}: auto trace differs from numpy twin")
        _check_against_log(t_auto, log_auto)
        log(f"[B] traced {name}: {n_calls} pwrites, {s_auto.n_records} "
            f"records, {s_auto.epochs} epochs, {len(d_auto)} .bin files "
            f"identical to numpy, records equal the call log; "
            f"{dt:.2f}s host wall under auto")
        log(f"[B] kernel calls under auto ({name}): "
            + (json.dumps(hits, sort_keys=True) if hits else "none"))
    (out / "ior" / "ior_shared.bin").unlink()
    log("[B] ok")


# ---------------------------------------------------------------------------
# four chips: multi-host finalize over JaxComm vs the ThreadComm reference
# ---------------------------------------------------------------------------

MH_CALLS = {"oneshot": (2000,), "stream": (1200, 800)}   # calls per part


def _mh_record(rec, rank: int, path: str, part: int, parts: tuple) -> None:
    from repro.core.recorder import attach, detach
    start = sum(parts[:part])
    attach(rec)
    try:
        _listing3(rank, N_CHIPS, path, range(start, start + parts[part]), 64)
    finally:
        detach()


def four_chip_worker(worker: int, port: int, out: Path,
                     rehearse: bool) -> int:
    import jax
    if rehearse:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=f"localhost:{port}",
                               num_processes=N_CHIPS, process_id=worker)
    device = _device_line(N_CHIPS, rehearse)
    # the TPU runtime numbers the processes by chip, not by worker: the
    # trace rank is JAX's process index, as JaxComm's is
    rank = jax.process_index()
    log(f"[4] worker {worker} is process {rank} of {jax.process_count()}")
    check(jax.process_count() == N_CHIPS,
          f"{jax.process_count()} processes")
    check(len(jax.local_devices()) == 1,
          f"local devices {jax.local_devices()}")
    from repro.core.comm import JaxComm, run_thread_world
    from repro.core.recorder import Recorder, RecorderConfig

    comm = JaxComm()
    data = str(out / "shared.bin")
    for mode, parts in MH_CALLS.items():
        rec = Recorder(rank=rank, comm=comm, config=RecorderConfig(
            trace_dir=str(out / mode / "jax")))
        _fixed_clock(rec)
        for part in range(len(parts)):
            if part:
                rec.flush(comm)
            _mh_record(rec, rank, data, part, parts)
        t0 = time.perf_counter()
        rec.finalize(comm)
        log(f"[4] rank {rank}: {mode} finalize over JaxComm "
            f"{time.perf_counter() - t0:.3f}s")

    verdict = "ok"
    if rank == 0:
        try:
            for mode, parts in MH_CALLS.items():
                ref = out / mode / "ref"
                recs = [Recorder(rank=r, config=RecorderConfig(
                    trace_dir=str(ref))) for r in range(N_CHIPS)]
                for rec in recs:
                    _fixed_clock(rec)
                for part in range(len(parts)):
                    if part:
                        run_thread_world(N_CHIPS,
                                         lambda c, r: recs[r].flush(c))
                    for r, rec in enumerate(recs):
                        _mh_record(rec, r, data, part, parts)
                run_thread_world(N_CHIPS, lambda c, r: recs[r].finalize(c))
                got, want = _bin_digests(out / mode / "jax"), \
                    _bin_digests(ref)
                check(want and got == want,
                      f"{mode}: JaxComm trace differs from ThreadComm")
                log(f"[4] {mode}: {len(got)} .bin files byte-identical to "
                    f"the ThreadComm reference")
        except Exception:
            traceback.print_exc()
            verdict = "fail"
    from jax.experimental import multihost_utils
    import numpy as np
    verdict_ok = bool(multihost_utils.broadcast_one_to_all(
        np.asarray(verdict == "ok")))
    jax.distributed.shutdown()
    if not verdict_ok:
        return 1
    if rank == 0:
        (out / "result.json").write_text(json.dumps(
            {"ok": True, "device": device}))
    return 0


def _free_ports(k: int) -> list:
    socks = [socket.socket() for _ in range(k)]
    for s in socks:
        s.bind(("localhost", 0))
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def four_chip_parent(out: Path, rehearse: bool, timeout_s: float) -> int:
    """Start one worker per chip; this process never imports JAX."""
    coord, *tpu_ports = _free_ports(1 + N_CHIPS)
    procs = []
    for worker in range(N_CHIPS):
        env = dict(os.environ)
        if not rehearse:
            env.update({
                "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                "TPU_PROCESS_BOUNDS": "2,2,1",
                "TPU_PROCESS_ADDRESSES": ",".join(
                    f"localhost:{p}" for p in tpu_ports),
                "TPU_PROCESS_PORT": str(tpu_ports[worker]),
                "TPU_VISIBLE_CHIPS": str(worker),
                "CLOUD_TPU_TASK_ID": str(worker),
            })
        cmd = [sys.executable, str(Path(__file__).resolve()), "--chips",
               str(N_CHIPS), "--worker", str(worker), "--port", str(coord),
               "--out", str(out)] + (["--rehearse"] if rehearse else [])
        logf = open(out / f"worker{worker}.log", "w")
        procs.append((subprocess.Popen(cmd, env=env, stdout=logf,
                                       stderr=subprocess.STDOUT), logf))
    # the others block in a collective once one worker fails: stop them all
    deadline = time.monotonic() + timeout_s
    try:
        while True:
            rcs = [p.poll() for p, _ in procs]
            if all(rc == 0 for rc in rcs) or any(rcs):
                break
            if time.monotonic() > deadline:
                log(f"[4] workers still running after {timeout_s:.0f}s")
                break
            time.sleep(0.5)
    finally:
        for p, f in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
            f.close()
    for worker in range(N_CHIPS):
        text = (out / f"worker{worker}.log").read_text()
        log(f"----- worker {worker} (last lines) -----")
        log("\n".join(text.splitlines()[-40:]))
    log(f"[4] worker exit codes: {rcs}")
    if any(rc != 0 for rc in rcs):
        return 1
    result = out / "result.json"
    if rehearse or not result.exists():
        return 0 if rehearse else 1
    log(result.read_text())
    return 0


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, N_CHIPS), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", type=Path, default=ROOT / ".chip_smoke")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend; prints no chip result")
    ap.add_argument("--timeout", type=float, default=900.0,
                    help="four-chip workers' wall-clock limit (s)")
    ap.add_argument("--worker", type=int, default=None, help=argparse.SUPPRESS)
    ap.add_argument("--port", type=int, default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    out = args.out.resolve()

    _find_repro()
    if args.worker is not None:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        try:
            rc = four_chip_worker(args.worker, args.port, out, args.rehearse)
        except BaseException:
            traceback.print_exc()
            rc = 1
        if rc:
            # exit now: jax.distributed's exit barrier would wait minutes
            # for workers that are blocked in a collective
            sys.stdout.flush()
            sys.stderr.flush()
            os._exit(rc)
        return rc

    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True)
    if args.chips == N_CHIPS:
        return four_chip_parent(out, args.rehearse, args.timeout)

    device = _device_line(1, args.rehearse)
    log(f"device: {device}")
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache() or 'from the environment'}")
    # the recorder's own warnings (a broken auto-flush among them) fail
    # the run instead of scrolling past
    warnings.filterwarnings("error", category=RuntimeWarning,
                            module=r"repro\.")
    failed = []
    for name, phase in (("A", phase_a), ("B", phase_b)):
        t0 = time.perf_counter()
        try:
            phase(out, args.seed, args.rehearse)
        except Exception:
            traceback.print_exc()
            failed.append(name)
        log(f"[{name}] {time.perf_counter() - t0:.1f}s")
    if failed:
        log(f"FAILED phases: {', '.join(failed)}")
        return 1
    if args.rehearse:
        log(f"rehearsal passed on {device['platform']}: not a chip result")
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
