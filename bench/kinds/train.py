"""Training cells: the program's ``Trainer`` inside a Recorder session.

Set-up makes the weights and the optimizer state from the seed in one
jitted call on the device (the configuration's reference module says
how), builds the ``Trainer`` with its compiled step and a ring of
distinct batches from the seed, and drives that same object through the
first ``checked_steps`` steps inside the session: they compile the step
and give the readings that the reference is held to.  The window then
runs the same ``Trainer`` on for ``--seconds``.

After the window: the session finalizes, the peak memory is read, the
program's state is freed, the trace is read back and compared with the
steps the window ran, and the reference runs the checked steps.
"""

from __future__ import annotations

import functools
import gc
import importlib.util
import math
import time
from typing import Any, Dict, List

import numpy as np

from .. import compare, flops, harness
from ..harness import Check, Outcome


@functools.lru_cache(maxsize=None)
def reference_module(config_name: str):
    """``configs/<config>_ref.py``, loaded once per process."""
    path = harness.BENCH / "configs" / f"{config_name}_ref.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_ref_{config_name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def _adamw_init():
    import jax
    from repro.optim import adamw_init
    return jax.jit(adamw_init)


def sizes(cell: harness.Cell, rehearse: bool):
    """The configuration and mix as run (``rehearse`` swaps in their
    small CPU sizes)."""
    conf, mix = dict(cell.config), dict(cell.mix)
    if rehearse:
        conf.update(conf.get("rehearse", {}))
        mix.update(mix.get("rehearse", {}))
    return conf, mix


def model_config(conf: Dict[str, Any]):
    from repro.models.config import ModelConfig
    prog = conf["program"]
    mc = ModelConfig(
        name=conf["name"], family="dense",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        qkv_bias=conf["attention_bias"], rope_theta=conf["rope_theta"],
        norm_eps=conf["rms_norm_eps"], dtype=prog["compute_dtype"],
        param_dtype=prog["param_dtype"], remat=prog["remat"],
        loss_chunk=prog["loss_chunk"])
    if mc.padded_vocab != conf["padded_vocab_size"]:
        raise SystemExit(f"the program pads the vocabulary to "
                         f"{mc.padded_vocab}, the configuration states "
                         f"{conf['padded_vocab_size']}")
    return mc


def seed32(seed: int) -> int:
    return int(np.random.SeedSequence(seed).generate_state(1)[0])


def batches(seed: int, n: int, batch: int, seq: int, vocab: int
            ) -> List[Dict[str, np.ndarray]]:
    """``n`` batches of uniform token rows from the seed."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        x = rng.integers(0, vocab, (batch, seq + 1), dtype=np.int32)
        out.append({"tokens": np.ascontiguousarray(x[:, :-1]),
                    "labels": np.ascontiguousarray(x[:, 1:])})
    return out


class TracedTrainer:
    """The program's ``Trainer`` with its state from the seed, and the
    hooks that time its steps and close the window."""

    def __init__(self, cell: harness.Cell, seed: int, conf, mix, spans):
        import jax
        from repro.optim import AdamWConfig
        from repro.train import Trainer, TrainerConfig
        self.ref = reference_module(cell.config_name)
        self.conf, self.mix, self.spans = conf, mix, spans
        self.key = jax.random.key(seed32(seed))
        self.ring = batches(seed, mix["ring"], mix["batch"], mix["seq_len"],
                            conf["vocab_size"])
        self.marks: List[float] = []
        self.window_end = math.inf
        tcfg = TrainerConfig(num_steps=mix["checked_steps"],
                             ckpt_dir=str(cell.out / "ckpt"), ckpt_every=0,
                             seed=seed32(seed))
        self.trainer = Trainer(model_config(conf), tcfg,
                               AdamWConfig(**conf["optimizer"]),
                               data=self._data, fault_hook=self._hook)
        # the program's own state from the reference's weights: exact
        # casts and zeros, on the device
        self.trainer.state = _adamw_init()(
            self.ref.init_weights(self.key, conf))
        self._grad = None

    def _data(self, step: int):
        with self.spans.span("data"):
            return self.ring[step % len(self.ring)]

    def _hook(self, step: int) -> None:
        import jax
        tr = self.trainer
        jax.block_until_ready(tr.state)
        now = time.perf_counter()
        self.marks.append(now)
        if step == 1 and self._grad is None:
            # the first gradient as the optimizer took it: mu / (1 - b1)
            b1 = self.conf["optimizer"]["b1"]
            self._grad = {k: v / (1 - b1) for k, v in
                          self.ref.leaf_norms_host(tr.state["mu"]).items()}
        if now >= self.window_end:
            tr.tcfg.num_steps = step + 1

    def checked_steps(self) -> Dict[str, Any]:
        """Run the first steps; return the program's readings."""
        tr = self.trainer
        tr.run()
        n = self.mix["checked_steps"]
        return {"loss": [m["loss"] for m in tr.metrics_log[:n]],
                "grad": self._grad,
                "delta": self.ref.delta_norms_host(
                    tr.state["master"], self.key, self.conf)}

    def window(self, seconds: float) -> Dict[str, float]:
        """Run on from the checked steps for ``seconds``."""
        import jax
        tr = self.trainer
        first = len(tr.metrics_log)
        tr.start_step = first
        tr.tcfg.num_steps = 1 << 40
        del self.marks[:]
        t0 = time.perf_counter()
        self.window_end = t0 + seconds
        tr.run()
        jax.block_until_ready(tr.state)
        t1 = time.perf_counter()
        steps = tr.metrics_log[first:]
        times = np.diff(np.asarray(self.marks + [t1]))
        tokens = len(steps) * self.mix["batch"] * self.mix["seq_len"]
        slow = np.argsort(times)[::-1][:3]
        harness.log("slowest window steps (ms): " + ", ".join(
            f"#{i} {times[i] * 1e3:.1f}" for i in slow))
        return {"t0": t0, "t1": t1, "steps": len(steps),
                "failed": sum(not math.isfinite(m["loss"]) for m in steps),
                "tokens_per_s": tokens / (t1 - t0),
                "step_p50_ms": float(np.percentile(times, 50)) * 1e3,
                "step_p90_ms": float(np.percentile(times, 90)) * 1e3}

    def free(self) -> None:
        self.trainer.state = None
        self.trainer = None
        gc.collect()


def read_back(trace_dir, n_steps: int, nbytes: int, ticks: List[int]
              ) -> Dict[str, Any]:
    """The trace's records against the steps the job ran: each step
    records ``step(s)`` and ``fetch_batch(s, nbytes)``, in that order."""
    from repro.core.reader import TraceReader
    want = [rec for s in range(n_steps)
            for rec in (("step", (s,), 0), ("fetch_batch", (s, nbytes), 0))]
    matcher = compare.TickMatcher(ticks)
    bad, n, first = compare.records(
        compare.plain(TraceReader(str(trace_dir)).iter_records(0), matcher),
        want)
    if matcher.bad and first is None:
        first = f"{matcher.bad} records' ticks are not the clock's"
    return {"records": n, "mismatched": bad + matcher.bad, "first": first}


def run(cell: harness.Cell, args, t_start: float) -> Outcome:
    from repro.core.recorder import RecorderConfig, session
    rehearse = args.rehearse
    device = harness.device_line(cell.chips, rehearse)
    harness.enable_compile_cache(rehearse)
    compiles = harness.CompileCounter()
    conf, mix = sizes(cell, rehearse)
    out = harness.fresh_dir(cell.out)
    trace_dir = out / "trace"
    spans = harness.Spans(annotate=bool(args.trace))
    tt = TracedTrainer(cell, args.seed, conf, mix, spans)
    ticks: List[int] = []
    tracer = conf["tracer"]
    sess = session(RecorderConfig(
        trace_dir=str(trace_dir), flush_interval_s=tracer["flush_interval_s"],
        encode_backend=tracer["encode_backend"]))
    with sess as rec:
        # in a traced run, two records a step: each one a span
        harness.instrument(rec, ticks, spans if args.trace else None,
                           span_each_record=True)
        prog = tt.checked_steps()
        with compiles.window(), \
                harness.profiled(bool(args.trace), out / "profile") as prof:
            setup_s = time.perf_counter() - t_start
            w = tt.window(args.seconds)
    peak = harness.memory_peak_bytes()
    n_steps = len(tt.trainer.metrics_log)
    tt.free()
    trace_bytes = harness.dir_bytes(trace_dir)
    nbytes = 2 * 4 * mix["batch"] * mix["seq_len"]
    back = read_back(trace_dir, n_steps, nbytes, ticks)
    if back["first"]:
        harness.log(f"trace read-back: {back['first']}")
    devsum = harness.device_summary(prof)
    ref = tt.ref.train_steps(tt.key, tt.ring[:mix["checked_steps"]], conf,
                             conf["optimizer"])
    nums = compare.train_numbers(prog, ref)
    harness.log(f"losses: program {prog['loss']}, reference {ref['loss']}")
    limits = conf["limits"]
    checks = [Check(k, v, limits[k]) for k, v in nums.items()]
    checks.append(Check("records_mismatched", back["mismatched"],
                        limits["records_mismatched"]))
    harness.log(f"window: {w['steps']} steps, compilations in the window "
                f"{compiles.in_window}, records {back['records']}, epochs "
                f"{sess.stats.epochs}, peak bytes {peak}")
    if device is not None:
        device = dict(device, memory_peak_bytes=peak)
    fpt = flops.train_flops_per_token(conf, mix["seq_len"])
    return Outcome(
        device=device, checks=checks, attempted=w["steps"],
        failed=w["failed"], spans=spans, devtrace=devsum,
        e2e={"train_tokens_per_s": w["tokens_per_s"],
             "train_step_p90_ms": w["step_p90_ms"],
             "trace_bytes_per_record": trace_bytes / (2 * n_steps),
             "setup_s": setup_s},
        counters={"tokens_per_s": w["tokens_per_s"],
                  "flops_per_token": fpt, "chips": cell.chips,
                  "window_s": w["t1"] - w["t0"], "steps": w["steps"],
                  "epochs": sess.stats.epochs,
                  "compiles_in_window": compiles.in_window,
                  "step_p50_ms": w["step_p50_ms"]})
