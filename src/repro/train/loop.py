"""Fault-tolerant training loop.

Production behaviors, all exercised by tests:

  * auto-resume from the newest valid checkpoint (crc-verified, falls back
    to older ones on corruption),
  * periodic checkpointing (sync or async thread) with keep-k GC, through
    the traced I/O facades -- a Recorder session sees the whole step loop
    (``frame.step`` events) plus the checkpoint call chains,
  * step retry with restore-on-repeated-failure,
  * gradient-accumulation microbatching (``accum_steps``) for memory,
  * deterministic, resumable data (state == step counter).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from ..checkpoint import CheckpointEngine
from ..core.apis import framework as frame
from ..models import get_model
from ..models.config import ModelConfig
from ..optim import AdamWConfig, adamw_init
from ..launch.steps import make_train_step


@dataclass
class TrainerConfig:
    num_steps: int = 100
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_every: int = 50
    keep: int = 2
    log_every: int = 10
    retry_max: int = 2
    async_ckpt: bool = False
    accum_steps: int = 1
    seed: int = 0


class Trainer:
    def __init__(self, cfg: ModelConfig, tcfg: TrainerConfig,
                 ocfg: Optional[AdamWConfig] = None,
                 data: Optional[Callable[[int], Dict]] = None,
                 fault_hook: Optional[Callable[[int], None]] = None):
        self.cfg = cfg
        self.tcfg = tcfg
        self.ocfg = ocfg or AdamWConfig()
        self.model = get_model(cfg)
        self.data = data
        self.fault_hook = fault_hook
        self.engine = CheckpointEngine(tcfg.ckpt_dir, keep=tcfg.keep,
                                       async_save=tcfg.async_ckpt)
        self._step_fn = jax.jit(
            make_train_step(cfg, self.ocfg, accum_steps=tcfg.accum_steps),
            donate_argnums=(0,))
        self.state = None
        self.start_step = 0
        self.metrics_log: List[Dict[str, float]] = []

    # -- state ----------------------------------------------------------------

    def init_state(self) -> None:
        """Fresh init or auto-resume from the newest valid checkpoint.

        The checkpoint is read against the state's shapes alone, so a
        resume never holds a fresh state and the restored one on the
        device at the same time."""
        def fresh():
            return adamw_init(self.model.init_params(
                jax.random.PRNGKey(self.tcfg.seed)))

        restored = self.engine.restore_latest(jax.eval_shape(fresh))
        if restored is not None:
            tree, manifest = restored
            self.state = jax.tree.map(jax.numpy.asarray, tree)
            self.start_step = int(manifest["meta"].get("next_step",
                                                       manifest["step"]))
        else:
            self.state = fresh()
            self.start_step = 0

    # -- loop -------------------------------------------------------------------

    def _run_step(self, step: int) -> Dict[str, float]:
        batch = self.data(step)
        frame.fetch_batch(step, sum(v.nbytes for v in batch.values()))
        if self.fault_hook is not None:
            self.fault_hook(step)
        self.state, metrics = self._step_fn(self.state, batch)
        return {k: float(v) for k, v in metrics.items()}

    def run(self) -> Dict[str, Any]:
        if self.state is None:
            self.init_state()
        step = self.start_step
        retries = 0
        while step < self.tcfg.num_steps:
            frame.step(step)
            try:
                metrics = self._run_step(step)
            except Exception:
                retries += 1
                if retries <= self.tcfg.retry_max:
                    continue  # transient failure: retry the same step
                # repeated failure: restore from last good checkpoint
                restored = self.engine.restore_latest(
                    jax.tree.map(np.asarray, self.state))
                if restored is None:
                    raise
                tree, manifest = restored
                self.state = jax.tree.map(jax.numpy.asarray, tree)
                step = int(manifest["meta"].get("next_step",
                                                manifest["step"]))
                retries = 0
                continue
            retries = 0
            metrics["step"] = step
            self.metrics_log.append(metrics)
            step += 1
            if self.tcfg.ckpt_every and step % self.tcfg.ckpt_every == 0:
                frame.ckpt_begin(step)
                self.engine.save(self.state, step, meta={"next_step": step})
                nbytes = sum(v.nbytes if hasattr(v, "nbytes") else 0
                             for v in jax.tree.leaves(self.state))
                frame.ckpt_end(step, nbytes)
        self.engine.wait()
        return {"final_step": step,
                "last_loss": self.metrics_log[-1]["loss"]
                if self.metrics_log else float("nan")}
