"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --smoke --steps 50 --batch 4 --seq 64 --trace-dir /tmp/trace

It trains in one process on the devices JAX finds.  ``--smoke`` selects
the reduced config so the example trains in CPU-minutes.  Checkpoints go
to ``--ckpt-dir``, by default ``<trace-dir>/ckpt`` or else a fresh
temporary directory, so a rerun never resumes from another run's state.
"""

from __future__ import annotations

import argparse
import json
import os
import tempfile

from ..configs import get_config, get_smoke_config
from ..core.recorder import RecorderConfig, session
from ..data import SyntheticConfig, synthetic_batch
from ..optim import AdamWConfig
from ..train import Trainer, TrainerConfig
from .compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None,
                    help="default: <trace-dir>/ckpt, else a fresh temp dir")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--async-ckpt", action="store_true")
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--trace-dir", default=None,
                    help="Recorder trace output (enables tracing)")
    args = ap.parse_args()
    if args.ckpt_dir is None:
        args.ckpt_dir = (os.path.join(args.trace_dir, "ckpt")
                         if args.trace_dir
                         else tempfile.mkdtemp(prefix="repro_train_ckpt_"))
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    dcfg = SyntheticConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                           batch_size=args.batch)

    def data(step):
        b = synthetic_batch(dcfg, step)
        if cfg.family == "vlm":
            import numpy as np
            b["patches"] = np.zeros((args.batch, cfg.n_patches, cfg.d_model),
                                    np.float32)
        if cfg.family == "encdec":
            import numpy as np
            b["frames"] = np.random.RandomState(step).randn(
                args.batch, args.seq, cfg.d_model).astype(np.float32)
        return b

    tcfg = TrainerConfig(num_steps=args.steps, ckpt_dir=args.ckpt_dir,
                         ckpt_every=args.ckpt_every,
                         async_ckpt=args.async_ckpt,
                         accum_steps=args.accum)
    ocfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                       total_steps=args.steps)

    def run():
        tr = Trainer(cfg, tcfg, ocfg, data=data)
        res = tr.run()
        print(json.dumps({"result": res,
                          "loss_first": tr.metrics_log[0]["loss"],
                          "loss_last": tr.metrics_log[-1]["loss"]},
                         indent=1))

    if args.trace_dir:
        with session(RecorderConfig(trace_dir=args.trace_dir)) as rec:
            run()
            print(f"traced {rec.n_records} records "
                  f"({len(rec.cst)} unique signatures) -> {args.trace_dir}")
    else:
        run()


if __name__ == "__main__":
    main()
