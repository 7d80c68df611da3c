"""Per-kernel micro-benchmarks.

Pallas-interpret timings on CPU measure the Python emulator, not TPU perf;
the portable numbers are (a) the XLA-path wall times on this host and
(b) the analytic FLOP/byte counts that feed the Section Roofline analysis.

``encode_sweep`` measures the trace-encode hot path (delta+zigzag, varint
packing) across batch sizes under every ``encode_backend`` and writes
``artifacts/bench/encode_kernels.json`` with the per-backend crossover
points (smallest batch where the batched backend beats the scalar Python
encoder) and the speedup at the 64k-record batch the streaming flusher
typically hands the encoder.
"""

from __future__ import annotations

import csv
import json
import os
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import encode_backend as eb
from repro.core.encoding import pack_uvarints
from repro.core.timestamps import delta_zigzag_encode
from repro.kernels.delta_encode.ops import delta_zigzag
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.kernels.ssd_scan.ref import ssd_scan_ref
from repro.models.layers import flash_attention_xla

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts", "bench")


def _timeit(fn, *args, reps=5) -> float:
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def _wall(fn, reps: int) -> float:
    fn()  # warm (jit compile / allocator)
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps


_ENCODE_OPS = ("delta_zigzag", "pack_uvarints")


def encode_sweep(smoke: bool = False) -> Dict[str, Any]:
    """Batch-size sweep of the encode hot path under every backend."""
    rng = np.random.RandomState(3)
    sizes = [256, 4096, 1 << 16] if smoke else \
        [64, 256, 1024, 4096, 16384, 1 << 16, 1 << 18]
    backends = ["python", "numpy", "pallas"]
    # pallas on a CPU-only host runs the interpreter: cap its sizes so the
    # sweep stays CI-sized (the crossover there is numpy's anyway)
    pallas_cap = (1 << 16) if eb.has_accelerator() else \
        (4096 if smoke else (1 << 14))

    timings: Dict[str, Dict[str, Dict[str, float]]] = {
        op: {b: {} for b in backends} for op in _ENCODE_OPS}

    for n in sizes:
        ticks = np.cumsum(rng.randint(0, 1000, size=n)).astype(np.int64)
        vals = [int(v) for v in rng.randint(0, 1 << 48, size=n,
                                            dtype=np.uint64)]
        reps = 1 if n >= (1 << 16) else 3
        for b in backends:
            if b == "pallas" and n > pallas_cap:
                continue
            timings["delta_zigzag"][b][str(n)] = _wall(
                lambda b=b, t=ticks: eb.delta_zigzag(t, b), reps)
            timings["pack_uvarints"][b][str(n)] = _wall(
                lambda b=b, v=vals: pack_uvarints(v, backend=b), reps)

    crossover: Dict[str, Dict[str, Optional[int]]] = {}
    speedup_64k: Dict[str, Dict[str, Optional[float]]] = {}
    for op in _ENCODE_OPS:
        crossover[op] = {}
        speedup_64k[op] = {}
        py = timings[op]["python"]
        for b in ("numpy", "pallas"):
            xs = [n for n in sizes
                  if str(n) in timings[op][b]
                  and timings[op][b][str(n)] < py[str(n)]]
            crossover[op][b] = min(xs) if xs else None
            k = str(1 << 16)
            speedup_64k[op][b] = (round(py[k] / timings[op][b][k], 2)
                                  if k in timings[op][b] else None)

    report = {
        "host_accelerator": eb.has_accelerator(),
        "interpret_mode": eb.interpret_mode(),
        "sizes": sizes,
        "timings_s": timings,
        "crossover_records": crossover,
        "speedup_at_64k": speedup_64k,
        "thresholds": {"numpy_min_batch": eb.NUMPY_MIN_BATCH,
                       "pallas_min_batch": eb.PALLAS_MIN_BATCH},
    }
    os.makedirs(ART, exist_ok=True)
    with open(os.path.join(ART, "encode_kernels.json"), "w") as f:
        json.dump(report, f, indent=2)
    return report


def encode_summary_lines(report: Dict[str, Any]) -> List[str]:
    lines = []
    for op in _ENCODE_OPS:
        for b in ("numpy", "pallas"):
            co = report["crossover_records"][op][b]
            sp = report["speedup_at_64k"][op][b]
            lines.append(
                f"encode,{op},{b},crossover="
                f"{co if co is not None else '-'}"
                f",speedup@64k={sp if sp is not None else '-'}x")
    return lines


def main(fast: bool = False, smoke: bool = False) -> List[str]:
    os.makedirs(ART, exist_ok=True)
    if smoke:
        # CI path: the encode sweep IS the artifact; skip the model kernels
        return encode_summary_lines(encode_sweep(smoke=True))
    rng = np.random.RandomState(0)
    rows = []

    B, S, H, D = (1, 512, 4, 64) if fast else (2, 1024, 8, 64)
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    flops = 4 * B * H * S * S * D  # qk^T + pv
    t_chunked = _timeit(jax.jit(lambda a, b, c: flash_attention_xla(
        a, b, c, causal=True)), q, k, v)
    t_naive = _timeit(jax.jit(lambda a, b, c: jnp.swapaxes(attention_ref(
        jnp.swapaxes(a, 1, 2), jnp.swapaxes(b, 1, 2), jnp.swapaxes(c, 1, 2),
        causal=True), 1, 2)), q, k, v)
    rows.append({"kernel": "flash_attention_xla", "us": t_chunked * 1e6,
                 "derived": f"gflops={flops/t_chunked/1e9:.1f}"})
    rows.append({"kernel": "attention_naive", "us": t_naive * 1e6,
                 "derived": f"gflops={flops/t_naive/1e9:.1f}"})

    Bs, nc, Q, nh, hd, ns = (1, 4, 64, 4, 32, 16) if fast else \
        (2, 8, 128, 8, 64, 32)
    x = jnp.asarray(rng.randn(Bs, nc, Q, nh, hd), jnp.float32)
    b = jnp.asarray(rng.randn(Bs, nc, Q, ns), jnp.float32)
    c = jnp.asarray(rng.randn(Bs, nc, Q, ns), jnp.float32)
    dt = jnp.asarray(rng.rand(Bs, nc, Q, nh), jnp.float32) * 0.1
    da = -jnp.asarray(rng.rand(Bs, nc, Q, nh), jnp.float32) * 0.5
    t_ref = _timeit(jax.jit(ssd_scan_ref), x, b, c, dt, da)
    rows.append({"kernel": "ssd_recurrence_ref", "us": t_ref * 1e6,
                 "derived": f"tokens={Bs*nc*Q}"})

    xx = jnp.asarray(rng.randn(4096, 1024), jnp.float32)
    w = jnp.asarray(rng.rand(1024), jnp.float32)
    t_norm = _timeit(jax.jit(rmsnorm_ref), xx, w)
    rows.append({"kernel": "rmsnorm_ref", "us": t_norm * 1e6,
                 "derived": f"GBps={(xx.nbytes*2)/t_norm/1e9:.1f}"})

    t = np.cumsum(rng.randint(0, 1000, size=1 << 16)).astype(np.uint32)
    tj = jnp.asarray(t)
    t_np = _timeit(lambda a: delta_zigzag_encode(np.asarray(a).reshape(-1, 2)), t)
    rows.append({"kernel": "delta_zigzag_numpy", "us": t_np * 1e6,
                 "derived": f"MBps={t.nbytes/t_np/1e6:.0f}"})

    with open(os.path.join(ART, "kernels.csv"), "w", newline="") as f:
        wcsv = csv.DictWriter(f, rows[0].keys())
        wcsv.writeheader()
        wcsv.writerows(rows)
    lines = [f"kernel,{r['kernel']},{r['us']:.1f}us,{r['derived']}"
             for r in rows]
    lines += encode_summary_lines(encode_sweep(smoke=fast))
    return lines


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="small shapes + reduced encode sweep")
    ap.add_argument("--smoke", action="store_true",
                    help="encode sweep only (CI): writes "
                         "artifacts/bench/encode_kernels.json")
    ns = ap.parse_args()
    for line in main(fast=ns.fast, smoke=ns.smoke):
        print(line)
