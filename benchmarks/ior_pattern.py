"""Paper Figs 4-5: impact of intra-/inter-process I/O pattern recognition,
plus the finalize-scaling experiment for the tree-reduction topology.

Fig 4 (blocksize): fixed nprocs, increasing call count per rank; with
intra-process recognition the trace size must be FLAT in call count.
Fig 5 (scaling): fixed call count, increasing nprocs; with inter-process
recognition the trace size must be FLAT in process count.

Finalize scaling: sweeps simulated rank counts over synthesized
IOR-shaped rank states and times the inter-process tree reduction.  The
reported wall time is the *critical path* a real deployment would see --
the slowest leaf build (leaves are built concurrently, one per rank) plus
the slowest merge of each O(log N) reduction round plus the root
materialization -- while ``cpu_s`` is the total sequential work.

Outputs CSV to artifacts/bench/ior_{blocksize,scaling}.csv and JSON to
artifacts/bench/finalize_scaling.json.
"""

from __future__ import annotations

import csv
import gc
import json
import os
import shutil
import tempfile
import time
from typing import List

from repro.core.interprocess import (make_rank_state, materialize_state,
                                     merge_rank_states)
from repro.core.recorder import RecorderConfig
from repro.core.specs import REGISTRY

from .workloads import ior_rank, run_ranks, synth_rank_states

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts", "bench")

CONFIGS = {
    "both": RecorderConfig(intra_patterns=True, inter_patterns=True,
                           timestamps=False),
    "intra_only": RecorderConfig(intra_patterns=True, inter_patterns=False,
                                 timestamps=False),
    "inter_only": RecorderConfig(intra_patterns=False, inter_patterns=True,
                                 timestamps=False),
    "none": RecorderConfig(intra_patterns=False, inter_patterns=False,
                           timestamps=False),
}


def blocksize(n_calls_list=(64, 256, 1024, 4096), nprocs: int = 64
              ) -> List[dict]:
    rows = []
    for n_calls in n_calls_list:
        for cname in ("both", "inter_only"):
            d = tempfile.mkdtemp()
            try:
                r = run_ranks(ior_rank, nprocs, CONFIGS[cname],
                              n_calls=n_calls, data_dir=d)
            finally:
                shutil.rmtree(d, ignore_errors=True)
            rows.append({"n_calls": n_calls, "nprocs": nprocs,
                         "config": cname,
                         "pattern_bytes": r["pattern_bytes"],
                         "n_records": r["n_records"]})
    return rows


def scaling(nprocs_list=(4, 16, 64, 256), n_calls: int = 256) -> List[dict]:
    rows = []
    for nprocs in nprocs_list:
        for cname in ("both", "intra_only", "none"):
            d = tempfile.mkdtemp()
            try:
                r = run_ranks(ior_rank, nprocs, CONFIGS[cname],
                              n_calls=n_calls, data_dir=d)
            finally:
                shutil.rmtree(d, ignore_errors=True)
            rows.append({"nprocs": nprocs, "n_calls": n_calls,
                         "config": cname,
                         "pattern_bytes": r["pattern_bytes"],
                         "n_records": r["n_records"]})
    return rows


def finalize_scaling(nprocs_list=(16, 64, 256, 1024, 4096),
                     n_groups: int = 32, n_calls: int = 64,
                     pattern: str = "linear") -> List[dict]:
    """Time the tree finalization over synthesized rank states."""
    rows: List[dict] = []
    for nprocs in nprocs_list:
        csts, cfgs = synth_rank_states(nprocs, n_groups=n_groups,
                                       n_calls=n_calls, pattern=pattern)
        gc.disable()  # GC pauses would dominate the per-round maxima
        try:
            gc.collect()
            # leaves are per-rank parallel work on a real run: the
            # critical path counts the slowest one only
            leaf_times = []
            states = []
            for r in range(nprocs):
                t0 = time.perf_counter()
                states.append(make_rank_state(r, csts[r], cfgs[r], REGISTRY))
                leaf_times.append(time.perf_counter() - t0)
            cpu = sum(leaf_times)
            wall = max(leaf_times)
            while len(states) > 1:
                nxt, round_times = [], []
                for i in range(0, len(states), 2):
                    if i + 1 < len(states):
                        t0 = time.perf_counter()
                        nxt.append(merge_rank_states(states[i],
                                                     states[i + 1]))
                        round_times.append(time.perf_counter() - t0)
                    else:
                        nxt.append(states[i])
                states = nxt
                cpu += sum(round_times)
                wall += max(round_times)
            t0 = time.perf_counter()
            merge, _ = materialize_state(states[0])
            dt = time.perf_counter() - t0
            cpu += dt
            wall += dt
        finally:
            gc.enable()
        rows.append({
            "nprocs": nprocs, "pattern": pattern,
            "n_groups": n_groups, "n_calls": n_calls,
            "wall_s": round(wall, 6), "cpu_s": round(cpu, 6),
            "cst_entries": len(merge.merged_entries),
        })
    return rows


def main(fast: bool = False) -> List[str]:
    os.makedirs(ART, exist_ok=True)
    out = []
    bs = blocksize((64, 256, 1024) if fast else (64, 256, 1024, 4096),
                   nprocs=16 if fast else 64)
    with open(os.path.join(ART, "ior_blocksize.csv"), "w", newline="") as f:
        w = csv.DictWriter(f, bs[0].keys())
        w.writeheader()
        w.writerows(bs)
    flat = [r["pattern_bytes"] for r in bs if r["config"] == "both"]
    grow = [r["pattern_bytes"] for r in bs if r["config"] == "inter_only"]
    out.append(f"ior_blocksize,intra_flat={max(flat) - min(flat)},"
               f"nointra_growth={grow[-1] - grow[0]}")
    sc = scaling((4, 16, 64) if fast else (4, 16, 64, 256),
                 n_calls=64 if fast else 256)
    with open(os.path.join(ART, "ior_scaling.csv"), "w", newline="") as f:
        w = csv.DictWriter(f, sc[0].keys())
        w.writeheader()
        w.writerows(sc)
    flat = [r["pattern_bytes"] for r in sc if r["config"] == "both"]
    lin = [r["pattern_bytes"] for r in sc if r["config"] == "none"]
    out.append(f"ior_scaling,inter_flat={max(flat) - min(flat)},"
               f"nopattern_growth={lin[-1] - lin[0]}")
    fs = finalize_scaling((16, 64, 256) if fast
                          else (16, 64, 256, 1024, 4096),
                          n_groups=8 if fast else 32,
                          n_calls=16 if fast else 64)
    with open(os.path.join(ART, "finalize_scaling.json"), "w") as f:
        json.dump(fs, f, indent=1)
    peak = max(fs, key=lambda r: r["nprocs"])
    out.append(f"finalize_scaling,nprocs={peak['nprocs']},"
               f"tree_s={peak['wall_s']},cst_entries={peak['cst_entries']}")
    return out


if __name__ == "__main__":
    for line in main():
        print(line)
