#!/usr/bin/env python3
"""Readings that a training cell's limits are set from, many seeds in one
process (the benchmark's own runs never run this).

    python3 bench/readings.py --workload <training cell> --seeds 1,2,3 \
        [--control-seeds 3]

For each seed: the program's checked steps through the cell's own
``Trainer`` path, then the float32 reference; the numbers compared
(``bench/compare.py``) are printed as one JSON line per seed.  For the
first ``--control-seeds`` seeds also: the control (the reference in the
program's place, its matrix products' operands rounded to float8_e4m3)
and a planted fault (the reference on the first half of each batch's
rows, the mean taken over those), each against the float32 reference.
A state left unchanged needs no run: its change reads 1 on every leaf.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--leaves", action="store_true",
                    help="also print every leaf's readings")
    args = ap.parse_args()
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if Path(p or ".").resolve() != ROOT / "bench"]
    from bench import compare, harness
    from bench.kinds import train
    cell = harness.find_cell(args.workload)
    harness.device_line(cell.chips, args.rehearse)
    harness.enable_compile_cache(args.rehearse)
    conf, mix = train.sizes(cell, args.rehearse)
    opt = conf["optimizer"]
    seeds = [int(s) for s in args.seeds.split(",")]
    for i, seed in enumerate(seeds):
        tt = train.TracedTrainer(cell, seed, conf, mix, harness.Spans())
        prog = tt.checked_steps()
        tt.free()
        batches = tt.ring[:mix["checked_steps"]]
        ref = tt.ref.train_steps(tt.key, batches, conf, opt)
        row = {"seed": seed, "program": compare.train_numbers(prog, ref),
               "loss": prog["loss"], "ref_loss": ref["loss"]}
        if args.leaves:
            row["leaves"] = {
                "grad": compare.leaf_gaps(prog["grad"], ref["grad"]),
                "delta": compare.leaf_gaps(prog["delta"], ref["delta"]),
                "delta_prog": prog["delta"], "delta_ref": ref["delta"],
                "grad_raw_ref": ref["grad_raw"]}
        if i < args.control_seeds:
            ctl = tt.ref.train_steps(tt.key, batches, conf, opt,
                                     matmul="fp8")
            half = tt.ref.train_steps(tt.key, batches, conf, opt,
                                      rows=mix["batch"] // 2)
            row["control"] = compare.train_numbers(ctl, ref)
            row["half_batch"] = compare.train_numbers(half, ref)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
