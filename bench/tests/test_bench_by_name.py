"""A cell, a mix and a per-layer metric are added as new files and
entries, with no edit to any file the benchmark has: the harness finds
them by name."""

import json
import os
import shutil
import subprocess
import sys

from bench import harness


def test_throwaway_cell(tmp_path):
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(harness.ROOT / "src", tmp_path / "src")
    bench = harness.benchmark()
    # a new mix: two units of one segment before each stop check
    mix = json.loads((harness.BENCH / "mixes" / "l3_rank1.json").read_text())
    mix["unit_segments"] = 2
    mix["rehearse"] = {"recorder": {"flush_every_n_records": 128,
                                    "ts_block_records": 128}}
    (tmp_path / "bench" / "mixes" / "throwaway.json").write_text(
        json.dumps(mix))
    (tmp_path / "bench" / "metrics" / "units.throwaway.py").write_text(
        '"""Units the window ran."""\n\n\n'
        'def read(ctx):\n    return ctx["counters"].get("units")\n')
    bench["workloads"].append({
        "name": "ior.l3.throwaway", "config": "ior_listing3",
        "traffic": "throwaway", "chips": 1, "why": "a test's own cell"})
    bench["per_layer"].append({
        "name": "units.throwaway", "unit": "units", "better": "higher",
        "source": "program_counter", "layer": "wrappers and record",
        "moves": "io_calls_per_s", "workloads": ["ior.l3.throwaway"]})
    for m in bench["end_to_end"]:
        if "workloads" in m and "ior.l3.rank1" in m["workloads"]:
            m["workloads"].append("ior.l3.throwaway")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for trace, want in ((0, "io_calls_per_s"), (1, "units.throwaway")):
        proc = subprocess.run(
            [sys.executable, "bench/run.py", "--workload", "ior.l3.throwaway",
             "--seed", "12", "--seconds", "1", "--trace", str(trace),
             "--rehearse", "--out", str(tmp_path / "out")], cwd=tmp_path,
            env=env, capture_output=True,
            text=True, timeout=600)
        assert proc.returncode == 0, proc.stderr[-3000:]
        line = json.loads(proc.stdout.strip().splitlines()[-1])
        assert line["correct"] is True
        assert want in line["metrics"]
    assert line["metrics"]["units.throwaway"]["value"] >= 1
