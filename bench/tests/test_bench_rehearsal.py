"""Each cell of ``BENCHMARK.json``, and each held cell (``held.json``),
rehearsed end to end on the CPU at its small sizes (``--rehearse``), and
the harness's refusals: no TPU, and a checkout that holds only the
benchmark."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness

BENCH = harness.benchmark()
ALL = harness.benchmark(held=True)
REQUIRED = ["correct", "attempted", "failed", "metrics", "device"]


def run_bench(args, cwd=harness.ROOT, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def check_line(line, trace, cell):
    keys = list(line)
    want = REQUIRED + (["breakdown"] if trace and "breakdown" in line
                       else []) + ["checks"]
    assert keys == want
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["count"] >= 1
    names = {m["name"] for m in (cell.per_layer() if trace
                                 else cell.end_to_end())}
    assert set(line["metrics"]) <= names
    if not trace:
        assert set(line["metrics"]) == names
    else:
        assert {"busy_s", "window_s"} <= set(line["device"])
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] == m["value"]
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("workload", [w["name"] for w in ALL["workloads"]
                                      if w["chips"] == 1])
def test_rehearse_each_cell(workload, tmp_path):
    cell = harness.find_cell(workload)
    for trace in (0, 1):
        proc = run_bench(["--workload", workload, "--seed", str(2**31 + 7),
                          "--seconds", "2", "--trace", str(trace),
                          "--rehearse", "--out", str(tmp_path)])
        check_line(last_line(proc), trace, cell)
        # the compared numbers, each with its limit, end standard error
        tail = proc.stderr.strip().splitlines()[-len(cell.config["limits"]):]
        assert all(t.startswith("check ") and "limit" in t for t in tail)


def test_no_tpu_no_result(tmp_path):
    proc = run_bench(["--workload", BENCH["workloads"][0]["name"],
                      "--seed", "1", "--seconds", "1", "--trace", "0",
                      "--out", str(tmp_path)])
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not proc.stdout.strip()


def test_benchmark_alone_does_not_run(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = run_bench(["--workload", "ior.l3.rank1", "--seed", "1",
                      "--seconds", "1", "--trace", "0", "--rehearse",
                      "--out", str(tmp_path / "out")], cwd=tmp_path)
    assert proc.returncode != 0
    assert not proc.stdout.strip()


def test_rehearse_multi_process_cell(tmp_path):
    """One CPU process per chip, collectives over gloo: the held four-chip
    cell."""
    name = "ior.l3.rank4.chips4"
    cell = harness.find_cell(name)
    proc = run_bench(["--workload", name, "--seed", str(2**31 + 9),
                      "--seconds", "2", "--trace", "0", "--rehearse",
                      "--out", str(tmp_path / "out")])
    line = last_line(proc)
    check_line(line, 0, cell)
    assert line["device"]["count"] == cell.chips
