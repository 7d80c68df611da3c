"""The four-process cell ``ior.l3.rank4.chips4``: its traced run
rehearsed over four CPU processes (gloo), and its reader of the record
path's time per call."""

import json
import os
import subprocess
import sys

import pytest

from bench import harness

NAME = "ior.l3.rank4.chips4"


def test_traced_rehearsal_reads_flush_and_record(tmp_path):
    cell = harness.find_cell(NAME)
    assert cell.chips == 4
    # the configuration states the layout that the mix runs
    assert (cell.config["ranks"], cell.config["comm"]) == (
        cell.mix["ranks"], cell.mix["comm"])
    listed = {m["name"] for m in cell.per_layer()}
    assert {"flush_ms.ior", "record_us.chips4",
            "collective_ms.chips4"} <= listed
    assert "record_us.ior" not in listed
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", NAME,
         "--seed", str(2**31 + 11), "--seconds", "2", "--trace", "1",
         "--rehearse", "--out", str(tmp_path / "out")],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["checks"]["records_mismatched"]["value"] == 0
    assert line["device"]["count"] == 4
    assert set(line["metrics"]) <= listed
    for name in ("flush_ms.ior", "record_us.chips4"):
        assert line["metrics"][name]["value"] > 0
    # every process ran its window with the exchanges it had built
    assert "compilations in the window [0, 0, 0, 0]" in proc.stderr


@pytest.mark.parametrize("processes", [1, 2, 4])
def test_record_us_reads_one_process_call(processes):
    """Each process runs 3 units of 1.0 s, 0.1 s of it a flush, over
    18,000 calls: 50 us a call, whatever the number of processes."""
    ctx = {"spans": {"unit": [1.0] * 3 * processes,
                     "flush": [0.1] * 3 * processes},
           "counters": {"calls": 54_000 * processes, "calls_local": 54_000}}
    got = harness.read_metric("record_us.chips4", ctx)
    assert got == pytest.approx(50.0)
    # the one-rank reader divides by one process's calls
    assert harness.read_metric("record_us.ior", ctx) == \
        pytest.approx(50.0 * processes)


def test_record_us_finds_nothing_without_a_loop():
    assert harness.read_metric(
        "record_us.chips4", {"spans": {}, "counters": {"calls": 10}}) is None
    assert harness.read_metric(
        "record_us.chips4",
        {"spans": {"unit": [1.0]}, "counters": {}}) is None
