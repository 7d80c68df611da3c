"""The one-rank mdtest-hard cell ``mdtest.hard.rank1``: its traced run
rehearsed on the CPU at the configuration's small size, a run with one
record altered, the control, and its metric readers."""

import json

import pytest

from bench import control, harness
from bench import run as bench_run
from bench.kinds import ior

NAME = "mdtest.hard.rank1"
METRICS = ("record_us.mdtest", "finalize_ms.mdtest", "cst_entries.mdtest")


def rehearse(capsys, out, trace=0):
    rc = bench_run.main(["--workload", NAME, "--seed", str(2**31 + 13),
                         "--seconds", "1", "--trace", str(trace),
                         "--rehearse", "--out", str(out)])
    assert rc == 0
    captured = capsys.readouterr()
    return json.loads(captured.out.strip().splitlines()[-1]), captured.err


def test_configuration_states_what_the_mix_runs():
    cell = harness.find_cell(NAME)
    assert cell.chips == 1
    assert cell.config["kind"] == "mdtest"
    assert cell.config["ranks"] == cell.mix["ranks"] == 1
    assert cell.config["files_per_rank"] == cell.mix["files_per_job"]
    assert cell.config["rehearse"]["files_per_rank"] == \
        cell.mix["rehearse"]["files_per_job"]
    assert {m["name"] for m in cell.per_layer()} == set(METRICS)
    assert {m["name"] for m in cell.end_to_end()} == {
        "io_calls_per_s", "trace_bytes_per_record", "setup_s"}


def test_traced_rehearsal_reads_every_metric(capsys, tmp_path):
    line, err = rehearse(capsys, tmp_path, trace=1)
    assert line["correct"] is True
    assert line["checks"]["records_mismatched"]["value"] == 0
    files = harness.find_cell(NAME).config["rehearse"]["files_per_rank"]
    assert line["attempted"] % (8 * files + 2) == 0
    assert set(line["metrics"]) == set(METRICS)
    assert all(m["value"] > 0 for m in line["metrics"].values())
    # a job's paths are patterns: its signatures do not grow with files
    assert line["metrics"]["cst_entries.mdtest"]["value"] <= 64
    assert "compilations in the window 0" in err


def test_one_altered_record_is_not_correct(capsys, monkeypatch, tmp_path):
    """The 777th call of each job has its last argument recorded altered:
    a path one character longer, a number one more."""
    from repro.core.recorder import Recorder
    files = harness.find_cell(NAME).config["rehearse"]["files_per_rank"]
    real = Recorder.record
    seen = [0]

    def record(self, func_id, raw_args, ret, depth, t0, t1):
        seen[0] += 1
        if seen[0] % (8 * files + 2) == 777:
            last = raw_args[-1]
            raw_args = raw_args[:-1] + (
                last + "x" if isinstance(last, str) else last + 1,)
        return real(self, func_id, raw_args, ret, depth, t0, t1)

    monkeypatch.setattr(Recorder, "record", record)
    line, _ = rehearse(capsys, tmp_path)
    assert line["correct"] is False
    assert line["checks"]["records_mismatched"]["value"] > 0


def test_control_is_not_correct(capsys, monkeypatch, tmp_path):
    """Timestamps off breaks the lossless guarantee: every call counts."""
    monkeypatch.setattr(ior, "recorder_config", ior.recorder_config)
    control.switch_on()
    line, _ = rehearse(capsys, tmp_path)
    assert line["correct"] is False
    assert line["checks"]["records_mismatched"]["value"] > 0


@pytest.mark.parametrize("ctx", [
    {"spans": {}, "counters": {"calls_local": 10}},
    {"spans": {"unit": [1.0]}, "counters": {}},
])
def test_readers_find_nothing_without_a_window(ctx):
    for name in METRICS:
        assert harness.read_metric(name, ctx) is None


def test_readers_read_the_window():
    ctx = {"spans": {"unit": [2.0, 2.0], "finalize": [0.01, 0.03]},
           "counters": {"calls_local": 100_000, "cst_entries": 15.0}}
    assert harness.read_metric("record_us.mdtest", ctx) == pytest.approx(40.0)
    assert harness.read_metric("finalize_ms.mdtest", ctx) == \
        pytest.approx(20.0)
    assert harness.read_metric("cst_entries.mdtest", ctx) == 15.0
