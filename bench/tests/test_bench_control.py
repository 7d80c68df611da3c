"""The controls, at sizes a test run holds: each must come out not
correct where the program comes out correct.

Training: the reference put in the program's place, its matrix products'
operands rounded to float8_e4m3 (the precision below the configuration's
bfloat16), against the float32 reference.  IOR: the Recorder with its own
switch for timestamps off, which breaks the configuration's guarantee of
a lossless trace."""

import json

from bench import compare, control, harness
from bench import run as bench_run
from bench.kinds import ior, train


def test_training_control_fails_a_limit(tmp_path):
    cell = harness.find_cell("train.qwen1_5_0_5b.live")
    cell.root = tmp_path
    conf, mix = train.sizes(cell, rehearse=True)
    limits, opt = conf["limits"], conf["optimizer"]
    for seed in (3000000001, 3000000002):
        tt = train.TracedTrainer(cell, seed, conf, mix, harness.Spans())
        prog = tt.checked_steps()
        tt.free()
        batches = tt.ring[:mix["checked_steps"]]
        ref = tt.ref.train_steps(tt.key, batches, conf, opt)
        ctl = tt.ref.train_steps(tt.key, batches, conf, opt, matmul="fp8")
        sound = compare.train_numbers(prog, ref)
        control = compare.train_numbers(ctl, ref)
        assert all(v <= limits[k] for k, v in sound.items()), sound
        assert any(v > limits[k] for k, v in control.items()), control


def test_ior_control_fails(capsys, monkeypatch, tmp_path):
    # restored when the test ends: the switch stays out of other tests
    monkeypatch.setattr(ior, "recorder_config", ior.recorder_config)
    control.switch_on()
    assert bench_run.main(["--workload", "ior.l3.rank1", "--seed", "9",
                           "--seconds", "1", "--trace", "0",
                           "--rehearse", "--out", str(tmp_path)]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["checks"]["records_mismatched"]["value"] > 0
