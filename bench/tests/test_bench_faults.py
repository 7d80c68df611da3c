"""A rehearsed run with the timed path broken underneath comes out not
correct: once for each fault a cell can have.  The harness's look for a
chip is skipped (``--rehearse``); the rest of the run is the real one."""

import json

import pytest

from bench import run as bench_run


def rehearse(capsys, workload, out):
    rc = bench_run.main(["--workload", workload, "--seed", "4000000077",
                         "--seconds", "1", "--trace", "0", "--rehearse",
                         "--out", str(out)])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def frozen_step(monkeypatch):
    """The train step returns the state it was given."""
    from repro.train import loop
    real = loop.make_train_step

    def make(*args, **kwargs):
        step = real(*args, **kwargs)

        def frozen(state, batch):
            return state, step(state, batch)[1]
        return frozen

    monkeypatch.setattr(loop, "make_train_step", make)


def half_batch(monkeypatch):
    """The job trains on the first half of each batch's rows, the loss the
    mean over those."""
    from repro.train import loop
    real = loop.Trainer.__init__

    def init(self, cfg, tcfg, ocfg=None, data=None, fault_hook=None):
        def half(step):
            return {k: v[: v.shape[0] // 2] for k, v in data(step).items()}
        real(self, cfg, tcfg, ocfg, data=half, fault_hook=fault_hook)

    monkeypatch.setattr(loop.Trainer, "__init__", init)


def altered_record(monkeypatch):
    """A call in every 997 has its last argument recorded off by one."""
    from repro.core.recorder import Recorder
    real = Recorder.record
    seen = [0]

    def record(self, func_id, raw_args, ret, depth, t0, t1):
        seen[0] += 1
        if seen[0] % 997 == 5:
            raw_args = raw_args[:-1] + (raw_args[-1] + 1,)
        return real(self, func_id, raw_args, ret, depth, t0, t1)

    monkeypatch.setattr(Recorder, "record", record)


def dropped_half(monkeypatch):
    """Every other call is not recorded."""
    from repro.core.recorder import Recorder
    real = Recorder.record
    seen = [0]

    def record(self, *args):
        seen[0] += 1
        if seen[0] % 2:
            return real(self, *args)

    monkeypatch.setattr(Recorder, "record", record)


FAULTS = {
    "train.qwen1_5_0_5b.live": [frozen_step, half_batch, altered_record],
    "ior.l3.rank1": [altered_record, dropped_half],
}


@pytest.mark.parametrize("workload,fault", [
    (w, f) for w, faults in FAULTS.items() for f in faults],
    ids=lambda x: getattr(x, "__name__", x))
def test_fault_is_not_correct(capsys, monkeypatch, tmp_path, workload,
                              fault):
    fault(monkeypatch)
    line = rehearse(capsys, workload, tmp_path)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


@pytest.mark.parametrize("workload", sorted(FAULTS))
def test_sound_run_is_correct(capsys, tmp_path, workload):
    assert rehearse(capsys, workload, tmp_path)["correct"] is True
