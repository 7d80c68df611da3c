"""The paper's scaling figures as assertions (Figs 4-6, Section 5.1-5.2)."""

import os
import shutil
import sys
import tempfile

import pytest

sys.path.insert(0, "/root/repo")  # benchmarks package lives at repo root
from benchmarks.workloads import flash_rank, ior_rank, run_ranks  # noqa: E402
from repro.core.recorder import RecorderConfig


def _data_dir():
    """The same data directory for every run of one test process: paths'
    decimal fields are part of the trace, so the compared runs must not
    differ in a random directory name."""
    d = os.path.join(tempfile.gettempdir(), f"repro_scaling_{os.getpid()}")
    os.makedirs(d, exist_ok=True)
    return d


def _ior(nprocs, n_calls, **cfg_kw):
    d = _data_dir()
    try:
        return run_ranks(ior_rank, nprocs, RecorderConfig(timestamps=False,
                                                          **cfg_kw),
                         n_calls=n_calls, data_dir=d)
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_fig4_intra_flat_in_calls():
    a = _ior(8, 32)["pattern_bytes"]
    b = _ior(8, 1024)["pattern_bytes"]
    assert abs(b - a) <= 4          # varint exponent growth only


def test_fig4_no_intra_grows():
    a = _ior(8, 32, intra_patterns=False)["pattern_bytes"]
    b = _ior(8, 1024, intra_patterns=False)["pattern_bytes"]
    assert b > 8 * a


def test_fig5_inter_flat_in_ranks():
    a = _ior(4, 128)["pattern_bytes"]
    b = _ior(64, 128)["pattern_bytes"]
    assert abs(b - a) <= 8


def test_fig5_no_inter_linear_in_ranks():
    a = _ior(4, 128, inter_patterns=False)["pattern_bytes"]
    b = _ior(64, 128, inter_patterns=False)["pattern_bytes"]
    assert b > 10 * a


def test_fig5_intra_off_inter_on_constant_but_larger():
    base = _ior(16, 128)["pattern_bytes"]
    a = _ior(4, 128, intra_patterns=False)["pattern_bytes"]
    b = _ior(64, 128, intra_patterns=False)["pattern_bytes"]
    # structurally constant in ranks (only varint widths of the larger
    # offsets grow -- log factor, the paper's "slightly larger" curve)
    assert abs(b - a) <= 0.05 * a
    assert a > base                  # ...and larger than with intra


def _flash(nprocs, iterations, **kw):
    d = _data_dir()
    try:
        return run_ranks(flash_rank, nprocs, RecorderConfig(timestamps=False),
                         data_dir=d, iterations=iterations, **kw)
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_fig6_weak_scaling_constant():
    a = _flash(8, 60)["pattern_bytes"]
    b = _flash(128, 60)["pattern_bytes"]
    assert abs(b - a) <= 16


def test_fig6_iterations_growth_and_rolling_mitigation():
    grow_small = _flash(8, 80)["pattern_bytes"]
    grow_big = _flash(8, 320)["pattern_bytes"]
    roll_small = _flash(8, 80, rolling=True)["pattern_bytes"]
    roll_big = _flash(8, 320, rolling=True)["pattern_bytes"]
    assert grow_big > grow_small + 100   # new filenames -> new signatures
    assert abs(roll_big - roll_small) <= 8


def test_fig7_collective_tracks_aggregators():
    # more aggregators (more nodes) -> more unique grammars, until stripe cap
    small = _flash(64, 40, mode="collective", stripe=8)
    big = _flash(1024, 40, mode="collective", stripe=8)
    assert big["n_unique_cfgs"] >= small["n_unique_cfgs"]


def test_table4_recorder_much_smaller_than_old():
    from repro.core.baselines import RecorderOld, ToolAdapter
    d = tempfile.mkdtemp()
    try:
        rec = run_ranks(flash_rank, 8, RecorderConfig(), data_dir=d,
                        iterations=60)
        old_total = 0
        for r in range(8):
            tool = RecorderOld(r)
            flash_rank(ToolAdapter(tool, rank=r), r, 8, data_dir=d,
                       iterations=60)
            old_total += tool.nbytes
    finally:
        shutil.rmtree(d, ignore_errors=True)
    assert old_total > 5 * rec["total_bytes"]
