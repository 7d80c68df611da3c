"""The profiler-trace reduction (``bench/devtrace.py``): on hand-made
intervals, and on a small trace recorded here on the CPU, where XLA's
operations run on host threads instead of a device plane."""

import time

import pytest

from bench import devtrace, harness

MS = 1e6  # ns


def test_union_busy_and_gaps():
    iv = [(0, 10), (5, 20), (30, 40), (39, 41), (50, 60)]
    assert devtrace.union(iv) == [(0, 20), (30, 41), (50, 60)]
    assert devtrace.covered_ns(iv, 10, 55) == 10 + 11 + 5
    assert devtrace.idle_gaps(iv, 10, 70) == [(20, 30), (41, 50), (60, 70)]
    assert devtrace.idle_gaps([], 0, 5) == [(0, 5)]


def test_summary_on_two_devices():
    window = [("bench.window", 0, 100 * MS)]
    spans = window + [("bench.flush", 40 * MS, 70 * MS),
                      ("bench.record", 45 * MS, 55 * MS)]
    ops = {"/device:TPU:0": [("fusion.1", 0, 40 * MS),
                             ("collective-permute.3", 70 * MS, 80 * MS)],
           "/device:TPU:1": [("fusion.2", 0, 60 * MS)]}
    s = devtrace.summarize(ops, spans)
    assert s["window_s"] == pytest.approx(0.1)
    assert s["busy_s"] == pytest.approx((0.05 + 0.06) / 2)
    assert s["collective_s"] == pytest.approx(0.01 / 2)
    kinds = dict(s["breakdown"]["device_ops"])
    assert kinds == pytest.approx({"fusion": 0.05, "collective-permute": 0.005})
    gaps = s["breakdown"]["idle_gaps"]
    # device 0 idles 40-70 ms (middle 55 ms: inside the record span) and
    # 80-100 ms; device 1 idles 60-100 ms (middle 80 ms: no span)
    assert gaps[0] == ["host:other", pytest.approx(0.04)]
    assert ["bench.record", pytest.approx(0.03)] in gaps


def test_nothing_to_read():
    spans = [("bench.window", 0, 10 * MS)]
    assert devtrace.summarize({"/device:TPU:0": []}, spans) is None
    assert devtrace.summarize({}, spans) is None
    assert devtrace.summarize({"/device:TPU:0": [("f", 0, 1)]}, []) is None


def _cpu_ops(pd):
    """XLA's operations of a CPU trace: events of the PjRt CPU client's
    threads that are not thread-pool bookkeeping."""
    evs = []
    for p in pd.planes:
        if p.name == devtrace.HOST_PLANE:
            for ln in p.lines:
                if ln.name.startswith("tf_XLA"):
                    evs.extend(e for e in devtrace._events(ln)
                               if not e[0].startswith("Threadpool")
                               and not e[0].startswith("Slinky")
                               and e[2] > e[1])
    return {"cpu": evs}


def test_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((384, 384), jnp.float32)
    f(x).block_until_ready()
    spans = harness.Spans(annotate=True)
    t0 = time.perf_counter()
    with harness.profiled(True, tmp_path / "prof") as prof:
        for _ in range(4):
            with spans.span("work"):
                f(x).block_until_ready()
            with spans.span("sleep"):
                time.sleep(0.02)
    host_s = time.perf_counter() - t0
    pd = ProfileData.from_file(str(prof["xplane"]))
    hs = devtrace.host_spans(pd)
    names = [n for n, _, _ in hs]
    assert names.count("bench.window") == 1
    assert names.count("bench.work") == 4 and names.count("bench.sleep") == 4
    sleeps = sorted((e - s) * 1e-9 for n, s, e in hs if n == "bench.sleep")
    assert sleeps == pytest.approx(sorted(spans.durations["sleep"]), abs=2e-3)
    s = devtrace.summarize(_cpu_ops(pd), hs)
    assert s is not None
    assert 0 < s["window_s"] <= host_s
    assert 0 < s["busy_s"] < s["window_s"]
    assert any(k.startswith("dot") for k, _ in s["breakdown"]["device_ops"])
    # the longest idle gaps are the sleeps, named by their span
    assert [n for n, _ in s["breakdown"]["idle_gaps"][:4]] == \
        ["bench.sleep"] * 4
    dots = devtrace.summarize(_cpu_ops(pd), hs,
                              is_collective=lambda n: n.startswith("dot"))
    assert 0 < dots["collective_s"] <= dots["busy_s"]


def test_merge_averages_processes():
    a = {"busy_s": 1.0, "window_s": 10.0, "collective_s": 0.2, "devices": 1,
         "breakdown": {"device_ops": [["fusion", 0.8]],
                       "idle_gaps": [["bench.flush", 3.0]]}}
    b = dict(a, busy_s=3.0, window_s=11.0, collective_s=0.4)
    m = devtrace.merge([a, None, b])
    assert (m["busy_s"], m["window_s"], m["collective_s"], m["devices"]) == \
        (2.0, 11.0, pytest.approx(0.3), 2)
    assert m["breakdown"]["device_ops"] == [["fusion", 0.8]]
    assert devtrace.merge([None]) is None


def test_op_kinds_and_self_time():
    assert devtrace.op_kind("%fusion.728 = bf16[2,512]{1,0} fusion(x)") == \
        "fusion"
    assert devtrace.op_kind("%while.3 = (s32[]) while(t), body=b") == "while"
    assert devtrace.op_kind("copy-start.35") == "copy-start"
    evs = [("%while.1 = w", 0, 100), ("%fusion.2 = f", 10, 30),
           ("%fusion.3 = f", 40, 90), ("%copy.4 = c", 50, 60)]
    got = dict((n, t) for n, t in devtrace.self_times(evs))
    assert got == {"%while.1 = w": 30, "%fusion.2 = f": 20,
                   "%fusion.3 = f": 40, "%copy.4 = c": 10}
    s = devtrace.summarize({"/device:TPU:0": evs},
                           [("bench.window", 0, 200)])
    assert dict(s["breakdown"]["device_ops"]) == pytest.approx(
        {"fusion": 60e-9, "while": 30e-9, "copy": 10e-9})
