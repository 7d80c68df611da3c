#!/usr/bin/env python3
"""Run one cell of the benchmark and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name in ``BENCHMARK.json``; its configuration's
``kind`` (``bench/kinds/``) sets up, warms every shape the window
uses, measures for ``--seconds`` and checks what the window produced
against the configuration's plain reference.  With ``--trace 0`` the
result carries the cell's end-to-end metrics; with ``--trace 1`` the
window runs under JAX's profiler and the result carries the per-layer
metrics (``bench/metrics/<name>.py``), the device's busy time and the
breakdown.  The last line of standard output is one JSON object; the
numbers compared, each beside its limit, are the last lines of standard
error.  Without a TPU (or with fewer chips than the cell asks for) the
run exits non-zero and prints no result; ``--rehearse`` runs the same
path at the configuration's small sizes on any backend, for the tests.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="small sizes on any backend (tests only)")
    ap.add_argument("--out", default=None,
                    help="outputs under this directory instead of "
                         "<checkout>/.bench_out (tests only)")
    ap.add_argument("--worker", type=int, default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--port", type=int, default=None, help=argparse.SUPPRESS)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    # the benchmark is the package ``bench`` of the checkout; the script's
    # own directory leaves the path so its modules do not shadow others
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if Path(p or ".").resolve() != ROOT / "bench"]
    import repro  # noqa: F401  (the system under test; absent -> no run)
    from bench import harness
    cell = harness.find_cell(args.workload)
    if args.out:
        cell.root = Path(args.out).resolve()
    kind = importlib.import_module(f"bench.kinds.{cell.config['kind']}")
    if args.worker is not None:
        return kind.worker(cell, args)
    res = kind.run(cell, args, T_START)
    if res is None:
        return 1
    if args.trace:
        ctx = {"cell": cell, "spans": res.spans.durations,
               "counters": res.counters, "devtrace": res.devtrace,
               "device": res.device}
        metrics = {}
        for m in cell.per_layer():
            v = harness.read_metric(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = (v, m["unit"])
        dt = res.devtrace or {}
        device = dict(res.device, busy_s=dt.get("busy_s", 0.0),
                      window_s=dt.get("window_s",
                                      res.counters.get("window_s", 0.0)))
        breakdown = dt.get("breakdown")
    else:
        metrics = {m["name"]: (res.e2e[m["name"]], m["unit"])
                   for m in cell.end_to_end()}
        device, breakdown = res.device, None
    harness.emit(checks=res.checks, attempted=res.attempted,
                 failed=res.failed, metrics=metrics, device=device,
                 breakdown=breakdown)
    return 0


if __name__ == "__main__":
    sys.exit(main())
