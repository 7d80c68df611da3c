#!/usr/bin/env python3
"""Run an IOR cell with its control switched on; the benchmark's own runs
never run this.

    python3 bench/control.py --workload <IOR cell> --seed <n> --seconds <s> \
        --trace 0

The control is the program's own switch for timestamps off
(``RecorderConfig.timestamps``), which breaks the configuration's
guarantee of a lossless trace: the run is the cell's own in every other
respect, and has to come out not correct.  (A training cell's control is
the reference in a lower precision: ``bench/readings.py``.)
"""

from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def switch_on() -> None:
    """Every Recorder an IOR cell builds records no timestamps."""
    from bench.kinds import ior
    real = ior.recorder_config

    def no_timestamps(conf, mix, trace_dir):
        rc = real(conf, mix, trace_dir)
        rc.timestamps = False
        return rc

    ior.recorder_config = no_timestamps


def main(argv=None) -> int:
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if Path(p or ".").resolve() != ROOT / "bench"]
    from bench import run
    switch_on()
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
