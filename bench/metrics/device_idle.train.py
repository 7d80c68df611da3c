"""Share of the traced window in which no operation ran on the device,
from the profiler's trace (``bench/devtrace.py``), in %."""


def read(ctx):
    d = ctx["devtrace"]
    if not d or d["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - d["busy_s"] / d["window_s"])
