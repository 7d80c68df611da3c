"""Device time of the collective operations (the ppermute exchange and
the all-gathers of the flush protocol) per flush, averaged over the
chips, from the profiler's trace, in ms."""


def read(ctx):
    d, n = ctx["devtrace"], ctx["counters"].get("flushes")
    if not d or not n:
        return None
    return 1e3 * d["collective_s"] / n
