"""Mean time of one finalize of an mdtest job's trace, in ms: patterns
and grammar, the rank's state, encode (a job's timestamps on the device)
and the trace's write."""


def read(ctx):
    f = ctx["spans"].get("finalize")
    return 1e3 * sum(f) / len(f) if f else None
