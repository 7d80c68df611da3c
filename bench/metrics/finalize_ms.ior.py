"""Mean time of one finalize of an IOR job's trace, in ms: patterns and
grammar, the inter-rank reduce, encode (timestamps on the device once a
job's batch is large enough) and the trace's write."""


def read(ctx):
    f = ctx["spans"].get("finalize")
    return 1e3 * sum(f) / len(f) if f else None
