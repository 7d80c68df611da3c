"""Host time of one traced call in the wrappers and the record path, in
us: the window's write loop less its flushes, over the calls this
process issued."""


def read(ctx):
    loop, flush = ctx["spans"].get("unit"), ctx["spans"].get("flush", [])
    calls = ctx["counters"].get("calls_local")
    if not loop or not calls:
        return None
    return 1e6 * (sum(loop) - sum(flush)) / calls
