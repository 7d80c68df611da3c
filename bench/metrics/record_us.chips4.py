"""Host time of one traced call in the wrappers and the record path, in
us, where several processes write at once: every process's write loop
less its flushes, summed over the processes, over the calls they all
issued.  That is the time of a call on one process.

``record_us.ior`` is not listed for a cell of several processes: it
divides the loop time of all the processes by the calls of one
(``calls_local``), so at four processes it reads four times the time of
a call."""


def read(ctx):
    loop, flush = ctx["spans"].get("unit"), ctx["spans"].get("flush", [])
    calls = ctx["counters"].get("calls")
    if not loop or not calls:
        return None
    return 1e6 * (sum(loop) - sum(flush)) / calls
