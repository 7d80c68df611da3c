"""Host time of one traced call of an mdtest job, in us: the jobs' calls
(the wrappers, the record path and the file system's own call) over the
calls this process issued."""


def read(ctx):
    jobs = ctx["spans"].get("unit")
    calls = ctx["counters"].get("calls_local")
    if not jobs or not calls:
        return None
    return 1e6 * sum(jobs) / calls
