"""Call signatures in one mdtest job's trace: the mean of the finalizes'
CST entries over the window's jobs.  A job touches thousands of distinct
paths; the count stays flat in the number of files where paths are
patterns."""


def read(ctx):
    return ctx["counters"].get("cst_entries")
