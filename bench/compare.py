"""The comparisons that decide ``correct``.

Training: each step's loss, the first gradient as the optimizer takes it
and the parameters' change after the checked steps, both by the worst
leaf: the gap between the program's norm of a leaf and the reference's,
over the larger of the reference's norm of that leaf and of the median
leaf.  Leaves whose reference gradient is under a thousandth of the
median leaf's move by round-off alone (a key's bias under softmax) and are
left out of the change.

Traces: the records read back from the trace against the harness's own
per-call log, exactly.
"""

from __future__ import annotations

import bisect
import itertools
import math
import statistics
from typing import Dict, Iterable, Iterator, Optional, Sequence, Set, Tuple

DEAD_LEAF = 1e-3


def loss_gap(prog: Sequence[float], ref: Sequence[float]) -> float:
    """Largest relative gap of the per-step losses (inf on a mismatch in
    length or a non-finite loss)."""
    if len(prog) != len(ref) or not prog:
        return math.inf
    gaps = [abs(p - r) / abs(r) for p, r in zip(prog, ref)]
    return max(gaps) if all(math.isfinite(g) for g in gaps) else math.inf


def live_leaves(ref_grad: Dict[str, float]) -> Set[str]:
    med = statistics.median(ref_grad.values())
    return {k for k, v in ref_grad.items() if v >= DEAD_LEAF * med}


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              keep: Optional[Iterable[str]] = None) -> Dict[str, float]:
    """Per leaf: the gap between the two norms over the larger of the
    reference's norm of the leaf and of the median leaf."""
    keys = sorted(set(ref) if keep is None else set(keep))
    if not keys:
        return {}
    med = statistics.median(ref[k] for k in keys)
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med) if k in prog
            else math.inf for k in keys}


def worst_leaf(prog: Dict[str, float], ref: Dict[str, float],
               keep: Optional[Iterable[str]] = None) -> float:
    gaps = leaf_gaps(prog, ref, keep).values()
    if not gaps or not all(math.isfinite(g) for g in gaps):
        return math.inf
    return max(gaps)


def train_numbers(prog: Dict, ref: Dict) -> Dict[str, float]:
    """``prog``/``ref``: {"loss": [...], "grad": {leaf: norm},
    "delta": {leaf: norm}}; ``ref`` also has "grad_raw"."""
    return {
        "loss": loss_gap(prog["loss"], ref["loss"]),
        "grad_leaf": worst_leaf(prog["grad"], ref["grad"]),
        "delta_leaf": worst_leaf(prog["delta"], ref["delta"],
                                 live_leaves(ref["grad_raw"])),
    }


class TickMatcher:
    """Counts records whose (entry, exit) ticks are not found, in order,
    among the ticks the clock returned.  The clock may be read for other
    purposes too, so the records' ticks are matched as a subsequence of
    the clock's log, each record's exit after its entry.  The clock is
    monotonic, so the log is sorted and each look-up is a binary search:
    a trace whose ticks do not match costs no more than one that does."""

    def __init__(self, log: Sequence[int]) -> None:
        self.log, self.pos, self.bad = log, 0, 0

    def feed(self, t0: Optional[int], t1: Optional[int]) -> None:
        log, n = self.log, len(self.log)
        if t0 is not None and t1 is not None:   # a record without ticks
            j = bisect.bisect_left(log, t0, self.pos)
            if j < n and log[j] == t0:
                k = bisect.bisect_left(log, t1, j + 1)
                if k < n and log[k] == t1:
                    self.pos = k + 1
                    return
        self.bad += 1


_END = "<nothing>"


def records(got: Iterable, want: Iterable) -> Tuple[int, int, Optional[str]]:
    """Compare two record streams position by position: returns the
    positions that differ (a missing or extra record counts), the records
    got, and the first difference."""
    bad, n, first = 0, 0, None
    for i, (a, b) in enumerate(itertools.zip_longest(got, want,
                                                     fillvalue=_END)):
        n += a is not _END
        if a != b:
            bad += 1
            if first is None:
                first = f"record {i}: trace has {a}, reference has {b}"
    return bad, n, first


def plain(recs: Iterable, ticks: TickMatcher) -> Iterator[Tuple]:
    """Read-back records as (function, arguments, return value), handles
    as their unified ids; feeds each record's ticks to ``ticks``."""
    for r in recs:
        ticks.feed(r.t_entry, r.t_exit)
        yield (r.func, tuple(getattr(a, "id", a) for a in r.args), r.ret)
