"""Plain reference of ``ior_listing3``: the calls each rank makes, as a
lossless trace must give them back.

Rank ``r`` of ``nranks`` writes transfer ``i`` at
``r * transfer_bytes + i * nranks * transfer_bytes``, ``bytes_written_per_call``
bytes each; a rank that ends its stream with ``fsync`` and ``close``
(the traced facades do) has those two calls last.  A record is
``(function, arguments, return value)`` with the file handle given as the
trace's unified id: the first and only file of each rank is handle 0.
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, Tuple

Record = Tuple[str, tuple, Any]


def offset(cfg: Dict[str, Any], rank: int, nranks: int, i: int) -> int:
    t = cfg["transfer_bytes"]
    return rank * t + i * nranks * t


def records(cfg: Dict[str, Any], rank: int, nranks: int, n_calls: int,
            closed: bool) -> Iterator[Record]:
    n = cfg["bytes_written_per_call"]
    for i in range(n_calls):
        yield ("pwrite", (0, n, offset(cfg, rank, nranks, i)), n)
    if closed:
        yield ("fsync", (0,), None)
        yield ("close", (0,), None)
