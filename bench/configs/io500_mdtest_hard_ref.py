"""Plain reference of ``io500_mdtest_hard``: the calls one rank makes in a
job, as a lossless trace must give them back.

The job makes the shared directory, then runs mdtest-hard's four phases
over its files, each phase over every file before the next begins:
create (open with the create flags, write ``write_bytes`` at offset 0,
close), stat (the file's size), read (open read-only, read ``read_bytes``
at offset 0, close) and delete (unlink); then it removes the directory.
A record is ``(function, arguments, return value)``: paths are the
literal strings the job passed, a file handle is the trace's unified id
(every file is handle 0: a closed id is the first one handed out again),
a read's return is the number of bytes read, a call with no return gives
None.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Iterator, List, Tuple

Record = Tuple[str, tuple, Any]


def directory(cfg: Dict[str, Any], out: str) -> str:
    return os.path.join(out, cfg["directory"])


def file_paths(cfg: Dict[str, Any], out: str, rank: int,
               n_files: int) -> List[str]:
    d = directory(cfg, out)
    return [os.path.join(d, cfg["file_name"].format(rank=rank, i=i))
            for i in range(n_files)]


def flags(names: List[str]) -> int:
    out = 0
    for name in names:
        out |= getattr(os, name)
    return out


def records(cfg: Dict[str, Any], out: str, rank: int,
            n_files: int) -> Iterator[Record]:
    d = directory(cfg, out)
    paths = file_paths(cfg, out, rank, n_files)
    w, r = cfg["write_bytes"], cfg["read_bytes"]
    create, read = flags(cfg["create_flags"]), flags(cfg["read_flags"])
    yield ("mkdir", (d, cfg["dir_mode"]), None)
    for p in paths:
        yield ("open", (p, create, cfg["file_mode"]), 0)
        yield ("pwrite", (0, w, 0), w)
        yield ("close", (0,), None)
    for p in paths:
        yield ("stat", (p,), w)
    for p in paths:
        yield ("open", (p, read, 0), 0)
        yield ("pread", (0, r, 0), r)
        yield ("close", (0,), None)
    for p in paths:
        yield ("unlink", (p,), None)
    yield ("rmdir", (d,), None)
