"""Reference inter-process merge: the plain gather-at-root pass.

Every rank's CST and CFG sit on one process, and the merge runs over all
ranks at once (paper §3.3):

  1. decode every rank's CST and group entries by (masked signature,
     occurrence index),
  2. fit each group present on every rank with a scalar loop over the
     ranks: a component is constant, rank-linear (``RankPattern``) or
     neither,
  3. intern the rewritten signatures rank by rank, terminal by terminal,
     then remap each rank's CFG and keep one copy of each distinct CFG.

O(ranks x calls) work on one process.  The Recorder's tree reduction
(``repro.core.interprocess``) must write the same bytes; the tests compare
the two.  One documented divergence: this pass coerces offset leaves that
are ``int`` subclasses (``bool``) through ``int()`` and fits them, while
the tree fits plain ``int`` leaves only.  The record path coerces offsets
to ``int`` before encoding, so real traces never hold such leaves.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

from repro.core.encoding import IterPattern, RankPattern, encode_signature
from repro.core.interprocess import (CfgResult, MergeResult, _MASK,
                                     _fit_has_rank_pattern, _masked_bytes,
                                     _split_offsets, dedupe_cfgs)
from repro.core.sequitur import remap_grammar
from repro.core.specs import FunctionRegistry


def fit_component(values: Sequence[int]) -> Optional[Any]:
    """Fit ``v_r = r*a + b`` over ranks; int if constant, RankPattern if
    linear with a != 0, None if not linear."""
    v0 = values[0]
    if all(v == v0 for v in values):
        return int(v0)
    if len(values) < 2:
        return None
    a = values[1] - values[0]
    if a == 0:
        return None
    for r, v in enumerate(values):
        if v != v0 + r * a:
            return None
    return RankPattern(a, v0)


def _fit_offsets(per_rank: List[tuple]) -> Optional[tuple]:
    """Fit each offset slot across ranks.  ``per_rank[r]`` is the tuple of
    offset values of rank r for this occurrence.  Values are ints or
    IterPattern with int components."""
    n_slots = len(per_rank[0])
    if any(len(v) != n_slots for v in per_rank):
        return None
    out = []
    for s in range(n_slots):
        col = [pr[s] for pr in per_rank]
        if all(isinstance(v, int) for v in col):
            fit = fit_component(col)
            if fit is None:
                return None
            out.append(fit)
        elif all(isinstance(v, IterPattern) for v in col):
            a_fit = fit_component([int(v.a) for v in col])
            b_fit = fit_component([int(v.b) for v in col])
            if a_fit is None or b_fit is None:
                return None
            out.append(IterPattern(a_fit, b_fit))
        else:
            return None  # mixed kinds across ranks: no merge
    return tuple(out)


def merge_csts(rank_csts: List[List[bytes]], registry: FunctionRegistry,
               inter_patterns: bool = True) -> MergeResult:
    """Merge per-rank CSTs into one (paper §3.3.1)."""
    nranks = len(rank_csts)
    # -- pass 1: decode + group by (masked signature, occurrence index) ------
    decoded: List[List[tuple]] = []        # [rank][t] = (masked_key, parts)
    groups: Dict[Tuple[bytes, int], Dict[int, tuple]] = {}
    group_order: List[Tuple[bytes, int]] = []
    for r, cst in enumerate(rank_csts):
        occ_counter: Dict[bytes, int] = {}
        rank_rows = []
        for t, sig in enumerate(cst):
            (func_id, tid, depth, masked, ret, offsets,
             ret_masked) = _split_offsets(registry, sig)
            mkey = _masked_bytes(func_id, tid, depth, masked, ret, ret_masked)
            j = occ_counter.get(mkey, 0)
            occ_counter[mkey] = j + 1
            gkey = (mkey, j)
            g = groups.get(gkey)
            if g is None:
                g = {}
                groups[gkey] = g
                group_order.append(gkey)
            g[r] = (t, offsets)
            rank_rows.append((gkey, (func_id, tid, depth, masked, ret,
                                     offsets, ret_masked)))
        decoded.append(rank_rows)

    # -- pass 2: fit rank-linear groups --------------------------------------
    merged_offsets: Dict[Tuple[bytes, int], tuple] = {}
    n_rank_patterns = 0
    if inter_patterns and nranks > 1:
        for gkey in group_order:
            g = groups[gkey]
            if len(g) != nranks:
                continue  # not present on every rank: no fit (paper: collective I/O case)
            per_rank = [g[r][1] for r in range(nranks)]
            if not per_rank[0]:
                continue  # no offset args: identical signatures merge by interning
            fit = _fit_offsets(per_rank)
            if fit is not None:
                merged_offsets[gkey] = fit
                if _fit_has_rank_pattern(fit):
                    n_rank_patterns += 1

    # -- pass 3: build merged table + per-rank remaps ------------------------
    table: Dict[bytes, int] = {}
    merged_entries: List[bytes] = []
    remaps: List[Dict[int, int]] = [dict() for _ in range(nranks)]

    def intern(sig: bytes) -> int:
        t = table.get(sig)
        if t is None:
            t = len(merged_entries)
            table[sig] = t
            merged_entries.append(sig)
        return t

    for r, rank_rows in enumerate(decoded):
        for old_t, (gkey, parts) in enumerate(rank_rows):
            func_id, tid, depth, masked, ret, offsets, ret_masked = parts
            fit = merged_offsets.get(gkey)
            use_offsets = fit if fit is not None else offsets
            it = iter(use_offsets)
            args = tuple(next(it) if v is _MASK else v for v in masked)
            if ret_masked:
                ret = next(it)
            sig = encode_signature(func_id, tid, depth, args, ret)
            remaps[r][old_t] = intern(sig)

    return MergeResult(merged_entries=merged_entries, remaps=remaps,
                       n_rank_patterns=n_rank_patterns)


def finalize_ranks(rank_csts: List[List[bytes]], rank_cfgs: List[bytes],
                   registry: FunctionRegistry, inter_patterns: bool = True
                   ) -> Tuple[MergeResult, CfgResult]:
    """The whole root-side merge: merge CSTs, remap CFGs, dedupe."""
    merge = merge_csts(rank_csts, registry, inter_patterns=inter_patterns)
    remapped = [remap_grammar(cfg, merge.remaps[r])
                for r, cfg in enumerate(rank_cfgs)]
    return merge, dedupe_cfgs(remapped)
