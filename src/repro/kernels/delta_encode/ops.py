from __future__ import annotations

from functools import partial

import jax

from .delta_encode import (
    delta_zigzag_pallas,
    delta_zigzag_varint_pallas,
    uvarint_encode64_pallas,
)


@partial(jax.jit, static_argnames=("block", "interpret"))
def delta_zigzag(ticks, *, block: int = 4096, interpret: bool = False):
    """Flat u32 ticks -> zigzag u32 deltas (matches core.timestamps)."""
    return delta_zigzag_pallas(ticks, block=block, interpret=interpret)


@partial(jax.jit, static_argnames=("block", "interpret"))
def delta_zigzag_varint(ticks, *, block: int = 4096,
                        interpret: bool = False):
    """Fused encode: flat u32 ticks -> (zigzag u32, varint byte counts,
    (5, n) byte planes with continuation bits)."""
    return delta_zigzag_varint_pallas(ticks, block=block,
                                      interpret=interpret)


@partial(jax.jit, static_argnames=("block", "interpret"))
def uvarint_encode64(lo, hi, *, block: int = 4096,
                     interpret: bool = False):
    """u64 values as (lo, hi) u32 planes -> (byte counts, (10, n) byte
    planes) for the host varint scatter."""
    return uvarint_encode64_pallas(lo, hi, block=block, interpret=interpret)

