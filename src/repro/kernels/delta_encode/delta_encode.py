"""Timestamp delta+zigzag Pallas kernel (Recorder Section 2.2.1 hot loop).

The tracing pipeline's only arithmetic-dense stage: millions of u32 ticks
-> first-order delta -> zigzag, before zlib.  On a real pod the staging
buffers can be encoded on-device before DMA to host.  The stream is laid
out in lane-dense (rows, 128) tiles (``kernels.stream_tiles``); each grid
step gets the element before its block through scalar prefetch, so the
cross-block delta is exact and blocks are independent.

Arithmetic is 32-bit two's-complement: deltas wrap mod 2^32, which matches
the reference encoder bit-for-bit whenever |delta| < 2^31 (tick deltas are
microseconds between adjacent events) and still roundtrips losslessly
through the mod-2^32 decoder otherwise.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..stream_tiles import (
    LANES,
    PARALLEL,
    block_carries,
    block_rows,
    from_tiles,
    prev_elements,
    to_tiles,
)


def _zigzag_delta(x_ref, carry_ref):
    x = x_ref[...]                                  # (R, 128) int32
    delta = x - prev_elements(x, carry_ref[pl.program_id(0)])  # wraps
    return (delta << 1) ^ (delta >> 31)


def _delta_kernel(carry_ref, x_ref, o_ref):
    o_ref[...] = _zigzag_delta(x_ref, carry_ref)


def _stream_call(kernel, ticks, block, n_out_planes, interpret):
    """Run a delta kernel over flat u32 ``ticks`` in (rows, 128) tiles;
    the first element's predecessor is 0, so it is kept as is."""
    n = ticks.shape[0]
    rows = block_rows(n, block)
    tiles, nb = to_tiles(jax.lax.bitcast_convert_type(ticks, jnp.int32),
                         rows, 0)
    tile_spec = pl.BlockSpec((rows, LANES), lambda i, c: (i, 0))
    out_specs = [tile_spec]
    out_shape = [jax.ShapeDtypeStruct(tiles.shape, jnp.int32)]
    if n_out_planes:
        out_specs += [tile_spec, pl.BlockSpec((n_out_planes, rows, LANES),
                                              lambda i, c: (0, i, 0))]
        out_shape += [jax.ShapeDtypeStruct(tiles.shape, jnp.int32),
                      jax.ShapeDtypeStruct((n_out_planes,) + tiles.shape,
                                           jnp.int32)]
    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(nb,), in_specs=[tile_spec],
            out_specs=out_specs),
        out_shape=out_shape,
        compiler_params=PARALLEL,
        interpret=interpret,
    )(block_carries(tiles, rows, 0), tiles)
    return [from_tiles(o, n) for o in outs]


def delta_zigzag_pallas(ticks: jax.Array, *, block: int = 4096,
                        interpret: bool = False) -> jax.Array:
    """ticks: flat u32 array -> zigzag'd u32 deltas (first element kept)."""
    (zz,) = _stream_call(_delta_kernel, ticks, block, 0, interpret)
    return jax.lax.bitcast_convert_type(zz, jnp.uint32)


# ---------------------------------------------------------------------------
# fused delta -> zigzag -> varint (lengths + byte planes)
# ---------------------------------------------------------------------------
#
# The variable-length total output size is data-dependent, so the kernel
# cannot emit the packed stream directly (Pallas output shapes are static).
# Instead it runs the per-element pass of the classic two-pass scheme:
# per-element byte counts plus five "byte planes" (plane j = byte j of
# every element, continuation bit already set).  The host half
# (encode_backend._emit_varint_bytes) does the exclusive-scan offsets and
# masked scatter -- pure vectorized numpy, no per-element Python.


def _varint_planes(zz, len_ref, plane_ref, n_planes):
    """Byte counts and byte planes of u32 values ``zz``; the planes hold
    byte j of every element with its continuation bit set."""
    ln = jnp.ones(zz.shape, jnp.int32)
    for k in range(1, n_planes):
        ln = ln + (zz >= jnp.uint32(1 << (7 * k))).astype(jnp.int32)
    len_ref[...] = ln
    for j in range(n_planes):
        b = (zz >> jnp.uint32(7 * j)) & jnp.uint32(0x7F)
        b = jnp.where(j < ln - 1, b | jnp.uint32(0x80), b)
        plane_ref[j] = b.astype(jnp.int32)


def _delta_varint_kernel(carry_ref, x_ref, zz_ref, len_ref, plane_ref):
    zz = _zigzag_delta(x_ref, carry_ref)
    zz_ref[...] = zz
    _varint_planes(jax.lax.bitcast_convert_type(zz, jnp.uint32), len_ref,
                   plane_ref, 5)


def delta_zigzag_varint_pallas(ticks: jax.Array, *, block: int = 4096,
                               interpret: bool = False):
    """Fused encode: flat u32 ticks -> (zigzag u32, varint byte counts,
    (5, n) byte planes).  A u32 varint is at most 5 bytes."""
    zz, lens, planes = _stream_call(_delta_varint_kernel, ticks, block, 5,
                                    interpret)
    return jax.lax.bitcast_convert_type(zz, jnp.uint32), lens, planes


def _uvarint64_kernel(lo_ref, hi_ref, len_ref, plane_ref):
    # u64 values arrive as (lo, hi) u32 planes -- Pallas TPU has no i64
    # lanes.  Byte j covers bits 7j..7j+6: for 7j < 32 that straddles the
    # lo/hi boundary, above it reads hi alone.  A u64 varint is <= 10 bytes.
    lo = lo_ref[...]
    hi = hi_ref[...]
    ln = jnp.ones(lo.shape, jnp.int32)
    for k in range(1, 10):
        s = 7 * k
        if s < 32:
            ge = (hi > 0) | (lo >= jnp.uint32(1 << s))
        else:
            ge = hi >= jnp.uint32(1 << (s - 32))
        ln = ln + ge.astype(jnp.int32)
    len_ref[...] = ln
    for j in range(10):
        s = 7 * j
        if s == 0:
            b = lo & jnp.uint32(0x7F)
        elif s < 32:
            b = ((lo >> jnp.uint32(s)) | (hi << jnp.uint32(32 - s))) \
                & jnp.uint32(0x7F)
        else:
            b = (hi >> jnp.uint32(s - 32)) & jnp.uint32(0x7F)
        b = jnp.where(j < ln - 1, b | jnp.uint32(0x80), b)
        plane_ref[j] = b.astype(jnp.int32)


def uvarint_encode64_pallas(lo: jax.Array, hi: jax.Array, *,
                            block: int = 4096, interpret: bool = False):
    """u64 values as (lo, hi) u32 arrays -> (byte counts, (10, n) byte
    planes) for the host scatter.  Elementwise; blocks are independent."""
    n = lo.shape[0]
    rows = block_rows(n, block)
    lo_t, nb = to_tiles(lo.astype(jnp.uint32), rows, 0)
    hi_t, _ = to_tiles(hi.astype(jnp.uint32), rows, 0)
    tile_spec = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    lens, planes = pl.pallas_call(
        _uvarint64_kernel,
        grid=(nb,),
        in_specs=[tile_spec, tile_spec],
        out_specs=[tile_spec,
                   pl.BlockSpec((10, rows, LANES), lambda i: (0, i, 0))],
        out_shape=[
            jax.ShapeDtypeStruct(lo_t.shape, jnp.int32),
            jax.ShapeDtypeStruct((10,) + lo_t.shape, jnp.int32),
        ],
        compiler_params=PARALLEL,
        interpret=interpret,
    )(lo_t, hi_t)
    return from_tiles(lens, n), from_tiles(planes, n)
