"""Symbol-stream statistics kernels (Sequitur / TraceView, ROADMAP dir. 2+4).

Three blocked passes over expanded symbol streams:

- ``row_boundaries``: row-change mask of an (n, k) matrix, the shared core
  of ``patterns.arith_segments`` (over row diffs: a new arithmetic run
  starts where the diff row changes) and ``Sequitur.push_stream`` RLE
  pre-tokenization (over the raw terminal column: run starts).
- ``histogram``: terminal occurrence counts via a one-hot compare against
  one tile of bins at a time, accumulated across stream blocks (the inner
  grid axis is sequential, so ``o_ref[...] +=`` across blocks is
  well-defined).
- ``digram_codes``: directly-follows pair codes ``prev * T + cur``; the
  host bincounts the codes into the digram histogram that seeds the DFG
  analyses.

Streams are laid out in lane-dense (rows, 128) tiles and each block gets
the element before it through scalar prefetch (``kernels.stream_tiles``),
so cross-block comparisons are exact.

All int32: symbol ids and diffs fit comfortably (callers guard and fall
back to numpy otherwise).
"""

from __future__ import annotations

from functools import partial

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
    is_stream_start,
    prev_elements,
    to_tiles,
)

# bins per histogram grid step: at the default block of 4096 elements the
# (HIST_BIN_TILE, rows, 128) one-hot takes 1 MiB of VMEM, whatever the
# terminal count
HIST_BIN_TILE = 64


def _boundary_kernel(carry_ref, x_ref, o_ref, *, k: int):
    i = pl.program_id(0)
    nb = pl.num_programs(0)
    x = x_ref[0]
    change = x != prev_elements(x, carry_ref[i])
    for j in range(1, k):                            # one column at a time
        x = x_ref[j]
        change = change | (x != prev_elements(x, carry_ref[j * nb + i]))
    o_ref[...] = (change | is_stream_start(x.shape, i)).astype(jnp.int32)


def row_boundaries_pallas(V: jax.Array, *, block: int = 4096,
                          interpret: bool = False) -> jax.Array:
    """(n, k) int32 matrix -> int32 mask, 1 where row i != row i-1
    (position 0 always 1).  Each column is streamed as its own flat
    (rows, 128) tile stack."""
    n, k = V.shape
    rows = block_rows(n, block)
    cols, nb = to_tiles(V.astype(jnp.int32).T, rows, 0)   # (k, nb*rows, 128)
    out = pl.pallas_call(
        partial(_boundary_kernel, k=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(nb,),
            in_specs=[pl.BlockSpec((k, rows, LANES), lambda i, c: (0, i, 0))],
            out_specs=pl.BlockSpec((rows, LANES), lambda i, c: (i, 0))),
        out_shape=jax.ShapeDtypeStruct(cols.shape[1:], jnp.int32),
        compiler_params=PARALLEL,
        interpret=interpret,
    )(block_carries(cols, rows, 0), cols)
    return from_tiles(out, n)


def _hist_kernel(x_ref, o_ref):
    t = pl.program_id(0)

    @pl.when(pl.program_id(1) == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]                                   # (rows, 128) int32
    bins = HIST_BIN_TILE * t + jax.lax.broadcasted_iota(
        jnp.int32, (HIST_BIN_TILE,) + x.shape, 0)
    hits = (x[None] == bins).astype(jnp.int32)       # one-hot over the tile
    o_ref[...] += hits.sum(axis=1)                   # per-lane partial counts


def histogram_pallas(stream: jax.Array, n_bins: int, *, block: int = 4096,
                     interpret: bool = False) -> jax.Array:
    """Flat int32 stream -> (n_bins,) occurrence counts (values outside
    [0, n_bins) are ignored).  The grid walks bin tiles x stream blocks;
    each bin tile accumulates per-lane counts that XLA sums at the end."""
    n = stream.shape[0]
    rows = block_rows(n, block)
    tiles, nb = to_tiles(stream.astype(jnp.int32), rows, -1)
    n_tiles = max(1, -(-n_bins // HIST_BIN_TILE))
    partial_counts = pl.pallas_call(
        _hist_kernel,
        grid=(n_tiles, nb),
        in_specs=[pl.BlockSpec((rows, LANES), lambda t, i: (i, 0))],
        out_specs=pl.BlockSpec((HIST_BIN_TILE, LANES), lambda t, i: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles * HIST_BIN_TILE, LANES),
                                       jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tiles)
    return partial_counts.sum(axis=1)[:n_bins]


def _digram_kernel(carry_ref, x_ref, o_ref, *, n_terminals: int):
    i = pl.program_id(0)
    x = x_ref[...]                                   # (rows, 128) int32
    codes = prev_elements(x, carry_ref[i]) * jnp.int32(n_terminals) + x
    o_ref[...] = jnp.where(is_stream_start(x.shape, i), jnp.int32(-1), codes)


def digram_codes_pallas(stream: jax.Array, n_terminals: int, *,
                        block: int = 4096,
                        interpret: bool = False) -> jax.Array:
    """Flat int32 terminal stream -> pair codes ``prev * T + cur``
    (position 0, which has no predecessor, yields -1)."""
    n = stream.shape[0]
    rows = block_rows(n, block)
    tiles, nb = to_tiles(stream.astype(jnp.int32), rows, 0)
    tile_spec = pl.BlockSpec((rows, LANES), lambda i, c: (i, 0))
    out = pl.pallas_call(
        partial(_digram_kernel, n_terminals=n_terminals),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(nb,), in_specs=[tile_spec],
            out_specs=tile_spec),
        out_shape=jax.ShapeDtypeStruct(tiles.shape, jnp.int32),
        compiler_params=PARALLEL,
        interpret=interpret,
    )(block_carries(tiles, rows, 0), tiles)
    return from_tiles(out, n)
