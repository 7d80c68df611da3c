"""Lane-dense tiling of flat 32-bit streams for the encode kernels.

The TPU lowers 32-bit data in (8, 128) tiles, so a flat stream of ``n``
elements is laid out row-major as ``(rows, 128)``: element ``e`` sits at
``[e // 128, e % 128]``.  ``n`` is padded up to whole blocks of
``block_rows`` rows (a multiple of 8) with a caller-chosen fill, and the
caller slices the padding off the result.

Kernels that need each element's predecessor (deltas, run boundaries,
digram codes) get it from :func:`prev_elements`: two ``pltpu.roll``\\ s
shift the block by one element in row-major order, and the element just
before the block arrives as a scalar through scalar prefetch
(:func:`block_carries`).  So blocks are independent, no scalar is ever
stored to vector memory, and no row is sliced out of a value.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8

#: compiler params of a 1-D grid whose blocks are independent
PARALLEL = pltpu.CompilerParams(dimension_semantics=("parallel",))


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def block_rows(n: int, block: int) -> int:
    """Rows per grid step for ``n`` elements and a requested ``block`` of
    elements: a whole number of (8, 128) tiles, no larger than needed."""
    want = _round_up(max(block, 1), SUBLANES * LANES) // LANES
    need = _round_up(-(-n // LANES), SUBLANES)
    return max(SUBLANES, min(want, need))


def to_tiles(flat: jax.Array, rows: int, fill: int) -> Tuple[jax.Array, int]:
    """``(..., n)`` -> ``(..., n_blocks * rows, 128)`` padded with ``fill``;
    returns the tiles and the number of blocks."""
    n = flat.shape[-1]
    per_block = rows * LANES
    nb = max(1, -(-n // per_block))
    pad = nb * per_block - n
    if pad:
        widths = [(0, 0)] * (flat.ndim - 1) + [(0, pad)]
        flat = jnp.pad(flat, widths, constant_values=fill)
    return flat.reshape(flat.shape[:-1] + (nb * rows, LANES)), nb


def from_tiles(tiles: jax.Array, n: int) -> jax.Array:
    """Inverse of :func:`to_tiles`: ``(..., rows, 128)`` -> ``(..., n)``."""
    return tiles.reshape(tiles.shape[:-2] + (-1,))[..., :n]


def block_carries(tiles: jax.Array, rows: int, init) -> jax.Array:
    """The element just before each block's first element, ``(..., nb)``
    flattened to 1-D for scalar prefetch; block 0 gets ``init``."""
    last = tiles[..., rows - 1::rows, LANES - 1]
    first = jnp.full(last.shape[:-1] + (1,), init, last.dtype)
    return jnp.concatenate([first, last[..., :-1]], axis=-1).reshape(-1)


def prev_elements(x: jax.Array, carry) -> jax.Array:
    """Predecessor of every element of an ``(R, 128)`` block in row-major
    order; the block's first element gets the scalar ``carry``."""
    left = pltpu.roll(x, 1, 1)            # [r, c] = x[r, c-1]; c=0: x[r, 127]
    up = pltpu.roll(left, 1, 0)           # [r, 0] = x[r-1, 127]
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(col > 0, left, jnp.where(row > 0, up, carry))


def is_stream_start(shape, block_index) -> jax.Array:
    """Mask of the stream's first element within block ``block_index``."""
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return (block_index == 0) & (row == 0) & (col == 0)
