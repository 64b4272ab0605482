"""Pallas TPU kernel: pack boolean bitmap columns into 32-bit words.

The paper's Algorithm 1 "wordizes" 32 table rows at a time on a CPU; the
TPU-native form packs a (rows x bitmaps) boolean tile resident in VMEM into
uint32 words with VPU shift/or reductions — 128 bitmaps per lane-dim tile,
256 rows (-> 8 output sublanes) per row-dim tile, so in/out tiles are the
native (8,128)x4B register tiling.

  in : bits  (R, C) int8/bool   R % 256 == 0, C % 128 == 0 (ops.py pads)
  out: words (R/32, C) uint32   bit j of word w = bits[32*w + j]
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROW_TILE = 256  # 8 words of 32 rows
LANE_TILE = 128


def pack32(bits: jax.Array) -> jax.Array:
    """(R, L) 0/1 uint32 -> (R/32, L) uint32, bit j of word w = row 32w+j.

    The shifted bits are disjoint, so their sum is their OR.  Mosaic
    reduces signed integers only, so the sum runs in int32 (wrapping into
    bit 31 is exact) and the result is bitcast back."""
    b = bits.reshape(bits.shape[0] // 32, 32, bits.shape[1])
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (1, 32, 1), 1)
    summed = jax.lax.bitcast_convert_type(b << shifts, jnp.int32).sum(axis=1)
    return jax.lax.bitcast_convert_type(summed, jnp.uint32)


def _kernel(bits_ref, words_ref):
    words_ref[...] = pack32(bits_ref[...].astype(jnp.uint32))


def bitpack_kernel(bits: jax.Array, *, interpret: bool = True) -> jax.Array:
    """bits: (R, C) -> (R//32, C) uint32.  Shapes must be tile-aligned."""
    R, C = bits.shape
    assert R % ROW_TILE == 0 and C % LANE_TILE == 0, (R, C)
    grid = (R // ROW_TILE, C // LANE_TILE)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((ROW_TILE, LANE_TILE), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((ROW_TILE // 32, LANE_TILE), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R // 32, C), jnp.uint32),
        interpret=interpret,
    )(bits)
