"""Pallas TPU kernel: top-k routing decisions -> packed k-of-E bitmap words.

The MoE integration (DESIGN.md §4): the (tokens x experts) dispatch matrix
is the paper's k-of-N bitmap index.  This kernel fuses the one-hot
expansion of top-k expert ids with the 32-row word packing of Algorithm 1,
yielding the EWAH-ready uint32 word matrix in one VMEM pass.

  in : eids (T, k) int32      T % 256 == 0
  out: words (T/32, E) uint32 E % 128 == 0 (ops.py pads)
       bit j of words[w, e] == 1  iff  expert e in eids[32*w + j]
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .bitpack import pack32

ROW_TILE = 256
LANE_TILE = 128


def _kernel(eids_ref, words_ref, *, k: int):
    e0 = pl.program_id(1) * LANE_TILE
    eids = eids_ref[...]  # (ROW_TILE, k)
    ecol = jax.lax.broadcasted_iota(jnp.int32, (ROW_TILE, LANE_TILE), 1) + e0
    hit = jnp.zeros((ROW_TILE, LANE_TILE), jnp.uint32)
    for i in range(k):  # k is small and static (4 or 8)
        hit |= (eids[:, i : i + 1] == ecol).astype(jnp.uint32)
    words_ref[...] = pack32(hit)


def moe_route_kernel(eids: jax.Array, n_experts: int, *, interpret: bool = True):
    T, k = eids.shape
    assert T % ROW_TILE == 0 and n_experts % LANE_TILE == 0
    return pl.pallas_call(
        partial(_kernel, k=k),
        grid=(T // ROW_TILE, n_experts // LANE_TILE),
        in_specs=[pl.BlockSpec((ROW_TILE, k), lambda i, j: (i, 0))],
        out_specs=pl.BlockSpec((ROW_TILE // 32, LANE_TILE), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((T // 32, n_experts), jnp.uint32),
        interpret=interpret,
    )(eids)
