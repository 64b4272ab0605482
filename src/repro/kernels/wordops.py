"""Pallas TPU kernel: word-aligned bitwise ops + clean-word classification.

The throughput path for EWAH logical operations on TPU (DESIGN.md §3):
tiles of packed words are combined with the VPU bitwise op while the same
pass classifies each result word (clean-0 / clean-1 / dirty), producing the
statistics the re-compression / size accounting needs — one VMEM round trip
for both jobs.

  in : a, b (N, 128) uint32
  out: r    (N, 128) uint32 = a OP b
       cls  (N, 128) int32 in {0,1,2}  (0x00, 0xFF.., dirty)
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROW_TILE = 64
LANE_TILE = 128

_OPS = {"and": 0, "or": 1, "xor": 2}


def _kernel(a_ref, b_ref, r_ref, cls_ref, *, op: int):
    a = a_ref[...]
    b = b_ref[...]
    if op == 0:
        r = a & b
    elif op == 1:
        r = a | b
    else:
        r = a ^ b
    r_ref[...] = r
    full = jnp.bitwise_not(jnp.zeros_like(r))  # 0xFFFFFFFF without capture
    cls_ref[...] = jnp.where(r == 0, 0, jnp.where(r == full, 1, 2)).astype(jnp.int32)


def wordops_kernel(a: jax.Array, b: jax.Array, op: str = "and",
                   *, interpret: bool = True):
    N, C = a.shape
    assert a.shape == b.shape and N % ROW_TILE == 0 and C % LANE_TILE == 0
    grid = (N // ROW_TILE, C // LANE_TILE)
    spec = pl.BlockSpec((ROW_TILE, LANE_TILE), lambda i, j: (i, j))
    return pl.pallas_call(
        partial(_kernel, op=_OPS[op]),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=(spec, spec),
        out_shape=(jax.ShapeDtypeStruct((N, C), jnp.uint32),
                   jax.ShapeDtypeStruct((N, C), jnp.int32)),
        interpret=interpret,
    )(a, b)
