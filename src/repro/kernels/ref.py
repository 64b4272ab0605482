"""Pure-jnp oracles for every Pallas kernel (tested via assert_allclose)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def bitpack(bits: jax.Array) -> jax.Array:
    """(R, C) bool/int -> (ceil(R/32), C) uint32 (zero-padded rows)."""
    R, C = bits.shape
    pad = (-R) % 32
    if pad:
        bits = jnp.pad(bits, ((0, pad), (0, 0)))
    b = bits.astype(jnp.uint32).reshape(-1, 32, C)
    shifts = jnp.arange(32, dtype=jnp.uint32)[None, :, None]
    return (b << shifts).sum(axis=1, dtype=jnp.uint32)


def wordops(a, b, op="and"):
    fn = {"and": jnp.bitwise_and, "or": jnp.bitwise_or,
          "xor": jnp.bitwise_xor}[op]
    r = fn(a, b)
    cls = jnp.where(r == 0, 0, jnp.where(r == jnp.uint32(0xFFFFFFFF), 1, 2)).astype(jnp.int32)
    return r, cls


def gray(x, inverse=False):
    x = x.astype(jnp.uint32)
    if not inverse:
        return x ^ (x >> jnp.uint32(1))
    for s in (1, 2, 4, 8, 16):
        x = x ^ (x >> jnp.uint32(s))
    return x


def histmm(vals, n_values):
    return jnp.zeros(n_values, jnp.float32).at[vals].add(1.0)


def moe_route(eids, n_experts):
    T, k = eids.shape
    onehot = jax.nn.one_hot(eids, n_experts, dtype=jnp.uint32).sum(1)
    onehot = jnp.minimum(onehot, 1)
    return bitpack(onehot)
