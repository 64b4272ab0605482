"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import ewah
from repro.kernels import ops, ref


@pytest.mark.parametrize("R,C", [(256, 128), (512, 256), (256, 384), (768, 128)])
@pytest.mark.parametrize("seed", [0, 1])
def test_bitpack_aligned(R, C, seed):
    r = np.random.default_rng(seed)
    bits = jnp.asarray(r.random((R, C)) < 0.3)
    out = ops.bitpack(bits)
    expect = ref.bitpack(bits)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


@pytest.mark.parametrize("R,C", [(100, 50), (33, 129), (1, 1), (300, 200)])
def test_bitpack_unaligned(R, C):
    r = np.random.default_rng(2)
    bits = jnp.asarray(r.random((R, C)) < 0.5)
    out = ops.bitpack(bits)
    padded = jnp.pad(bits, ((0, (-R) % 32), (0, 0)))
    expect = ref.bitpack(padded)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


def test_bitpack_matches_cpu_codec():
    """Kernel output bit layout == the numpy codec's pack_bits layout."""
    r = np.random.default_rng(3)
    bits = r.random((96, 4)) < 0.4
    out = np.asarray(ops.bitpack(jnp.asarray(bits)))
    for c in range(4):
        np.testing.assert_array_equal(out[:, c], ewah.pack_bits(bits[:, c]))


@pytest.mark.parametrize("op", ["and", "or", "xor"])
@pytest.mark.parametrize("n", [128, 1000, 8192, 33])
def test_wordops(op, n):
    r = np.random.default_rng(4)
    a = jnp.asarray(r.integers(0, 2**32, size=n, dtype=np.uint32))
    b = jnp.asarray(r.integers(0, 2**32, size=n, dtype=np.uint32))
    # seed some clean words
    a = a.at[::7].set(0).at[::11].set(0xFFFFFFFF)
    rk, ck = ops.wordops(a, b, op)
    rr, cr = ref.wordops(a, b, op)
    np.testing.assert_array_equal(np.asarray(rk), np.asarray(rr))
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr))


@pytest.mark.parametrize("inverse", [False, True])
@pytest.mark.parametrize("n", [64, 1000, 4096])
def test_gray_kernel(inverse, n):
    r = np.random.default_rng(5)
    x = jnp.asarray(r.integers(0, 2**32, size=n, dtype=np.uint32))
    out = ops.gray(x, inverse)
    expect = ref.gray(x, inverse)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


def test_gray_roundtrip_kernel():
    x = jnp.arange(2048, dtype=jnp.uint32)
    g = ops.gray(x)
    back = ops.gray(g, inverse=True)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


@pytest.mark.parametrize("T,V", [(512, 128), (2048, 256), (1000, 100), (512, 91)])
def test_histogram(T, V):
    r = np.random.default_rng(6)
    vals = jnp.asarray(r.integers(0, V, size=T, dtype=np.int32))
    out = ops.histogram(vals, V)
    expect = np.bincount(np.asarray(vals), minlength=V)
    np.testing.assert_array_equal(np.asarray(out).astype(np.int64), expect)
    assert float(np.asarray(out).sum()) == T


@pytest.mark.parametrize("T,E,k", [(256, 128, 4), (512, 60, 4), (300, 64, 8), (256, 60, 1)])
def test_moe_route_bitmap(T, E, k):
    r = np.random.default_rng(7)
    eids = jnp.asarray(r.integers(0, E, size=(T, k), dtype=np.int32))
    out = ops.moe_route_bitmap(eids, E)
    expect = ref.moe_route(eids, E)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))
    # row/word cross-check against the numpy codec
    words = np.asarray(out)
    e0 = int(eids[0, 0])
    assert words[0, e0] & 1


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 300), st.integers(1, 200), st.integers(0, 100))
def test_bitpack_property(R, C, seed):
    r = np.random.default_rng(seed)
    bits = jnp.asarray(r.random((R, C)) < 0.5)
    out = np.asarray(ops.bitpack(bits))
    # unpack and compare
    back = ewah.unpack_bits(out[:, 0], R)
    np.testing.assert_array_equal(back, np.asarray(bits)[:, 0])


def test_kernel_feeds_ewah_pipeline():
    """bitpack kernel words -> numpy EWAH compress -> roundtrip."""
    r = np.random.default_rng(8)
    col = np.sort(r.integers(0, 12, size=2000))
    onehot = col[:, None] == np.arange(12)[None, :]
    words = np.asarray(ops.bitpack(jnp.asarray(onehot)))
    for c in range(12):
        stream = ewah.compress(words[:, c])
        back = ewah.decompress(stream)
        np.testing.assert_array_equal(back, words[:, c])


def test_resolve_interpret_follows_the_backend():
    """``interpret=None`` means compiled on a TPU and interpreted anywhere
    else; an explicit choice is kept; the jax query backend resolves it
    once, at construction, through the same helper."""
    from repro.core.query import JaxBackend

    on_tpu = jax.default_backend() == "tpu"
    assert ops.resolve_interpret() is (not on_tpu)
    assert ops.resolve_interpret(True) is True
    assert ops.resolve_interpret(False) is False
    assert JaxBackend().interpret is (not on_tpu)
    assert JaxBackend(interpret=False).interpret is False
