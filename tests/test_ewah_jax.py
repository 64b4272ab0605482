"""JAX EWAH vs numpy oracle."""

import numpy as np
import pytest

from repro.core import ewah, ewah_jax

from helpers import random_words


@pytest.mark.parametrize("n", [1, 2, 32, 100, 1000])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_compress_matches_oracle(n, seed):
    words = random_words(n, seed=seed)
    expect = ewah.compress(words)
    cap = len(expect) + 8
    stream, length = ewah_jax.compress(words, cap)
    assert int(length) == len(expect)
    np.testing.assert_array_equal(np.asarray(stream)[: int(length)], expect)


@pytest.mark.parametrize("seed", range(4))
def test_size_matches_oracle(seed):
    words = random_words(700, seed=seed)
    assert int(ewah_jax.compressed_size(words)) == len(ewah.compress(words))


@pytest.mark.parametrize("n", [1, 33, 256, 999])
@pytest.mark.parametrize("seed", [0, 3])
def test_decompress_roundtrip(n, seed):
    words = random_words(n, seed=seed)
    stream, length = ewah_jax.compress(words, n + 8)
    out = ewah_jax.decompress(stream, length, n)
    np.testing.assert_array_equal(np.asarray(out), words)


@pytest.mark.parametrize("op", ["and", "or", "xor"])
def test_logical_op(op):
    a = random_words(500, seed=1)
    b = random_words(500, seed=2)
    ca, la = ewah_jax.compress(a, 520)
    cb, lb = ewah_jax.compress(b, 520)
    res, length = ewah_jax.logical_op(ca, la, cb, lb, 500, op, 520)
    fn = {"and": np.bitwise_and, "or": np.bitwise_or, "xor": np.bitwise_xor}[op]
    expect = ewah.compress(fn(a, b))
    np.testing.assert_array_equal(np.asarray(res)[: int(length)], expect)


def test_all_clean():
    words = np.zeros(1000, dtype=np.uint32)
    stream, length = ewah_jax.compress(words, 8)
    assert int(length) == 1
    out = ewah_jax.decompress(stream, length, 1000)
    np.testing.assert_array_equal(np.asarray(out), words)


def _dirty(rng, n):
    return rng.integers(1, 0xFFFFFFFF, size=n, dtype=np.uint32)


def _words(*parts):
    return ewah.compress(np.concatenate(parts).astype(np.uint32))


def _ones(n):
    return np.full(n, ewah.FULL, np.uint32)


def _zeros(n):
    return np.zeros(n, np.uint32)


# name -> (n_words, extra capacity past the longest stream, a stream from
# a generator, the five other streams of the (2, 3, C) batch: random
# streams of n_words, or more of the first kind)
DECODE_CASES = {
    "starts_dirty": (300, 0, lambda r, n: _words(
        _dirty(r, 5), random_words(n - 5, seed=9)), "random"),
    "dirty_run_over_max_dirty": (40_000, 0, lambda r, n: _words(
        _zeros(3), _dirty(r, n - 3)), "random"),
    "clean_run_over_max_clean": (70_010, 0, lambda r, n: _words(
        _ones(70_000), _dirty(r, n - 70_000)), "random"),
    "all_clean1": (1000, 0, lambda r, n: _words(_ones(n)), "random"),
    "all_dirty": (257, 40, lambda r, n: _words(_dirty(r, n)), "random"),
    "odd_width": (333, 0, lambda r, n: _words(random_words(n, seed=5)),
                  "random"),
    "capacity_past_width": (200, 150, lambda r, n: _words(
        random_words(n, seed=6)), "random"),
    # seven clean-0 markers before one dirty word: most of the stream is
    # dropped, so compaction moves the word by every bit of C - 1
    "mostly_markers": (6 * ewah.MAX_CLEAN + 6, 0, lambda r, n: _words(
        _zeros(n - 1), _dirty(r, 1)), "same"),
    # clean-1 markers with no clean run: 17 empty ones first, so the first
    # dirty word drops by 17, the top bit of C - 1 = 28
    "clean1_markers_without_run": (40, 0, lambda r, n: np.concatenate([
        [ewah.make_marker(1, 0, 0)] * 17,
        [ewah.make_marker(1, 0, 2)], _dirty(r, 2),
        [ewah.make_marker(1, 0, 0), ewah.make_marker(1, 3, 1)], _dirty(r, 1),
        [ewah.make_marker(0, 4, 0), ewah.make_marker(1, 0, 3)], _dirty(r, 3),
        [ewah.make_marker(1, n - 13, 0)]]).astype(np.uint32), "same"),
    # the stream decodes past n_words, and its words there start more than
    # 511 slots past their rank: they are dropped, not wrapped into range
    "decodes_past_width": (500, 0, lambda r, n: _words(
        _zeros(n + 100), _dirty(r, 200)), "random"),
}


@pytest.mark.parametrize("case", sorted(DECODE_CASES))
def test_decompress_batched_matches_oracle(case):
    """``decompress`` of (m, B, C) streams against the numpy codec, with
    random words past each stream's length and one stream of length 0."""
    n, extra, make, others = DECODE_CASES[case]
    rng = np.random.default_rng(sorted(DECODE_CASES).index(case))
    streams = [make(rng, n)] + [
        make(rng, n) if others == "same" else
        ewah.compress(random_words(n, seed=s)) for s in range(5)]
    C = max(len(s) for s in streams) + extra
    batch = rng.integers(0, 1 << 32, size=(6, C), dtype=np.uint32)
    lengths = np.array([len(s) for s in streams], np.int32)
    for i, s in enumerate(streams):
        batch[i, : len(s)] = s
    lengths[-1] = 0  # a live prefix of nothing: all of its words are padding
    out = np.asarray(ewah_jax.decompress(
        batch.reshape(2, 3, C), lengths.reshape(2, 3), n)).reshape(6, n)
    for i in range(6):
        expect = np.zeros(n, np.uint32)
        dec = ewah.decompress(batch[i, : lengths[i]])[:n]
        expect[: len(dec)] = dec
        np.testing.assert_array_equal(out[i], expect, err_msg=f"stream {i}")
    # the first stream has the shape its case names
    first = ewah.unpack_marker(streams[0][0])
    shape = {
        "starts_dirty": first[1] == 0,
        "dirty_run_over_max_dirty": len(streams[0]) == (n - 3) + 2,
        "clean_run_over_max_clean": first == (1, ewah.MAX_CLEAN, 0),
        "all_clean1": len(streams[0]) == 1,
        "all_dirty": len(streams[0]) == n + 1 and C > n + 1,
        "odd_width": n % 128 != 0,
        "capacity_past_width": C > n + 1,
        "mostly_markers": len(streams[0]) == 8 and C == 8,
        "clean1_markers_without_run": (
            len(ewah.decompress(streams[0])) == n and C == 29),
        "decodes_past_width": len(ewah.decompress(streams[0])) > n,
    }
    assert shape[case]
