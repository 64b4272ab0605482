"""In-graph (jit-able) EWAH: vectorized compress / decompress / size.

TPU adaptation (DESIGN.md §3): the CPU codec is a sequential append loop;
here compression is re-cast as classify -> run-labeling -> exclusive-scan ->
scatter, which is O(n) work at O(log n) depth and maps onto VPU-friendly
primitives.  The *size-only* path (what the sorting heuristics optimize) is a
pure reduction.

Restrictions of the vectorized path (asserted): one marker per (clean,dirty)
group, i.e. clean runs < 2^16 and dirty runs < 2^15 words — always true for
the in-graph uses (MoE dispatch bitmaps over <= 32767-word streams).  The
numpy oracle in ``ewah.py`` has no such restriction.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .ewah import FULL, MAX_CLEAN, MAX_DIRTY  # noqa: F401  (shared constants)


def classify(words: jax.Array) -> jax.Array:
    """0 = clean-0, 1 = clean-1, 2 = dirty."""
    return jnp.where(words == 0, 0, jnp.where(words == FULL, 1, 2)).astype(jnp.int32)


def _run_ids(kind: jax.Array):
    start = jnp.concatenate([jnp.ones(1, bool), kind[1:] != kind[:-1]])
    run_id = jnp.cumsum(start) - 1
    return start, run_id


@partial(jax.jit, static_argnames=("capacity",))
def compress(words: jax.Array, capacity: int):
    """EWAH-compress a uint32 word vector. Returns (stream[capacity], length).

    Requires n_words <= MAX_DIRTY (asserted statically) so that every
    (clean run, dirty run) group fits a single marker.
    """
    kind = classify(words)
    start, _ = _run_ids(kind)
    return compress_from_runs(words, kind, start, capacity)


def compress_from_runs(words: jax.Array, kind: jax.Array, start: jax.Array,
                       capacity: int):
    """Scan/scatter epilogue of the vectorized compressor.

    ``kind`` (0/1/2 per word) and ``start`` (run-boundary flags) come either
    from :func:`classify` + ``_run_ids`` (the jnp path in :func:`compress`)
    or from the fused Pallas prefix pass (``kernels.ops.recompress_batch``).
    Vmappable — the jax query backend re-encodes a whole batch of query
    results per dispatch.  Returns (stream[capacity], length).
    """
    n = words.shape[0]
    assert n <= MAX_DIRTY, f"vectorized path supports <= {MAX_DIRTY} words"
    run_id = jnp.cumsum(start.astype(jnp.int32)) - 1
    n_runs = run_id[-1] + 1
    idx = jnp.arange(n)

    run_kind = jax.ops.segment_max(kind, run_id, num_segments=n)
    run_len = jax.ops.segment_sum(jnp.ones(n, jnp.int32), run_id, num_segments=n)
    run_valid = jnp.arange(n) < n_runs

    # groups: every clean run opens a group; a leading dirty run opens one too
    run_is_clean = run_kind < 2
    grp_start = run_is_clean | (jnp.arange(n) == 0)
    grp_of_run = jnp.cumsum(grp_start & run_valid) - 1
    n_groups = jnp.maximum(grp_of_run[jnp.maximum(n_runs - 1, 0)] + 1, 1)

    grp_nclean = jax.ops.segment_sum(
        jnp.where(run_is_clean & run_valid, run_len, 0), grp_of_run, num_segments=n)
    grp_ndirty = jax.ops.segment_sum(
        jnp.where(~run_is_clean & run_valid, run_len, 0), grp_of_run, num_segments=n)
    grp_ctype = jax.ops.segment_max(
        jnp.where(run_is_clean & run_valid, run_kind, 0), grp_of_run, num_segments=n)

    grp_size = jnp.where(jnp.arange(n) < n_groups, 1 + grp_ndirty, 0)
    grp_off = jnp.cumsum(grp_size) - grp_size  # exclusive scan
    total = grp_off[jnp.maximum(n_groups - 1, 0)] + grp_size[jnp.maximum(n_groups - 1, 0)]

    # markers
    marker = (
        (grp_ctype.astype(jnp.uint32) << 31)
        | (grp_nclean.astype(jnp.uint32) << 15)
        | grp_ndirty.astype(jnp.uint32)
    )
    out = jnp.zeros(capacity + 1, jnp.uint32)
    mpos = jnp.where(jnp.arange(n) < n_groups, grp_off, capacity)
    out = out.at[mpos].set(marker, mode="drop")

    # dirty words: word i (dirty) goes to grp_off[g] + 1 + rank-within-dirty-run
    word_run = run_id
    word_grp = grp_of_run[word_run]
    run_start_idx = jax.ops.segment_min(idx, run_id, num_segments=n)
    t = idx - run_start_idx[word_run]
    is_dirty_w = kind == 2
    dpos = jnp.where(is_dirty_w, grp_off[word_grp] + 1 + t, capacity)
    out = out.at[dpos].set(words, mode="drop")
    return out[:capacity], total


@partial(jax.jit, static_argnames=("capacity",))
def compressed_size(words: jax.Array, capacity: int = 0):
    """Compressed size in words (markers + dirty), no materialization.

    Exact for streams within the single-marker-per-group restriction.
    """
    n = words.shape[0]
    kind = classify(words)
    start, run_id = _run_ids(kind)
    n_runs = run_id[-1] + 1
    run_kind = jax.ops.segment_max(kind, run_id, num_segments=n)
    run_valid = jnp.arange(n) < n_runs
    run_is_clean = run_kind < 2
    n_groups = jnp.maximum(
        jnp.sum((run_is_clean & run_valid).astype(jnp.int32))
        + jnp.where(run_kind[0] == 2, 1, 0), 1)
    n_dirty = jnp.sum((kind == 2).astype(jnp.int32))
    return n_groups + n_dirty


def _shift(x, s: int, fill, up: bool):
    """Move ``x`` along axis 0 by ``s`` rows, filling the rows it leaves.

    ``up`` moves row p + s to row p (toward lower indices), else row p - s
    to row p.  A static pad with one negative edge: no gather."""
    edge = (-s, s, 0) if up else (s, -s, 0)
    return jax.lax.pad(x, jnp.asarray(fill, x.dtype),
                       [edge] + [(0, 0, 0)] * (x.ndim - 1))


def _route(key, carried, dist, bits, up: bool):
    """One pass of a log-step shift network along axis 0.

    A word (a row where ``key >= 0``) moves by ``2**b`` at the stage of
    bit ``b`` where that bit of ``dist(key, carried)`` is set; ``bits``
    gives the stages in order.  The ``carried`` arrays travel with their
    words."""
    for b in bits:
        s = 1 << b
        d = dist(key, carried)
        mv = (key >= 0) & ((d & s) != 0)
        arrive = _shift(mv, s, False, up)
        key = jnp.where(arrive, _shift(key, s, -1, up), jnp.where(mv, -1, key))
        carried = tuple(jnp.where(arrive, _shift(c, s, 0, up), c)
                        for c in carried)
    return key, carried


@partial(jax.jit, static_argnames=("n_words",))
def decompress(stream: jax.Array, length, n_words: int):
    """Expand EWAH streams into n_words uint32 words each, scatter-free.

    ``stream`` is (..., C) with ``length`` (...) live words per stream;
    returns (..., n_words).  One scan walks all streams in step and gives
    every stream word the output slot where it starts.  The words that
    write something are kept: dirty words with a nonzero value and
    clean-1 markers with nclean > 0 (clean-0 runs and empty runs are the
    zeros the output starts as).  Along each stream, a shift network of
    static shifts and selects routes them to their slots, with no scatter
    and no gather:

    * compaction: each kept word moves left by the number of dropped words
      before it, one bit of that count per stage, least significant first;
    * expansion: it moves right from its rank to its slot, most
      significant bit first.

    Both displacements never decrease along a stream, so no two words meet
    at any stage: for kept words i < j, the gap between them after a stage
    is (j - i) less the part of (d_j - d_i) routed so far, which is at most
    the dropped words between them in compaction (gap >= 1), and in
    expansion the slots' high bits routed so far never decrease (gap >=
    j - i).  Kept slots are strictly increasing (a dirty word takes one
    slot, a kept marker nclean > 0), so every word lands on a slot of its
    own.  A clean-1 marker carries its run length; the running maximum of
    the run ends along the stream then fills each run with ones.  Words
    that land at or past n_words are dropped."""
    lead, C = stream.shape[:-1], stream.shape[-1]
    words = stream.reshape(-1, C).T               # (C, K): scan over C
    length = jnp.reshape(length, (-1,))
    K = words.shape[1]

    def step(carry, w):
        i, dirty_rem, out_pos = carry
        active = i < length
        is_dirty = dirty_rem > 0
        ctype = (w >> 31) & 1
        nclean = ((w >> 15) & 0xFFFF).astype(jnp.int32)
        ndirty = (w & 0x7FFF).astype(jnp.int32)
        # marker event: clean run [out_pos, out_pos + nclean)
        mk = active & ~is_dirty
        c_len = jnp.where(mk, nclean, 0)
        new_out = out_pos + jnp.where(is_dirty, 1, c_len)
        new_dirty = jnp.where(is_dirty, dirty_rem - 1, jnp.where(mk, ndirty, 0))
        dirty_word = jnp.where(active & is_dirty, w, jnp.uint32(0))
        clean1 = (mk & (ctype == 1)).astype(jnp.int32)
        return (i + 1, new_dirty, new_out), (out_pos, dirty_word, clean1, c_len)

    zero = jnp.zeros(K, jnp.int32)
    _, (start, dval, c1, clen) = jax.lax.scan(
        step, (jnp.int32(0), zero, zero), words)  # each (C, K)
    # every stream is a column of (rows, K); a kept word carries
    # key = slot * 2 + clean-1 tag (-1 where no word is) and its payload:
    # the dirty value, or the clean-1 run length
    run1 = (c1 != 0) & (clen > 0)
    keep = ((dval != 0) | run1) & (start < n_words)
    key = jnp.where(keep, start * 2 + c1, -1)
    pay = jnp.where(run1, clen.astype(jnp.uint32), dval)
    keep = keep.astype(jnp.int32)
    rank = jnp.cumsum(keep, axis=0) - keep
    row = jax.lax.broadcasted_iota(jnp.int32, (C, K), 0)
    key, (pay, _) = _route(key, (pay, row - rank), lambda k, c: c[1],
                           range((C - 1).bit_length()), up=True)
    # compacted: the kept words sit in rows [0, kept) < n_words
    if C >= n_words:
        key, pay = key[:n_words], pay[:n_words]
    else:
        pad = [(0, n_words - C), (0, 0)]
        key = jnp.pad(key, pad, constant_values=-1)
        pay = jnp.pad(pay, pad)
    row = jax.lax.broadcasted_iota(jnp.int32, (n_words, K), 0)
    key, (pay,) = _route(key, (pay,), lambda k, c: (k >> 1) - row,
                         reversed(range((n_words - 1).bit_length())), up=False)
    full = (key >= 0) & ((key & 1) == 1)
    end = jnp.where(full, row + pay.astype(jnp.int32), 0)
    full = jax.lax.cummax(end, axis=0) > row
    out = jnp.where(full, FULL, jnp.where(key >= 0, pay, jnp.uint32(0)))
    return out.T.reshape(*lead, n_words)


def logical_op(stream_a, len_a, stream_b, len_b, n_words: int, op: str, capacity: int):
    """Compressed op via decompress->op->recompress (vectorized path).

    The O(|A|+|B|) streaming merge lives in the numpy codec and the Pallas
    wordops kernel covers the word-level op; in-graph we trade compressed-
    domain skipping for 128-lane parallelism (DESIGN.md §3).
    """
    a = decompress(stream_a, len_a, n_words)
    b = decompress(stream_b, len_b, n_words)
    fn = {"and": jnp.bitwise_and, "or": jnp.bitwise_or, "xor": jnp.bitwise_xor}[op]
    return compress(fn(a, b), capacity)
