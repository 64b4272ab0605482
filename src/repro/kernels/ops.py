"""jit'd public wrappers around the Pallas kernels.

Handle padding to tile boundaries, resolve ``interpret=None`` through
:func:`resolve_interpret` (compiled on a TPU, interpreted elsewhere so the
kernels validate on CPU), and expose a ``use_kernel`` switch falling back
to the jnp reference implementation.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..core import ewah_jax
from . import ref
from .bitpack import LANE_TILE, ROW_TILE, bitpack_kernel
from .gray import gray_kernel
from .histmm import TOK_TILE, VAL_TILE, histmm_kernel
from .moe_route import moe_route_kernel
from .planfuse import planfuse_kernel
from .recompress import recompress_kernel
from .slicefold import slicefold_kernel
from .wordops import wordops_kernel


def resolve_interpret(interpret: bool | None = None) -> bool:
    """The one place ``interpret=None`` is decided: compiled Pallas on a
    TPU, the interpreter on any other backend (so the kernels validate on
    the CPU).  An explicit True/False is kept as given."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _pad_to(x, mult, axis, value=0):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def bitpack(bits, use_kernel=True, interpret=None):
    """(R, C) bool -> (ceil(R/32), C) uint32."""
    R, C = bits.shape
    if not use_kernel:
        return ref.bitpack(_pad_to(bits, 32, 0))[: -(-R // 32)]
    interpret = resolve_interpret(interpret)
    x = _pad_to(_pad_to(bits, ROW_TILE, 0), LANE_TILE, 1)
    out = bitpack_kernel(x, interpret=interpret)
    return out[: -(-R // 32), :C]


@partial(jax.jit, static_argnames=("op", "use_kernel", "interpret"))
def wordops(a, b, op="and", use_kernel=True, interpret=None):
    """1-D compressed-word vectors -> (result words, classification)."""
    n = a.shape[0]
    if not use_kernel:
        return ref.wordops(a, b, op)
    interpret = resolve_interpret(interpret)
    lanes = 128
    rows = -(-n // lanes)
    from .wordops import ROW_TILE as RT
    rows_p = -(-rows // RT) * RT
    a2 = jnp.zeros((rows_p * lanes,), jnp.uint32).at[:n].set(a).reshape(rows_p, lanes)
    b2 = jnp.zeros((rows_p * lanes,), jnp.uint32).at[:n].set(b).reshape(rows_p, lanes)
    r, cls = wordops_kernel(a2, b2, op, interpret=interpret)
    return r.reshape(-1)[:n], cls.reshape(-1)[:n]


@partial(jax.jit, static_argnames=("op", "use_kernel", "interpret"))
def wordops_fold(stacked, op="and", use_kernel=True, interpret=None):
    """Fold ``op`` across axis 0 of (m, n) word vectors -> (n,).

    Tree reduction: each level combines *all* of its pairs in one flattened
    ``wordops`` launch, so a whole batch of queries (n = B * words-per-query)
    folds in ceil(log2 m) kernel dispatches — the query plane's batched
    jax-backend primitive.
    """
    m, n = stacked.shape
    while m > 1:
        even = (m // 2) * 2
        a = stacked[0:even:2].reshape(-1)
        b = stacked[1:even:2].reshape(-1)
        r, _ = wordops(a, b, op, use_kernel=use_kernel, interpret=interpret)
        merged = r.reshape(even // 2, n)
        if m % 2:
            merged = jnp.concatenate([merged, stacked[-1:]], axis=0)
        stacked = merged
        m = stacked.shape[0]
    return stacked[0]


@partial(jax.jit, static_argnames=("op", "use_kernel", "interpret"))
def container_pairs(a, b, op="and", use_kernel=True, interpret=None):
    """Batched Roaring-container merge in word space: (P, W) uint32 pairs
    -> (P, W), one padded Pallas launch for a whole fold round's chunk
    pairs (W = containers.CHUNK_WORDS in the backend)."""
    if op not in ("and", "or", "andnot"):
        raise ValueError(f"unknown container merge op {op!r}")
    a = jnp.asarray(a, jnp.uint32)
    b = jnp.asarray(b, jnp.uint32)
    if not use_kernel:
        if op == "and":
            return a & b
        if op == "or":
            return a | b
        return a & ~b
    interpret = resolve_interpret(interpret)
    from .containers import LANE_TILE as LT
    from .containers import ROW_TILE as RT
    from .containers import containerops_kernel
    P, W = a.shape
    a2 = _pad_to(_pad_to(a, RT, 0), LT, 1)
    b2 = _pad_to(_pad_to(b, RT, 0), LT, 1)
    r = containerops_kernel(a2, b2, op, interpret=interpret)
    return r[:P, :W]


@partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def container_gallop(positions, words, use_kernel=True, interpret=None):
    """Galloping array∩bitmap membership for a batch of chunk pairs.

    ``positions``: (P, L) int32 local chunk positions, right-padded with
    -1.  ``words``: (P, containers.CHUNK_WORDS) uint32 bitmap payloads.
    Each position gallops straight to its word (``pos >> 5`` — the gather
    happens here at the jnp level, not inside the kernel) and the Pallas
    bit-test kernel checks the whole padded batch in one launch.  Returns
    (P, L) uint32 flags: 1 where the bitmap holds the position, 0 for
    misses and padding.
    """
    pos = jnp.asarray(positions, jnp.int32)
    w = jnp.asarray(words, jnp.uint32)
    safe = jnp.maximum(pos, 0)
    gathered = jnp.take_along_axis(w, safe >> 5, axis=1)
    if not use_kernel:
        hits = (gathered >> (safe & 31).astype(jnp.uint32)) & jnp.uint32(1)
    else:
        interpret = resolve_interpret(interpret)
        from .containers import LANE_TILE as LT
        from .containers import ROW_TILE as RT
        from .containers import member_kernel
        P, L = pos.shape
        g2 = _pad_to(_pad_to(gathered, RT, 0), LT, 1)
        p2 = _pad_to(_pad_to(safe, RT, 0), LT, 1)
        hits = member_kernel(g2, p2, interpret=interpret)[:P, :L]
    return jnp.where(pos >= 0, hits, jnp.uint32(0))


@partial(jax.jit, static_argnames=("ops", "use_kernel", "interpret"))
def slice_fold(stacked, ops, use_kernel=True, interpret=None):
    """Left-fold (m, n) word vectors with a per-step op -> (n,).

    The batched slice-fold entry point of the bit-sliced encoding: ``ops``
    is a static tuple of m-1 names from {'and', 'or', 'xor'}, applied
    sequentially (``r = (stacked[0] ops[0] stacked[1]) ops[1] ...``) —
    the slice-plane comparison circuit, where the op sequence encodes the
    comparison constant's bits.  The jax query backend flattens a whole
    batch of queries into n = B * words-per-query, so all planes of every
    comparison in the batch dispatch in ONE padded Pallas call
    (``kernels.slicefold``) instead of m - 1 two-operand launches.
    """
    m, n = stacked.shape
    if len(ops) != m - 1:
        raise ValueError(f"slice_fold got {m} planes but {len(ops)} ops "
                         "(need exactly m - 1)")
    if m == 1:
        return stacked[0]
    if not use_kernel:
        fns = {"and": jnp.bitwise_and, "or": jnp.bitwise_or,
               "xor": jnp.bitwise_xor}
        r = stacked[0]
        for i, op in enumerate(ops):
            r = fns[op](r, stacked[i + 1])
        return r
    interpret = resolve_interpret(interpret)
    lanes = 128
    from .slicefold import ROW_TILE as RT
    rows = -(-n // lanes)
    rows_p = -(-rows // RT) * RT
    x = (jnp.zeros((m, rows_p * lanes), jnp.uint32)
         .at[:, :n].set(stacked).reshape(m, rows_p, lanes))
    out = slicefold_kernel(x, tuple(ops), interpret=interpret)
    return out.reshape(-1)[:n]


@partial(jax.jit, static_argnames=("tape", "use_kernel", "interpret"))
def plan_fuse(stacked, tape, use_kernel=True, interpret=None):
    """Evaluate a lowered plan tape over (m, n) word planes in ONE Pallas
    launch -> (result (n,), kind (n,)).

    ``tape`` is the static stack-machine program from
    ``core.query.lower_plan`` (``(opcode, arg)`` int pairs — PUSH leaf /
    NOT / binary OP); the jax backend flattens a whole batch of queries
    into n = B * words-per-query, so every fold, interior merge, the root
    op, AND the recompress classification of the entire plan dispatch in
    one padded megakernel call (``kernels.planfuse``) instead of one
    launch per stage.  ``kind`` is the per-word EWAH class of the result
    (0 = clean-0, 1 = clean-1, 2 = dirty) — the run-start/scan emit stages
    of recompression consume it directly.
    """
    from .planfuse import ROW_TILE as RT
    from .planfuse import NOT, OP_AND, OP_OR, PUSH

    m, n = stacked.shape
    if not use_kernel:
        full = jnp.uint32(0xFFFFFFFF)
        stack = []
        for opcode, arg in tape:
            if opcode == PUSH:
                stack.append(stacked[arg])
            elif opcode == NOT:
                stack.append(stack.pop() ^ full)
            else:
                b = stack.pop()
                a = stack.pop()
                fn = (jnp.bitwise_and if arg == OP_AND else
                      jnp.bitwise_or if arg == OP_OR else jnp.bitwise_xor)
                stack.append(fn(a, b))
        r = stack.pop()
        return r, ewah_jax.classify(r)
    interpret = resolve_interpret(interpret)
    lanes = 128
    rows = -(-n // lanes)
    rows_p = -(-rows // RT) * RT
    x = (jnp.zeros((m, rows_p * lanes), jnp.uint32)
         .at[:, :n].set(stacked).reshape(m, rows_p, lanes))
    r, kind = planfuse_kernel(x, tape, interpret=interpret)
    return r.reshape(-1)[:n], kind.reshape(-1)[:n]


@partial(jax.jit, static_argnames=("capacity", "use_kernel", "interpret"))
def recompress_batch(words, capacity, use_kernel=True, interpret=None):
    """(B, W) dense uint32 word rows -> (streams (B, capacity), lengths (B,)).

    In-graph EWAH re-encode of a batch of query results (the compressed-
    domain closure of the jax backend: ``wordops_fold`` output goes back to
    EWAH without leaving the graph).  One Pallas launch computes per-word
    classification + run-start flags for the *whole* batch — rows get an
    opposite-class sentinel as word 0's predecessor, so runs never bleed
    across queries — then the scan/scatter epilogue
    (``ewah_jax.compress_from_runs``) vmaps over rows.

    Requires W <= 2**15 - 1 (one marker per group, asserted statically).
    """
    B, W = words.shape
    words = words.astype(jnp.uint32)
    sent = jnp.where(words[:, :1] == 0, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
    prev = jnp.concatenate([sent, words[:, :-1]], axis=1)
    if use_kernel:
        interpret = resolve_interpret(interpret)
        lanes = 128
        from .recompress import ROW_TILE as RT
        n = B * W
        rows = -(-n // lanes)
        rows_p = -(-rows // RT) * RT
        w2 = (jnp.zeros((rows_p * lanes,), jnp.uint32)
              .at[:n].set(words.reshape(-1)).reshape(rows_p, lanes))
        p2 = (jnp.zeros((rows_p * lanes,), jnp.uint32)
              .at[:n].set(prev.reshape(-1)).reshape(rows_p, lanes))
        kind, start = recompress_kernel(w2, p2, interpret=interpret)
        kind = kind.reshape(-1)[:n].reshape(B, W)
        start = start.reshape(-1)[:n].reshape(B, W)
    else:
        kind = ewah_jax.classify(words)
        start = (kind != ewah_jax.classify(prev)).astype(jnp.int32)
    return jax.vmap(
        lambda w, k, s: ewah_jax.compress_from_runs(w, k, s, capacity)
    )(words, kind, start)


@partial(jax.jit, static_argnames=("capacity", "use_kernel", "interpret"))
def recompress(words, capacity, use_kernel=True, interpret=None):
    """(W,) dense uint32 words -> (stream[capacity], length), in-graph."""
    streams, lengths = recompress_batch(
        words[None, :], capacity, use_kernel=use_kernel, interpret=interpret)
    return streams[0], lengths[0]


@partial(jax.jit, static_argnames=("inverse", "use_kernel", "interpret"))
def gray(x, inverse=False, use_kernel=True, interpret=None):
    """uint32 vector -> Gray code (or inverse)."""
    n = x.shape[0]
    if not use_kernel:
        return ref.gray(x, inverse)
    interpret = resolve_interpret(interpret)
    lanes = 128
    from .gray import ROW_TILE as RT
    rows = -(-n // lanes)
    rows_p = -(-rows // RT) * RT
    x2 = jnp.zeros((rows_p * lanes,), jnp.uint32).at[:n].set(x).reshape(rows_p, lanes)
    out = gray_kernel(x2, inverse, interpret=interpret)
    return out.reshape(-1)[:n]


@partial(jax.jit, static_argnames=("n_values", "use_kernel", "interpret"))
def histogram(vals, n_values, use_kernel=True, interpret=None):
    """int32 values -> (n_values,) float32 counts."""
    if not use_kernel:
        return ref.histmm(vals, n_values)
    interpret = resolve_interpret(interpret)
    n = vals.shape[0]
    v_pad = -(-n_values // VAL_TILE) * VAL_TILE
    # pad tokens with an out-of-range value -> lands in a padded count slot
    pad_val = n_values if v_pad > n_values else None
    t_pad = (-n) % TOK_TILE
    if t_pad and pad_val is None:
        v_pad += VAL_TILE
        pad_val = n_values
    x = jnp.concatenate([vals, jnp.full((t_pad,), pad_val or 0, vals.dtype)]) \
        if t_pad else vals
    out = histmm_kernel(x, v_pad, interpret=interpret)
    return out[:n_values]


@partial(jax.jit, static_argnames=("n_experts", "use_kernel", "interpret"))
def moe_route_bitmap(eids, n_experts, use_kernel=True, interpret=None):
    """(T, k) top-k expert ids -> (ceil(T/32), E) uint32 dispatch words."""
    T, k = eids.shape
    if not use_kernel:
        return ref.moe_route(eids, n_experts)
    interpret = resolve_interpret(interpret)
    from .moe_route import LANE_TILE as LT, ROW_TILE as RT
    e_pad = -(-n_experts // LT) * LT
    t_pad = (-T) % RT
    x = jnp.concatenate(
        [eids, jnp.full((t_pad, k), -1, eids.dtype)]) if t_pad else eids
    out = moe_route_kernel(x, e_pad, interpret=interpret)
    return out[: -(-T // 32), :n_experts]
