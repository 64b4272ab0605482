"""Multi-host serve plane: cross-process fan-out, compressed wire
shipping, and the sharded two-phase checkpoint commit.

The process harness spawns real worker subprocesses (loopback TCP, the
production transport) and proves every query surface bit-identical to
the single-process ``SegmentedIndex`` over an identically-built writer —
across all encodings, with tombstones, TTLs, an open buffer, and live
compaction racing queries.
"""

import os
import socket
import time

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import ewah
from repro.core.ewah_stream import EwahStream, concat_streams
from repro.core.lifecycle import BackgroundCompactor, IndexWriter
from repro.core.query import And, Eq, In, Not, Or, Range
from repro.core.segment import Segment, SegmentedIndex
from repro.core.strategies import IndexSpec
from repro.dist import checkpoint as ckpt
from repro.dist.query_fanout import assign_segments
from repro.dist.serve_plane import (ServePlane, WireError, recv_msg,
                                    seal_from_state, segment_state,
                                    send_msg)

KINDS = ["equality", "bitsliced", "bitsliced-gray", "binned", "roaring"]

PREDS = [
    Eq(0, 5),
    Eq(1, 117),
    Range(1, 40, 160),
    In(2, [1, 7, 23]),
    And(Eq(0, 3), Not(Eq(2, 2))),
    Or(Range(1, 0, 30), Eq(2, 31)),
    Not(Eq(0, 0)),
]

T0 = 1000.0


def build_writer(clock, n_per: int = 224):
    """Deterministic writer: one segment per encoding kind (the chooser
    pinned), three histogram-auto segments, staggered TTL deadlines, and
    a non-word-aligned open-buffer tail.  Two calls build bit-identical
    states (modulo segment generations)."""
    spec = IndexSpec(encoding="auto")
    rng = np.random.default_rng(42)
    segs, pos = [], 0
    for i, kind in enumerate(KINDS + [None, None, None]):
        cols = [rng.integers(0, 12, n_per), rng.integers(0, 200, n_per),
                rng.integers(0, 40, n_per)]
        expiry = np.full(n_per, np.inf)
        expiry[::9] = T0 + 5.0 * (i + 1)
        chooser = None if kind is None else (
            lambda c, h, k, _k=kind: _k)
        segs.append(Segment.seal(cols, spec, row_start=pos, expiry=expiry,
                                 encoding_chooser=chooser))
        pos += n_per
    w = IndexWriter.from_parts(spec, segments=tuple(segs), clock=clock)
    tail = [rng.integers(0, 12, 40), rng.integers(0, 200, 40),
            rng.integers(0, 40, 40)]
    w.append(tail, ttl=200.0)
    return w


def assert_plane_matches(ref: IndexWriter, plane: ServePlane, now,
                         backend: str = "numpy", **opts):
    """Every query surface agrees bit-for-bit with the single-process
    engine: row ids, merged streams, and compressed-domain counts.

    ``words_scanned`` is deliberately NOT compared: the result cache keys
    on leaf *content*, so scan counts depend on what the executing
    process ran before (a hit reports fewer scanned words) — the single
    process gets cross-segment hits that isolated workers cannot share.
    """
    want = ref.index.execute_compressed_many(PREDS, backend=backend,
                                             now=now, **opts)
    got = plane.execute_compressed_many(PREDS, backend=backend, now=now,
                                        **opts)
    for pred, (_, wm), (_, gm) in zip(PREDS, want, got):
        assert wm == gm, f"merged stream for {pred}"  # content equality
    want_rows = ref.index.query_many(PREDS, backend=backend, now=now,
                                     **opts)
    got_rows = plane.query_many(PREDS, backend=backend, now=now, **opts)
    for pred, (wr, _), (gr, gs) in zip(PREDS, want_rows, got_rows):
        np.testing.assert_array_equal(wr, gr, err_msg=f"rows for {pred}")
        assert gs >= 0
    want_counts = [ref.index.count(p, backend=backend, now=now, **opts)
                   for p in PREDS]
    assert plane.count_many(PREDS, backend=backend, now=now,
                            **opts) == want_counts


# ---------------------------------------------------------------------------
# The 8-host acceptance matrix
# ---------------------------------------------------------------------------


def test_eight_host_lifecycle_bit_identity():
    """8 worker processes, every encoding kind (pinned + histogram-auto),
    tombstones, TTL expiry, an open buffer, and compaction — every stage
    bit-identical to the single-process engine."""
    clock = [T0]
    ref = build_writer(lambda: clock[0])
    with ServePlane(build_writer(lambda: clock[0]), n_hosts=8) as plane:
        assert plane.world_size == 8
        assert_plane_matches(ref, plane, now=clock[0])
        # the fleet actually shares the load: segments spread over ranks
        assert len(set(plane._owner_of.values())) >= 4

        # deletes: sealed segments + open buffer, broadcast to owners
        ids = np.concatenate([np.arange(50, 400, 7),
                              np.arange(1800, 1835)])  # buffer span too
        assert ref.delete(row_ids=ids) == plane.delete(row_ids=ids)
        assert_plane_matches(ref, plane, now=clock[0])

        # predicate delete resolves to the identical row set
        assert ref.delete(Eq(2, 9), now=clock[0]) == \
            plane.delete(Eq(2, 9), now=clock[0])
        assert_plane_matches(ref, plane, now=clock[0])

        # TTLs: advance the shared clock past three segments' deadlines;
        # workers fold expiry against the coordinator's "now"
        clock[0] = T0 + 16.0
        assert_plane_matches(ref, plane, now=None)

        # compaction: explicit span, then the size-tiered policy — both
        # re-encode from merged histograms and re-home ownership
        assert ref.compact(span=(0, 3)) is not None
        assert plane.compact(span=(0, 3)) is not None
        assert_plane_matches(ref, plane, now=clock[0])
        assert (ref.compact(fanout=4, ratio=50.0) is None) == \
            (plane.compact(fanout=4, ratio=50.0) is None)
        assert_plane_matches(ref, plane, now=clock[0])

        # close the writer: the final (non-aligned) segment seals and the
        # plane keeps serving it
        ref.close()
        plane.writer_close()
        assert_plane_matches(ref, plane, now=clock[0])

        stats = plane.stats()
        assert stats["result_bytes_compressed"] > 0
        assert stats["ship_bytes"] > 0


def test_two_host_jax_fused_bit_identity():
    """The jax backend (megakernel fusion on) runs inside workers, in the
    Pallas interpreter the caller asked for, and still merges
    bit-identically with the numpy reference; without an explicit
    ``interpret=True`` the plane refuses it."""
    clock = [T0]
    ref = build_writer(lambda: clock[0], n_per=96)
    with ServePlane(build_writer(lambda: clock[0], n_per=96),
                    n_hosts=2) as plane:
        with pytest.raises(ValueError, match="interpret=True"):
            plane.query_many(PREDS, backend="jax", now=clock[0])
        want = ref.index.query_many(PREDS, backend="numpy", now=clock[0])
        got = plane.query_many(PREDS, backend="jax", interpret=True,
                               now=clock[0])
        for (wr, _), (gr, _) in zip(want, got):
            np.testing.assert_array_equal(wr, gr)


def test_compaction_races_queries():
    """A background compactor keeps merging (and the plane keeps
    re-homing segments) while queries stream; every answer equals the
    precomputed truth — readers never see a torn segment list."""
    clock = [T0]
    w = build_writer(lambda: clock[0])
    expected = [rows for rows, _ in w.index.query_many(PREDS, now=T0)]
    with ServePlane(w, n_hosts=2) as plane:
        compactor = BackgroundCompactor(w, interval=0.001, fanout=2,
                                        ratio=50.0)
        try:
            deadline = time.monotonic() + 30.0
            rounds = 0
            while (compactor.stats["compactions"] < 2
                   and time.monotonic() < deadline):
                got = plane.query_many(PREDS, now=T0)
                for want_rows, (rows, _) in zip(expected, got):
                    np.testing.assert_array_equal(want_rows, rows)
                rounds += 1
        finally:
            compactor.close()
        assert compactor.stats["compactions"] >= 1
        assert rounds >= 1
        got = plane.query_many(PREDS, now=T0)
        for want_rows, (rows, _) in zip(expected, got):
            np.testing.assert_array_equal(want_rows, rows)


# ---------------------------------------------------------------------------
# Sharded two-phase checkpoint commit
# ---------------------------------------------------------------------------


def test_sharded_checkpoint_roundtrip_and_resharding(tmp_path):
    """Each host writes only the segment dirs it owns; the commit barrier
    flips LATEST only after every CRC ack; restore reassembles the full
    writer and re-shards over a *smaller* world (a host lost since the
    save is tolerated by design)."""
    clock = [T0]
    ref = build_writer(lambda: clock[0])
    ref.delete(row_ids=np.arange(0, 500, 11))
    with ServePlane(build_writer(lambda: clock[0]), n_hosts=4) as plane:
        plane.delete(row_ids=np.arange(0, 500, 11))
        plane.save_checkpoint(str(tmp_path), 1)
        want_step1 = ref.index.query_many(PREDS, now=T0)

        # mutate past the save point, save again
        ref.delete(row_ids=np.arange(600, 900, 5))
        plane.delete(row_ids=np.arange(600, 900, 5))
        plane.save_checkpoint(str(tmp_path), 2, keep=2)
        want_step2 = ref.index.query_many(PREDS, now=T0)

    assert ckpt.latest_step(str(tmp_path)) == 2
    step2 = os.path.join(str(tmp_path), "step_00000002")
    # per-host sharding really happened: one dir per segment + manifest
    seg_dirs = [d for d in os.listdir(step2) if d.startswith("segment_")]
    assert len(seg_dirs) == 8
    import json
    with open(os.path.join(step2, "manifest.json")) as f:
        manifest = json.load(f)
    assert sorted(set(manifest["owners"])) != [0]  # spread over hosts

    # restore at HALF the world size: ownership re-shards over 2 hosts
    with ServePlane.restore(str(tmp_path), n_hosts=2,
                            clock=lambda: clock[0]) as restored:
        assert restored.restored_step == 2
        got = restored.query_many(PREDS, now=T0)
        for (wr, _), (gr, _) in zip(want_step2, got):
            np.testing.assert_array_equal(wr, gr)
        assert len(set(restored._owner_of.values())) <= 2

    # corrupt one shard of the newest step: load falls back to step 1
    victim = os.path.join(step2, "segment_00003", "state.npz")
    with open(victim, "r+b") as f:
        f.seek(30)
        byte = f.read(1)
        f.seek(30)
        f.write(bytes([byte[0] ^ 0xFF]))
    with ServePlane.restore(str(tmp_path), n_hosts=2,
                            clock=lambda: clock[0]) as fallback:
        assert fallback.restored_step == 1
        got = fallback.query_many(PREDS, now=T0)
        for (wr, _), (gr, _) in zip(want_step1, got):
            np.testing.assert_array_equal(wr, gr)


# ---------------------------------------------------------------------------
# Wire framing + state shipping (no subprocesses)
# ---------------------------------------------------------------------------


def test_wire_roundtrip_and_crc():
    a, b = socket.socketpair()
    try:
        payload = {"xs": np.arange(5), "s": "héllo", "n": 7}
        send_msg(a, "ship", payload)
        op, got, n = recv_msg(b)
        assert op == "ship" and got["n"] == 7 and got["s"] == "héllo"
        np.testing.assert_array_equal(got["xs"], np.arange(5))
        assert n > 0

        # flip one payload byte: the CRC must catch it
        import pickle
        import struct
        import zlib
        from repro.dist import serve_plane as sp
        body = pickle.dumps(("ship", payload))
        frame = sp._FRAME.pack(sp._FRAME_MAGIC, sp._FRAME_VERSION, 0, 0,
                               len(body), zlib.crc32(body))
        corrupted = bytearray(body)
        corrupted[3] ^= 0xFF
        a.sendall(frame + bytes(corrupted))
        with pytest.raises(WireError, match="CRC"):
            recv_msg(b)

        # wrong magic is rejected before any payload read
        a.sendall(sp._FRAME.pack(b"NOPE", sp._FRAME_VERSION, 0, 0, 0, 0))
        with pytest.raises(WireError, match="magic"):
            recv_msg(b)
    finally:
        a.close()
        b.close()


def test_segment_state_reseal_is_bit_identical():
    """segment_state -> seal_from_state reproduces the exact index —
    row permutation, per-column encodings, compressed size — including
    tombstones, TTLs, and a purged (row_ids) span, regardless of which
    chooser originally picked the encodings."""
    rng = np.random.default_rng(3)
    n = 160
    keep = np.sort(rng.choice(200, size=n, replace=False)).astype(np.int64)
    expiry = np.full(n, np.inf)
    expiry[::5] = T0 + 3
    seg = Segment.seal(
        [rng.integers(0, 9, n), rng.integers(0, 300, n)],
        IndexSpec(encoding="auto"), row_start=int(keep[0]),
        span_stop=205, row_ids=keep, expiry=expiry,
        encoding_chooser=lambda c, h, k: "roaring" if c == 0 else None)
    seg.delete_ids(keep[::7])

    rebuilt = seal_from_state(segment_state(seg), IndexSpec(encoding="auto"))
    np.testing.assert_array_equal(seg.index.row_perm,
                                  rebuilt.index.row_perm)
    assert seg.index.encodings() == rebuilt.index.encodings()
    assert seg.index.size_words() == rebuilt.index.size_words()
    assert seg.row_stop == rebuilt.row_stop
    np.testing.assert_array_equal(seg.ingest_ids(), rebuilt.ingest_ids())
    for surface in (seg, rebuilt):
        surface.fold_expired(T0 + 10)
    assert seg.tombstones == rebuilt.tombstones
    np.testing.assert_array_equal(seg.dead_ids(T0 + 10),
                                  rebuilt.dead_ids(T0 + 10))


def test_segment_state_rejects_dropped_row_store():
    seg = Segment.seal([np.arange(64) % 5], None, keep_columns=False)
    with pytest.raises(ValueError, match="keep_columns"):
        segment_state(seg)


def test_zero_row_segment_state_roundtrip():
    empty = Segment.empty(96, 160)
    rebuilt = seal_from_state(segment_state(empty), None)
    assert rebuilt.n_rows == 0
    assert (rebuilt.row_start, rebuilt.row_stop) == (96, 160)


# ---------------------------------------------------------------------------
# Placement policy
# ---------------------------------------------------------------------------


class _FakeSeg:
    def __init__(self, words):
        self._words = words

    def size_words(self):
        return self._words


def test_assign_segments_contiguous_and_balanced():
    owners = assign_segments([_FakeSeg(100)] * 8, 8)
    assert owners == list(range(8))          # equal sizes: one each
    owners = assign_segments([_FakeSeg(50)] * 16, 4)
    assert owners == sorted(owners)          # contiguous runs per host
    assert all(owners.count(r) == 4 for r in range(4))
    # skew: one huge segment pulls the boundary, small ones pack together
    owners = assign_segments(
        [_FakeSeg(10_000)] + [_FakeSeg(10)] * 6, 2)
    assert owners[0] == 0 and owners[-1] == 1
    assert owners == sorted(owners)


def test_assign_segments_edges():
    assert assign_segments([], 4) == []
    assert assign_segments([_FakeSeg(5)], 8) == [0]
    owners = assign_segments([_FakeSeg(0), _FakeSeg(0)], 2)  # floor 1
    assert owners == sorted(owners) and set(owners) <= {0, 1}
    with pytest.raises(ValueError):
        assign_segments([_FakeSeg(1)], 0)


# ---------------------------------------------------------------------------
# Satellite: any word-aligned partition concatenates bit-identically
# ---------------------------------------------------------------------------


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 10**6),
       st.lists(st.tuples(st.integers(0, 6),
                          st.sampled_from(["random", "zeros", "ones"])),
                min_size=1, max_size=6))
def test_concat_any_word_aligned_partition(seed, parts):
    """concat_streams over ANY word-aligned partition — including
    zero-row shards (empty parts) and fully-tombstoned shards (all-zero
    result parts) — is bit-identical to compressing the unpartitioned
    whole."""
    rng = np.random.default_rng(seed)
    pieces = []
    for n_words, style in parts:
        if style == "random":
            piece = rng.integers(0, 1 << 32, n_words, dtype=np.uint64)
            piece = piece.astype(np.uint32)
        elif style == "zeros":
            piece = np.zeros(n_words, dtype=np.uint32)
        else:
            piece = np.full(n_words, 0xFFFFFFFF, dtype=np.uint32)
        pieces.append(piece)
    whole = (np.concatenate(pieces) if pieces
             else np.zeros(0, dtype=np.uint32))
    merged = concat_streams([ewah.compress(p) for p in pieces])
    np.testing.assert_array_equal(merged, ewah.compress(whole))
    n_rows = len(whole) * 32
    assert (EwahStream(merged, n_rows).count()
            == int(np.bitwise_count(whole).sum()))


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10**6),
       st.lists(st.integers(0, 4), min_size=1, max_size=5))
def test_partitioned_segments_query_like_one(seed, weights):
    """Query-level partition property: segments sealed over any
    word-aligned split of the same rows (zero-row shards included, one
    shard fully tombstoned) return the same ingest-order row ids and
    live counts as a single-segment seal."""
    rng = np.random.default_rng(seed)
    sizes = [w * 32 for w in weights]
    n = sum(sizes)
    cols = [rng.integers(0, 6, n), rng.integers(0, 40, n)]
    spec = IndexSpec(encoding="auto")

    whole = SegmentedIndex([Segment.seal(cols, spec, row_start=0)]
                           if n else [Segment.empty(0, 0)])
    segs, pos = [], 0
    for s in sizes:
        segs.append(Segment.empty(pos, pos) if s == 0 else
                    Segment.seal([c[pos:pos + s] for c in cols], spec,
                                 row_start=pos))
        pos += s
    view = SegmentedIndex(segs)

    kill = segs[seed % len(segs)]
    dead = np.arange(kill.row_start, kill.row_stop, dtype=np.int64)
    for surface in (whole, view):
        surface.delete(row_ids=dead)

    for pred in (Eq(0, 2), Range(1, 5, 25), Not(Eq(0, 0))):
        want, _ = whole.query(pred, now=T0)
        got, _ = view.query(pred, now=T0)
        np.testing.assert_array_equal(want, got)
        assert whole.count(pred, now=T0) == view.count(pred, now=T0)
