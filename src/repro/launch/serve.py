"""Batched serving driver with histogram-aware request packing.

Requests arrive with varying prompt lengths; batching equal-length-bin
requests together minimizes padding waste.  We sort the admission queue by
(length-bin frequency, length) — Gray-Frequency (paper §4.2) applied to the
serving plane: popular length classes form dense runs and batches.

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b [--no-smoke]
"""

from __future__ import annotations

import argparse
import time
from contextlib import contextmanager, nullcontext
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.runtime import make_lock
from repro.configs import get_config
from repro.core import BitmapIndex, Eq, IndexSpec, IndexWriter, trace
from repro.core.lifecycle import BackgroundCompactor
from repro.core.query import PLAN_STATS
from repro.dist.sharding import (batch_shardings, cache_shardings,
                                 param_shardings, replicated)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_cli_mesh
from repro.models import transformer
from repro.models.common import ShardingCtx
from repro.serve.prefill import prefill_with_cache
from repro.train import serve_step
from repro.workload import WORKLOAD_STATS


def make_requests(n, rng, max_len=96):
    """Synthetic request stream with a skewed length distribution."""
    bins = np.array([16, 24, 32, 48, 64, 96])
    probs = np.array([0.35, 0.25, 0.2, 0.1, 0.07, 0.03])
    lens = bins[rng.choice(len(bins), size=n, p=probs)]
    jitter = rng.integers(-4, 4, size=n)
    return np.clip(lens + jitter, 8, max_len)


BIN_WIDTH = 8  # length-bin granularity for admission packing


class SegmentedAdmission:
    """In-flight re-binning admission queue (the streaming serving plane).

    New requests ``admit`` into the **open segment** of an
    :class:`~repro.core.lifecycle.IndexWriter` — queryable immediately,
    no index rebuild — and every ``seal_rows`` admitted requests the
    word-aligned prefix seals into an immutable segment that serves
    concurrently through the compressed engine.  Each ``pack`` re-bins the
    *entire* queue against the live length-bin histogram (bins in
    descending frequency, the paper's Gray-Frequency order applied to
    serving), so a length class that becomes popular mid-stream promotes
    earlier requests too: admission order is re-derived in flight, never
    frozen at arrival.

    With ``compactor=True`` a
    :class:`~repro.core.lifecycle.BackgroundCompactor` merges the sealed
    admission segments off-thread (size-tiered), so sustained ingest never
    pauses for index maintenance; ``retire(row_ids)`` tombstones served
    requests (one compressed merge — the compactor purges them later), so
    the queue drains without rebuilds.  ``close()`` drains the compactor.

    With ``hosts >= 2`` the sealed segments serve through a
    :class:`~repro.dist.serve_plane.ServePlane` — a fleet of worker
    *processes*, each owning a word-aligned contiguous run of segments
    (re-homed whenever the compactor changes the segment list) and
    shipping only compressed result streams back; packs are bit-identical
    to the in-process path (docs/dist.md).
    """

    def __init__(self, backend: str = "numpy", seal_rows: int = 256,
                 compactor: bool = False, compact_interval: float = 0.02,
                 hosts: int = 0):
        self.spec = IndexSpec(row_order="unsorted", column_order="given")
        # feed the process-wide workload telemetry into compactions: the
        # background compactor re-encodes merged admission segments toward
        # the live predicate mix once enough samples accumulate
        self.writer = IndexWriter(self.spec, seal_rows=seal_rows,
                                  workload_stats=WORKLOAD_STATS)
        self._plane = None
        if hosts >= 2:
            from repro.dist.serve_plane import ServePlane

            self._plane = ServePlane(self.writer, n_hosts=hosts)
        self.backend = backend
        # _lock keeps the shadow length store and the writer append one
        # atomic admission (a pack between the two would otherwise see a
        # row the histogram doesn't, and index row ids would drift from
        # _lengths positions); ordered before the writer's own lock
        self._lock = make_lock("admission._lock")
        self._lengths: list = []       # guarded-by: _lock
        self._compactor = (BackgroundCompactor(self.writer,  # guarded-by: _lock
                                               interval=compact_interval)
                           if compactor else None)

    def admit(self, lengths) -> None:
        """Append arriving request lengths to the open segment."""
        lengths = np.asarray(lengths)
        if len(lengths):
            with self._lock:
                self._lengths.append(lengths)
                self.writer.append([lengths // BIN_WIDTH])

    def retire(self, row_ids) -> int:
        """Tombstone served requests so later packs skip them; returns the
        newly-retired count."""
        row_ids = np.asarray(row_ids, dtype=np.int64)
        if self._plane is not None:
            # the plane broadcasts the tombstones to owning workers too
            return self._plane.delete(row_ids=row_ids)
        return self.writer.delete(row_ids=row_ids)

    def close(self) -> None:
        """Drain and stop the background compactor, if one is running,
        then shut down the serve-plane worker fleet (plane mode)."""
        with self._lock:
            comp, self._compactor = self._compactor, None
        if comp is not None:
            # off-lock: draining joins the scheduler thread, whose
            # compactions must not wait on an admission-held lock
            comp.close()
        if self._plane is not None:
            self._plane.close()

    @property
    def lengths(self) -> np.ndarray:
        with self._lock:
            return (np.concatenate(self._lengths) if self._lengths
                    else np.zeros(0, dtype=np.int64))

    @property
    def n_segments(self) -> int:
        return len(self.writer.segments)

    def pack(self, batch_size: int) -> list:
        """Re-bin the whole queue and emit index-batches (one Eq(bin) plan
        per bin over sealed segments + the open buffer, bins in descending
        frequency, lengths ascending within a bin)."""
        # _lock spans the lengths snapshot AND the index query: an admit
        # landing between the two would return row ids the snapshot does
        # not cover yet (lengths[rows] IndexError / wrong-bin placement)
        with self._lock:
            lengths = (np.concatenate(self._lengths) if self._lengths
                       else np.zeros(0, dtype=np.int64))
            if not len(lengths):
                return []
            bins = lengths // BIN_WIDTH
            uniq, counts = np.unique(bins, return_counts=True)
            by_freq = uniq[np.lexsort((uniq, -counts))]
            preds = [Eq(0, int(b)) for b in by_freq]
            # plane mode fans the per-bin plans out across the worker
            # processes; results are bit-identical to the local engine
            surface = (self._plane if self._plane is not None
                       else self.writer.index)
            results = surface.query_many(preds, backend=self.backend)
        order = np.concatenate(
            [rows[np.argsort(lengths[rows], kind="stable")]
             for rows, _ in results])
        return [order[i : i + batch_size]
                for i in range(0, len(order), batch_size)]


def pack_batches(lengths, batch_size, histogram_aware=True, backend="numpy",
                 query_fanout=0, admission="rebuild", compactor=False,
                 hosts=0):
    """Return list of index-batches; histogram-aware = Gray-Frequency order.

    The histogram-aware path runs through the bitmap query plane: a bitmap
    index over the length-bin column, one Eq(bin) plan per bin, bins admitted
    in descending frequency (paper §4.2 applied to serving), lengths
    ascending within a bin.  With backend="jax" all per-bin plans share one
    batched device dispatch (same plan shape -> one padded kernel launch).
    With query_fanout > 1 the admission index shards over word-aligned row
    ranges (repro.dist.query_fanout) and every per-bin plan fans out, each
    shard shipping its compressed result stream — the multi-host admission
    topology, exercised in-process.

    ``admission="segmented"`` exercises the streaming path instead of a
    one-shot rebuild: lengths arrive in waves through
    :class:`SegmentedAdmission` (appends to the open segment, auto-seals,
    sealed segments serve concurrently) and the final ``pack`` re-bins
    everything in flight.  ``compactor=True`` (segmented mode only) runs a
    :class:`~repro.core.lifecycle.BackgroundCompactor` during the waves, so
    packing also exercises concurrent off-thread compaction.  Batches are
    identical to the rebuild path — the lifecycle changes *when* index work
    happens, not the answer.

    ``hosts >= 2`` (segmented mode only) serves the sealed admission
    segments through a :class:`~repro.dist.serve_plane.ServePlane` worker
    fleet — each pack's per-bin plans fan out across processes and only
    compressed result streams come back.
    """
    lengths = np.asarray(lengths)
    n = len(lengths)
    if compactor and admission != "segmented":
        raise ValueError(
            "compactor=True requires admission='segmented' (the rebuild "
            "path has no writer to compact)")
    if hosts >= 2 and admission != "segmented":
        raise ValueError(
            "hosts>=2 requires admission='segmented' (the serve plane "
            "wraps the segmented writer)")
    if not histogram_aware:
        order = np.arange(n)
        return [order[i : i + batch_size] for i in range(0, n, batch_size)]
    if admission == "segmented":
        if query_fanout > 1:
            raise ValueError(
                "segmented admission and query_fanout are separate "
                "topologies; pick one")
        q = SegmentedAdmission(backend=backend, compactor=compactor,
                               hosts=hosts)
        try:
            waves = max(1, min(4, n // max(batch_size, 1)))
            for chunk in np.array_split(lengths, waves):
                q.admit(chunk)
            return q.pack(batch_size)
        finally:
            q.close()
    if admission != "rebuild":
        raise ValueError(f"unknown admission mode {admission!r}; "
                         "known: rebuild, segmented")
    bins = lengths // BIN_WIDTH
    spec = IndexSpec(row_order="unsorted", column_order="given")
    uniq, counts = np.unique(bins, return_counts=True)
    by_freq = uniq[np.lexsort((uniq, -counts))]
    if query_fanout > 1:
        from repro.dist.query_fanout import ShardedIndex

        sidx = ShardedIndex.build([bins], spec, n_shards=query_fanout)
        # unsorted row order keeps row_perm the identity, so fan-out's
        # original-space ids are directly comparable to the single
        # path; query_many keeps all bins' per-shard plans in one
        # backend call (same-shape plans batch across bins and shards)
        results = sidx.query_many([Eq(0, int(b)) for b in by_freq],
                                  backend=backend)
    else:
        idx = BitmapIndex.build([bins], spec)
        results = idx.query_many([Eq(0, int(b)) for b in by_freq],
                                 backend=backend)
    order = np.concatenate(
        [rows[np.argsort(lengths[rows], kind="stable")]
         for rows, _ in results])
    return [order[i : i + batch_size] for i in range(0, n, batch_size)]


class PhaseProfile:
    """Wall-clock accounting per serving phase — the top-ops summary
    ``serve --profile`` prints next to the JAX profiler trace (the trace
    has per-HLO detail for TensorBoard; this table answers "where did the
    wall time go" without leaving the terminal).  Spans are cheap enough
    to always run; callers block on device results inside a span only
    when profiling, so honest per-phase attribution never perturbs the
    unprofiled path's async dispatch pipelining."""

    def __init__(self):
        self.acc: dict = {}

    @contextmanager
    def span(self, name: str):
        """The phase as a :class:`repro.core.trace.span` named
        ``serve.<name>``, its seconds added to ``name``."""
        s = trace.span(f"serve.{name}")
        try:
            with s:
                yield
        finally:
            self.acc[name] = self.acc.get(name, 0.0) + s.seconds

    def report(self, total: float | None = None) -> None:
        tot = total or sum(self.acc.values()) or 1.0
        print("# top serving phases (wall-clock)")
        for name, s in sorted(self.acc.items(), key=lambda kv: -kv[1]):
            print(f"  {name:<12} {s * 1e3:9.1f} ms  {s / tot:6.1%}")


def padding_waste(lengths, batches):
    total = 0
    used = 0
    for b in batches:
        l = lengths[b]
        total += int(l.max()) * len(b)
        used += int(l.sum())
    return 1.0 - used / max(total, 1)


def main(argv=None):
    """Serve ``--requests`` synthetic requests; returns a summary dict
    (``requests``, ``answered``: distinct requests that were packed and
    decoded, ``tokens``, ``finite``: every prefill's logits finite,
    ``seconds``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the architecture's reduced smoke config "
                         "(--no-smoke: its published widths)")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--gen-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--mesh", default=None,
                    help="data,model (default: all devices data-parallel)")
    ap.add_argument("--query-backend", default="numpy",
                    choices=("numpy", "jax"),
                    help="query-plane backend for admission packing")
    ap.add_argument("--query-fanout", type=int, default=0,
                    help="shard the admission index over N word-aligned row "
                         "ranges and fan every packing query out across "
                         "them (0/1 = single index)")
    ap.add_argument("--admission", default="rebuild",
                    choices=("rebuild", "segmented"),
                    help="'segmented' streams requests through an "
                         "IndexWriter (in-flight re-binning: appends hit "
                         "the open segment, sealed segments serve "
                         "concurrently) instead of rebuilding the "
                         "admission index per pack")
    ap.add_argument("--compactor", action="store_true",
                    help="run a background compactor thread over the "
                         "segmented admission writer while requests stream "
                         "in (requires --admission segmented)")
    ap.add_argument("--hosts", type=int, default=0,
                    help="serve sealed admission segments through a "
                         "multi-process ServePlane with N segment-owning "
                         "worker processes; only compressed result streams "
                         "cross the wire (requires --admission segmented; "
                         "0/1 = in-process)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="emit a JAX profiler trace of the serving loop to "
                         "DIR (read with: tensorboard --logdir DIR) plus a "
                         "wall-clock top-phase summary on stdout; see "
                         "docs/fusion.md for the reading workflow")
    ap.add_argument("--plan-stats", default=None, metavar="PATH",
                    help="persist the query plan-shape recorder "
                         "(core.query.PLAN_STATS): load at startup so the "
                         "jax backend warms up with last run's autotuned "
                         "capacity buckets, autotune + save at exit")
    ap.add_argument("--workload-stats", default=None, metavar="PATH",
                    help="persist the workload telemetry recorder "
                         "(repro.workload.WORKLOAD_STATS): load at startup "
                         "so compaction's cost model starts warm with last "
                         "run's predicate mix, save at exit")
    args = ap.parse_args(argv)
    if args.hosts >= 2 and args.query_backend == "jax":
        ap.error("--hosts workers are CPU processes that cannot share the "
                 "accelerator; use --query-backend numpy with --hosts")

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    rng = np.random.default_rng(0)

    if args.plan_stats:
        warm = PLAN_STATS.load(args.plan_stats)
        print(f"plan-stats {'loaded from' if warm else 'cold start at'} "
              f"{args.plan_stats}: buckets {list(PLAN_STATS.boundaries)}")

    if args.workload_stats:
        warm = WORKLOAD_STATS.load(args.workload_stats)
        print(f"workload-stats {'loaded from' if warm else 'cold start at'} "
              f"{args.workload_stats}: {WORKLOAD_STATS.stats()}")

    mesh = make_cli_mesh(args.mesh)
    dp = mesh.shape["data"]
    # batches smaller than the data axis fall back to replication
    rules = {"batch": None} if args.batch % dp else None

    with ShardingCtx(mesh, rules):
        p_sh = param_shardings(mesh, cfg, rules=rules)
        c_sh = cache_shardings(mesh, cfg, rules=rules)
        tok_sh = batch_shardings(mesh, cfg, "decode", rules=rules)["tokens"]
        params = jax.jit(lambda k: transformer.init_params(k, cfg),
                         out_shardings=p_sh)(jax.random.PRNGKey(0))

        lengths = make_requests(args.requests, rng)
        for mode in (False, True):
            batches = pack_batches(lengths, args.batch, histogram_aware=mode,
                                   backend=args.query_backend,
                                   query_fanout=args.query_fanout,
                                   admission=args.admission,
                                   compactor=args.compactor,
                                   hosts=args.hosts if mode else 0)
            waste = padding_waste(lengths, batches)
            print(f"packing histogram_aware={mode} "
                  f"(query backend {args.query_backend}, "
                  f"fanout {args.query_fanout}, "
                  f"admission {args.admission}, "
                  f"hosts {args.hosts}): "
                  f"padding waste {waste:.1%}")

        prof = PhaseProfile()
        with prof.span("pack"):
            batches = pack_batches(lengths, args.batch, histogram_aware=True,
                                   backend=args.query_backend,
                                   query_fanout=args.query_fanout,
                                   admission=args.admission,
                                   compactor=args.compactor,
                                   hosts=args.hosts)
        step = jax.jit(partial(serve_step, cfg=cfg),
                       in_shardings=(p_sh, tok_sh, c_sh, replicated(mesh)),
                       out_shardings=(tok_sh, c_sh), donate_argnums=(2,))
        prefill = jax.jit(
            lambda p, toks: prefill_with_cache(p, cfg, toks, args.max_len),
            in_shardings=(p_sh, tok_sh), out_shardings=(None, c_sh))
        # --profile wraps the loop in a JAX profiler trace (per-HLO detail
        # for TensorBoard); spans block on device results only then, so
        # the unprofiled path keeps its async dispatch pipelining
        trace_cm = (jax.profiler.trace(args.profile) if args.profile
                    else nullcontext())
        t0 = time.time()
        generated = 0
        finite, tok = jnp.bool_(True), None
        with trace_cm:
            for bi, idx in enumerate(batches):
                b = len(idx)
                # ragged tail: pad to the full batch (one compiled shape,
                # and the data axis always divides); surplus rows are
                # dropped on count
                if b < args.batch:
                    idx = np.concatenate(
                        [idx, np.repeat(idx[-1], args.batch - b)])
                # pad to a 16-token bucket so jit reuses compiled prefill
                # variants
                prompt_len = min(-(-int(lengths[idx].max()) // 16) * 16,
                                 args.max_len - args.gen_tokens)
                prompts = rng.integers(0, cfg.vocab_size,
                                       size=(args.batch, prompt_len),
                                       dtype=np.int32)
                # fused prefill: one forward pass fills the whole KV cache
                with prof.span("prefill"):
                    logits, cache = prefill(params, jnp.asarray(prompts))
                    if args.profile:
                        jax.block_until_ready(cache)
                finite &= jnp.isfinite(logits).all()
                tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
                cache_len = jnp.int32(prompt_len)
                generated += b
                for t in range(args.gen_tokens - 1):
                    with prof.span("decode"):
                        tok, cache = step(params, tok, cache, cache_len)
                        if args.profile:
                            jax.block_until_ready(tok)
                    cache_len += 1
                    generated += b
            # the clock stops when the last token exists, not when the
            # last step was enqueued
            jax.block_until_ready((finite, tok))
    dt = time.time() - t0
    answered = len(np.unique(np.concatenate(batches))) if batches else 0
    print(f"served {len(lengths)} requests, {generated} tokens "
          f"in {dt:.1f}s ({generated/dt:.1f} tok/s)")
    if args.profile:
        print(f"profiler trace written to {args.profile} "
              f"(tensorboard --logdir {args.profile})")
        prof.report()
    if args.plan_stats:
        PLAN_STATS.autotune()
        PLAN_STATS.save(args.plan_stats)
        print(f"plan-stats saved to {args.plan_stats}: {PLAN_STATS.stats()}")
    if args.workload_stats:
        WORKLOAD_STATS.save(args.workload_stats)
        print(f"workload-stats saved to {args.workload_stats}: "
              f"{WORKLOAD_STATS.stats()}")
    return {"requests": len(lengths), "answered": answered,
            "tokens": generated, "finite": bool(finite), "seconds": dt}


if __name__ == "__main__":
    main()
