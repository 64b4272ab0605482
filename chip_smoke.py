"""Drive the system's main path once on one TPU chip and check the answers.

Two phases run in this one process, which alone holds the chip, on data
made from ``--seed``:

* **index** — a TPC-H ``lineitem``-shaped table from the paper's DBGEN
  profile (``repro.data.tables.make_dbgen_like``: cardinalities 7, 11,
  2526 and n/35) of 8 x 1,048,544 rows is ingested through
  ``IndexWriter(IndexSpec(encoding="auto"), seal_rows=1_048_544)``: eight
  sealed segments of exactly ``MAX_DIRTY`` = 32767 words, the largest a
  result can have and still be re-encoded on the device.  About 1% of
  the rows are tombstoned by a predicate.  Then 32 predicates mixing
  Eq, In, Range (bit-sliced and equality-encoded), And, Or and Not go
  through ``SegmentedIndex.query_many(..., backend="jax")`` and
  ``count``.  Every answer must be bit-identical to ``backend="numpy"``
  (computed per segment in CPU worker processes, which never touch the
  chip) and equal to a dense numpy mask over the raw columns.
* **serve** — ``repro.launch.serve.main`` at the published tinyllama-1.1b
  widths (random weights from a fixed seed) with segmented admission
  packed by the jax query backend; every request must be answered with
  finite logits.

The last line of standard output is ``{"ok": true, "device": {...}}``,
printed only when both phases pass.  Without a TPU the script refuses to
start; any failure exits non-zero without that line.

  python chip_smoke.py [--seed 0]
"""

from __future__ import annotations

import argparse
import json
import multiprocessing
import os
import sys
import time
from concurrent.futures import ProcessPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

SEGMENTS = 8
SEAL_ROWS = 1_048_544          # 32767 words of 32 rows: ewah.MAX_DIRTY
GONE = (2, 100, 124)           # Range tombstoned: 25 of 2526 ship dates, ~1%
SERVE_ARGV = ["--arch", "tinyllama-1.1b", "--no-smoke",
              "--query-backend", "jax", "--admission", "segmented",
              "--requests", "16", "--batch", "8", "--gen-tokens", "4"]


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing answer."""


def _repro():
    """Import the package from the checkout this script sits in."""
    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SmokeFailure(f"no repro package under {src}: run this script "
                           "from a checkout of the repository")
    if src not in sys.path:
        sys.path.insert(0, src)


def dense_mask(pred, columns) -> np.ndarray:
    """Straightforward dense evaluation of a predicate over raw columns,
    written apart from the index so it can judge it."""
    from repro.core import And, Eq, In, Not, Or, Range

    if isinstance(pred, Eq):
        return columns[pred.col] == pred.value
    if isinstance(pred, In):
        return np.isin(columns[pred.col], pred.values)
    if isinstance(pred, Range):
        c = columns[pred.col]
        return (c >= pred.lo) & (c <= pred.hi)
    if isinstance(pred, Not):
        return ~dense_mask(pred.child, columns)
    if isinstance(pred, (And, Or)):
        masks = [dense_mask(c, columns) for c in pred.children]
        fold = np.logical_and if isinstance(pred, And) else np.logical_or
        return fold.reduce(masks)
    raise TypeError(f"not a predicate: {pred!r}")


def predicates(cards, rng) -> list:
    """32 predicates over the four lineitem-shaped columns: 0 and 1 are
    small domains (equality-encoded under ``auto``), 2 and 3 large ones
    (bit-sliced)."""
    from repro.core import And, Eq, In, Not, Or, Range

    def val(c):
        return int(rng.integers(0, cards[c]))

    def span(c, width):
        width = min(width, cards[c] - 1)  # small rehearsal tables
        lo = int(rng.integers(0, cards[c] - width))
        return Range(c, lo, lo + width - 1)

    def some(c, k):
        return In(c, rng.choice(cards[c], size=k, replace=False))

    preds = [Eq(0, val(0)), Eq(1, val(1)), Eq(2, val(2)), Eq(3, val(3)),
             some(0, 3), some(1, 4), some(2, 8), some(3, 5),
             span(0, 3), span(1, 5), span(2, 30), span(2, 365),
             span(2, 1500), span(3, 1000), span(3, cards[3] // 2),
             Range(2, 0, cards[2] - 1),
             Not(Eq(1, val(1))), Not(span(2, 700)), Not(some(0, 2))]
    preds += [And(span(2, 365), span(1, 3), Range(0, 0, 3)),
              And(Eq(0, val(0)), Eq(1, val(1))),
              And(span(2, 90), Not(Eq(1, val(1)))),
              And(span(3, 20_000), span(2, 200)),
              Or(Eq(0, val(0)), Eq(1, val(1))),
              Or(span(2, 40), span(3, 500)),
              Or(And(Eq(0, val(0)), span(2, 100)), Eq(3, val(3))),
              Not(And(span(1, 6), span(2, 1200))),
              Not(Or(Eq(0, val(0)), span(3, 50_000))),
              And(Or(some(1, 3), span(2, 60)), Not(span(3, 10_000))),
              And(Eq(0, val(0)), Eq(1, val(1)), span(2, 500), span(3, 80_000)),
              Or(span(0, 2), span(1, 2), span(2, 10), span(3, 10)),
              And(Not(Eq(0, val(0))), Not(Eq(1, val(1))), span(2, 2000))]
    return preds


def build(seed: int, segments: int, seal_rows: int, only=None):
    """The generated table and its index with ~1% of the rows tombstoned:
    ``(columns, writer, deleted)``.  With ``only``, just that segment is
    sealed (alone it seals to the same bitmaps it has in the whole)."""
    from repro.core import IndexSpec, IndexWriter, Range
    from repro.data.tables import make_dbgen_like

    columns = make_dbgen_like(segments * seal_rows, seed=seed)
    writer = IndexWriter(IndexSpec(encoding="auto"), seal_rows=seal_rows)
    for s in range(segments) if only is None else [only]:
        # one append per segment: each seals whole
        writer.append([c[s * seal_rows:(s + 1) * seal_rows] for c in columns])
    return columns, writer, writer.delete(Range(*GONE))


def _cpu_worker():
    """Pool initializer: reference workers run on the CPU, before anything
    imports jax, because the chip belongs to the parent process."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    _repro()


def numpy_reference(seed: int, segments: int, seal_rows: int, s: int,
                    preds) -> list:
    """Segment ``s``'s compressed answers from the numpy backend, one
    stream per predicate."""
    _, writer, _ = build(seed, segments, seal_rows, only=s)
    results = writer.index.execute_compressed_many(preds, backend="numpy")
    return [per_seg[0].data for per_seg, _ in results]


def index_phase(seed: int, segments: int = SEGMENTS,
                seal_rows: int = SEAL_ROWS, log=print) -> dict:
    """Build, tombstone, query and check; returns the phase's counts and
    times.  Raises :class:`SmokeFailure` on any wrong answer."""
    from repro.core import Range
    from repro.core.query import get_backend

    t0 = time.perf_counter()
    columns, writer, deleted = build(seed, segments, seal_rows)
    build_s = time.perf_counter() - t0
    n = segments * seal_rows
    index = writer.index
    if index.n_segments != segments or writer.snapshot()[1] is not None:
        raise SmokeFailure(f"expected {segments} sealed segments and no open "
                           f"buffer, got {index.n_segments}")
    live = ~dense_mask(Range(*GONE), columns)
    if deleted != n - int(live.sum()):
        raise SmokeFailure(f"delete tombstoned {deleted} rows, the dense "
                           f"mask says {n - int(live.sum())}")
    log(f"index: {n} rows in {segments} segments "
        f"(encodings {index.encodings()[0]}), table generated and built "
        f"on the host in {build_s:.3f} s, {deleted} rows tombstoned")

    preds = predicates([int(c.max()) + 1 for c in columns],
                       np.random.default_rng(seed))
    be = get_backend("jax")
    t0 = time.perf_counter()
    got = index.query_many(preds, backend="jax")  # host arrays: device done
    cold_s = time.perf_counter() - t0
    paths = be.group_paths()
    be.result_cache.clear()
    t0 = time.perf_counter()
    again = index.query_many(preds, backend="jax")
    warm_s = time.perf_counter() - t0

    # the numpy backend answers segment by segment in CPU worker processes
    # (its cursor engine is pure Python, about 40 s per segment here) while
    # this process checks the device's answers against the dense mask
    t0 = time.perf_counter()
    with ProcessPoolExecutor(
            max_workers=min(segments, os.cpu_count() or 1),
            mp_context=multiprocessing.get_context("spawn"),
            initializer=_cpu_worker) as pool:
        refs = [pool.submit(numpy_reference, seed, segments, seal_rows, s,
                            preds) for s in range(segments)]
        dev = index.execute_compressed_many(preds, backend="jax")
        for i, pred in enumerate(preds):
            truth = np.flatnonzero(dense_mask(pred, columns) & live)
            checks = {
                "jax rows == dense mask": np.array_equal(got[i][0], truth),
                "warm rows == cold rows": np.array_equal(again[i][0],
                                                         got[i][0]),
                "count == dense count": (index.count(pred, backend="jax")
                                         == len(truth)),
            }
            bad = [k for k, ok in checks.items() if not ok]
            if bad:
                raise SmokeFailure(f"predicate {i} {pred!r}: "
                                   f"{', '.join(bad)}")
        for s, ref in enumerate(refs):
            for i, want in enumerate(ref.result()):
                if not np.array_equal(dev[i][0][s].data, want):
                    raise SmokeFailure(
                        f"predicate {i} {preds[i]!r}: segment {s}'s jax "
                        "stream differs from the numpy backend's")
    check_s = time.perf_counter() - t0
    if paths["host_reencode"]:
        raise SmokeFailure(f"{paths['host_reencode']} plan groups were "
                           "re-encoded on the host")
    matched = sum(len(r) for r, _ in got)
    log(f"index: {len(preds)} predicates, {matched} matching rows in all, "
        "every segment's stream bit-identical to the numpy backend, rows "
        "and counts equal to the dense mask")
    log(f"index: plan groups fused {paths['fused']}, per-stage "
        f"{paths['staged']}, host re-encode {paths['host_reencode']}; "
        f"interpret={be.interpret}")
    log(f"index: query_many cold {cold_s:.3f} s (compile included), "
        f"warm {warm_s:.3f} s (result cache cleared); checking against "
        f"the numpy backend and the dense mask took {check_s:.3f} s")
    return {"rows": n, "deleted": deleted, "predicates": len(preds),
            "paths": paths, "interpret": be.interpret,
            "cold_s": cold_s, "warm_s": warm_s}


def serve_phase(argv=SERVE_ARGV, log=print) -> dict:
    """The serve launcher through its own ``main``; checks that every
    request was answered with finite logits."""
    from repro.launch.serve import main as serve_main

    out = serve_main(list(argv))
    want_tokens = out["requests"] * int(argv[argv.index("--gen-tokens") + 1])
    if not (out["answered"] == out["requests"] and out["finite"]
            and out["tokens"] == want_tokens):
        raise SmokeFailure(f"serve answered {out['answered']} of "
                           f"{out['requests']} requests, {out['tokens']} "
                           f"tokens (want {want_tokens}), finite logits "
                           f"{out['finite']}")
    log(f"serve: {out['answered']}/{out['requests']} requests, "
        f"{out['tokens']} tokens in {out['seconds']:.3f} s (compile included)")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated table and predicates")
    args = ap.parse_args(argv)
    try:
        _repro()
        import jax

        from repro.launch.compile_cache import enable_compile_cache

        devices = jax.devices()
        dev = devices[0]
        if dev.platform != "tpu":
            raise SmokeFailure(f"no TPU: JAX found {dev.platform!r} devices; "
                               "this smoke runs on the chip only")
        print(f"device: {dev.device_kind} x {len(devices)}, compile cache "
              f"{enable_compile_cache()}", flush=True)
        t0 = time.perf_counter()
        index = index_phase(args.seed)
        if index["interpret"] is not False:
            raise SmokeFailure("the jax backend resolved interpret=True")
        serve_phase()
        print(f"both phases passed in {time.perf_counter() - t0:.3f} s")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
