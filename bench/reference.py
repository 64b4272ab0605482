"""The plain reference: a dense numpy evaluation of a predicate over the
generated raw columns, written apart from the index so that it can judge
it.  It imports nothing of the program: predicates are the benchmark's own
JSON-shaped trees (:mod:`bench.mix`), not the program's classes.

A predicate is a list: ``["eq", col, v]``, ``["in", col, [v, ...]]``,
``["range", col, lo, hi]`` (inclusive), ``["not", p]``, ``["and", p, ...]``
or ``["or", p, ...]``, with ``col`` a column position.
"""

from __future__ import annotations

import numpy as np


def dense_mask(pred, columns) -> np.ndarray:
    """Boolean row mask of ``pred`` over the raw columns."""
    op = pred[0]
    if op == "eq":
        return columns[pred[1]] == pred[2]
    if op == "in":
        return np.isin(columns[pred[1]], np.asarray(pred[2], dtype=np.int64))
    if op == "range":
        c = columns[pred[1]]
        return (c >= pred[2]) & (c <= pred[3])
    if op == "not":
        return ~dense_mask(pred[1], columns)
    if op in ("and", "or"):
        fold = np.logical_and if op == "and" else np.logical_or
        return fold.reduce([dense_mask(p, columns) for p in pred[1:]])
    raise ValueError(f"not a predicate: {pred!r}")


def live_mask(delete_pred, columns) -> np.ndarray:
    """Rows that survive the configuration's delete."""
    return ~dense_mask(delete_pred, columns)


def answer(pred, columns, live, kind: str):
    """What a request must return: the ascending ingest ids of the live
    rows that match (``kind="rows"``) or their number (``"count"``)."""
    mask = dense_mask(pred, columns) & live
    if kind == "rows":
        return np.flatnonzero(mask)
    if kind == "count":
        return int(np.count_nonzero(mask))
    raise ValueError(f"unknown answer kind {kind!r}")
