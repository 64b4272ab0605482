"""Core: the paper's contribution — EWAH compression, k-of-N encodings,
histogram-aware row/column reordering, compressed-domain logical ops — behind
one composable API: IndexSpec (strategy registry) -> IndexWriter (append /
seal / compact lifecycle) -> Segment / SegmentedIndex -> predicate algebra
(query.Eq/In/Range/And/Or/Not) -> pluggable backends.  BitmapIndex.build is
the seal-once convenience over the writer."""

from . import (column_order, encoding, encodings, ewah, ewah_stream,
               histogram, index_size, query, sorting, strategies, trace)
from .bitmap_index import BitmapIndex, assign_codes, index_size_report
from .ewah_stream import EwahStream
from .lifecycle import (BackgroundCompactor, IndexWriter, compact,
                        size_tiered_pick)
from .query import And, Eq, In, Not, Or, Range, evaluate_mask
from .segment import Segment, SegmentedIndex
from .strategies import IndexSpec

__all__ = [
    "BackgroundCompactor",
    "BitmapIndex",
    "EwahStream",
    "IndexSpec",
    "IndexWriter",
    "Segment",
    "SegmentedIndex",
    "assign_codes",
    "compact",
    "evaluate_mask",
    "index_size_report",
    "size_tiered_pick",
    "And",
    "Eq",
    "In",
    "Not",
    "Or",
    "Range",
    "column_order",
    "encoding",
    "encodings",
    "ewah",
    "ewah_stream",
    "histogram",
    "index_size",
    "query",
    "sorting",
    "strategies",
    "trace",
]

# import-cycle note: segment/lifecycle import bitmap_index at module level;
# bitmap_index reaches lifecycle lazily inside build(), so the order above
# (bitmap_index first) is load-bearing.
