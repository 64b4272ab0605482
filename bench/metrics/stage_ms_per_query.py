"""Backend staging on the host: self time of the program's
``query.lookup`` (container lowering, leaf digests, result-cache lookups,
grouping), ``query.pad`` (leaf streams padded into each group's batch)
and ``query.dispatch`` (transfer and launch) spans, milliseconds per
untraced window request (``bench/records.py``)."""

from bench import records


def read(run):
    return records.ms_per_query(
        run, {"query.lookup", "query.pad", "query.dispatch"})
