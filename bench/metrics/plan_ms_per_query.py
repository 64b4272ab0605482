"""Planner: self time of the program's ``query.plan`` span (snapshot, live
masks, ``compile_plan`` and ``with_live_mask`` for every segment),
milliseconds per untraced window request (``bench/records.py``)."""

from bench import records


def read(run):
    return records.ms_per_query(run, {"query.plan"})
