"""Result unpacking on the host: self time of the program's
``query.readback``, ``query.results``, ``query.concat``,
``query.to_rows``, ``query.map_ids``, ``query.sort`` and ``query.count``
spans (device-to-host copy, stream wrapping, concatenation, rows, id
mapping, the final sort or count), milliseconds per untraced window
request (``bench/records.py``)."""

from bench import records

SPANS = {"query.readback", "query.results", "query.concat", "query.to_rows",
         "query.map_ids", "query.sort", "query.count"}


def read(run):
    return records.ms_per_query(run, SPANS)
