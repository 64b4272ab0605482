"""Device, on the host's clock: self time of the program's
``query.device_wait`` span, the host blocked on the group programs'
outputs after dispatch, milliseconds per untraced window request
(``bench/records.py``)."""

from bench import records


def read(run):
    return records.ms_per_query(run, {"query.device_wait"})
