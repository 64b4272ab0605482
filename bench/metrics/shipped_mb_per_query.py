"""Host-to-device transfer: the program's ``shipped_bytes`` counter, the
padded leaf-stream batches and their length arrays sent to the device,
megabytes (1e6 bytes) per untraced window request
(``bench/records.py``)."""

from bench import records


def read(run):
    window = records.window(run)
    if window is None:
        return None
    return records.counter_sum(window, "shipped_bytes") * 1e-6 / len(window)
