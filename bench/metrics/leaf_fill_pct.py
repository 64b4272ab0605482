"""Decode: the share of the shipped leaf-stream words that are live,
100 * the program's ``leaf_words`` counter (summed stream lengths) over
its ``padded_words`` (B * m * C, every leaf padded to its group's
capacity), over the untraced window requests (``bench/records.py``).
The decode's loop runs over all ``C`` words of each leaf."""

from bench import records


def read(run):
    window = records.window(run)
    if window is None:
        return None
    padded = records.counter_sum(window, "padded_words")
    if not padded:
        return None
    return 100.0 * records.counter_sum(window, "leaf_words") / padded
