"""Index build: EWAH bytes of every sealed segment's bitmaps
(``SegmentedIndex.size_words() * 4``) per row ingested, the paper's
quantity."""


def read(run):
    if not run["rows"]:
        return None
    return 4.0 * run["index_words"] / run["rows"]
