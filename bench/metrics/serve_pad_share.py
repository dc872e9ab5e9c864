"""Share of the rows launched on the device that were bucket padding
(``serve/batcher.py`` ``BucketedPredict``), in percent, over the window."""


def read(ctx):
    c = ctx["counters"]
    total = c.get("rows_launched", 0) + c.get("rows_padded", 0)
    if not total:
        return None
    return 100.0 * c["rows_padded"] / total
