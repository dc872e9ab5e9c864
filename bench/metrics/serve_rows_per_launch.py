"""Request rows per device launch over the window (``serve/batcher.py``:
rows the ``MicroBatcher`` packed into each ``BucketedPredict`` launch)."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("launches"):
        return None
    return c["rows_launched"] / c["launches"]
