"""Device busy milliseconds per predict launch in the traced window: what
one served batch costs the chip."""


def read(ctx):
    trace, launches = ctx["trace"], ctx["counters"].get("launches")
    if trace is None or not launches or trace.pace_device is None:
        return None
    return 1e3 * trace.busy(trace.pace_device) / launches
