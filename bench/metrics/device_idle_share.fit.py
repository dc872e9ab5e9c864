"""Share of the traced window in which the device that sets the pace (the
one with the most busy time) ran no operation, in percent."""


def read(ctx):
    trace = ctx["trace"]
    share = None if trace is None else trace.idle_share()
    return None if share is None else 100.0 * share
