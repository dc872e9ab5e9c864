"""Share of the traced window of the LM fits in which the device that sets
the pace ran no operation, in percent."""


def read(ctx):
    trace = ctx["trace"]
    share = None if trace is None else trace.idle_share()
    return None if share is None else 100.0 * share
