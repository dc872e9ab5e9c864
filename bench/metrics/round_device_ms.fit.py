"""Device busy milliseconds of the fit program (the program with the most
busy time on the pace device: the round loop, ``core/engine.py``
``_run_rounds``) per round completed in the traced window."""


def read(ctx):
    trace, rounds = ctx["trace"], ctx["counters"].get("rounds")
    busy = {} if trace is None else trace.module_busy()
    if not busy or not rounds:
        return None
    return 1e3 * max(busy.values()) / rounds
