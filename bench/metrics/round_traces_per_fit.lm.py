"""Round programs the program built (traced) per ``fit_lm`` call in the
window, from its own counter (``repro.utils.tracing``): ``round_traces``
counts the Python body of each engine's jitted ``run``, which runs only
while JAX traces it, and each call's top-level span (``gal.fit_lm``)
records what the call added to it. None without the program's spans."""
from bench.lib import program_trace


def read(ctx):
    prog = program_trace.load(ctx)
    traces = None if prog is None else prog.count("round_traces")
    return None if traces is None else traces / len(prog.fits())
