"""Device busy milliseconds per round of the step-4 weight fit
(``core/weights.py`` ``fit_weights``), in the tabular round loop
(``core/engine.py`` ``_run_rounds``): the pace device's operations under
the program's scope ``gal.weight_fit``, each interval counted once, over
the rounds the program ran in the window (as its fits' spans record them).
None where no operation carries a program scope."""
from bench.lib.program_trace import phase_ms_per_round


def read(ctx):
    return phase_ms_per_round(ctx, "weight_fit")
