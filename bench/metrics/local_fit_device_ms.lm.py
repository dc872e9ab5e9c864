"""Device busy milliseconds per round of the organizations' local fits, in
the LM round loop (``core/gal_lm.py`` ``_fit_lm_grouped``): the pace
device's operations under the program's scope ``gal.local_fit``, each
interval counted once, over the rounds the program ran in the window (as
its fits' spans record them). None where no operation carries a program
scope."""
from bench.lib.program_trace import phase_ms_per_round


def read(ctx):
    return phase_ms_per_round(ctx, "local_fit")
