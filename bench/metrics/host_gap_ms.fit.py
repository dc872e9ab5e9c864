"""Milliseconds per ``gal.fit`` call in which the pace device ran nothing
while the call's top-level span (``gal.fit``) was open: the idle time the
program's own host code causes, whatever the cause. None without the
program's spans."""
from bench.lib import program_trace


def read(ctx):
    prog = program_trace.load(ctx)
    gap = None if prog is None else prog.host_gap_s()
    return None if gap is None else 1e3 * gap
