"""Host milliseconds per ``fit_lm`` spent tracing, lowering and compiling
(or loading from the persistent cache) inside the window: the LM fit entry
(``core/gal_lm.py`` builds its ``jax.jit`` on every call). From JAX's own
monitoring events."""


def read(ctx):
    fits = ctx["counters"].get("fits")
    if not fits:
        return None
    ev = ctx["events"]
    return 1e3 * (ev["trace_s"] + ev["lower_s"] + ev["compile_s"]) / fits
