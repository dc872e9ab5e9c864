"""Host milliseconds per fit spent tracing, lowering and compiling (or
loading from the persistent cache) inside the window: the fit entry
(``core/gal.py`` ``fit``, ``core/plan.py``, the engine's per-call
``jax.jit``). From JAX's own monitoring events."""


def read(ctx):
    fits = ctx["counters"].get("fits")
    if not fits:
        return None
    ev = ctx["events"]
    return 1e3 * (ev["trace_s"] + ev["lower_s"] + ev["compile_s"]) / fits
