"""Names for what the program does, for a profiler trace.

* ``span(name)`` is a host span ``gal.<name>``
  (``jax.profiler.TraceAnnotation``). While no profiler is tracing it
  costs about a microsecond. A span opened inside ``fit_span`` on the same
  thread carries that fit's sequence number as its ``fit`` argument, so the
  spans of one fit share an identifier; nesting on the thread gives each
  span its parent.
* ``fit_span(name)`` is the span of one whole fit call. As it closes it
  records in its own arguments what the fit counted (``count``): the
  rounds it ran and the round programs it built (``rounds``,
  ``round_traces``), so a trace of a window holds the program's counts for
  that window. A fit reuses the round program of an earlier fit with an
  equal signature (``repro.core.engine.round_program``: the plan, the
  config, the loss and metrics or the LM's train steps and fit spec, and
  the rounds), so ``round_traces`` 0 on a fit's span means a reused
  program.
* ``scope(name)`` is a device scope ``gal.<name>`` (``jax.named_scope``):
  it names the operations traced under it in their metadata and changes no
  computation.
* ``count(name, n)`` adds ``n`` to the open fit's tally of ``name``;
  outside a fit it does nothing.
"""
from __future__ import annotations

import contextlib
import itertools
import threading
from typing import Iterator

import jax

PREFIX = "gal."
# what a fit's span records of its own tally as it closes
FIT_COUNTS = ("rounds", "round_traces")

_fit_ids = itertools.count(1)
_local = threading.local()


def count(name: str, n: int = 1) -> None:
    tally = getattr(_local, "tally", None)
    if tally is not None:
        tally[name] = tally.get(name, 0) + int(n)


def scope(name: str):
    return jax.named_scope(PREFIX + name)


def span(name: str):
    fit = getattr(_local, "fit", None)
    if fit is None:
        return jax.profiler.TraceAnnotation(PREFIX + name)
    return jax.profiler.TraceAnnotation(PREFIX + name, fit=fit)


@contextlib.contextmanager
def fit_span(name: str) -> Iterator[int]:
    outer = (getattr(_local, "fit", None), getattr(_local, "tally", None))
    _local.fit, _local.tally = next(_fit_ids), {}
    try:
        with span(name) as annotation:
            yield _local.fit
            annotation.set_metadata(
                **{k: _local.tally.get(k, 0) for k in FIT_COUNTS})
    finally:
        _local.fit, _local.tally = outer
