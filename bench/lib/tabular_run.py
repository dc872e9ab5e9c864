"""What the tabular runners share: the configuration's data made on the
device from the seed, its fit through ``gal.fit``, and the reference fit of
``bench/lib/tabular.py``."""
from __future__ import annotations

from typing import Any, Dict

import jax

from bench.lib import tabular


def _zoo():
    from repro.models import zoo
    return zoo


class TabularRun:
    """Shared by the tabular runners: the configuration's data, its fit
    through ``gal.fit`` and the reference fit. The organizations' model,
    the loss and the eta method come from the configuration; the reference
    is written for the ones in ``MODELS``, ``LOSSES`` and ``ETA_METHODS``,
    and any other is refused before anything runs."""

    MODELS = {"linear": lambda c: _zoo().Linear(ridge=c["ridge"])}
    LOSSES = ("mse",)
    ETA_METHODS = ("lbfgs",)

    def __init__(self, cfg: Dict[str, Any], traffic: Dict[str, Any],
                 seed: int, devices):
        for key, known in (("model", self.MODELS), ("loss", self.LOSSES),
                           ("eta_method", self.ETA_METHODS)):
            if cfg[key] not in known:
                raise ValueError(f"{cfg['name']}: {key} {cfg[key]!r} has no "
                                 f"reference here (known: {sorted(known)})")
        self.cfg, self.traffic, self.seed = cfg, traffic, int(seed)
        self.devices = devices
        self.counters: Dict[str, Any] = {}
        key = tabular.seed_key(self.seed)
        self.data_key = jax.random.fold_in(key, 0)
        self.fit_key = jax.random.fold_in(key, 1)

    def make_data(self, n_rows: int, n_test: int):
        c = self.cfg
        with jax.profiler.TraceAnnotation("make_data"):
            out = tabular.make_data(self.data_key, n=n_rows,
                                    d=c["n_features"], m=c["n_orgs"],
                                    n_test=n_test)
            jax.block_until_ready(out)
        return out

    def gal_config(self):
        from repro.core.gal import GALConfig
        c = self.cfg
        return GALConfig(rounds=c["rounds"], eta_method=c["eta_method"],
                         weight_epochs=c["weight_epochs"],
                         weight_lr=c["weight_lr"],
                         weight_decay=c["weight_decay"], engine=c["engine"])

    def fit(self, xs, y):
        from repro.core import gal
        from repro.core.losses import get_loss
        from repro.core.organizations import make_orgs
        c = self.cfg
        with jax.profiler.TraceAnnotation("org_init"):
            orgs = make_orgs(list(xs), self.MODELS[c["model"]](c))
        with jax.profiler.TraceAnnotation("fit"):
            result = gal.fit(self.fit_key, orgs, y, get_loss(c["loss"]),
                             self.gal_config())
        return result

    def reference(self, xs, y, precision: str = "highest"):
        c = self.cfg
        return tabular.reference_fit(
            self.fit_key, xs, y, rounds=c["rounds"], ridge=c["ridge"],
            weight_epochs=c["weight_epochs"], weight_lr=c["weight_lr"],
            weight_decay=c["weight_decay"], precision=precision)
