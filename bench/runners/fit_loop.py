"""The ``fit_loop`` runner: back-to-back whole ``gal.fit`` collaborations.

Parameters (``bench/traffic/<mix>.json``): ``holdout_rows``, the held-out
rows on which the window's last fit is compared with the reference.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import harness, tabular
from bench.lib.tabular_run import TabularRun


class Runner(TabularRun):
    """Back-to-back ``gal.fit`` collaborations on device data; each call is
    a whole T-round fit. Reports rounds completed per second over the
    window."""

    def setup(self):
        c = self.cfg
        self.xs, self.y, self.xs_test, _ = self.make_data(
            c["n_rows"], self.traffic["holdout_rows"])
        self.result = self.fit(self.xs, self.y)     # compiles, then warm
        jax.block_until_ready(self.result.stacked_params)

    def window(self, seconds: float) -> Dict[str, Any]:
        loop = harness.fit_loop(self, seconds,
                                lambda: self.fit(self.xs, self.y))
        return {"attempted": loop["fits"], "failed": 0, "metrics": {
            "fit_rounds_per_s": loop["rounds"] / loop["elapsed_s"]}}

    def release(self):
        res = self.result
        self.fitted = {
            "etas": np.asarray(res.etas, np.float64),
            "weights": np.stack([np.asarray(w) for w in res.weights]),
            "preds": np.asarray(res.predict(list(self.xs_test)))[:, 0],
            "local": round0_local_fits(res, self.xs_test)}
        del self.result, res

    def _readings(self, etas, weights, preds, local) -> Dict[str, float]:
        if not hasattr(self, "_ref"):
            ref = self.reference(self.xs, self.y)
            self._ref = (ref, tabular.reference_predict(ref, self.xs_test),
                         tabular.reference_local_fits(ref, self.xs_test))
        ref, ref_preds, ref_local = self._ref
        return tabular.gaps(etas, weights, preds, local, ref["etas"],
                            ref["weights"], ref_preds, ref_local)

    def check(self) -> List[dict]:
        f = self.fitted
        return harness.compare(
            self._readings(f["etas"], f["weights"], f["preds"], f["local"]),
            self.cfg["limits"]["fit"])

    def control(self) -> Dict[str, float]:
        """The readings of the reference computed in bfloat16, put in the
        program's place."""
        ctl = self.reference(self.xs, self.y, "bfloat16")
        return self._readings(ctl["etas"], ctl["weights"],
                              tabular.reference_predict(ctl, self.xs_test),
                              tabular.reference_local_fits(ctl, self.xs_test))


def round0_local_fits(res, xs) -> np.ndarray:
    """Each organization's round-0 fitted values on ``xs``, (M, n), through
    the program's prediction stage: the fitted ensemble cut to round 0,
    with eta 1, F0 zero and all of the weight on that organization."""
    m = len(xs)
    out = []
    for i in range(m):
        alone = dataclasses.replace(
            res, etas=[1.0], weights=[jnp.eye(m, dtype=jnp.float32)[i]],
            f0=jnp.zeros_like(res.f0))
        out.append(np.asarray(alone.predict(list(xs)))[:, 0])
    return np.stack(out)
