"""The ``lm_fit_loop`` runner: back-to-back whole ``fit_lm``
collaborations of the configuration's organizations.

Takes no parameters of its own; the sizes are the configuration's.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import harness, lm, tabular


class Runner:
    """Back-to-back whole ``fit_lm`` collaborations, each with fresh
    organizations that start from the weights made at set-up. Reports
    label tokens times rounds completed per second over the window."""

    def __init__(self, cfg: Dict[str, Any], traffic: Dict[str, Any],
                 seed: int, devices):
        self.cfg, self.traffic, self.seed = cfg, traffic, int(seed)
        self.devices = devices
        self.counters: Dict[str, Any] = {}
        key = tabular.seed_key(self.seed)
        self.data_key = jax.random.fold_in(key, 0)
        self.weight_key = jax.random.fold_in(key, 2)
        self.fit_key = jax.random.fold_in(key, 1)

    SIZE_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff",
                 "vocab", "rope_theta", "act", "norm", "dtype")

    def model_config(self):
        """The program's architecture config with every size the file
        states; a size that differs from the program's published config
        has to be one the file lists as reduced."""
        from dataclasses import replace
        from repro.configs import get_arch
        c = self.cfg
        full = get_arch(c["arch"])
        changed = [k for k in self.SIZE_KEYS if getattr(full, k) != c[k]]
        if set(changed) - set(c["reduced"]):
            raise ValueError(f"{c['arch']}: {changed} differ from the "
                             f"published config, reduced lists "
                             f"{c['reduced']}")
        return replace(full, **{k: c[k] for k in self.SIZE_KEYS})

    def setup(self):
        from repro.models import transformer as tfm
        c = self.cfg
        self.mc = self.model_config()
        with jax.profiler.TraceAnnotation("make_data"):
            self.tokens, self.labels = lm.make_tokens(
                self.data_key, vocab=c["vocab"], batch=c["batch"],
                seq=c["seq"])
            self.dims = (c["n_layers"], c["d_model"], c["n_heads"],
                         self.mc.hd, c["d_ff"], c["vocab"], c["dtype"])
            self.weights = lm.init_weights(self.weight_key, dims=self.dims,
                                           m=c["n_orgs"])
            jax.block_until_ready((self.tokens, self.weights))
        want = jax.eval_shape(lambda k: tfm.init_params(k, self.mc),
                              jax.random.PRNGKey(0))
        got = jax.eval_shape(lambda: self.weights[0])
        if (jax.tree_util.tree_structure(want)
                != jax.tree_util.tree_structure(got)
                or jax.tree_util.tree_leaves(want)
                != jax.tree_util.tree_leaves(got)):
            raise ValueError("the benchmark's weights do not have the "
                             "program's parameter layout")
        self.result = self.fit()
        jax.block_until_ready(self.result.resume_state["f"])

    def orgs(self):
        from repro.core.gal_lm import LMOrganization
        from repro.train.steps import make_train_step
        c = self.cfg
        out = []
        for i, fn in enumerate(lm.view_fns(c["vocab"], c["n_orgs"])):
            org = LMOrganization(i, self.mc, fn)
            org.params, org.lr = self.weights[i], c["local_lr"]
            org._train_step, opt = make_train_step(
                self.mc, "gal_residual", lr=c["local_lr"], weight_decay=0.0)
            org.opt_state = opt.init(org.params)
            out.append(org)
        return out

    def fit(self):
        from repro.core.gal_lm import fit_lm
        c = self.cfg
        with jax.profiler.TraceAnnotation("org_init"):
            orgs = self.orgs()
        with jax.profiler.TraceAnnotation("fit"):
            return fit_lm(self.fit_key, orgs, self.tokens, self.labels,
                          rounds=c["rounds"], local_steps=c["local_steps"],
                          use_kernel=c["use_kernel"], engine=c["engine"])

    def window(self, seconds: float) -> Dict[str, Any]:
        loop = harness.fit_loop(self, seconds, self.fit)
        tokens = loop["rounds"] * self.cfg["batch"] * self.cfg["seq"]
        return {"attempted": loop["fits"], "failed": 0, "metrics": {
            "lm_tokens_per_s": tokens / loop["elapsed_s"]}}

    def release(self):
        res = self.result
        self.fitted = {"etas": np.asarray(res.etas, np.float64),
                       "weights": np.stack([np.asarray(w)
                                            for w in res.weights]),
                       "f": res.resume_state["f"],
                       "local": round0_local_logits(res, self.tokens)}
        del self.result, res

    def arch(self):
        """What the reference takes of the architecture, as the
        configuration states it; it has no projection bias."""
        c = self.cfg
        if c["use_qkv_bias"]:
            raise ValueError("the reference has no bias on the projections")
        return (c["n_heads"], c["rope_theta"], c["partial_rotary_factor"],
                c["layer_norm_eps"])

    def reference(self, precision: str = "highest"):
        c = self.cfg
        return lm.reference_fit(
            self.fit_key, self.weights,
            lm.views(self.tokens, c["vocab"], c["n_orgs"]), self.labels,
            rounds=c["rounds"], local_steps=c["local_steps"],
            lr=c["local_lr"], arch=self.arch(),
            weight_epochs=c["weight_epochs"], precision=precision)

    def _readings(self, etas, weights, f, local) -> Dict[str, float]:
        if not hasattr(self, "_ref"):
            self._ref = self.reference()
        return lm.gaps(etas, weights, f, local, self._ref)

    def check(self) -> List[dict]:
        f = self.fitted
        return harness.compare(
            self._readings(f["etas"], f["weights"], f["f"], f["local"]),
            self.cfg["limits"]["fit"])

    def control(self) -> Dict[str, float]:
        """The readings of the reference computed with float8 matrix
        products, put in the program's place."""
        ctl = self.reference("float8")
        return self._readings(ctl["etas"], ctl["weights"], ctl["f"],
                              ctl["local0"])


def round0_local_logits(res, tokens) -> jax.Array:
    """Each organization's round-0 fitted logits on the fit's tokens,
    (M, B*S, V), through the program's prediction stage: the fitted
    ensemble cut to round 0, with eta 1, F0 zero and all of the weight on
    that organization."""
    m = len(res.orgs)
    out = []
    for i in range(m):
        alone = dataclasses.replace(
            res, etas=[1.0], weights=[jnp.eye(m, dtype=jnp.float32)[i]],
            f0=jnp.zeros_like(res.f0))
        logits = alone.predict(tokens, rounds=1)
        out.append(logits.reshape(-1, logits.shape[-1]))
    return jnp.stack(out)
