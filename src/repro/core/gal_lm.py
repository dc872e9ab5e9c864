"""GAL at LM scale: the paper's protocol with assigned-architecture orgs.

Alice holds next-token labels; each organization holds a private *view* of
the token stream (vertical split, e.g. vocab factorization or a modality) and
a private sequence model (any repro.configs architecture). Per round:

  1. Alice computes the pseudo-residual r = onehot(y) - softmax(F) in logit
     space with the fused Pallas kernel (repro.kernels.residual_xent).
  2. r is broadcast — dense (paper-faithful) or top-K compressed
     (beyond-paper transport; see train.steps.gal_residual_topk_loss).
  3. Each org runs `local_steps` AdamW steps of its architecture on the
     residual-fit objective.
  4. Alice fits assistance weights on the simplex and line-searches eta.
  5. F <- F + eta * sum_m w_m f_m.

Execution mirrors the tabular engines (``repro.core.gal``): the org
execution planner (``repro.core.plan.plan_lm_orgs``) partitions the orgs
into groups keyed by (architecture config, local lr), and the **grouped
engine** runs ALL groups inside one traced round step scanned over T
rounds — a transformer org assisting an RWKV org (the paper's
model-autonomy claim at its most dramatic) compiles into a single
``lax.scan`` with one ``jax.vmap``-ed local fit per group, group fitted
values concatenated back into org order before the step-4 weight fit, and
exactly one host sync per ``fit_lm``. ``engine="scan"`` is the
single-group veneer over the same path; the **Python reference loop**
remains as a pure per-org test oracle (``tests/test_lm_conformance.py``
pins every compiled cell against it, draw for draw).

Fits are persistent and resumable like the tabular path: per-round
post-fit params ride the scan outputs (so ``GALLMResult.predict(rounds=t)``
replays any round prefix), ``repro.checkpoint.save_lm_artifact`` writes a
versioned ``gal-artifact/v1`` directory (architecture configs serialize
through the ``repro.configs`` registry schema), and
``fit_lm(..., resume_from=...)`` extends a collaboration from round
``t_next`` bitwise-identically to an uninterrupted fit.

This module stays deliberately *small*: it composes repro.core (weights,
line-search, plan), repro.train.steps (losses, local-step scan) and
repro.models (architectures).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.engine import round_program
from repro.core.losses import CrossEntropyLoss
from repro.core.plan import (ExecutionPlan, plan_lm_orgs, plan_mismatch,
                             plan_to_manifest)
from repro.core.weights import fit_weights, uniform_weights
from repro.kernels.ops import residual_xent
from repro.models import transformer as tfm
from repro.optim.lbfgs import line_search
from repro.train.steps import make_train_step, run_local_steps
from repro.utils import tracing

# the step-4 weight fit budget at LM scale: the (M, B*S, V) pred stack is
# the dominant operand, so fewer Adam epochs than the tabular default
WEIGHT_EPOCHS = 60

_ENGINES = ("auto", "scan", "grouped", "python")


def compute_residual(labels: jnp.ndarray, ensemble_logits: jnp.ndarray,
                     use_kernel: bool = True) -> jnp.ndarray:
    """r = onehot(labels) - softmax(F): (B, S) x (B, S, V) -> (B, S, V)."""
    return residual_xent(ensemble_logits, labels, use_kernel=use_kernel)


def topk_compress(residual: jnp.ndarray, k: int):
    """Keep the k largest-|r| entries per token: (vals, idx)."""
    vals, idx = jax.lax.top_k(jnp.abs(residual), k)
    vals = jnp.take_along_axis(residual, idx, axis=-1)
    return vals, idx


@dataclass
class LMOrganization:
    """One org: private token view + private architecture."""
    index: int
    cfg: ModelConfig
    view_fn: Callable[[jnp.ndarray], jnp.ndarray]   # tokens -> private view
    params: Any = None
    opt_state: Any = None
    lr: Optional[float] = None
    _train_step: Any = None

    def init(self, rng: jax.Array, lr: float = 1e-3):
        self.params = tfm.init_params(rng, self.cfg)
        self.lr = lr
        self._train_step, opt = make_train_step(
            self.cfg, "gal_residual", lr=lr, weight_decay=0.0)
        self.opt_state = opt.init(self.params)

    def fit_round(self, rng: jax.Array, tokens: jnp.ndarray,
                  residual: jnp.ndarray, local_steps: int = 10) -> jnp.ndarray:
        """Fit the broadcast residual; return f_m(x_m) on the batch."""
        del rng  # the local fit is deterministic (AdamW on the fixed batch)
        view = self.view_fn(tokens)
        batch = {"tokens": view, "residual": residual}
        self.params, self.opt_state, _ = run_local_steps(
            self._train_step, self.params, self.opt_state, batch, local_steps)
        logits, _ = tfm.apply(self.params, self.cfg, view)
        return logits.astype(jnp.float32)

    def predict(self, tokens: jnp.ndarray) -> jnp.ndarray:
        logits, _ = tfm.apply(self.params, self.cfg, self.view_fn(tokens))
        return logits.astype(jnp.float32)


@dataclass
class GALLMResult:
    """A fitted LM collaboration. Compiled-engine results carry the plan,
    per-round post-fit params per group (``predict(rounds=t)`` replays any
    prefix), and the resume carry ``save_lm_artifact`` persists."""
    orgs: List[LMOrganization]
    f0: jnp.ndarray
    etas: List[float] = field(default_factory=list)
    weights: List[jnp.ndarray] = field(default_factory=list)
    history: Dict[str, List[float]] = field(default_factory=dict)
    engine: str = "python"
    plan: Optional[ExecutionPlan] = None
    # per group: post-fit round params stacked (T, Mg, ...) — None when the
    # fit ran with store_round_params=False (resume still works; prefix
    # prediction does not)
    group_params: Optional[List[Any]] = None
    # {"t_next", "f", "active", "params": per-group, "opts": per-group}
    resume_state: Optional[Dict[str, Any]] = None
    # the fit's identity for resume validation + the artifact manifest
    fit_spec: Optional[Dict[str, Any]] = None

    @property
    def rounds(self) -> int:
        return len(self.etas)

    @property
    def vocab(self) -> int:
        return int(self.f0.shape[-1])

    def predict(self, tokens: Optional[jnp.ndarray] = None,
                rounds: Optional[int] = None,
                views: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """The Prediction Stage at round prefix ``t``:
        F_t = F_0 + sum_{tau<t} eta_tau sum_m w_m,tau f_m,tau(x_m).

        ``tokens`` (B, S) routes through the attached orgs' private
        ``view_fn``s; a loaded artifact has no orgs, so callers pass the
        pre-computed ``views`` stack (M, B, S) instead — view functions are
        private code and never touch the artifact. Returns (B, S, V)
        ensemble logits."""
        t = self.rounds if rounds is None else int(rounds)
        if not 0 <= t <= self.rounds:
            raise ValueError(f"rounds={t} outside the fitted range "
                             f"[0, {self.rounds}]")
        if views is None:
            if not self.orgs:
                raise ValueError(
                    "no organizations attached (loaded artifact): pass "
                    "views= — the (M, B, S) stack of private token views")
            views = jnp.stack([org.view_fn(tokens) for org in self.orgs])
        b, s = int(views.shape[1]), int(views.shape[2])
        v = self.vocab
        f = jnp.broadcast_to(
            jnp.reshape(self.f0, (1, v)), (b * s, v)).astype(jnp.float32)
        if t == 0:
            return f.reshape(b, s, v)
        if self.plan is None or self.group_params is None:
            raise ValueError(
                "this result does not carry per-round params (python "
                "reference results and store_round_params=False fits "
                "cannot replay round prefixes)")
        group_preds = []
        for gi, group in enumerate(self.plan.groups):
            cfg = group.model
            views_g = views[jnp.asarray(group.indices)]
            p_t = jax.tree_util.tree_map(
                lambda l: l[:t], self.group_params[gi])
            per_org = jax.vmap(
                lambda p, vv, cfg=cfg: tfm.apply(p, cfg, vv)[0])
            per_round = jax.vmap(lambda p, vg=views_g, fn=per_org: fn(p, vg))
            pred = per_round(p_t)                      # (t, Mg, B, S, V)
            group_preds.append(
                pred.astype(jnp.float32).reshape(t, group.size, b * s, v))
        preds = jnp.concatenate(group_preds, axis=1)
        inv = tuple(self.plan.inverse_permutation)
        if inv != tuple(range(len(inv))):
            preds = preds[:, jnp.asarray(inv)]
        etas = jnp.asarray(self.etas[:t], jnp.float32)
        w = jnp.stack([jnp.asarray(wt, jnp.float32)
                       for wt in self.weights[:t]])
        f = f + jnp.einsum("t,tm,tmnv->nv", etas, w, preds)
        return f.reshape(b, s, v)


def _l2(r, f):
    return jnp.mean(jnp.square(r - f))


def scan_compatible(orgs: List[LMOrganization]) -> bool:
    """True when the single-group fast path applies: one shared
    (architecture config, local lr) group, all orgs initialized. Mixed
    groups are NOT a fallback anymore — they run compiled on the grouped
    engine; this predicate only distinguishes the ``engine="scan"``
    veneer."""
    plan = plan_lm_orgs(orgs)
    return plan.compiled and plan.n_groups == 1


def _make_fit_spec(orgs, labels, local_steps, eta_method, use_weights,
                   use_kernel, eta_stop_threshold) -> Dict[str, Any]:
    return {
        "local_steps": int(local_steps), "eta_method": str(eta_method),
        "use_weights": bool(use_weights), "use_kernel": bool(use_kernel),
        "eta_stop_threshold": float(eta_stop_threshold),
        "lrs": [float(org.lr) for org in orgs],
        "batch": int(labels.shape[0]), "seq": int(labels.shape[1]),
        "vocab": int(orgs[0].cfg.vocab),
    }


def fit_lm(rng: jax.Array, orgs: List[LMOrganization], tokens: jnp.ndarray,
           labels: jnp.ndarray, rounds: int = 4, local_steps: int = 10,
           eta_method: str = "lbfgs", use_weights: bool = True,
           use_kernel: bool = False, engine: str = "auto",
           eta_stop_threshold: float = 0.0, store_round_params: bool = True,
           resume_from: Any = None) -> GALLMResult:
    """Run GAL assistance rounds on an LM task (single host scale).

    tokens/labels: (B, S) int32. The overarching loss L1 is next-token xent;
    orgs fit logit-space residuals with ell_2 (paper Table 9 defaults).

    ``engine``: auto | scan | grouped | python. ``auto`` always compiles —
    ``scan`` for a single (cfg, lr) group, ``grouped`` for mixed
    architectures/lrs; ``python`` is the per-org reference oracle.
    ``eta_stop_threshold`` stops assistance once |eta| drops below it (the
    stopping round is recorded, later rounds are frozen no-ops).
    ``resume_from`` (a compiled ``GALLMResult`` or a ``save_lm_artifact``
    directory) extends the collaboration from its ``t_next`` cursor,
    bitwise-identical to an uninterrupted ``rounds``-round fit.
    ``store_round_params=False`` drops the per-round param stack (halves
    device memory; ``predict(rounds=t)`` then needs a re-fit).

    A compiled fit reuses the round program of an earlier fit with an
    equal signature (``repro.core.engine.round_program``): the plan (each
    group's architecture config, org positions and ids), each group's
    train step by identity (``make_train_step`` returns the same step for
    equal arguments), ``local_steps``, ``use_weights``, ``use_kernel``,
    ``eta_method``, ``eta_stop_threshold``, the resume cursor, ``rounds``
    and ``store_round_params``. Batch, sequence and vocab sizes are read
    from the arguments; tokens, labels and views are arguments, never
    constants of the program.
    """
    with tracing.fit_span("fit_lm"):
        with tracing.span("plan"):
            if engine not in _ENGINES:
                raise ValueError(
                    f"unknown engine {engine!r} (one of {_ENGINES})")
            plan = plan_lm_orgs(orgs)
            if not plan.compiled:
                raise ValueError(f"cannot fit this org set: {plan.reason}")
            vocabs = {int(org.cfg.vocab) for org in orgs}
            if len(vocabs) != 1:
                raise ValueError(
                    f"all organizations must share Alice's vocab (the "
                    f"residual broadcast is one (B, S, V) tensor); got "
                    f"vocabs {sorted(vocabs)}")
            if engine == "scan" and plan.n_groups != 1:
                raise ValueError(
                    "engine='scan' needs one shared (architecture config, "
                    "lr) group across orgs — use engine='grouped' for a "
                    "mixed set: " + plan.describe())
            spec = _make_fit_spec(orgs, labels, local_steps, eta_method,
                                  use_weights, use_kernel,
                                  eta_stop_threshold)
            resume = None
            if resume_from is not None:
                if engine == "python":
                    raise ValueError(
                        "resume_from= needs a compiled engine (the python "
                        "reference loop has no resume carry)")
                resume = _prepare_lm_resume(resume_from, plan, spec, rounds)
        if engine == "python":
            return _fit_lm_python(rng, orgs, plan, tokens, labels, rounds,
                                  spec)
        label = engine if engine != "auto" else (
            "scan" if plan.n_groups == 1 else "grouped")
        return _fit_lm_grouped(rng, orgs, plan, tokens, labels, rounds, spec,
                               label, store_round_params, resume)


def _prepare_lm_resume(resume_from: Any, plan: ExecutionPlan,
                       spec: Dict[str, Any], rounds: int) -> Dict[str, Any]:
    """Validate a resume source against the supplied org set + fit
    arguments and hand back the restored carry. The gates mirror the
    tabular ``gal._prepare_resume``: identical plan partition (same groups,
    members, architecture configs), identical fit identity (local steps,
    eta method, weight/kernel flags, lrs, batch geometry), and a cursor
    strictly before the requested round count."""
    from repro.checkpoint.checkpoint import (lm_model_spec,
                                             load_lm_artifact, loss_spec)
    art = resume_from
    if isinstance(art, (str, Path)):
        art = load_lm_artifact(art)
    if not isinstance(art, GALLMResult) or art.resume_state is None:
        raise ValueError(
            "resume_from= must be a compiled-engine GALLMResult or a "
            "save_lm_artifact directory (python results have no carry)")
    manifest = plan_to_manifest(art.plan, lm_model_spec, loss_spec)
    mismatch = plan_mismatch(plan, manifest, lm_model_spec, loss_spec)
    if mismatch:
        raise ValueError(f"resume org set does not match the artifact: "
                         f"{mismatch}")
    for k, v in spec.items():
        if art.fit_spec is not None and art.fit_spec.get(k) != v:
            raise ValueError(
                f"resume fit argument mismatch: {k}={v!r} here, "
                f"{art.fit_spec.get(k)!r} in the artifact")
    rs = art.resume_state
    t_next = int(rs["t_next"])
    if rounds <= t_next:
        raise ValueError(f"rounds={rounds} <= the artifact's resume cursor "
                         f"t_next={t_next}: nothing to fit")
    return {
        "t_next": t_next, "f": rs["f"], "active": rs["active"],
        "params": tuple(rs["params"]), "opts": tuple(rs["opts"]),
        "etas_prev": list(art.etas), "weights_prev": list(art.weights),
        "hist_prev": {k: list(v) for k, v in art.history.items()},
        "group_params_prev": art.group_params,
    }


def _stack_org_trees(trees: List[Any]) -> Any:
    return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *trees)


def _fit_lm_grouped(rng, orgs, plan, tokens, labels, rounds, spec, label,
                    store_round_params, resume) -> GALLMResult:
    """The compiled path: every plan group's vmapped local fit inside ONE
    traced round step, scanned over rounds ``t0..rounds``; exactly one
    host sync for the whole fit."""
    b, s = labels.shape
    vocab = spec["vocab"]
    xent = CrossEntropyLoss()
    groups = plan.groups

    with tracing.span("stage"):
        y1 = jax.nn.one_hot(labels.reshape(-1), vocab)
        f0 = xent.init_prediction(y1)
        views = [jnp.stack([orgs[i].view_fn(tokens) for i in g.indices])
                 for g in groups]
        if resume is None:
            t0 = 0
            params0 = tuple(_stack_org_trees([orgs[i].params
                                              for i in g.indices])
                            for g in groups)
            opts0 = tuple(_stack_org_trees([orgs[i].opt_state
                                            for i in g.indices])
                          for g in groups)
            f_init = jnp.broadcast_to(jnp.reshape(f0, (1, vocab)),
                                      (b * s, vocab)).astype(jnp.float32)
            active0 = jnp.asarray(True)
        else:
            t0 = resume["t_next"]
            params0, opts0 = resume["params"], resume["opts"]
            f_init = jnp.asarray(resume["f"])
            active0 = jnp.asarray(resume["active"])

    with tracing.span("launch"):
        gal_lm_rounds = round_program(
            _lm_rounds, plan,
            tuple(orgs[g.indices[0]]._train_step for g in groups),
            spec["local_steps"], spec["use_weights"], spec["use_kernel"],
            spec["eta_method"], spec["eta_stop_threshold"], t0, rounds,
            store_round_params)
        params, opts, f_fin, active_fin, outs = gal_lm_rounds(
            rng, y1, labels, tuple(views), params0, opts0, f_init, active0)
    with tracing.span("finalize"):
        round_params = outs.pop("params", None)
        with tracing.span("sync"):
            scalars = jax.device_get(outs)        # the ONE host sync
        valid = np.asarray(scalars["valid"], bool)
        n_exec = int(valid.sum())                 # rounds actually executed
        tracing.count("rounds", n_exec)

        for g, group in enumerate(groups):        # write back evolved state
            for j, i in enumerate(group.indices):
                orgs[i].params = jax.tree_util.tree_map(
                    lambda l, j=j: l[j], params[g])
                orgs[i].opt_state = jax.tree_util.tree_map(
                    lambda l, j=j: l[j], opts[g])

    result = GALLMResult(orgs=orgs, f0=f0, engine=label, plan=plan,
                         fit_spec=spec)
    result.etas = [float(e) for e in scalars["eta"][:n_exec]]
    result.weights = [jnp.asarray(w) for w in scalars["w"][:n_exec]]
    new_xents = [float(v) for v in scalars["xent"][:n_exec]]
    if store_round_params:
        new_gp = [jax.tree_util.tree_map(lambda l: l[:n_exec], gp)
                  for gp in round_params]
    if resume is None:
        result.history["train_xent"] = [float(scalars["xent0"])] + new_xents
        if store_round_params:
            result.group_params = new_gp
    else:
        result.etas = resume["etas_prev"] + result.etas
        result.weights = resume["weights_prev"] + result.weights
        result.history = resume["hist_prev"]
        result.history["train_xent"] = (
            result.history["train_xent"] + new_xents)
        if store_round_params and resume["group_params_prev"] is not None:
            result.group_params = [
                jax.tree_util.tree_map(
                    lambda a, c: jnp.concatenate([a, c], axis=0), prev, new)
                for prev, new in zip(resume["group_params_prev"], new_gp)]
    result.resume_state = {
        "t_next": int(rounds), "f": f_fin, "active": active_fin,
        "params": tuple(params), "opts": tuple(opts),
    }
    return result


def _lm_rounds(plan: ExecutionPlan, steps: tuple, local_steps: int,
               use_weights: bool, use_kernel: bool, eta_method: str,
               thr: float, t0: int, rounds: int,
               store_round_params: bool) -> Callable:
    """The round program of ``_fit_lm_grouped`` for one signature,
    unjitted: the plan (each group's architecture config, org positions
    and ids), each group's local train step by identity (it holds the
    group's lr), the fit spec, the rounds ``t0 .. rounds`` and whether
    the per-round params are kept. The batch, sequence and vocab sizes
    are read from the arguments' shapes; the tokens' views, like all of
    the fit's data, arrive as arguments."""
    groups = plan.groups
    m = plan.n_orgs
    xent = CrossEntropyLoss()
    vsteps = [jax.vmap(step, in_axes=(0, 0, {"tokens": 0, "residual": None}))
              for step in steps]
    cfgs = [g.model for g in groups]
    sizes = [g.size for g in groups]
    inv = tuple(plan.inverse_permutation)
    permuted = inv != tuple(range(m))

    # the round program (its XLA module is ``jit_gal_lm_rounds``)
    def gal_lm_rounds(key, y1_in, labels_in, views_in, params_in, opts_in,
                      f_in, active_in):
        tracing.count("round_traces")     # runs only while JAX traces it
        b, s = labels_in.shape
        vocab = y1_in.shape[-1]

        def round_step(carry, t):
            params_l, opts_l, f, active = carry
            with tracing.scope("residual"):
                k_round = jax.random.fold_in(key, t)
                residual = compute_residual(
                    labels_in, f.reshape(b, s, vocab), use_kernel=use_kernel)
            with tracing.scope("local_fit"):
                new_params, new_opts, preds_g = [], [], []
                for g in range(len(groups)):
                    p, o, _ = run_local_steps(
                        vsteps[g], params_l[g], opts_l[g],
                        {"tokens": views_in[g], "residual": residual},
                        local_steps)
                    pred = jax.vmap(
                        lambda pp, vv, cfg=cfgs[g]: tfm.apply(pp, cfg, vv)[0]
                    )(p, views_in[g])
                    preds_g.append(pred.astype(jnp.float32).reshape(
                        sizes[g], b * s, vocab))
                    new_params.append(p)
                    new_opts.append(o)
                preds = jnp.concatenate(preds_g, axis=0)
                if permuted:                    # back to original org order
                    preds = preds[jnp.asarray(inv)]
            with tracing.scope("weight_fit"):
                if use_weights and m > 1:
                    w = fit_weights(jax.random.fold_in(k_round, 29),
                                    residual.reshape(b * s, vocab), preds,
                                    _l2, epochs=WEIGHT_EPOCHS)
                else:
                    w = uniform_weights(m)
            with tracing.scope("combine"):
                direction = jnp.einsum("m,mnk->nk", w, preds)
            with tracing.scope("eta"):
                eta = line_search(lambda e: xent(y1_in, f + e * direction),
                                  method=eta_method, x0=1.0)
                eta_eff = jnp.where(active, eta, 0.0)
                f_new = f + eta_eff * direction
                if thr > 0.0:
                    # early stop: freeze org state on inactive rounds so the
                    # final carry equals the python loop's break semantics
                    def frz(new, old):
                        return jax.tree_util.tree_map(
                            lambda a, c: jnp.where(active, a, c), new, old)
                    new_params = [frz(p, q) for p, q in zip(new_params,
                                                            params_l)]
                    new_opts = [frz(o, q) for o, q in zip(new_opts, opts_l)]
                    new_active = active & (jnp.abs(eta) >= thr)
                else:
                    new_active = active
                outs = {"eta": eta_eff,
                        "w": jnp.where(active, w, jnp.zeros_like(w)),
                        "xent": xent(y1_in, f_new), "valid": active}
            if store_round_params:
                outs["params"] = tuple(new_params)
            return (tuple(new_params), tuple(new_opts), f_new,
                    new_active), outs

        carry0 = (params_in, opts_in, f_in, active_in)
        (params, opts, f, active), outs = jax.lax.scan(
            round_step, carry0, jnp.arange(t0, rounds))
        outs["xent0"] = xent(y1_in, f_in)
        return params, opts, f, active, outs

    return gal_lm_rounds


def _fit_lm_python(rng, orgs, plan, tokens, labels, rounds,
                   spec) -> GALLMResult:
    """Reference path (pure test oracle): interpreter-order per-org
    dispatch. History is accumulated device-side and fetched once at the
    end — no per-round float() syncs. Per-round param snapshots are kept
    so ``predict(rounds=t)`` replays prefixes exactly like the compiled
    engines (arrays are immutable — snapshots are references)."""
    b, s = labels.shape
    vocab = spec["vocab"]
    xent = CrossEntropyLoss()
    y1 = jax.nn.one_hot(labels.reshape(-1), vocab)
    f0 = xent.init_prediction(y1)
    f = jnp.broadcast_to(jnp.reshape(f0, (1, vocab)),
                         (b * s, vocab)).astype(jnp.float32)
    thr = spec["eta_stop_threshold"]
    result = GALLMResult(orgs=orgs, f0=f0, engine="python", plan=plan,
                         fit_spec=spec)
    etas_d, ws, xents = [], [], [xent(y1, f)]
    param_snaps: List[List[Any]] = []             # per round: per-org params

    for t in range(rounds):
        k_round = jax.random.fold_in(rng, t)
        residual = compute_residual(
            labels, f.reshape(b, s, vocab), use_kernel=spec["use_kernel"])
        preds = []
        for org in orgs:
            fitted = org.fit_round(jax.random.fold_in(k_round, org.index),
                                   tokens, residual,
                                   local_steps=spec["local_steps"])
            preds.append(fitted.reshape(b * s, vocab))
        preds = jnp.stack(preds)                       # (M, B*S, V)
        if spec["use_weights"] and len(orgs) > 1:
            w = fit_weights(jax.random.fold_in(k_round, 29),
                            residual.reshape(b * s, vocab), preds,
                            _l2, epochs=WEIGHT_EPOCHS)
        else:
            w = uniform_weights(len(orgs))
        direction = jnp.einsum("m,mnk->nk", w, preds)
        eta = line_search(lambda e: xent(y1, f + e * direction),
                          method=spec["eta_method"], x0=1.0)
        f = f + eta * direction
        etas_d.append(eta)
        ws.append(w)
        xents.append(xent(y1, f))
        param_snaps.append([org.params for org in orgs])
        if thr > 0.0 and abs(float(eta)) < thr:
            break

    etas_h, xents_h = jax.device_get((etas_d, xents))
    tracing.count("rounds", len(etas_h))
    result.etas = [float(e) for e in etas_h]
    result.weights = ws
    result.history["train_xent"] = [float(v) for v in xents_h]
    # assemble the compiled engines' (T, Mg, ...) group layout so the
    # prediction stage is one shared code path
    result.group_params = [
        jax.tree_util.tree_map(
            lambda *rounds_: jnp.stack(rounds_),
            *[_stack_org_trees([snap[i] for i in g.indices])
              for snap in param_snaps])
        for g in plan.groups
    ] if param_snaps else [None] * plan.n_groups
    return result
