"""The GAL round engine (paper Algorithm 1), from Alice's perspective.

Per assistance round t:
  1. r^t   = -dL1(y, F^{t-1})/dF          (pseudo-residual, Alice)
  2. broadcast r^t (optionally privatized: DP/IP)          -> all orgs
  3. f_m^t = argmin_{f in F_m} E_N ell_m(r^t, f(x_m))       (orgs, parallel)
  4. w-hat = argmin_{w in simplex} E_N ell_1(r^t, sum w_m f_m^t)   (Alice)
  5. eta-hat = argmin_eta E_N L1(y, F^{t-1} + eta sum w_m f_m^t)   (Alice, L-BFGS)
  6. F^t = F^{t-1} + eta-hat * sum_m w-hat_m f_m^t

Prediction stage: F^T(x*) = F^0 + sum_t eta^t sum_m w_m^t f_m^t(x_m*).

Engine selection is driven by the org execution planner
(``repro.core.plan.plan_orgs``), which partitions the organizations into
homogeneous groups (model signature, local ell_q, noise sigma, slice rank)
or names the reason the compiled engines cannot run. Four executions of the
same algorithm live here:

  * the **org-sharded multi-device path** (``repro.core.engine.fit_shard``):
    single-group noiseless plans with the org axis mapped onto a real
    device mesh — one organization per device along an "org" axis; residual
    broadcast / fitted-value gather / weighted direction run as real
    collectives (``GALConfig.engine="shard"`` forces it);
  * the **grouped fused engine** (``repro.core.engine.fit_grouped``): ANY
    plan the planner compiles — heterogeneous model autonomy (the paper's
    GB–SVM mix), per-org local losses (ell_q or any traceable custom
    callable via the autodiff-residual path), noisy orgs, and Deep Model
    Sharing (shared extractor in the scan carry, per-round heads stacked
    on a (T, ...) axis) — one vmap per group inside the same scanned round
    step, group fitted values concatenated in org order before the weight
    fit, single host sync per ``fit``; on a matching device count the
    group stacks shard over an "org" mesh (``GALConfig.engine="grouped"``
    forces it);
  * the **scan fast path** (``repro.core.engine.fit_scan``): the legacy
    single-group veneer over the grouped engine for homogeneous orgs
    (``GALConfig.engine="scan"`` forces it);
  * the **Python reference path**: per-org dispatch in interpreter order —
    now a pure TEST ORACLE (``tests/test_conformance.py``); the remaining
    TRUE fallbacks are genuinely non-array inputs, non-scan-safe models
    and non-traceable local losses (``GALConfig.engine="python"`` forces
    it).

Every engine records the per-round communication and model-memory ledgers
(``history["comm_broadcast_bytes"/"comm_gather_bytes"/"model_memories"]``)
under the paper's Table-14 convention via ``repro.core.protocol_sim`` — the
shard engine's numbers come from its real collective operand shapes, the
other engines simulate the identical wire protocol. Eval metrics are
device-side on every engine (``metrics=...`` resolved from
``repro.metrics.METRICS``), evaluated inside the round loop.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine as engine_mod
from repro.core.losses import Loss, lq_loss
from repro.core.organizations import Organization
from repro.core.plan import (ExecutionPlan, dms_interface_reason,
                             plan_orgs)
from repro.core.privacy import apply_privacy
from repro.core.protocol_sim import gal_model_memories, gal_round_bytes
from repro.core.weights import fit_weights, uniform_weights
from repro.launch.mesh import org_mesh_eligible
from repro.metrics.metrics import METRICS, get_metric
from repro.optim.lbfgs import line_search
from repro.utils import tracing

_COMPILED_ENGINES = ("scan", "shard", "grouped")


def _resolve_metrics(metric_fn, metrics, eval_sets):
    """Normalize the metric arguments into one ``{column: fn}`` map.

    ``metrics`` entries are registry names (``repro.metrics.METRICS``) or
    pure-jnp callables (column = ``__name__``); the legacy single
    ``metric_fn`` keeps its historical ``"<eval>_metric"`` column. Every
    metric is validated up front with ``jax.eval_shape`` — ALL engines now
    evaluate metrics device-side inside the round loop (the host-side
    metric escape hatch is retired), so a non-traceable callable is an
    error naming the registry, not a silent Python fallback."""
    mmap: Dict[str, Callable] = {}
    if metric_fn is not None:
        mmap["metric"] = metric_fn
    for entry in (metrics or ()):
        name = entry if isinstance(entry, str) else \
            getattr(entry, "__name__", f"metric{len(mmap)}")
        # each metric owns one "<eval>_<name>" column: a duplicate would
        # silently clobber it, and "loss" would collide with the per-round
        # loss curve the engines already record
        if name == "loss":
            raise ValueError(
                "metric name 'loss' collides with the engines' per-round "
                "'<eval>_loss' column; rename the callable")
        if name in mmap:
            raise ValueError(
                f"duplicate metric name {name!r}: each metric needs a "
                f"distinct history column (rename the callable or drop "
                f"the duplicate)")
        mmap[name] = get_metric(entry) if isinstance(entry, str) else entry
    if not mmap:
        return None
    if eval_sets:
        for mname, fn in mmap.items():
            if not engine_mod.metric_traceable(fn, eval_sets):
                raise ValueError(
                    f"metric {mname!r} is not jax-traceable (failed "
                    f"jax.eval_shape over the eval shapes): every engine "
                    f"evaluates metrics device-side inside the round loop "
                    f"now — use a registry metric "
                    f"(repro.metrics.METRICS: {METRICS.names()}) or a "
                    f"pure-jnp callable")
    return mmap


@dataclass(frozen=True)
class GALConfig:
    rounds: int = 10
    # assisted learning rate (paper: L-BFGS line search; eta=1 const ablation)
    eta_method: str = "lbfgs"          # lbfgs | golden | constant
    eta0: float = 1.0
    eta_stop_threshold: float = 0.0    # stop assistance when |eta| drops below
    # gradient assistance weights (paper: softmax+Adam; uniform ablation)
    use_weights: bool = True
    weight_epochs: int = 100
    weight_lr: float = 0.1
    weight_decay: float = 5e-4
    # Alice's regression loss ell_1 used in the weight objective
    alice_q: float = 2.0
    # privacy on the broadcast residual (paper Sec 4.5)
    privacy: Optional[str] = None      # None | dp | ip
    privacy_alpha: float = 1.0
    privacy_intervals: int = 1
    # wire dtype of the step-2 residual broadcast: "bf16" casts the
    # privatized residual to bfloat16 BEFORE it leaves Alice (halving the
    # ledgered comm_broadcast_bytes exactly) and upcasts after; every
    # engine applies the identical cast, so they stay draw-for-draw equal
    # under compression too. "float32" is the uncompressed protocol.
    residual_dtype: str = "float32"    # float32 | bf16
    # org-sharded engine only: shard each org's N training rows across a
    # second "data" mesh axis (device_count must factor as org-axis size x
    # data_shards; see launch.mesh.org_mesh_eligible). The per-round local
    # fits, weight fit, and eta line search reduce across it.
    data_shards: int = 1
    # dynamic-membership fault injection (core/membership.py): each org
    # independently skips each round with probability straggler_sim, from a
    # schedule seeded by straggler_seed (deterministic per config; rounds
    # are repaired so at least one org always attends). Composes (AND)
    # with an explicit fit(membership=...) schedule.
    straggler_sim: Optional[float] = None
    straggler_seed: int = 0
    # engine selection: "auto" asks the planner (repro.core.plan) and picks
    # the most capable engine that applies — org-sharded collectives for a
    # single noiseless group on an org mesh, the scan fast path for a
    # single noiseless group on one host, the grouped fused engine for any
    # other compilable plan (heterogeneous models, per-org/custom losses,
    # noisy orgs, Deep Model Sharing), else the Python reference loop.
    # "python" forces the reference loop; "scan"/"shard"/"grouped" force a
    # compiled engine, raising with the planner's ineligibility reason when
    # it cannot run. NOTE metrics/metric_fn are traced device-side on EVERY
    # engine — they must be jax-traceable (repro.metrics.METRICS entries
    # are).
    engine: str = "auto"               # auto | scan | shard | grouped | python


@dataclass
class GALResult:
    orgs: List[Organization]
    loss: Loss
    f0: jnp.ndarray                    # (1, K)
    etas: List[float] = field(default_factory=list)
    weights: List[jnp.ndarray] = field(default_factory=list)
    history: Dict[str, List[float]] = field(default_factory=dict)
    # compiled-engine extras. Single-group results keep the legacy fields:
    # per-round params as ONE stacked pytree with leaves (T, M, ...), the
    # shared model that applies them, and the padded input geometry needed
    # to stack prediction-stage slices.
    stacked_params: Any = None
    model: Any = None
    org_dims: Optional[List[int]] = None
    pad_to: Optional[int] = None
    # planner-grouped results (any compiled engine): the ExecutionPlan that
    # ran, per-GROUP stacked params (list of pytrees, leaves (T, M_g, ...))
    # and per-group stacking geometry; prediction stays one vmap+einsum per
    # group (engine.grouped_predict).
    plan: Optional[ExecutionPlan] = None
    group_params: Optional[List[Any]] = None
    group_dims: Optional[List[List[int]]] = None
    group_pads: Optional[List[Optional[int]]] = None
    mesh_devices: int = 0              # devices the group stacks sharded over
    engine: str = "python"
    # the config this result was fit with (stored in the artifact manifest
    # and compat-checked on resume)
    config: Optional["GALConfig"] = None
    # compiled engines only: the final round-scan carry — ensemble state f,
    # per-eval-set carries, post-scan RNG key, early-stop flag, DMS
    # extractor/head/residual buffers, and the resume cursor t_next. This
    # is what checkpoint.save_artifact persists and
    # fit(..., resume_from=...) restores; python-engine results keep None
    # (their state lives in the Organization objects and cannot resume).
    resume_state: Optional[Dict[str, Any]] = None
    # the executed membership ledger: one row of per-org attendance bools
    # per executed round (org order), or None when every org attended
    # every round and no schedule was requested. Persisted in the
    # gal-artifact/v1 manifest; a grown resume pads the historical rows
    # with False for the joining orgs.
    membership: Optional[List[List[bool]]] = None

    @property
    def rounds(self) -> int:
        return len(self.etas)

    def predict(self, xs: Sequence[jnp.ndarray], rounds: Optional[int] = None
                ) -> jnp.ndarray:
        """Prediction stage: assemble org outputs for new data xs[m].

        Fast-path results evaluate the whole (rounds x orgs) ensemble with a
        nested vmap + one einsum; reference results loop per (round, org).
        """
        t_max = self.rounds if rounds is None else min(rounds, self.rounds)
        if self.group_params is not None and self.plan is not None:
            return engine_mod.grouped_predict(
                self.plan.groups, self.group_params, self.group_dims,
                self.group_pads, self.etas, self.weights, self.f0, xs,
                t_max,
            )
        return self.predict_legacy(xs, rounds)

    def predict_legacy(self, xs: Sequence[jnp.ndarray],
                       rounds: Optional[int] = None) -> jnp.ndarray:
        """Per-(round, org) Python assembly of the prediction stage — the
        reference the stacked path is measured against (benchmarks, serving).
        Needs per-org round params: call ``unpack_to_orgs()`` first on
        fast-path results, and pad xs to ``pad_to`` columns there.

        Reads LIVE Organization state: a later ``gal.fit``/``al.fit`` on
        the same org objects resets it (see
        ``Organization.reset_round_state``) and invalidates this path for
        results of earlier fits — refit fresh orgs to keep old results."""
        if not self.orgs:
            raise ValueError(
                "this result has no Organizations attached (loaded from an "
                "artifact): predict() serves directly from the stacked "
                "group params; the legacy per-(round, org) path needs live "
                "orgs")
        t_max = self.rounds if rounds is None else min(rounds, self.rounds)
        n = xs[0].shape[0]
        f = jnp.broadcast_to(self.f0, (n, self.f0.shape[-1]))
        for t in range(t_max):
            preds = jnp.stack([
                org.predict_round(t, xs[m]) for m, org in enumerate(self.orgs)
            ])
            f = f + self.etas[t] * jnp.einsum("m,mnk->nk", self.weights[t], preds)
        return f

    def unpack_to_orgs(self) -> None:
        """Copy fast-path per-round params back into the Organization objects
        so legacy per-(round, org) flows (``predict_round``) work. The params
        were fit on slices zero-padded to each group's pad width (``pad_to``
        for single-group results, ``group_pads[g]`` otherwise) — pad inputs
        with ``repro.data.partition.pad_and_stack`` before applying them.
        DMS groups restore the shared extractor and the per-round head list
        from the stacked ``(T, ...)`` head buffer."""
        if not self.orgs:
            raise ValueError(
                "this result has no Organizations attached (loaded from an "
                "artifact): there is nothing to unpack into — serve through "
                "predict(), or resume the fit with the original org data")
        if self.group_params is not None and self.plan is not None:
            for gi, g in enumerate(self.plan.groups):
                for j, i in enumerate(g.indices):
                    if g.dms:
                        gp = self.group_params[gi]
                        self.orgs[i]._dms_extractor = \
                            jax.tree_util.tree_map(
                                lambda l, j=j: l[j], gp["extractor"])
                        self.orgs[i]._dms_heads = [
                            jax.tree_util.tree_map(
                                lambda l, t=t, j=j: l[j, t], gp["heads"])
                            for t in range(self.rounds)
                        ]
                        continue
                    self.orgs[i]._round_params = [
                        jax.tree_util.tree_map(
                            lambda l, t=t, j=j: l[t, j],
                            self.group_params[gi])
                        for t in range(self.rounds)
                    ]
            return
        if self.stacked_params is None:
            return
        for i, org in enumerate(self.orgs):
            org._round_params = [
                jax.tree_util.tree_map(
                    lambda l, t=t, i=i: l[t, i], self.stacked_params)
                for t in range(self.rounds)
            ]


def fit(rng: jax.Array, orgs: List[Organization], y: jnp.ndarray, loss: Loss,
        config: GALConfig = GALConfig(),
        eval_sets: Optional[Dict[str, tuple]] = None,
        metric_fn: Optional[Callable] = None,
        metrics: Optional[Sequence] = None,
        resume_from: Any = None,
        membership: Any = None) -> GALResult:
    """Run T assistance rounds. ``eval_sets`` maps name -> (xs_list, y) and is
    evaluated with the *prediction-stage* mechanics each round (paper's
    validation protocol), producing the per-round curves of Fig. 4.

    ``metrics`` names device-side eval metrics — registry names from
    ``repro.metrics.METRICS`` (``"mad"``, ``"accuracy"``, ``"auroc"``) or
    pure-jnp callables — each recorded per round as
    ``history["<eval>_<metric>"]`` inside the engines' single host sync.
    The legacy single ``metric_fn`` still fills ``history["<eval>_metric"]``
    but is now traced device-side on EVERY engine (including the Python
    reference); non-traceable callables raise up front.

    ``resume_from`` extends a previously fitted collaboration instead of
    starting one: pass a compiled-engine ``GALResult`` (in-memory) or the
    path of a ``checkpoint.save_artifact`` directory. The engines restore
    the round-scan carry — ensemble state, per-eval carries, RNG chain,
    early-stop flag, DMS buffers — and run only rounds ``t0..T``
    (``t0`` = the artifact's completed rounds, ``T = config.rounds``),
    appending etas/weights/history columns so the resumed result is
    draw-for-draw identical to an uninterrupted ``T``-round fit. The org
    set must plan into the identical group partition (same models, losses,
    sigmas, slice widths) — or into a *compatible growth* of it (mid-fit
    join): the original orgs unchanged in their original positions plus
    new orgs appended after them, each joining an existing non-DMS group
    (same model/loss/sigma, slice width within the group's fitted pad) or
    forming a new non-DMS group. Joining orgs enter at round ``t0`` with a
    zeroed weight history — the stitched result's weights, group params
    and membership ledger carry exact zeros for them over the already-
    completed rounds. The config must match except ``rounds`` /
    ``engine``, and the eval-set names must match the saved carries; any
    divergence raises with the specific mismatch.

    ``membership`` is an optional (rounds, M) boolean attendance schedule
    (see ``repro.core.membership``): orgs absent from round t are masked
    out of that round's weight fit (weight exactly 0.0), contribute
    nothing to the direction, and drop out of the round's communication /
    model-memory ledgers. ``GALConfig.straggler_sim`` composes a seeded
    random dropout schedule on top (logical AND). On a resume, schedule
    rows before ``t0`` are overridden by the collaboration's recorded
    history (the artifact's membership ledger; joining orgs absent).

    Engine dispatch is planner-driven: ``repro.core.plan.plan_orgs``
    partitions the orgs into homogeneous groups or names the reason the
    compiled engines cannot run; forcing a compiled engine on an
    uncompilable set raises that reason verbatim."""
    with tracing.fit_span("fit"):
        with tracing.span("plan"):
            p = _plan_fit(orgs, y, loss, config, eval_sets, metric_fn,
                          metrics, resume_from, membership)
        if p.python:
            return _fit_python(rng, orgs, y, loss, config, eval_sets,
                               p.metric_map, membership=p.sched)
        result = _dispatch_compiled(rng, orgs, y, loss, config, eval_sets,
                                    p.metric_map, p.plan, p.resume_eng,
                                    p.sched)
        if p.resume_art is not None:
            result = _stitch_resume(p.resume_art, result, p.plan,
                                    growth=p.growth)
        return result


class _FitPlan(NamedTuple):
    metric_map: Dict[str, Callable]
    plan: ExecutionPlan
    sched: Any                  # resolved membership schedule, or None
    resume_art: Any             # the artifact resumed from, or None
    resume_eng: Any             # its resume carry for the engines, or None
    growth: Any
    python: bool                # the Python reference loop runs


def _plan_fit(orgs, y, loss, config, eval_sets, metric_fn, metrics,
              resume_from, membership) -> _FitPlan:
    """``fit``'s planning, validation and resume preparation."""
    if config.engine not in ("auto", "python") + _COMPILED_ENGINES:
        raise ValueError(f"unknown engine {config.engine!r}")
    if config.residual_dtype not in ("float32", "fp32", "bf16", "bfloat16"):
        raise ValueError(
            f"unknown residual_dtype {config.residual_dtype!r}: "
            "expected 'float32' or 'bf16'")
    if config.data_shards < 1:
        raise ValueError(f"data_shards must be >= 1, got "
                         f"{config.data_shards}")
    if config.data_shards > 1 and config.engine not in ("auto", "shard"):
        raise ValueError(
            f"data_shards={config.data_shards} needs the org-sharded "
            f"engine (its 'data' mesh axis); engine={config.engine!r} "
            "cannot honor it — use engine='shard' or 'auto'")
    for org in orgs:
        org.reset_round_state()  # a refit must not read stale round params
    metric_map = _resolve_metrics(metric_fn, metrics, eval_sets)
    plan = plan_orgs(orgs, eval_sets,
                     probe_shape=(int(y.shape[0]), int(y.shape[-1])))
    if config.data_shards > 1 and not (
            plan.compiled and plan.homogeneous
            and org_mesh_eligible(len(orgs), config.data_shards)):
        raise ValueError(
            f"data_shards={config.data_shards} needs a homogeneous org set "
            f"on an (org x data) mesh: {len(orgs)} orgs over "
            f"{jax.device_count()} devices / {config.data_shards} data "
            f"shard(s) is not eligible "
            f"({plan.reason or 'see launch.mesh.org_mesh_eligible'})")
    from repro.core.membership import resolve_membership
    sched = resolve_membership(membership, config.straggler_sim,
                               config.straggler_seed, config.rounds,
                               len(orgs))

    resume_art = resume_eng = growth = None
    if resume_from is not None:
        if isinstance(resume_from, (str, Path)):
            from repro.checkpoint.checkpoint import load_artifact
            # custom (non-registry) models/losses are stored by name only;
            # the org set being resumed holds the live objects, so resolve
            # the artifact's names against them (the artifact stores names,
            # not code — supplying the same-named implementation is the
            # caller's side of that contract, as with load_artifact)
            models_map: Dict[str, Any] = {}
            losses_map: Dict[str, Any] = {}
            for o in orgs:
                models_map.setdefault(type(o.model).__name__, o.model)
                if o.local_loss is not None:
                    # same name fallback chain as checkpoint.loss_spec, so
                    # partials/callable instances resolve too
                    losses_map.setdefault(
                        getattr(o.local_loss, "__name__",
                                type(o.local_loss).__name__), o.local_loss)
            losses_map.setdefault(
                getattr(loss, "__name__", type(loss).__name__), loss)
            resume_art = load_artifact(resume_from, losses=losses_map,
                                       models=models_map)
        else:
            resume_art = resume_from
        if config.engine == "python":
            raise ValueError(
                "resume_from needs a compiled engine (the python reference "
                "loop holds its state in live Organization objects and "
                "cannot restore an artifact carry); use engine='auto'")
        if not plan.compiled:
            raise ValueError(
                f"resume_from needs a compilable organization set: "
                f"{plan.reason}")
        resume_eng, growth = _prepare_resume(resume_art, orgs, plan, y,
                                             loss, config, eval_sets,
                                             metric_map)
        if growth is not None and config.straggler_sim:
            raise ValueError(
                "straggler_sim cannot span a mid-fit join: the seeded "
                "schedule draws over (rounds, M) and a grown M would "
                "retroactively change the already-completed rounds' "
                "draws — pass an explicit membership schedule instead")
        sched = _resume_schedule(resume_art, resume_eng, growth, sched,
                                 config, len(orgs))

    if not plan.compiled:
        if config.engine in _COMPILED_ENGINES:
            # the ONE ineligibility path for every compiled engine: the
            # planner's human-readable reason, verbatim
            raise ValueError(
                f"engine={config.engine!r} cannot compile these "
                f"organizations: {plan.reason}")
        # interface check only, NOT scan_safe: a duck-typed model with the
        # full extractor/head surface still runs the reference DMS loop.
        # When even that surface is missing, the python engine cannot run
        # it either — surface a clear error instead of an AttributeError
        # three steps into round 0.
        for o in orgs:
            why = (dms_interface_reason(o)
                   if getattr(o, "dms", False) else None)
            if why:
                raise ValueError(
                    f"cannot run these organizations on ANY engine: {why}")
    return _FitPlan(metric_map, plan, sched, resume_art, resume_eng, growth,
                    python=not plan.compiled or config.engine == "python")


def _resume_schedule(art: GALResult, resume_eng: Dict[str, Any], growth,
                     sched, config: GALConfig, m: int):
    """Assemble the full-rounds engine schedule for a resumed fit: rows
    before ``t_next`` are the collaboration's recorded history — the
    artifact's membership ledger over the original orgs, padded with False
    for orgs joining now — and rows from ``t_next`` on come from the
    caller's resolved schedule (all live when none was given). Historical
    rows drive the DMS dead-slot masks and the stitched ledger; they are
    never re-executed. Returns None when no membership story exists at
    all (no schedule, no artifact ledger, no join), which keeps the
    pre-membership engine path bit-for-bit."""
    art_rows = art.membership
    if sched is None and art_rows is None and growth is None:
        return None
    t0 = int(resume_eng["t_next"])
    m_old = growth["m_old"] if growth is not None else m
    hist = (np.ones((t0, m_old), bool) if art_rows is None
            else np.asarray(art_rows, bool))
    if hist.shape != (t0, m_old):
        raise ValueError(
            f"artifact membership ledger shape {hist.shape} does not "
            f"match its {t0} completed rounds over {m_old} orgs")
    full = np.zeros((t0, m), bool)
    full[:, :m_old] = hist
    exec_rows = (np.ones((config.rounds - t0, m), bool) if sched is None
                 else np.asarray(sched, bool)[t0:])
    return np.vstack([full, exec_rows])


def _dispatch_compiled(rng, orgs, y, loss, config, eval_sets, metric_map,
                       plan, resume, membership=None) -> GALResult:
    if config.engine == "scan":
        if not plan.homogeneous:
            raise ValueError(
                "engine='scan' runs ONE noiseless homogeneous group; the "
                f"planner found {plan.describe()} — use engine='grouped' "
                "(or 'auto') to fuse heterogeneous/noisy/DMS organizations")
        return _fit_fast(engine_mod.fit_scan, "scan", plan,
                         rng, orgs, y, loss, config, eval_sets, metric_map,
                         resume=resume, membership=membership)
    if config.engine == "shard":
        if plan.homogeneous:
            # fit_shard itself raises the org-mesh "must divide" error
            return _fit_fast(engine_mod.fit_shard, "shard", plan,
                             rng, orgs, y, loss, config, eval_sets,
                             metric_map, resume=resume,
                             membership=membership)
        return _fit_fast(engine_mod.fit_grouped, "grouped", plan,
                         rng, orgs, y, loss, config, eval_sets, metric_map,
                         require_mesh=True, resume=resume,
                         membership=membership)
    if config.engine == "grouped":
        return _fit_fast(engine_mod.fit_grouped, "grouped", plan,
                         rng, orgs, y, loss, config, eval_sets, metric_map,
                         resume=resume, membership=membership)
    # auto: most capable engine that applies
    if plan.homogeneous and org_mesh_eligible(len(orgs),
                                              config.data_shards):
        return _fit_fast(engine_mod.fit_shard, "shard", plan,
                         rng, orgs, y, loss, config, eval_sets, metric_map,
                         resume=resume, membership=membership)
    if plan.homogeneous:
        return _fit_fast(engine_mod.fit_scan, "scan", plan,
                         rng, orgs, y, loss, config, eval_sets, metric_map,
                         resume=resume, membership=membership)
    return _fit_fast(engine_mod.fit_grouped, "grouped", plan,
                     rng, orgs, y, loss, config, eval_sets, metric_map,
                     resume=resume, membership=membership)


def _fit_fast(engine_fn, name, plan, rng, orgs, y, loss, config, eval_sets,
              metrics, require_mesh: bool = False,
              resume: Optional[Dict[str, Any]] = None,
              membership=None) -> GALResult:
    if engine_fn is engine_mod.fit_shard:
        out = engine_fn(rng, orgs, y, loss, config, eval_sets, metrics,
                        resume=resume, membership=membership)
    else:
        if require_mesh:
            from repro.launch.mesh import grouped_mesh_eligible
            if plan.has_dms:
                raise ValueError(
                    "engine='shard' cannot org-shard a Deep Model Sharing "
                    "plan (its extractor/head carry is single-host); use "
                    "engine='grouped' (or 'auto')")
            if not grouped_mesh_eligible([g.size for g in plan.groups]):
                raise ValueError(
                    f"engine='shard' on a {plan.n_groups}-group plan needs "
                    f"the device count ({len(jax.devices())}) to divide "
                    f"every group size {[g.size for g in plan.groups]} on "
                    "a multi-device host; use engine='grouped' for the "
                    "single-host fused path")
        out = engine_fn(rng, orgs, y, loss, config, eval_sets, metrics,
                        plan=plan, resume=resume, membership=membership)
    with tracing.span("finalize"):
        return _fast_result(orgs, y, loss, out, name, plan, config)


def _fast_result(orgs, y, loss, out, engine: str, plan: ExecutionPlan,
                 config: Optional[GALConfig] = None) -> GALResult:
    single = plan.n_groups == 1 and not plan.has_dms
    group_params = out.get("group_params")
    if group_params is None:            # fit_shard: legacy single-stack dict
        group_params = [out["params"]]
        group_dims = [out["dims"]]
        group_pads = [out["pad_to"]]
    else:
        group_dims = out["group_dims"]
        group_pads = out["group_pads"]
    return GALResult(
        orgs=orgs, loss=loss, f0=loss.init_prediction(y),
        etas=out["etas"], weights=out["weights"], history=out["history"],
        stacked_params=out.get("params") if single else None,
        model=plan.groups[0].model if single else None,
        org_dims=group_dims[0] if single else None,
        pad_to=group_pads[0] if single else None,
        plan=plan, group_params=group_params, group_dims=group_dims,
        group_pads=group_pads, mesh_devices=out.get("mesh_devices", 0),
        engine=engine, config=config, resume_state=out.get("resume"),
        membership=out.get("membership"),
    )


# history columns with NO round-0 init row (appended per executed round
# only): the stitcher concatenates them verbatim, everything else drops
# the resumed segment's restored-carry "init" entry first
_LEDGER_COLS = ("comm_broadcast_bytes", "comm_gather_bytes",
                "model_memories")


def _prepare_resume(art: GALResult, orgs, plan: ExecutionPlan, y, loss,
                    config: GALConfig, eval_sets,
                    metric_map: Optional[Dict[str, Callable]] = None
                    ) -> tuple:
    """Validate a resume request against the artifact and build the engine
    resume dict. Every check raises with the specific mismatch — a resumed
    carry on the wrong org set / config / data would produce silently
    wrong rounds, which is strictly worse than an error.

    Returns ``(resume_dict, growth)``: ``growth`` is None for an identical
    org set, or — for a *compatible growth* (mid-fit join, see
    ``plan.plan_growth_mismatch``) — a dict with the artifact geometry the
    stitcher needs (``m_old``, per-old-group sizes) to zero-pad the
    joining orgs' completed-round history."""
    import dataclasses as _dc

    from repro.checkpoint.checkpoint import loss_spec, model_spec
    from repro.core.plan import (plan_growth_mismatch, plan_mismatch,
                                 plan_to_manifest)
    from repro.data.partition import group_widths

    rs = art.resume_state
    if rs is None:
        raise ValueError(
            "this result/artifact has no resume state: python-engine fits "
            "hold their rounds in live Organization objects and cannot "
            "resume — refit on a compiled engine and save that")
    manifest = plan_to_manifest(art.plan, model_spec, loss_spec)
    growth = None
    why = plan_mismatch(plan, manifest, model_spec, loss_spec)
    if why is not None:
        gwhy = plan_growth_mismatch(plan, manifest, model_spec, loss_spec)
        if gwhy is not None:
            raise ValueError(
                f"resume_from organization set does not match the "
                f"artifact's execution plan ({why}) and is not a "
                f"compatible growth of it ({gwhy})")
        old_sizes = [len(g["org_ids"]) for g in manifest["groups"]]
        growth = {"m_old": sum(old_sizes), "old_sizes": old_sizes,
                  "n_old_groups": len(old_sizes)}
    dims_now = group_widths([o.x_train for o in orgs],
                            [g.indices for g in plan.groups])
    dims_art = [[int(d) for d in gd] for gd in art.group_dims]
    if growth is None:
        if dims_now != dims_art:
            raise ValueError(
                f"resume_from slice widths {dims_now} do not match the "
                f"artifact's fitted widths {dims_art} (per group, in org "
                f"order)")
    else:
        # original members must keep their fitted widths; joiners must fit
        # inside the group's fitted pad (stack_groups would otherwise grow
        # the pad and the completed rounds' params could not be stitched)
        for gi, n_old in enumerate(growth["old_sizes"]):
            if dims_now[gi][:n_old] != dims_art[gi]:
                raise ValueError(
                    f"resume_from group {gi} original-member slice widths "
                    f"{dims_now[gi][:n_old]} do not match the artifact's "
                    f"fitted widths {dims_art[gi]}")
            pad = art.group_pads[gi]
            wide = [w for w in dims_now[gi][n_old:]
                    if pad is not None and w > pad]
            if wide:
                raise ValueError(
                    f"orgs joining group {gi} have slice widths {wide} "
                    f"wider than the group's fitted pad ({pad}); the "
                    f"completed rounds' params were fit on {pad}-column "
                    f"stacks and cannot be re-padded — join with narrower "
                    f"slices or form a new group (different model config)")
    t0 = int(rs["t_next"])
    if config.rounds <= t0:
        raise ValueError(
            f"resume needs config.rounds > the artifact's {t0} completed "
            f"rounds (got rounds={config.rounds}); the artifact already "
            f"serves predictions for every fitted round prefix")
    if art.config is not None:
        # rounds/engine/data_shards are run-placement knobs, free to change
        # on resume; everything else (residual_dtype included) is protocol
        a = _dc.replace(art.config, rounds=0, engine="auto", data_shards=1)
        b = _dc.replace(config, rounds=0, engine="auto", data_shards=1)
        if a != b:
            diff = [f.name for f in _dc.fields(GALConfig)
                    if getattr(a, f.name) != getattr(b, f.name)]
            raise ValueError(
                f"resume config mismatch on {diff}: the resumed rounds "
                f"must draw from the same protocol as the fitted ones "
                f"(only rounds, engine and data_shards may change)")
    if loss_spec(loss) != loss_spec(art.loss):
        raise ValueError(
            f"resume loss mismatch: artifact was fit with "
            f"{loss_spec(art.loss)}, resume called with {loss_spec(loss)}")
    f = jnp.asarray(rs["f"])
    if tuple(f.shape) != (int(y.shape[0]), int(y.shape[-1])):
        raise ValueError(
            f"resume target shape {tuple(y.shape)} does not match the "
            f"artifact's ensemble carry {tuple(f.shape)} — resuming needs "
            f"the original training rows")
    # cheap data-identity gate: F^0 is a deterministic function of y
    # (mean/median/prior init), so a same-shape-but-different target —
    # where the restored carry would silently produce rounds no
    # uninterrupted fit could — is caught here for any label drift that
    # moves the init
    f0_now = np.asarray(loss.init_prediction(y))
    if not np.allclose(f0_now, np.asarray(art.f0), rtol=1e-6, atol=1e-7):
        raise ValueError(
            "resume target y does not look like the data the artifact was "
            "fit on (loss.init_prediction(y) differs from the artifact's "
            "F^0) — resuming needs the original training targets")
    saved_evals = dict(rs.get("f_evals") or {})
    names_now = sorted((eval_sets or {}).keys())
    if sorted(saved_evals) != names_now:
        raise ValueError(
            f"resume eval_sets {names_now} do not match the artifact's "
            f"saved eval carries {sorted(saved_evals)}; pass the same "
            f"eval sets the original fit used")
    for nm, fe in saved_evals.items():
        y_e = eval_sets[nm][1]
        if tuple(jnp.asarray(fe).shape) != (int(y_e.shape[0]),
                                            int(y.shape[-1])):
            raise ValueError(
                f"resume eval set {nm!r} has {int(y_e.shape[0])} rows, the "
                f"artifact's carry has {int(jnp.asarray(fe).shape[0])}")
    # fail on metric drift BEFORE the engine runs: the resumed rounds'
    # history columns must extend the artifact's exactly (the stitcher
    # re-checks, but by then the whole resumed fit has been paid for)
    expected = {"train_loss", *_LEDGER_COLS}
    for nm in (eval_sets or {}):
        expected.add(f"{nm}_loss")
        for mname in (metric_map or {}):
            expected.add(f"{nm}_{mname}")
    # "contributions" is a post-fit annotation (core/contrib.py), not a
    # per-round curve: it never blocks a resume, and the stitcher drops it
    # (the scores describe the artifact's org set up to ITS final round)
    if expected != set(art.history) - {"contributions"}:
        raise ValueError(
            f"resume history columns would not match the artifact's "
            f"(differing: "
            f"{sorted(expected ^ (set(art.history) - {'contributions'}))})"
            f"; resume with the same metrics/metric_fn the original fit "
            f"used")
    return {
        "t_next": t0,
        "f": f,
        "f_evals": {nm: jnp.asarray(v) for nm, v in saved_evals.items()},
        "key": jnp.asarray(rs["key"]),
        "active": jnp.asarray(rs["active"]),
        "state": jax.tree_util.tree_map(jnp.asarray,
                                        dict(rs.get("state") or {})),
    }, growth


def _stitch_resume(art: GALResult, new: GALResult, plan: ExecutionPlan,
                   growth=None) -> GALResult:
    """Concatenate an artifact's completed rounds with the freshly resumed
    ones into one seamless GALResult: etas/weights append, history columns
    extend (ledger columns verbatim, curve columns minus the restored-carry
    init row), fresh-fit group params concatenate on the round axis, and
    DMS group params are taken whole from the resumed carry (its stacked
    head buffer already spans every round).

    ``growth`` (from ``_prepare_resume``) marks a mid-fit join: orgs that
    joined at the resume point get a zeroed completed-round history — the
    artifact's per-round weights gain exact-zero columns, grown groups'
    params gain zero org-lanes, brand-new groups get zero rounds, and the
    stitched membership ledger records them absent — so ``predict`` at any
    pre-join prefix reproduces the original collaboration exactly. The
    artifact's post-fit "contributions" annotation (if any) is dropped:
    the scores describe the OLD org set up to the old final round."""
    art_hist = {c: v for c, v in art.history.items()
                if c != "contributions"}
    if set(art_hist) != set(new.history):
        raise ValueError(
            f"resumed history columns do not match the artifact's "
            f"(differing: {sorted(set(new.history) ^ set(art_hist))}); "
            f"resume with the same metrics/metric_fn the original fit "
            f"used")
    hist: Dict[str, List[float]] = {}
    for col, vals in new.history.items():
        old = list(art_hist[col])
        hist[col] = old + (list(vals) if col in _LEDGER_COLS
                           else list(vals[1:]))
    t_old = len(art.etas)
    m_new = sum(g.size for g in plan.groups)
    n_old_groups = (growth["n_old_groups"] if growth is not None
                    else plan.n_groups)
    old_sizes = (growth["old_sizes"] if growth is not None
                 else [g.size for g in plan.groups])
    group_params: List[Any] = []
    for gi, g in enumerate(plan.groups):
        if g.dms:
            group_params.append(new.group_params[gi])
            continue
        leaves_new, treedef = jax.tree_util.tree_flatten(
            new.group_params[gi])
        if gi >= n_old_groups:
            # a group born at the join: its completed rounds are exact
            # zeros (its orgs were absent, weight 0, so any value would be
            # inert — zeros keep the artifact readable)
            group_params.append(treedef.unflatten([
                jnp.concatenate([
                    jnp.zeros((t_old,) + jnp.asarray(b).shape[1:],
                              jnp.asarray(b).dtype), jnp.asarray(b)],
                    axis=0)
                for b in leaves_new]))
            continue
        # concatenate leaf-by-leaf in flatten order rather than with a
        # two-tree tree_map: a disk-loaded artifact holds tuples as lists
        # (the self-describing npz form), which flatten to the same leaf
        # sequence but not the same treedef as the fresh fit's params
        leaves_old = jax.tree_util.tree_leaves(art.group_params[gi])
        if len(leaves_old) != len(leaves_new):
            raise ValueError(
                f"resumed group {gi} params have {len(leaves_new)} leaves, "
                f"the artifact's have {len(leaves_old)} — the model "
                f"implementation changed since the artifact was saved")
        lanes_added = g.size - old_sizes[gi]
        stitched = []
        for a, b in zip(leaves_old, leaves_new):
            a = jnp.asarray(a)
            if lanes_added:
                # joiners' lanes over the completed rounds: exact zeros
                a = jnp.pad(a, ((0, 0), (0, lanes_added))
                            + ((0, 0),) * (a.ndim - 2))
            stitched.append(jnp.concatenate([a, jnp.asarray(b)], axis=0))
        group_params.append(treedef.unflatten(stitched))
    new.etas = list(art.etas) + list(new.etas)
    old_w = [jnp.asarray(w) for w in art.weights]
    if growth is not None:
        old_w = [jnp.pad(w, (0, m_new - growth["m_old"])) for w in old_w]
    new.weights = old_w + list(new.weights)
    new.history = hist
    new.group_params = group_params
    if plan.n_groups == 1 and not plan.has_dms:
        new.stacked_params = group_params[0]
    # stitched membership ledger: recorded history (joiners absent) in
    # front of the executed rows; stays None only when no membership story
    # exists on either side
    new_rows = new.membership
    if growth is not None or art.membership is not None \
            or new_rows is not None:
        m_old = growth["m_old"] if growth is not None else m_new
        old_rows = np.asarray(
            art.membership if art.membership is not None
            else np.ones((t_old, m_old), bool), bool)
        full = np.zeros((t_old, m_new), bool)
        full[:, :m_old] = old_rows
        exec_rows = np.asarray(
            new_rows if new_rows is not None
            else np.ones((len(new.etas) - t_old, m_new), bool), bool)
        new.membership = np.vstack([full, exec_rows]).tolist()
    return new


def _fit_python(rng, orgs, y, loss, config, eval_sets, metrics,
                membership=None) -> GALResult:
    """Reference interpreter-order engine (the conformance oracle).

    ``membership`` is the resolved bool (rounds, M) schedule or None. The
    oracle mirrors the compiled engines' membership semantics exactly:
    every org still runs its local fit each round (fresh-fit params stay
    round-aligned and the RNG chain stays org-independent) but an absent
    org's round is DEAD — exact-zero assistance weight, no ledger bytes,
    and for DMS orgs a skipped refit with a zero head in that round's
    slot (``Organization.fit_round(live=False)``)."""
    n = y.shape[0]
    k = y.shape[-1]
    f0 = loss.init_prediction(y)
    f_train = jnp.broadcast_to(f0, (n, k))
    alice_loss = lq_loss(config.alice_q)
    org_ids = jnp.asarray([org.index for org in orgs], jnp.uint32)

    result = GALResult(orgs=orgs, loss=loss, f0=f0, config=config)
    hist = result.history
    hist["train_loss"] = [float(loss(y, f_train))]
    f_evals = {}
    if eval_sets:
        for name, (xs_e, y_e) in eval_sets.items():
            f_evals[name] = jnp.broadcast_to(f0, (y_e.shape[0], k))
            hist[f"{name}_loss"] = [float(loss(y_e, f_evals[name]))]
            for mname, metric_fn in (metrics or {}).items():
                hist[f"{name}_{mname}"] = [
                    float(metric_fn(y_e, f_evals[name]))]
    # simulated per-round communication + model-memory ledgers (Table-14
    # convention, same formulas as the fused engines) — appended per
    # EXECUTED round so early stopping trims them like the fused engines do
    eval_ns = [int(y_e.shape[0]) for (_, y_e) in (eval_sets or {}).values()]
    from repro.core.engine import _resid_wire_bytes
    rb = _resid_wire_bytes(config)
    if membership is None:
        bcast_b, gather_b = gal_round_bytes(n, k, len(orgs), eval_ns,
                                            resid_dtype_bytes=rb)
        bcast_l = gather_l = None
    else:
        from repro.core.membership import membership_comm_ledger
        bcast_l, gather_l = membership_comm_ledger(membership, n, k,
                                                   eval_ns,
                                                   resid_dtype_bytes=rb)
    memories = gal_model_memories(config.rounds, [org.dms for org in orgs],
                                  membership=membership)
    hist["comm_broadcast_bytes"] = []
    hist["comm_gather_bytes"] = []
    hist["model_memories"] = []

    for t in range(config.rounds):
        row = None if membership is None else membership[t]
        rng, k_round = jax.random.split(rng)
        # 1. pseudo-residual
        residual = loss.residual(y, f_train)
        # 2. broadcast (privatized in hindsight if configured); under
        # residual_dtype="bf16" the wire carries bfloat16 — round-trip the
        # cast so the oracle sees exactly what the compiled engines see
        r_bcast = apply_privacy(
            jax.random.fold_in(k_round, 13), residual, config.privacy,
            alpha=config.privacy_alpha, n_intervals=config.privacy_intervals,
        )
        if rb == 2:
            r_bcast = r_bcast.astype(jnp.bfloat16).astype(residual.dtype)
        # 3. parallel local fits
        preds = jnp.stack([
            org.fit_round(jax.random.fold_in(k_round, org.index), r_bcast,
                          live=bool(row[m]) if row is not None else True)
            for m, org in enumerate(orgs)
        ])                                                    # (M, N, K)
        # 4. gradient assistance weights (masked over this round's live orgs)
        mask = None if row is None else jnp.asarray(row)
        if config.use_weights and len(orgs) > 1:
            w = fit_weights(
                jax.random.fold_in(k_round, 29), residual, preds, alice_loss,
                epochs=config.weight_epochs, lr=config.weight_lr,
                weight_decay=config.weight_decay,
                mask=mask, org_ids=org_ids,
            )
        else:
            w = uniform_weights(len(orgs), mask=mask)
        direction = jnp.einsum("m,mnk->nk", w, preds)
        # 5. line-search the gradient assisted learning rate
        eta = line_search(
            lambda e: loss(y, f_train + e * direction),
            method=config.eta_method, x0=config.eta0,
        )
        # 6. update the ensemble
        f_train = f_train + eta * direction
        result.etas.append(float(eta))
        result.weights.append(w)
        hist["train_loss"].append(float(loss(y, f_train)))
        hist["comm_broadcast_bytes"].append(
            bcast_b if membership is None else bcast_l[t])
        hist["comm_gather_bytes"].append(
            gather_b if membership is None else gather_l[t])
        hist["model_memories"].append(memories[t])
        if eval_sets:
            for name, (xs_e, y_e) in eval_sets.items():
                preds_e = jnp.stack([
                    org.predict_round(t, xs_e[m]) for m, org in enumerate(orgs)
                ])
                f_evals[name] = f_evals[name] + eta * jnp.einsum(
                    "m,mnk->nk", w, preds_e
                )
                hist[f"{name}_loss"].append(float(loss(y_e, f_evals[name])))
                for mname, metric_fn in (metrics or {}).items():
                    hist[f"{name}_{mname}"].append(
                        float(metric_fn(y_e, f_evals[name]))
                    )
        if (config.eta_stop_threshold > 0.0
                and abs(float(eta)) < config.eta_stop_threshold):
            break
    if membership is not None:
        result.membership = np.asarray(
            membership[:len(result.etas)], bool).tolist()
    tracing.count("rounds", len(result.etas))
    return result
