"""Fused, scan-compiled GAL round engines (paper Algorithm 1, fast paths).

The reference engine in ``repro.core.gal`` executes Algorithm 1 as a Python
loop: every round pays M Python dispatches for the local fits, a re-traced
line search, and several ``float()`` host round-trips for history keeping.
This module compiles the whole assistance stage into ONE device program for
every organization set the execution planner (``repro.core.plan``) can
partition into homogeneous groups — including the paper's heterogeneous
scenarios (model autonomy's GB–SVM mix, per-org local ell_q losses, noisy
orgs). Per traced round:

  * each planner group's residual fits are ``jax.vmap``-ed over that group's
    stacked inputs ``(M_g, N, d_g)`` (vertical slices zero-padded to a
    common width *within the group* — inert for pad-invariant fits,
    width-split groups otherwise; see ``repro.data.partition.stack_groups``);
  * the group fitted values are concatenated along the org axis — back in
    original org order — before the step-4 weight fit, so Algorithm 1 sees
    one (M, N, K) block exactly as the reference engine does;
  * one round (residual -> privacy -> group fits -> assistance weights ->
    eta line-search -> ensemble update -> eval bookkeeping) is a single
    traced step function;
  * the T-round loop is ``jax.lax.scan`` over that step, with etas, weights,
    per-round params and the loss/metric history materialized device-side.

The ONLY host synchronization is a single ``jax.device_get`` of the scalar
bundle after the scan returns — matching GAL's communication structure
(orgs are parallel within a round; rounds are sequential).

Noisy organizations (paper Table 6) are traceable end to end: training-stage
noise uses the same ``fold_in(org_key, 777)`` keys as the reference engine,
and prediction-stage noise derives from ``fold_in(PRNGKey(org.index), t)``
(see ``Organization.predict_round``) — no Python ``hash`` anywhere — so the
grouped engine, the Python loop, and the stacked prediction path all draw
identical noise for a given (org, round).

Deep Model Sharing (paper Sec. 4.2/5) is traceable too: a DMS group's
shared extractor and its per-round heads ride the round scan's carry with
FIXED shapes — the heads as one stacked ``(M_g, T, ...)`` buffer, the
broadcast-residual history as a shared ``(T, N, K)`` buffer — and each
round's joint refit (``_dms_org_round``) masks the not-yet-live head slots
out of the objective, so their gradients are exactly zero and the refit
reproduces ``Organization._fit_round_dms`` term for term. The Table-14
memory win is ledgered per round in ``history["model_memories"]``.

The fused executions share that round step structure:

  * ``fit_grouped`` — the planner-driven engine: one vmap per group inside
    the shared round step; on a multi-device host where the device count
    divides every group size, each group's org stack is placed sharded
    along an "org" mesh axis (``launch.mesh.grouped_mesh_eligible``), so a
    mixed-model org set maps onto the mesh with one org-shard of every
    group per device;
  * ``fit_scan`` — the legacy single-group veneer over ``fit_grouped``
    (homogeneous orgs, single host);
  * ``fit_shard`` — the org-SHARDED multi-device path
    (``GALConfig.engine="shard"``): the org axis maps onto a real device
    mesh (``repro.launch.mesh.make_org_mesh``, one organization per device
    along an "org" axis). Each org's padded slice, per-round params and
    local fits live on its own device; Alg. 1's communication structure
    becomes real collectives — the residual broadcast is a masked ``psum``
    from Alice's device (step 2), the fitted values are ``all_gather``-ed
    back for the weight fit (step 4), and the weighted direction is a
    ``psum`` over the org axis (step 6). The bytes crossing that collective
    boundary are recorded in a per-round communication ledger
    (``history["comm_broadcast_bytes"]`` / ``history["comm_gather_bytes"]``,
    mirroring the paper's Table-14 accounting in
    ``repro.core.protocol_sim``).

RNG discipline replicates the reference engine exactly (split per round;
``fold_in(k_round, 13)`` privacy, ``fold_in(k_round, org.index)`` per-org fit,
``fold_in(k_round, 29)`` weight fit), so for deterministic local models
(ridge / kernel ridge / stumps) all three engines agree to float tolerance.

Early stopping (``eta_stop_threshold``) cannot break a ``lax.scan``; instead
rounds after the threshold crossing are masked (eta forced to 0, ensemble
frozen) and trimmed from the returned history on the host side.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.losses import Loss, lq_loss
from repro.core.plan import ExecutionPlan, plan_orgs
from repro.core.privacy import apply_privacy
from repro.core.protocol_sim import gal_model_memories, gal_round_bytes
from repro.core.weights import fit_weights, uniform_weights
from repro.optim.optimizers import adam, apply_updates
from repro.data.partition import (pad_and_stack, pad_and_stack_sharded,
                                  stack_groups)
from repro.launch.mesh import (grouped_mesh_eligible, make_org_mesh,
                               org_block_size, org_mesh_eligible)
from repro.launch.sharding import org_replicated, org_stack_sharding
from repro.optim.lbfgs import line_search
from repro.utils import tracing


def scan_compatible(orgs: Sequence[Any],
                    eval_sets: Optional[Dict[str, tuple]] = None) -> bool:
    """True when the legacy single-group fast path can run these orgs: the
    planner compiles them into exactly ONE noiseless group (one shared
    scan-safe model config, one shared ell_q, stackable slices, no DMS).
    Heterogeneous / noisy / per-loss sets that still compile — as multiple
    groups — are the grouped engine's territory (``plan_orgs(...).compiled``)
    and return False here."""
    p = plan_orgs(orgs, eval_sets)
    return p.compiled and p.homogeneous


def metric_traceable(metric_fn: Callable,
                     eval_sets: Dict[str, tuple]) -> bool:
    """True when metric_fn traces cleanly over abstract (y_e, f) values.

    EVERY engine evaluates metrics under jit inside the round loop now
    (the host-side escape hatch is retired); ``gal.fit`` probes each
    metric with ``jax.eval_shape`` up front and raises — naming the
    ``repro.metrics.METRICS`` registry — for host-side callables
    (``float(...)``, numpy/sklearn calls) instead of crashing mid-trace.
    """
    try:
        for _, y_e in eval_sets.values():
            f_spec = jax.ShapeDtypeStruct((y_e.shape[0], y_e.shape[-1]),
                                          jnp.float32)
            y_spec = jax.ShapeDtypeStruct(y_e.shape, y_e.dtype)
            jax.eval_shape(metric_fn, y_spec, f_spec)
        return True
    except Exception:
        return False


def shard_eligible(orgs: Sequence[Any],
                   eval_sets: Optional[Dict[str, tuple]] = None,
                   data_shards: int = 1) -> bool:
    """True when the org-sharded multi-device path can run these orgs:
    scan-compatible AND an "org" mesh exists — one-to-one (len(orgs)
    divides the org-axis device count) or block placement (the org-axis
    device count divides len(orgs), a block of orgs per device); see
    ``launch.mesh.org_mesh_eligible``. ``engine="auto"`` prefers this path
    whenever it holds."""
    return (scan_compatible(orgs, eval_sets)
            and org_mesh_eligible(len(orgs), data_shards))


# The round programs built so far, least recently used first. A key is the
# builder and its arguments, the program's signature: a builder's body
# reads nothing else, so no device array of any fit is kept here.
_ROUND_PROGRAMS_MAX = 16
_round_programs: "OrderedDict[tuple, Callable]" = OrderedDict()
_round_programs_lock = threading.Lock()


def round_program(build: Callable[..., Callable], *signature) -> Callable:
    """``jax.jit(build(*signature))``, built once per signature.

    A fit whose signature equals an earlier fit's gets the jitted program
    that fit built, so the program is neither traced, lowered nor loaded
    again; JAX itself keys that program on the shapes, dtypes and
    placements of its arguments. ``build`` is a module-level function
    whose program reads its arguments and nothing else of the fit. The
    store keeps ``_ROUND_PROGRAMS_MAX`` programs and drops the least
    recently used; an unhashable signature gets a program that is not
    kept."""
    key = (build,) + signature
    try:
        hash(key)
    except TypeError:
        return jax.jit(build(*signature))
    with _round_programs_lock:
        program = _round_programs.pop(key, None)
        if program is None:
            program = jax.jit(build(*signature))    # lazy: nothing traced
        _round_programs[key] = program
        if len(_round_programs) > _ROUND_PROGRAMS_MAX:
            _round_programs.popitem(last=False)
    return program


def clear_round_programs() -> None:
    """Empty the store: the next fit of every signature builds anew."""
    with _round_programs_lock:
        _round_programs.clear()


def _finalize(outs: Dict[str, Any], init: Dict[str, Any], masked: bool,
              rounds: int, dims: Sequence[int], pad_to: Optional[int],
              comm: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Shared host-side tail of the fused engines: ONE ``jax.device_get``
    of the scalar bundle, early-stop trimming, history assembly.

    History columns: train/eval losses and metrics get the round-0 ``init``
    entry prepended (length T+1); ``comm`` maps ledger columns to exact
    per-round Python ints (so the accounting never loses precision to f32
    at scale) — either one value repeated every round (static collective
    shapes) or a length-``rounds`` list (e.g. the model-memory ledger,
    which grows per round for fresh-fit orgs), trimmed like every other
    column on early stop."""
    params_stacked = outs.pop("params")           # stays on device
    with tracing.span("sync"):
        scalars, init = jax.device_get((outs, init))  # the ONE host sync
    n_valid = int(scalars["valid"].sum()) if masked else rounds
    tracing.count("rounds", n_valid)
    history: Dict[str, List[float]] = {}
    for col, vals in scalars.items():
        if col in ("eta", "w", "valid"):
            continue
        history[col] = [float(init[col])] + [float(v) for v in vals[:n_valid]]
    for col, per_round in (comm or {}).items():
        history[col] = (list(per_round[:n_valid])
                        if isinstance(per_round, (list, tuple))
                        else [per_round] * n_valid)
    return {
        "params": jax.tree_util.tree_map(lambda l: l[:n_valid], params_stacked),
        "etas": [float(e) for e in scalars["eta"][:n_valid]],
        "weights": [jnp.asarray(w) for w in scalars["w"][:n_valid]],
        "history": history,
        "dims": dims,
        "pad_to": pad_to,
    }


def _resid_wire_bytes(config) -> int:
    """Per-element width of the residual broadcast on the wire (step 2):
    2 under ``GALConfig(residual_dtype="bf16")``, 4 otherwise. The ONE
    place the ledgers and the engines read the compressed-broadcast knob."""
    return 2 if getattr(config, "residual_dtype", "float32") in (
        "bf16", "bfloat16") else 4


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _grad_allreduce(x, axes):
    """Identity whose VJP psums the cotangent over ``axes``.

    Inside ``shard_map`` a ``psum`` in the loss transposes to identity, so
    ``jax.grad`` of a psum'd global-mean objective yields only the LOCAL
    shard's gradient contribution — correct values, shard-local gradients.
    Wrapping a replicated scalar input (the line-search eta) in this
    primitive reassembles the global gradient at the leaf, the same
    correction ``fit_weights(grad_axes=...)`` applies explicitly per step."""
    return x


def _grad_allreduce_fwd(x, axes):
    return x, None


def _grad_allreduce_bwd(axes, _, ct):
    for ax in axes:
        ct = jax.lax.psum(ct, ax)
    return (ct,)


_grad_allreduce.defvjp(_grad_allreduce_fwd, _grad_allreduce_bwd)


def _run_rounds(key, y_in, evals_in, broadcast, fit_orgs, *, loss, config,
                m, n, k, masked, metrics, alice_loss, state0=(), t0=0,
                restore=None, member_sched=None, org_ids=None,
                wfit_kwargs=None, f0=None, eta_grad_axes=()):
    """The shared T-round loop of both fused engines: Alg. 1 steps 1-6
    traced once and scanned over rounds ``t0 .. config.rounds`` (``t0=0``
    for a fresh fit; a resumed fit restores the scan carry and picks up
    mid-sequence).

    The org axis enters ONLY through two primitives supplied by the caller:

      * ``broadcast(r)`` — step 2's residual distribution (identity on the
        vmap engine; a masked psum from Alice's device on the mesh engine);
      * ``fit_orgs(k_round, r_bcast, t, state, active)
        -> (state, params_out, preds, combine)`` — step 3's parallel fits.
        ``state`` is the caller's opaque carry through the round scan (the
        DMS groups' shared extractor / stacked-head buffers; ``()`` for
        stateless engines) — updates must be frozen when ``active`` is
        False so early-stopped rounds leave it untouched. ``params_out``
        is the per-round params output (group-stacked / org-sharded; an
        EMPTY pytree for state-carried groups), ``preds`` the (M, N, K)
        fitted values — in org order — handed to the step-4 weight fit, and
        ``combine(w, name)`` the weighted org-sum of fitted values on the
        train set (``name=None``) or eval set ``name`` (einsum vs psum).
        ``t`` is the 0-based round index, which noisy groups fold into the
        prediction-stage noise keys.

    ``metrics`` maps metric names to in-trace callables ``(y, f) ->
    scalar`` (the device-side metric registry, ``repro.metrics.METRICS``);
    each eval set gets one history column per metric, so the whole eval
    curve stays inside the single post-scan host sync.

    ``restore`` resumes an interrupted collaboration: a
    ``(f, f_evals, active)`` triple (the artifact's saved carry — the
    ensemble state after round ``t0``, the per-eval-set carries, and the
    early-stop flag) replaces the cold-start carry, and ``key`` must be
    the post-round-``t0`` RNG key, so the scanned rounds ``t0..T`` draw
    exactly what an uninterrupted ``T``-round fit would have drawn (the
    per-round split chain continues where it left off — including through
    early-stop-masked rounds, which still split).

    ``member_sched`` is the (config.rounds, M) boolean membership schedule
    (``core.membership``); round t's row rides the scan inputs next to the
    round index, masks that round's weight fit (absent orgs get weight
    exactly 0.0 — so they also contribute exact zeros to the direction and
    to every eval combine), and is handed to ``fit_orgs`` for engine-side
    bookkeeping (DMS carry freezing). ``org_ids`` keys the weight-fit
    theta draws by org IDENTITY, so a reduced org set draws the same
    per-org jitter — together these make a masked fit bitwise-equal to
    fitting the reduced org set. ``None`` means every org attends every
    round (the pre-membership fast path, bit-identical to it).

    ``wfit_kwargs`` distributes the step-4 weight fit: a callable mapping
    this round's ``(preds, residual)`` to extra ``fit_weights`` kwargs (the
    block-sharded engine supplies a Gram-statistics ``objective_fn`` for
    the quadratic alice loss, a psum-combining ``combine_fn`` otherwise,
    plus ``grad_axes``; None keeps the replicated fit byte-identical).
    ``f0``
    overrides the cold-start ensemble init ``loss.init_prediction(y_in)``
    — the data-sharded engine computes it host-side from the FULL label
    vector, since e.g. a median init is not a per-shard reduction.

    ``config.residual_dtype="bf16"`` casts the privatized residual to
    bfloat16 BEFORE it crosses ``broadcast`` (the wire) and upcasts after:
    the identity broadcast of the vmap engines and the single-contributor
    psum of the mesh engine both reproduce the rounded values exactly, so
    all engines stay draw-for-draw identical under compression too. Alice's
    own weight-fit / line-search steps keep her full-precision residual —
    only what leaves her device is compressed.

    Everything else — residual, privacy, weight fit, eta line search,
    masked early stopping, history bookkeeping — is engine-independent and
    lives here exactly once. Returns ``(outs, init, carry_final)``; the
    full final carry is what ``GALResult.resume_state`` (and therefore the
    on-disk artifact) persists.
    """
    have_sched = member_sched is not None
    compress = _resid_wire_bytes(config) == 2

    def round_step(carry, xs):
        t, member_row = xs
        # membership off -> the literal pre-membership code path (mask=None
        # everywhere), so an unmasked fit stays bit-identical to before
        member = member_row if have_sched else None
        f, f_evals, key, active, state = carry
        # 1. pseudo-residual
        with tracing.scope("residual"):
            key, k_round = jax.random.split(key)
            residual = loss.residual(y_in, f)
        # 2. privatized broadcast
        with tracing.scope("broadcast"):
            r_wire = apply_privacy(
                jax.random.fold_in(k_round, 13), residual, config.privacy,
                alpha=config.privacy_alpha,
                n_intervals=config.privacy_intervals,
            )
            if compress:
                r_wire = r_wire.astype(jnp.bfloat16)
            r_bcast = broadcast(r_wire)
            if r_bcast.dtype != residual.dtype:
                r_bcast = r_bcast.astype(residual.dtype)
        # 3. parallel local fits over the org axis
        with tracing.scope("local_fit"):
            state, params_out, preds, combine = fit_orgs(
                k_round, r_bcast, t, state, active, member)
        # 4. gradient assistance weights (masked over this round's live orgs)
        with tracing.scope("weight_fit"):
            if config.use_weights and m > 1:
                w = fit_weights(
                    jax.random.fold_in(k_round, 29), residual, preds,
                    alice_loss, epochs=config.weight_epochs,
                    lr=config.weight_lr, weight_decay=config.weight_decay,
                    mask=member, org_ids=org_ids,
                    **(wfit_kwargs(preds, residual)
                       if wfit_kwargs is not None else {}),
                )
            else:
                w = uniform_weights(m, mask=member)
        with tracing.scope("combine"):
            direction = combine(w, None)

        # 5. line-search eta   6. masked ensemble update
        # on a data-sharded mesh the loss value is global (psum'd) but its
        # AD gradient is shard-local; _grad_allreduce on eta restores the
        # global gradient the secant iteration needs
        with tracing.scope("eta"):
            eta_in = ((lambda e: _grad_allreduce(e, eta_grad_axes))
                      if eta_grad_axes else (lambda e: e))
            eta = line_search(
                lambda e: loss(y_in, f + eta_in(e) * direction),
                method=config.eta_method, x0=config.eta0,
            )
            eta_eff = jnp.where(active, eta, 0.0) if masked else eta
            f_new = f + eta_eff * direction

            outs = {"params": params_out, "eta": eta_eff, "w": w,
                    "valid": active, "train_loss": loss(y_in, f_new)}
            new_evals = {}
            for name, (_, y_e) in evals_in.items():
                fe = f_evals[name] + eta_eff * combine(w, name)
                new_evals[name] = fe
                outs[f"{name}_loss"] = loss(y_e, fe)
                for mname, metric_fn in (metrics or {}).items():
                    outs[f"{name}_{mname}"] = metric_fn(y_e, fe)
            new_active = (active & (jnp.abs(eta) >= config.eta_stop_threshold)
                          if masked else active)
        return (f_new, new_evals, key, new_active, state), outs

    if restore is None:
        f0v = loss.init_prediction(y_in) if f0 is None else f0
        f = jnp.broadcast_to(f0v, (n, k))
        f_evals = {
            name: jnp.broadcast_to(f0v, (y_e.shape[0], k))
            for name, (_, y_e) in evals_in.items()
        }
        active0 = jnp.asarray(True)
    else:
        f, f_evals_r, active0 = restore
        f_evals = {name: f_evals_r[name] for name in evals_in}
        active0 = jnp.asarray(active0)
    # on a resume the "init" row is the restored-carry loss, not round 0's —
    # the caller stitches the artifact's history in front and drops it
    init = {"train_loss": loss(y_in, f)}
    for name, (_, y_e) in evals_in.items():
        init[f"{name}_loss"] = loss(y_e, f_evals[name])
        for mname, metric_fn in (metrics or {}).items():
            init[f"{name}_{mname}"] = metric_fn(y_e, f_evals[name])
    carry0 = (f, f_evals, key, active0, state0)
    sched_rows = (jnp.ones((config.rounds - t0, m), bool)
                  if member_sched is None else member_sched[t0:])
    carry, outs = _scan_rounds(round_step, carry0,
                               (jnp.arange(t0, config.rounds), sched_rows))
    return outs, init, carry


def _scan_rounds(step, carry, xs):
    """``lax.scan(step, carry, xs)``, kept a loop when it has one round.

    XLA inlines a loop of one trip into its caller, where the known
    starting carry fuses into the round's math differently than inside the
    loop; a one-round fit then differs from round 0 of a longer fit in the
    last f32 bits. Resume and the contributivity counterfactuals promise
    bitwise equality across such splits, so a single round runs in a
    ``while_loop`` bounded by a value hidden behind an optimization barrier.
    Longer fits stay a ``lax.scan``, whose trip count the roofline parser
    reads from the HLO."""
    length = jax.tree_util.tree_leaves(xs)[0].shape[0]
    if length != 1:
        return jax.lax.scan(step, carry, xs)
    at = lambda i: jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), xs)
    out_shapes = jax.eval_shape(step, carry, at(0))[1]
    ys = jax.tree_util.tree_map(
        lambda s: jnp.zeros((length,) + s.shape, s.dtype), out_shapes)

    def body(state):
        i, c, ys = state
        c, y = step(c, at(i))
        ys = jax.tree_util.tree_map(
            lambda b, v: jax.lax.dynamic_update_index_in_dim(b, v, i, 0),
            ys, y)
        return i + 1, c, ys

    n = jax.lax.optimization_barrier(jnp.int32(length))
    _, carry, ys = jax.lax.while_loop(lambda s: s[0] < n, body,
                                      (jnp.int32(0), carry, ys))
    return carry, ys


def _dms_org_round(model, lloss, key_m, x_m, ext_m, heads_m, rhist, t,
                   k_out, live_m=None):
    """One organization's Deep Model Sharing refit at 0-based round ``t``,
    replicating ``Organization._fit_round_dms`` with FIXED-shape buffers so
    the whole thing lives inside the scanned round step:

      * ``heads_m`` is the stacked ``(T, ...)`` head buffer — round ``t``'s
        fresh head (``init_head(fold_in(rng, t+1))``, the reference's
        1-based key) is written into slot ``t``;
      * ``rhist`` is the shared ``(T, N, K)`` broadcast-residual history;
      * the joint extractor+heads Adam refit optimizes the reference's
        per-slot objective — mean over rounds <= t of
        ``lloss(r^s, head_s(features(x)))`` — with slots beyond ``t``
        masked out, so their gradients are exactly zero and Adam leaves
        them untouched (the masked mean equals the reference's mean over
        its t live heads term for term).

    ``live_m`` is this org's (T,) membership column (None = always live):
    rounds the org skipped are dead slots — their heads stay zero, they are
    masked out of the refit objective exactly like not-yet-live slots, and
    the divisor counts attended rounds only. (The caller freezes the whole
    per-org state update when the org is absent THIS round; the column
    keeps its past absences out of every later refit.)

    Returns the refit ``(ext_m, heads_m)`` and this round's fitted values
    ``apply_head(heads_m[t], features(ext_m, x_m))``.
    """
    head_new = model.init_head(jax.random.fold_in(key_m, t + 1), k_out)
    heads_m = jax.tree_util.tree_map(
        lambda buf, hn: jax.lax.dynamic_update_index_in_dim(buf, hn, t, 0),
        heads_m, head_new)
    rounds_total = rhist.shape[0]
    mask = jnp.arange(rounds_total) <= t
    if live_m is not None:
        mask = mask & live_m
    n_live = jnp.maximum(jnp.sum(mask), 1) if live_m is not None else t + 1

    def objective(p):
        ext, heads = p
        feats = model.features({**ext, "head": None}, x_m)
        preds = jax.vmap(lambda h: model.apply_head(h, feats))(heads)
        # double-where: not-yet-live slots hold zero heads on zero
        # residuals, exactly where losses like sqrt(|r-f|) have an
        # unbounded derivative — masking only the OUTPUT would still
        # backprop 0 * inf = NaN into the shared extractor. Evaluating
        # dead slots at a fixed unit offset keeps their loss gradient
        # finite, the inner where zeroes their cotangent exactly, and the
        # outer where drops their (arbitrary) value from the sum; live
        # slots are untouched.
        mask3 = mask[:, None, None]
        safe_preds = jnp.where(mask3, preds, rhist + 1.0)
        per_slot = jax.vmap(lloss)(rhist, safe_preds)       # (T,)
        return jnp.sum(jnp.where(mask, per_slot, 0.0)) / n_live

    opt = adam(getattr(model, "lr", 1e-3))

    def step(carry, _):
        p, s = carry
        g = jax.grad(objective)(p)
        upd, s = opt.update(g, s, p)
        return (apply_updates(p, upd), s), None

    params = (ext_m, heads_m)
    (params, _), _ = jax.lax.scan(step, (params, opt.init(params)), None,
                                  length=getattr(model, "epochs", 100))
    ext_m, heads_m = params
    return ext_m, heads_m, _dms_apply(model, ext_m, heads_m, t, x_m)


def _dms_apply(model, ext_m, heads_m, t, x_m):
    """DMS prediction for one org: round ``t``'s head over the shared
    extractor's features (the traced twin of ``predict_round``)."""
    feats = model.features({**ext_m, "head": None}, x_m)
    head_t = jax.tree_util.tree_map(lambda l: l[t], heads_m)
    return model.apply_head(head_t, feats)


def _pad_rounds(resume_state: Dict[str, Any], groups, t0: int,
                rounds: int) -> Dict[str, Any]:
    """Grow a restored DMS carry from ``t0`` round slots to ``rounds``:
    the shared residual-history buffer pads on axis 0, every group's
    stacked head buffer on axis 1 (after the org axis). The padding is
    zeros — exactly what an uninterrupted ``rounds``-round fit would hold
    in its not-yet-live slots, so the masked per-slot DMS objective is
    unchanged term for term."""
    pad = rounds - t0
    state = dict(resume_state)
    if pad > 0 and "rhist" in state:
        rh = jnp.asarray(state["rhist"])
        state["rhist"] = jnp.pad(rh, ((0, pad),) + ((0, 0),) * (rh.ndim - 1))
        for gi, g in enumerate(groups):
            if not g.dms:
                continue
            gs = state[f"g{gi}"]
            state[f"g{gi}"] = {
                "extractor": gs["extractor"],
                "heads": jax.tree_util.tree_map(
                    lambda l: jnp.pad(
                        jnp.asarray(l),
                        ((0, 0), (0, pad)) + ((0, 0),) * (l.ndim - 2)),
                    gs["heads"]),
            }
    return state


def fit_grouped(rng: jax.Array, orgs: Sequence[Any], y: jnp.ndarray,
                loss: Loss, config: Any,
                eval_sets: Optional[Dict[str, tuple]] = None,
                metrics: Optional[Dict[str, Callable]] = None, *,
                plan: Optional[ExecutionPlan] = None,
                resume: Optional[Dict[str, Any]] = None,
                membership=None) -> Dict[str, Any]:
    """Run Algorithm 1 as one jitted scan over the planner's groups.

    Every group is a ``jax.vmap`` of its own model over its own stacked
    slice block, all inside the SAME traced round step; group fitted values
    are concatenated back into org order before the step-4 weight fit, so a
    heterogeneous GB–SVM mix, per-org local losses (ell_q or any traceable
    callable) and noisy orgs pay the same single host sync as the
    homogeneous case. Deep Model Sharing groups (paper Sec. 4.2/5) carry
    their shared extractor and stacked ``(T, ...)`` head buffer through the
    round scan (``_dms_org_round``); the Table-14 memory win is recorded in
    ``history["model_memories"]``. On a multi-device host where the device
    count divides every group size (and the plan is neither a single
    noiseless group — that case belongs to ``fit_shard``'s real
    collectives — nor stateful DMS), each group's stack is placed
    org-sharded along an "org" mesh axis and GSPMD partitions every
    group's fits across the devices.

    A fit reuses the jitted round program of an earlier fit with an equal
    signature (``round_program``), which then is neither traced, lowered
    nor loaded again. The signature is the plan (each group's model, local
    loss, noise sigma and DMS flag by value, its org positions and ids),
    the frozen ``GALConfig``, the loss, the metric callables by identity
    and the first round ``t0``; sizes, dtypes and placements are read from
    the arguments, and every array of the fit is an argument. A model or
    loss enters by value where it is a frozen dataclass (the zoo's are)
    and by identity otherwise, so one changed in place after a fit keeps
    its key: change it by making a new one.

    Returns a dict with host lists ``etas`` / ``weights``, the ``history``
    dict (losses/metrics as floats, the simulated per-round communication
    and model-memory ledgers as exact ints), device-side per-group stacked
    params ``group_params`` (leaves ``(T_valid, M_g, ...)``; DMS groups
    instead carry ``{"extractor": (M_g, ...), "heads": (M_g, T, ...)}``),
    the per-group ``group_dims`` / ``group_pads`` geometry, and —
    single-group fresh-fit plans only — the legacy ``params`` / ``dims`` /
    ``pad_to`` fields.

    ``resume`` (built by ``gal.fit(..., resume_from=...)``) restores the
    round-scan carry of a saved artifact — the ensemble state, per-eval
    carries, post-scan RNG key, early-stop flag, and (for DMS plans) the
    extractor/head/residual buffers, padded out to the new round count —
    and scans only rounds ``t_next .. config.rounds``; the returned dict
    then covers the NEW rounds only (the caller stitches).

    ``membership`` is the resolved bool (config.rounds, M) attendance
    schedule from ``core.membership.resolve_membership`` (None = all
    live): round t's row masks the weight fit (absent orgs get weight
    exactly 0.0), DMS orgs freeze their shared-extractor/head state in
    rounds they skip (their skipped slots stay dead in every later
    refit), and the per-round communication / model-memory ledgers count
    only the live orgs. On a resume the schedule must cover ALL rounds —
    rows before ``t_next`` are the collaboration's recorded history (they
    drive the DMS dead-slot masks), rows from ``t_next`` on are executed.
    """
    if plan is None:
        plan = plan_orgs(orgs, eval_sets)
    if not plan.compiled:
        raise ValueError(
            f"cannot compile this organization set: {plan.reason}")
    groups = plan.groups
    m = len(orgs)
    n, k = y.shape[0], y.shape[-1]
    masked = config.eta_stop_threshold > 0.0

    # staging: the orgs' slices stacked per group and placed, the operands
    # of the round program
    with tracing.span("stage"):
        mesh = None
        if (not plan.homogeneous and not plan.has_dms
                and grouped_mesh_eligible([g.size for g in groups])):
            mesh = make_org_mesh(len(jax.devices()))

        index_groups = [g.indices for g in groups]
        group_x, group_dims, group_pads = stack_groups(
            [org.x_train for org in orgs], index_groups, mesh=mesh)
        org_ids_all = jnp.asarray([org.index for org in orgs], jnp.uint32)
        sched_np = (None if membership is None
                    else np.asarray(membership, bool))
        sched_in = None if sched_np is None else jnp.asarray(sched_np)

        y_in = y if mesh is None else jax.device_put(y, org_replicated(mesh))
        eval_stacks = {}
        if eval_sets:
            for name, (xs_e, y_e) in eval_sets.items():
                stacks_e, _, _ = stack_groups(list(xs_e), index_groups,
                                              pad_tos=group_pads, mesh=mesh)
                y_e_in = (y_e if mesh is None
                          else jax.device_put(y_e, org_replicated(mesh)))
                eval_stacks[name] = (tuple(stacks_e), y_e_in)

        t0 = 0
        key0 = rng
        resume_in = None
        if resume is not None:
            t0 = int(resume["t_next"])
            key0 = jnp.asarray(resume["key"])
            resume_in = {
                "f": jnp.asarray(resume["f"]),
                "f_evals": {nm: jnp.asarray(v)
                            for nm, v in resume.get("f_evals", {}).items()},
                "active": jnp.asarray(resume["active"]),
                "state": _pad_rounds(resume.get("state", {}) or {}, groups,
                                     t0, config.rounds),
            }
            if mesh is not None:
                resume_in = jax.tree_util.tree_map(
                    lambda a: jax.device_put(a, org_replicated(mesh)),
                    resume_in)
        if mesh is not None:
            org_ids_all = jax.device_put(org_ids_all, org_replicated(mesh))
            if sched_in is not None:
                sched_in = jax.device_put(sched_in, org_replicated(mesh))

    with tracing.span("launch"):
        gal_rounds = round_program(_grouped_rounds, plan, config, loss,
                                   tuple((metrics or {}).items()), t0)
        outs, init, carry = gal_rounds(
            key0, y_in, tuple(group_x), eval_stacks, resume_in, sched_in,
            org_ids_all)
    state_final = carry[4]
    dms_flags = [False] * m
    for g in groups:
        for i in g.indices:
            dms_flags[i] = g.dms
    eval_ns = [int(y_e.shape[0])
               for (_, y_e) in (eval_sets or {}).values()]
    rb = _resid_wire_bytes(config)
    if sched_np is None:
        bcast_b, gather_b = gal_round_bytes(n, k, m, eval_ns,
                                            resid_dtype_bytes=rb)
    else:
        from repro.core.membership import membership_comm_ledger
        bcast_l, gather_l = membership_comm_ledger(sched_np, n, k, eval_ns,
                                                   resid_dtype_bytes=rb)
        bcast_b, gather_b = bcast_l[t0:], gather_l[t0:]
    single = len(groups) == 1 and not plan.has_dms
    with tracing.span("finalize"):
        out = _finalize(outs, init, masked, config.rounds - t0,
                        dims=group_dims[0] if single else None,
                        pad_to=group_pads[0] if single else None,
                        comm={"comm_broadcast_bytes": bcast_b,
                              "comm_gather_bytes": gather_b,
                              "model_memories": gal_model_memories(
                                  config.rounds, dms_flags,
                                  membership=sched_np)[t0:]})
    if sched_np is not None:
        # executed rows only (early-stop trimmed), host bools in org order
        out["membership"] = sched_np[t0:t0 + len(out["etas"])].tolist()
    group_params = list(out["params"])            # tuple trimmed by _finalize
    for gi, g in enumerate(groups):
        if g.dms:
            # the final carry state IS the fitted DMS ensemble: the shared
            # extractor after the last live round plus every round's head
            group_params[gi] = state_final[f"g{gi}"]
    out["params"] = group_params[0] if single else None
    out["group_params"] = group_params
    out["group_dims"] = group_dims
    out["group_pads"] = group_pads
    out["plan"] = plan
    out["mesh_devices"] = 0 if mesh is None else len(jax.devices())
    # the final round-scan carry, verbatim: what save_artifact persists and
    # a later fit(resume_from=...) restores. The key has been split once
    # per scanned round (masked rounds included), so resuming continues
    # the exact per-round draw chain of an uninterrupted longer fit.
    out["resume"] = {"t_next": config.rounds, "f": carry[0],
                     "f_evals": carry[1], "key": carry[2],
                     "active": carry[3], "state": state_final}
    return out


def _grouped_rounds(plan: ExecutionPlan, config: Any, loss: Loss,
                    metrics: tuple, t0: int) -> Callable:
    """The round program of ``fit_grouped`` for one signature, unjitted:
    the plan (its groups hold each model, local loss, noise sigma and DMS
    flag by value, and the org positions and ids), the frozen
    ``GALConfig``, the loss, the metrics as ``(name, callable)`` pairs and
    the first round ``t0``. Sizes are read from the arguments' shapes;
    the fit's data arrive as arguments only."""
    groups = plan.groups
    m = plan.n_orgs
    alice_loss = lq_loss(config.alice_q)
    masked = config.eta_stop_threshold > 0.0
    metrics = dict(metrics)

    # the round program (its XLA module is ``jit_gal_rounds``)
    def gal_rounds(key, y_dev, xg_in, evals_in, res_in, sched_dev,
                   ids_dev):
        tracing.count("round_traces")     # runs only while JAX traces it
        n, k = y_dev.shape[0], y_dev.shape[-1]
        group_ids = [jnp.asarray(g.org_ids, jnp.uint32) for g in groups]
        group_pos = [jnp.asarray(g.indices, jnp.int32) for g in groups]
        inv_perm = jnp.asarray(plan.inverse_permutation, jnp.int32)
        # DMS carry: one shared (T, N, K) residual-history buffer plus each
        # DMS group's extractor stack and (M_g, T, ...) head buffers. The
        # extractor inits replicate the reference exactly: round 0's
        # k_round is split(rng)[1], and org m's init key fold_in(., index).
        # On a resume the carry arrives fully formed from the artifact.
        state0: Dict[str, Any] = {} if res_in is None else res_in["state"]
        restore = (None if res_in is None
                   else (res_in["f"], res_in["f_evals"], res_in["active"]))
        if plan.has_dms and res_in is None:
            k_round0 = jax.random.split(key)[1]
            state0["rhist"] = jnp.zeros((config.rounds, n, k), y_dev.dtype)
            for gi, g in enumerate(groups):
                if not g.dms:
                    continue
                keys0 = jax.vmap(lambda i: jax.random.fold_in(
                    k_round0, i))(group_ids[gi])

                def init_ext(key_m, x_m, model=g.model):
                    full = model.init(key_m, x_m, k)
                    return {kk: v for kk, v in full.items() if kk != "head"}

                head_spec = jax.eval_shape(
                    lambda kk, model=g.model: model.init_head(kk, k),
                    jax.random.PRNGKey(0))
                state0[f"g{gi}"] = {
                    "extractor": jax.vmap(init_ext)(keys0, xg_in[gi]),
                    "heads": jax.tree_util.tree_map(
                        lambda s: jnp.zeros(
                            (g.size, config.rounds) + s.shape, s.dtype),
                        head_spec),
                }

        def fit_orgs(k_round, r_bcast, t, state, active, member):
            new_state = dict(state)
            if plan.has_dms:
                new_state["rhist"] = jax.lax.dynamic_update_index_in_dim(
                    state["rhist"], r_bcast, t, 0)
            # one vmapped model PER GROUP, all in the same traced step
            params_g, preds_g, dms_g = [], [], {}
            for gi, g in enumerate(groups):
                keys = jax.vmap(
                    lambda i: jax.random.fold_in(k_round, i))(group_ids[gi])
                if g.dms:
                    gs = state[f"g{gi}"]

                    if sched_dev is None:
                        def dms_one(key_m, x_m, ext_m, heads_m,
                                    model=g.model, lloss=g.local_loss):
                            return _dms_org_round(
                                model, lloss, key_m, x_m, ext_m, heads_m,
                                new_state["rhist"], t, k)

                        ext_new, heads_new, preds_t = jax.vmap(dms_one)(
                            keys, xg_in[gi], gs["extractor"], gs["heads"])
                    else:
                        # each org's (T,) membership column rides the vmap:
                        # its skipped rounds are dead head slots, masked
                        # out of every later refit objective
                        live_g = sched_dev[:, group_pos[gi]].T    # (Mg, T)

                        def dms_one(key_m, x_m, ext_m, heads_m, live_m,
                                    model=g.model, lloss=g.local_loss):
                            return _dms_org_round(
                                model, lloss, key_m, x_m, ext_m, heads_m,
                                new_state["rhist"], t, k, live_m)

                        ext_new, heads_new, preds_t = jax.vmap(dms_one)(
                            keys, xg_in[gi], gs["extractor"], gs["heads"],
                            live_g)
                        # absent THIS round: the whole per-org DMS state
                        # update is frozen — the skipped slot's head stays
                        # zero and the shared extractor is untouched,
                        # exactly as the reference loop's skip would leave
                        keep = member[group_pos[gi]]

                        def _frz(a, b, keep=keep):
                            shape = keep.shape + (1,) * (a.ndim - 1)
                            return jnp.where(keep.reshape(shape), a, b)

                        ext_new = jax.tree_util.tree_map(
                            _frz, ext_new, gs["extractor"])
                        heads_new = jax.tree_util.tree_map(
                            _frz, heads_new, gs["heads"])
                    new_state[f"g{gi}"] = {"extractor": ext_new,
                                           "heads": heads_new}
                    dms_g[gi] = new_state[f"g{gi}"]
                    params_t = ()      # state-carried; no per-round output
                else:
                    def fit_one(key_m, x_m, model=g.model,
                                lloss=g.local_loss):
                        params = model.fit(key_m, x_m, r_bcast, lloss)
                        return params, model.apply(params, x_m)

                    params_t, preds_t = jax.vmap(fit_one)(keys, xg_in[gi])
                if g.noise_sigma > 0.0:
                    # training-stage output noise, reference-engine keys
                    # (fold_in(org_key, 777), see Organization.fit_round)
                    preds_t = preds_t + g.noise_sigma * jax.vmap(
                        lambda kk: jax.random.normal(
                            jax.random.fold_in(kk, 777), (n, k)))(keys)
                params_g.append(params_t)
                preds_g.append(preds_t)
            if masked and plan.has_dms:
                # early-stopped rounds must leave the DMS carry untouched,
                # exactly as the reference loop's `break` would
                new_state = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(active, a, b), new_state, state)
                for gi in dms_g:
                    dms_g[gi] = new_state[f"g{gi}"]
            # concatenate group blocks back into ORG order for step 4
            preds = jnp.concatenate(preds_g, axis=0)[inv_perm]   # (M, N, K)

            def combine(w, name):
                if name is None:
                    return jnp.einsum("m,mnk->nk", w, preds)
                out = None
                for gi, g in enumerate(groups):
                    if g.dms:
                        gs = dms_g[gi]
                        pe = jax.vmap(
                            lambda e, h, x, model=g.model: _dms_apply(
                                model, e, h, t, x)
                        )(gs["extractor"], gs["heads"], evals_in[name][0][gi])
                    else:
                        pe = jax.vmap(g.model.apply)(params_g[gi],
                                                     evals_in[name][0][gi])
                    if g.noise_sigma > 0.0:
                        # prediction-stage noise, engine-independent keys
                        # (fold_in(PRNGKey(index), t), see predict_round)
                        pkeys = jax.vmap(lambda i: jax.random.fold_in(
                            jax.random.PRNGKey(i), t))(group_ids[gi])
                        pe = pe + g.noise_sigma * jax.vmap(
                            lambda kk: jax.random.normal(
                                kk, pe.shape[1:]))(pkeys)
                    part = jnp.einsum("m,mnk->nk", w[group_pos[gi]], pe)
                    out = part if out is None else out + part
                return out

            return new_state, tuple(params_g), preds, combine

        return _run_rounds(key, y_dev, evals_in, lambda r: r, fit_orgs,
                           loss=loss, config=config, m=m, n=n, k=k,
                           masked=masked, metrics=metrics,
                           alice_loss=alice_loss, state0=state0, t0=t0,
                           restore=restore, member_sched=sched_dev,
                           org_ids=ids_dev)

    return gal_rounds


def fit_scan(rng: jax.Array, orgs: Sequence[Any], y: jnp.ndarray, loss: Loss,
             config: Any, eval_sets: Optional[Dict[str, tuple]] = None,
             metrics: Optional[Dict[str, Callable]] = None, *,
             plan: Optional[ExecutionPlan] = None,
             resume: Optional[Dict[str, Any]] = None,
             membership=None) -> Dict[str, Any]:
    """The legacy homogeneous fast path: ``fit_grouped`` on a single-group
    plan (one model vmapped over one org stack). Kept as the named engine
    behind ``GALConfig.engine="scan"``; the dispatch in ``gal.fit`` enforces
    the single-noiseless-group contract before calling it."""
    return fit_grouped(rng, orgs, y, loss, config, eval_sets, metrics,
                       plan=plan, resume=resume, membership=membership)


class _DataAxisLoss:
    """Loss proxy for the data-sharded engine: the global mean loss is the
    psum of the equal shards' local means; the pseudo-residual stays an
    elementwise (hence shard-local) map. ``init_prediction`` is NOT a
    per-shard reduction (think median inits) — the engine computes it
    host-side from the full label vector and threads it through
    ``_run_rounds(f0=...)``, so the proxy never evaluates it in-trace."""

    def __init__(self, base: Loss, axis: str, shards: int):
        self.base, self.axis, self.shards = base, axis, shards

    def __call__(self, y, f):
        return jax.lax.psum(self.base(y, f), self.axis) / self.shards

    def residual(self, y, f):
        return self.base.residual(y, f)

    def init_prediction(self, y):
        return self.base.init_prediction(y)


def _shard_program(rng: jax.Array, orgs: Sequence[Any], y: jnp.ndarray,
                   loss: Loss, config: Any,
                   eval_sets: Optional[Dict[str, tuple]] = None,
                   metrics: Optional[Dict[str, Callable]] = None,
                   resume: Optional[Dict[str, Any]] = None,
                   membership=None) -> Dict[str, Any]:
    """Build (but do not run) the org-sharded engine's compiled program:
    placement, shard_map wrapping, jit, and the operand list. ``fit_shard``
    executes it; ``lower_shard_round`` hands its lowered HLO to the
    roofline tools so the collective traffic the compiler actually emits
    can be reconciled with the protocol ledger's ints."""
    from jax.sharding import NamedSharding

    m = len(orgs)
    data_shards = int(getattr(config, "data_shards", 1) or 1)
    if not org_mesh_eligible(m, data_shards):
        raise ValueError(
            f"engine='shard' needs an org mesh: {m} orgs must divide the "
            f"org-axis device count or be divisible by it for block "
            f"placement ({jax.device_count()} devices / {data_shards} data "
            f"shard(s), multi-device host required)")
    mesh = make_org_mesh(m, data_shards)
    bsz = org_block_size(m, data_shards)
    has_data = data_shards > 1
    model = orgs[0].model
    local_loss = orgs[0].local_loss
    n, k = y.shape[0], y.shape[-1]
    if has_data:
        if config.privacy:
            raise ValueError(
                "data_shards > 1 cannot run a privatized broadcast: the "
                "per-shard noise draws would not match the protocol's "
                "single (N, K) draw")
        if not getattr(model, "data_parallel", False):
            raise ValueError(
                f"data_shards > 1 needs a data-parallel local model "
                f"(fit accepting data_axis); {type(model).__name__} "
                f"does not declare data_parallel")
        if n % data_shards:
            raise ValueError(
                f"data_shards={data_shards} must divide the train rows "
                f"({n}) into equal shards")
    n_local = n // data_shards
    alice_loss = lq_loss(config.alice_q)
    masked = config.eta_stop_threshold > 0.0
    loss_in = _DataAxisLoss(loss, "data", data_shards) if has_data else loss
    alice_in = (_DataAxisLoss(alice_loss, "data", data_shards)
                if has_data else alice_loss)

    # org-major placement: a block of bsz org slices / ids per device (one
    # each under one-to-one placement), Alice state replicated; with a data
    # axis, each org's rows are additionally split across it
    x_stack, dims = pad_and_stack_sharded(
        [org.x_train for org in orgs], mesh, block_size=bsz,
        shard_data=has_data)
    pad_to = int(x_stack.shape[-1]) if x_stack.ndim == 3 else None
    org_ids = jax.device_put(
        jnp.asarray([org.index for org in orgs], jnp.uint32),
        org_stack_sharding(mesh, 1, block_size=bsz))
    # Alice's full id vector + the membership schedule ride replicated:
    # the weight fit is her step, not a per-device one
    ids_full = jax.device_put(
        jnp.asarray([org.index for org in orgs], jnp.uint32),
        org_replicated(mesh))
    sched_np = None if membership is None else np.asarray(membership, bool)
    sched_in = (None if sched_np is None
                else jax.device_put(jnp.asarray(sched_np),
                                    org_replicated(mesh)))
    y_spec = P("data") if has_data else P()
    y_dev = jax.device_put(y, NamedSharding(mesh, y_spec))
    eval_stacks, eval_in_specs = {}, {}
    if eval_sets:
        for name, (xs_e, y_e) in eval_sets.items():
            # eval slices stay replicated over "data": the prediction
            # stage is per-org, not per-row-shard
            xe_stack, _ = pad_and_stack_sharded(list(xs_e), mesh,
                                                pad_to=pad_to,
                                                block_size=bsz)
            eval_stacks[name] = (xe_stack,
                                 jax.device_put(y_e, org_replicated(mesh)))
            eval_in_specs[name] = (P("org"), P())

    t0 = 0
    key0 = rng
    extras: Dict[str, Any] = {}
    extras_specs: Dict[str, Any] = {}
    if has_data:
        # init ensemble from the FULL label vector, host-side (a median
        # init is not a per-shard reduction); rides the mesh replicated
        extras["f0"] = jnp.asarray(loss.init_prediction(y))
        extras_specs["f0"] = P()
    if resume is not None:
        t0 = int(resume["t_next"])
        key0 = jnp.asarray(resume["key"])
        # the restored carry is org-independent: replicate it on the mesh
        # (the ensemble state shards over "data" when that axis exists)
        extras["resume"] = {
            "f": jax.device_put(jnp.asarray(resume["f"]),
                                NamedSharding(mesh, y_spec)),
            "f_evals": {nm: jax.device_put(
                jnp.asarray(resume.get("f_evals", {})[nm]),
                org_replicated(mesh)) for nm in eval_stacks},
            "active": jax.device_put(jnp.asarray(resume["active"]),
                                     org_replicated(mesh))}
        extras_specs["resume"] = {
            "f": y_spec,
            "f_evals": {name: P() for name in eval_stacks},
            "active": P()}

    def gal_rounds(key, y_in, x_in, ids_in, evals_in, sched_dev, ids_all,
                   extra):
        tracing.count("round_traces")     # runs only while JAX traces it
        pos = jax.lax.axis_index("org")

        def broadcast(r_wire):
            # step 2 as a REAL collective: only Alice's device row (org
            # position 0) contributes, so the psum equals her privatized
            # residual exactly while crossing every device boundary
            return jax.lax.psum(
                jnp.where(pos == 0, r_wire, jnp.zeros_like(r_wire)), "org")

        wfit = None
        if bsz == 1 and not has_data:
            my_x = x_in[0]             # this device's org slice (N, d_max)
            my_id = ids_in[0]

            def fit_orgs(k_round, r_bcast, t, state, active, member):
                del t, active, member  # single noiseless fresh-fit group:
                # stateless, and membership acts purely through the step-4
                # weight mask (w[pos] == 0.0 zeroes this device's psum term)
                # THIS device's local fit only (the scan engine's vmap axis
                # became the mesh axis); RNG key identical to other engines
                params_m = model.fit(jax.random.fold_in(k_round, my_id),
                                     my_x, r_bcast, local_loss)
                pred_m = model.apply(params_m, my_x)          # (N, K)
                # step 4's inputs: fitted values gathered back to Alice
                preds = jax.lax.all_gather(pred_m, "org")     # (M, N, K)

                def combine(w, name):
                    # weighted org-sum as a psum over the mesh axis
                    out_m = pred_m if name is None \
                        else model.apply(params_m, evals_in[name][0][0])
                    return jax.lax.psum(w[pos] * out_m, "org")

                params_out = jax.tree_util.tree_map(lambda l: l[None],
                                                    params_m)
                return state, params_out, preds, combine
        else:
            # block placement / data axis: this device fits its WHOLE block
            # of bsz orgs (vmap inside the manual region), combines are a
            # block-local einsum + psum, and the step-4 weight fit is
            # distributed — each device optimizes against its own block of
            # fitted values, with the per-step theta gradient psummed back
            # to the replicated trajectory (see weights.fit_weights)
            def fit_orgs(k_round, r_bcast, t, state, active, member):
                del t, active, member
                keys = jax.vmap(
                    lambda i: jax.random.fold_in(k_round, i))(ids_in)

                def fit_one(key_m, x_m):
                    if has_data:
                        p = model.fit(key_m, x_m, r_bcast, local_loss,
                                      data_axis="data")
                    else:
                        p = model.fit(key_m, x_m, r_bcast, local_loss)
                    return p, model.apply(p, x_m)

                params_b, preds_b = jax.vmap(fit_one)(keys, x_in)
                # (M, N_local, K): the protocol's fitted-value gather, now
                # of block-local stacks
                preds = jax.lax.all_gather(preds_b, "org", tiled=True)

                def combine(w, name):
                    out_b = (preds_b if name is None
                             else jax.vmap(model.apply)(params_b,
                                                        evals_in[name][0]))
                    wl = jax.lax.dynamic_slice(w, (pos * bsz,), (bsz,))
                    return jax.lax.psum(
                        jnp.einsum("b,bnk->nk", wl, out_b), "org")

                return state, params_b, preds, combine

            grad_axes = ((("org",) if bsz > 1 else ())
                         + (("data",) if has_data else ()))

            def wfit(preds, residual):
                if bsz == 1:
                    # one org per device, rows sharded: the replicated
                    # einsum stands, only the loss mean reduces over "data"
                    return {"grad_axes": grad_axes}
                blk = jax.lax.dynamic_slice(
                    preds, (pos * bsz, 0, 0), (bsz,) + preds.shape[1:])
                if getattr(alice_in, "q", None) == 2.0:
                    # quadratic alice loss (the alice_q=2 default): the
                    # objective  mean (r - sum_m w_m p_m)^2  factors through
                    # per-block Gram statistics computed ONCE per round,
                    #   G_blk = blk . preds^T   (B, M)
                    #   c_blk = blk . r         (B,)
                    # so each of the 100 Adam epochs costs O(B*M) flops and
                    # a single (M,) gradient psum — no (N, K) tensor is
                    # touched, let alone reduced, inside the epoch loop.
                    # Each device's value is its block's partial sum; the
                    # explicit grad psum in fit_weights reassembles the
                    # exact replicated gradient (Adam never reads the
                    # value). Masked orgs still contribute exact zeros:
                    # w == 0.0 annihilates their rows and columns.
                    g_blk = jnp.einsum("bnk,mnk->bm", blk, preds)
                    c_blk = jnp.einsum("bnk,nk->b", blk, residual)
                    rss = jnp.sum(jnp.square(residual))
                    denom = residual.size

                    def objective_fn(w):
                        wl = jax.lax.dynamic_slice(w, (pos * bsz,), (bsz,))
                        quad = jnp.dot(wl, g_blk @ w) \
                            - 2.0 * jnp.dot(wl, c_blk)
                        return (quad + rss) / denom

                    return {"m": m, "objective_fn": objective_fn,
                            "grad_axes": grad_axes}

                def combine_fn(w):
                    wl = jax.lax.dynamic_slice(w, (pos * bsz,), (bsz,))
                    local = jnp.einsum("b,bnk->nk", wl, blk)
                    # forward: the exact psum'd combination; backward: AD
                    # sees only the local block's path (the other blocks
                    # enter as a stop_gradient constant), so the epoch's
                    # second (N, K) all-reduce — psum's transpose — never
                    # exists. The explicit (M,) grad psum in fit_weights
                    # reassembles the identical global gradient.
                    total = jax.lax.psum(jax.lax.stop_gradient(local), "org")
                    return total - jax.lax.stop_gradient(local) + local

                return {"m": m, "combine_fn": combine_fn,
                        "grad_axes": grad_axes}

        res_in = extra.get("resume")
        restore = (None if res_in is None
                   else (res_in["f"], res_in["f_evals"], res_in["active"]))
        return _run_rounds(key, y_in, evals_in, broadcast, fit_orgs,
                           loss=loss_in, config=config, m=m, n=n_local, k=k,
                           masked=masked, metrics=metrics,
                           alice_loss=alice_in, t0=t0, restore=restore,
                           member_sched=sched_dev, org_ids=ids_all,
                           wfit_kwargs=wfit, f0=extra.get("f0"),
                           eta_grad_axes=(("data",) if has_data else ()))

    # everything in the scalar bundle is replicated (collectives + identical
    # per-device programs on replicated inputs); only the per-round params
    # keep an org axis, split block-wise over the mesh
    out_specs = {"params": P(None, "org"), "eta": P(), "w": P(),
                 "valid": P(), "train_loss": P()}
    for name in eval_stacks:
        out_specs[f"{name}_loss"] = P()
        for mname in (metrics or {}):
            out_specs[f"{name}_{mname}"] = P()
    # the returned carry is fully replicated — ensemble state, per-eval
    # carries, key and early-stop flag ride the collectives — except the
    # train-set ensemble, which shards over "data" when that axis exists;
    # the state slot is the empty tuple (shard plans are stateless)
    carry_specs = (y_spec, {name: P() for name in eval_stacks}, P(), P(), ())
    x_spec = P("org", "data") if has_data else P("org")
    in_specs = [P(), y_spec, x_spec, P("org"), eval_in_specs, P(), P(),
                extras_specs]
    operands = [key0, y_dev, x_stack, org_ids, eval_stacks, sched_in,
                ids_full, extras]
    run_sharded = jax.shard_map(
        gal_rounds, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=(out_specs, P(), carry_specs),
        check_vma=False,
    )
    return {"jit": jax.jit(run_sharded), "operands": operands,
            "mesh": mesh, "dims": dims, "pad_to": pad_to,
            "sched_np": sched_np, "t0": t0, "n": n, "k": k, "m": m,
            "eval_ns": [int(y_e.shape[0])
                        for (_, y_e) in eval_stacks.values()],
            "block_size": bsz, "data_shards": data_shards,
            "masked": masked}


def fit_shard(rng: jax.Array, orgs: Sequence[Any], y: jnp.ndarray, loss: Loss,
              config: Any, eval_sets: Optional[Dict[str, tuple]] = None,
              metrics: Optional[Dict[str, Callable]] = None,
              resume: Optional[Dict[str, Any]] = None,
              membership=None) -> Dict[str, Any]:
    """Run Algorithm 1 org-sharded across devices (see the module docstring).

    Same contract as ``fit_scan`` — the T-round ``lax.scan``, the single
    host sync, and the returned dict are identical — but the org axis is a
    real device mesh instead of a ``vmap``: an org's padded slice,
    per-round params, and fitted values never leave its device except
    through Alg. 1's three collectives (residual broadcast, fitted-value
    gather, weighted direction psum). Two placements (see
    ``launch.mesh.org_mesh_eligible``): one-to-one — one org per device —
    and block — a contiguous block of ``M // device_count`` orgs per
    device, fitted by a vmap inside the manual region, with the step-4
    weight fit distributed over the blocks. ``GALConfig(data_shards=...)``
    adds a second "data" mesh axis splitting each org's N rows (the
    per-round weight fit and eta line search reduce across it);
    ``GALConfig(residual_dtype="bf16")`` halves the broadcast wire width.
    The returned history carries the per-round communication ledger
    (``comm_broadcast_bytes`` / ``comm_gather_bytes``, paper Table-14
    convention: Alice already holds her own residual copy, every org —
    Alice included — ships its fitted values; the broadcast column counts
    the compressed wire dtype).

    ``resume`` restores an artifact's round-scan carry (replicated across
    the mesh — the ensemble state and RNG chain are org-independent) and
    scans rounds ``t_next .. config.rounds`` only, exactly as
    ``fit_grouped`` does; shard plans are stateless (no DMS carry).

    ``membership`` (resolved bool (rounds, M) schedule or None) rides the
    mesh replicated: an absent org's device still fits — the collectives
    have static shapes — but its assistance weight is exactly 0.0, so its
    psum contribution is exact zeros and the recorded per-round wire
    ledger counts only the live orgs."""
    with tracing.span("stage"):
        prog = _shard_program(rng, orgs, y, loss, config, eval_sets,
                              metrics, resume, membership)
    with tracing.span("launch"):
        outs, init, carry = prog["jit"](*prog["operands"])
    # per-round ledger of the collectives above, from the (static) operand
    # shapes — exact ints, Table-14 convention (Alice already holds her
    # residual copy; all M orgs ship fitted values for the train AND eval
    # prediction stages). gal_round_bytes is the one formula every
    # engine's ledger comes from, so the history is engine-independent.
    n, k, m = prog["n"], prog["k"], prog["m"]
    t0, sched_np, eval_ns = prog["t0"], prog["sched_np"], prog["eval_ns"]
    rb = _resid_wire_bytes(config)
    if sched_np is None:
        bcast_b, gather_b = gal_round_bytes(n, k, m, eval_ns,
                                            resid_dtype_bytes=rb)
    else:
        from repro.core.membership import membership_comm_ledger
        bcast_l, gather_l = membership_comm_ledger(sched_np, n, k, eval_ns,
                                                   resid_dtype_bytes=rb)
        bcast_b, gather_b = bcast_l[t0:], gather_l[t0:]
    with tracing.span("finalize"):
        out = _finalize(outs, init, prog["masked"], config.rounds - t0,
                        prog["dims"], prog["pad_to"],
                        comm={"comm_broadcast_bytes": bcast_b,
                              "comm_gather_bytes": gather_b,
                              "model_memories": gal_model_memories(
                                  config.rounds, [False] * m,
                                  membership=sched_np)[t0:]})
    if sched_np is not None:
        out["membership"] = sched_np[t0:t0 + len(out["etas"])].tolist()
    out["resume"] = {"t_next": config.rounds, "f": carry[0],
                     "f_evals": carry[1], "key": carry[2],
                     "active": carry[3], "state": {}}
    return out


def lower_shard_round(rng: jax.Array, orgs: Sequence[Any], y: jnp.ndarray,
                      loss: Loss, config: Any,
                      eval_sets: Optional[Dict[str, tuple]] = None,
                      metrics: Optional[Dict[str, Callable]] = None):
    """Lower — without executing — the exact compiled program ``fit_shard``
    would run, returning the ``jax.stages.Lowered`` handle. Roofline's
    ``collective_bytes_from_hlo`` / ``hlo_stats.analyze`` read its HLO
    (``.as_text()``) to attribute collective traffic; see
    ``roofline.analysis.gal_shard_round_collectives`` for the mapping from
    those per-partition HLO bytes to the protocol ledger's ints."""
    prog = _shard_program(rng, orgs, y, loss, config, eval_sets, metrics)
    return prog["jit"].lower(*prog["operands"])


def grouped_predict(groups: Sequence[Any], group_params: Sequence[Any],
                    group_dims: Sequence[Sequence[int]],
                    group_pads: Sequence[Optional[int]],
                    etas: Sequence[float], weights: Sequence[jnp.ndarray],
                    f0: jnp.ndarray, xs: Sequence[jnp.ndarray],
                    t_max: int) -> jnp.ndarray:
    """Prediction stage for a planner-grouped ensemble.

    Per group: one nested (rounds x group-orgs) vmap of the group's model
    over its stacked slices, contracted with that group's slice of the
    assistance weights in a single einsum — then summed over groups. Deep
    Model Sharing groups featurize each org's slice ONCE through the final
    shared extractor and read round t's head from the stacked ``(T, ...)``
    head axis (exactly ``predict_round``'s final-state replay). Noisy
    groups add the engine-independent prediction-stage noise
    (``fold_in(PRNGKey(org.index), t)``, matching
    ``Organization.predict_round``), so grouped predictions equal the
    Python reference assembly draw for draw.
    """
    n = xs[0].shape[0]
    k = f0.shape[-1]
    f = jnp.broadcast_to(f0, (n, k))
    if t_max == 0:
        return f
    etas_t = jnp.asarray(etas[:t_max], jnp.float32)
    w_t = jnp.stack(list(weights[:t_max]))                       # (T, M)
    out = f
    for gi, g in enumerate(groups):
        xs_g = [xs[i] for i in g.indices]
        if xs_g[0].ndim == 2:
            # the zero-pad would silently swallow mis-sized/mis-ordered
            # slices that the reference engine rejects — keep that net
            got = [int(x.shape[-1]) for x in xs_g]
            if got != [int(d) for d in group_dims[gi]]:
                raise ValueError(
                    f"prediction slice widths {got} do not match the "
                    f"fitted per-org widths {list(group_dims[gi])} of "
                    f"group {g.describe()} (check org order)")
        x_stack, _ = pad_and_stack(xs_g, pad_to=group_pads[gi])
        if g.dms:
            gp = group_params[gi]

            def dms_preds(ext_m, heads_m, x_m, model=g.model):
                # features once per org; every round's head off the stack
                feats = model.features({**ext_m, "head": None}, x_m)
                return jax.vmap(
                    lambda h: model.apply_head(h, feats)
                )(jax.tree_util.tree_map(lambda l: l[:t_max], heads_m))

            preds = jnp.swapaxes(jax.vmap(dms_preds)(
                gp["extractor"], gp["heads"], x_stack), 0, 1)    # (T,Mg,N,K)
        else:
            params_t = jax.tree_util.tree_map(lambda l: l[:t_max],
                                              group_params[gi])
            preds = jax.vmap(
                lambda p, model=g.model: jax.vmap(model.apply)(p, x_stack)
            )(params_t)                                          # (T,Mg,N,K)
        if g.noise_sigma > 0.0:
            ids = jnp.asarray(g.org_ids, jnp.uint32)
            noise = jax.vmap(lambda t: jax.vmap(
                lambda i: jax.random.normal(
                    jax.random.fold_in(jax.random.PRNGKey(i), t), (n, k))
            )(ids))(jnp.arange(t_max))
            preds = preds + g.noise_sigma * noise
        out = out + jnp.einsum("t,tm,tmnk->nk", etas_t,
                               w_t[:, jnp.asarray(g.indices)], preds)
    return out
