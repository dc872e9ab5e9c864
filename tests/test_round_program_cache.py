"""A fit reuses the round program of an earlier fit with an equal signature.

``repro.core.engine.round_program`` keeps each jitted round program under
its signature: the builder and everything its traced body reads that is
not an argument. A second fit of the same signature, with fresh
organizations, builds nothing (``round_traces`` 0) and answers bit for bit
as the first; a changed signature builds anew; new data of the same shapes
gives the answer of a fit from an emptied store; the store keeps no array
of any fit and never more than its bound.
"""
from __future__ import annotations

import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.utils import tracing

ROUNDS = 2


def counted(fit):
    """``fit()`` and the round programs it built."""
    built = []
    real = tracing.count

    def count(name, n=1):
        if name == "round_traces":
            built.append(n)
        real(name, n)

    tracing.count = count
    try:
        out = fit()
    finally:
        tracing.count = real
    return out, sum(built)


def assert_same(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b), strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---- tabular engines ------------------------------------------------------

def _xs(seed, m=4, n=96, d=3):
    key = jax.random.PRNGKey(seed)
    return [jax.random.normal(jax.random.fold_in(key, i), (n, d))
            for i in range(m)]


def _y(xs, seed=0):
    noise = jax.random.normal(jax.random.PRNGKey(100 + seed),
                              (xs[0].shape[0], 1))
    return jnp.sum(jnp.concatenate(xs, axis=1), 1, keepdims=True) + noise


def _models(engine_name, ridge=1e-3):
    from repro.models import zoo
    if engine_name == "scan":
        return zoo.Linear(ridge=ridge)
    # two groups, interleaved, so the group blocks go back into org order
    return [zoo.Linear(ridge=ridge), zoo.Linear(ridge=10 * ridge)] * 2


def tabular_fit(engine_name, xs, y, ridge=1e-3, eval_xs=None,
                membership=None, **config):
    """A whole ``gal.fit`` of fresh organizations: what it answers."""
    from repro.core import gal
    from repro.core.losses import get_loss
    from repro.core.organizations import make_orgs
    kw = {"membership": membership}
    if eval_xs is not None:
        kw.update(eval_sets={"val": (eval_xs, _y(eval_xs, 1))},
                  metrics=["mad"])
    cfg = gal.GALConfig(**{"rounds": ROUNDS, "engine": engine_name,
                           **config})
    res = gal.fit(jax.random.PRNGKey(7), make_orgs(xs, _models(engine_name,
                                                               ridge)),
                  y, get_loss("mse"), cfg, **kw)
    assert res.engine == engine_name
    return {"etas": res.etas, "weights": res.weights,
            "history": dict(res.history),
            "pred": res.predict(xs)}


TABULAR = ("scan", "grouped")


@pytest.mark.parametrize("engine_name", TABULAR)
def test_second_fit_reuses_the_program(engine_name):
    xs = _xs(0)
    y = _y(xs)
    first, b1 = counted(lambda: tabular_fit(engine_name, xs, y))
    second, b2 = counted(lambda: tabular_fit(engine_name, xs, y))
    assert (b1, b2) == (1, 0)
    assert_same(first, second)


@pytest.mark.parametrize("engine_name", TABULAR)
@pytest.mark.parametrize("change", [
    {"ridge": 1e-1}, {"weight_epochs": 7}, {"rounds": ROUNDS + 1},
    {"eta_method": "constant"}], ids=lambda c: next(iter(c)))
def test_changed_signature_rebuilds(engine_name, change):
    xs = _xs(0)
    y = _y(xs)
    base, _ = counted(lambda: tabular_fit(engine_name, xs, y))
    changed, built = counted(lambda: tabular_fit(engine_name, xs, y,
                                                 **change))
    assert built == 1
    assert changed["history"] != base["history"]
    engine.clear_round_programs()
    cold, built = counted(lambda: tabular_fit(engine_name, xs, y, **change))
    assert built == 1
    assert_same(changed, cold)


@pytest.mark.parametrize("engine_name", TABULAR)
@pytest.mark.parametrize("new", ["y", "xs"])
def test_new_data_gives_a_cold_builds_answer(engine_name, new):
    xs = _xs(0)
    y = _y(xs)
    first, _ = counted(lambda: tabular_fit(engine_name, xs, y))
    xs2 = _xs(1) if new == "xs" else xs
    y2 = _y(xs, seed=3) if new == "y" else y
    hot, built = counted(lambda: tabular_fit(engine_name, xs2, y2))
    assert built == 0
    assert not np.array_equal(np.asarray(hot["pred"]),
                              np.asarray(first["pred"]))
    engine.clear_round_programs()
    cold, built = counted(lambda: tabular_fit(engine_name, xs2, y2))
    assert built == 1
    assert_same(hot, cold)


def _hit_equals_cold(fit):
    counted(fit)
    hot, built = counted(fit)
    assert built == 0
    engine.clear_round_programs()
    cold, built = counted(fit)
    assert built == 1
    assert_same(hot, cold)


@pytest.mark.parametrize("engine_name", TABULAR)
def test_membership_fit_equals_a_cold_one(engine_name):
    xs = _xs(0)
    y = _y(xs)
    sched = np.array([[True, False, True, True], [True, True, False, True]])
    _hit_equals_cold(lambda: tabular_fit(engine_name, xs, y,
                                         membership=sched))


@pytest.mark.parametrize("engine_name", TABULAR)
def test_eval_set_with_metrics_fit_equals_a_cold_one(engine_name):
    xs = _xs(0)
    y = _y(xs)
    _hit_equals_cold(lambda: tabular_fit(engine_name, xs, y,
                                         eval_xs=_xs(5, n=32)))


def test_resumed_fit_equals_a_cold_one():
    from repro.core import gal
    from repro.core.losses import get_loss
    from repro.core.organizations import make_orgs
    xs = _xs(0)
    y = _y(xs)

    def fit(rounds, resume_from=None):
        return gal.fit(jax.random.PRNGKey(7), make_orgs(xs, _models("scan")),
                       y, get_loss("mse"),
                       gal.GALConfig(rounds=rounds, engine="scan"),
                       resume_from=resume_from)

    part = fit(ROUNDS)

    def resumed():
        res = fit(ROUNDS + 2, resume_from=part)
        return {"etas": res.etas, "weights": res.weights,
                "pred": res.predict(xs)}
    _hit_equals_cold(resumed)


def test_reused_program_records_no_build_on_its_span(tmp_path):
    xs = _xs(0)
    y = _y(xs)
    tabular_fit("scan", xs, y)
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(tabular_fit("scan", xs, y)["pred"])
    finally:
        jax.profiler.stop_trace()
    trace = jax.profiler.ProfileData.from_file(
        str(next(tmp_path.rglob("*.xplane.pb"))))
    tops = [dict(e.stats) for plane in trace.planes for line in plane.lines
            for e in line.events if e.name == "gal.fit"]
    assert len(tops) == 1
    assert tops[0]["round_traces"] == 0 and tops[0]["rounds"] == ROUNDS


def test_store_keeps_no_array_of_a_fit(monkeypatch):
    stacked = []
    real = engine.stack_groups

    def spy(*args, **kwargs):
        out = real(*args, **kwargs)
        stacked.extend(weakref.ref(a) for a in out[0])
        return out
    monkeypatch.setattr(engine, "stack_groups", spy)
    xs = _xs(0)
    y = _y(xs)
    res, built = counted(lambda: tabular_fit("scan", xs, y))
    assert built == 1 and stacked
    assert len(engine._round_programs) == 1
    del res, xs, y
    gc.collect()
    assert all(ref() is None for ref in stacked)


def _identity_program(i):
    def program(x):
        return x + i
    return program


def test_store_never_holds_more_than_its_bound():
    first = engine.round_program(_identity_program, 0)
    assert engine.round_program(_identity_program, 0) is first
    for i in range(1, 3 * engine._ROUND_PROGRAMS_MAX):
        engine.round_program(_identity_program, i)
        assert len(engine._round_programs) <= engine._ROUND_PROGRAMS_MAX
    assert len(engine._round_programs) == engine._ROUND_PROGRAMS_MAX
    assert engine.round_program(_identity_program, 0) is not first
    # an unhashable signature gets a program, and the store keeps nothing
    engine.clear_round_programs()
    assert engine.round_program(_identity_program, np.ones(2)) is not None
    assert not engine._round_programs


# ---- the LM engine ----------------------------------------------------------

@pytest.fixture(scope="module")
def lm():
    from repro.configs import get_arch
    from repro.models import transformer as tfm
    cfg = get_arch("llama3-8b", smoke=True)
    key = jax.random.PRNGKey(1)
    tokens = jax.random.randint(key, (2, 16), 0, cfg.vocab)
    weights = [tfm.init_params(jax.random.fold_in(key, i), cfg)
               for i in range(2)]
    return cfg, tokens, weights


def lm_fit(lm, tokens=None, lr=1e-3, step=None, **kw):
    """A whole ``fit_lm`` of fresh organizations from the same weights."""
    from repro.core import gal_lm
    from repro.train.steps import make_train_step
    cfg, tokens0, weights = lm
    tokens = tokens0 if tokens is None else tokens
    orgs = []
    for i in range(2):
        org = gal_lm.LMOrganization(
            i, cfg, lambda t, i=i: (t * (i + 2)) % cfg.vocab)
        org.params, org.lr = weights[i], lr
        org._train_step, opt = make_train_step(cfg, "gal_residual", lr=lr,
                                               weight_decay=0.0)
        if step is not None:
            org._train_step = step(org._train_step)
        org.opt_state = opt.init(org.params)
        orgs.append(org)
    labels = jnp.roll(tokens, -1, axis=1)
    res = gal_lm.fit_lm(jax.random.PRNGKey(3), orgs, tokens, labels,
                        **{"rounds": ROUNDS, "local_steps": 2, **kw})
    return {"etas": res.etas, "weights": res.weights,
            "xent": res.history["train_xent"], "f": res.resume_state["f"],
            "params": [o.params for o in orgs]}


def test_lm_second_fit_reuses_the_program(lm):
    first, b1 = counted(lambda: lm_fit(lm))
    second, b2 = counted(lambda: lm_fit(lm))
    assert (b1, b2) == (1, 0)
    assert_same(first, second)


@pytest.mark.parametrize("change", [
    {"rounds": ROUNDS + 1}, {"local_steps": 3}, {"lr": 3e-3}],
    ids=lambda c: next(iter(c)))
def test_lm_changed_signature_rebuilds(lm, change):
    counted(lambda: lm_fit(lm))
    changed, built = counted(lambda: lm_fit(lm, **change))
    assert built == 1
    engine.clear_round_programs()
    cold, built = counted(lambda: lm_fit(lm, **change))
    assert built == 1
    assert_same(changed, cold)


def test_lm_new_tokens_give_a_cold_builds_answer(lm):
    first, _ = counted(lambda: lm_fit(lm))
    tokens = (lm[1] * 7 + 3) % lm[0].vocab
    hot, built = counted(lambda: lm_fit(lm, tokens=tokens))
    assert built == 0
    assert not np.array_equal(np.asarray(hot["f"]), np.asarray(first["f"]))
    engine.clear_round_programs()
    cold, built = counted(lambda: lm_fit(lm, tokens=tokens))
    assert built == 1
    assert_same(hot, cold)


def test_lm_own_train_step_is_never_replaced(lm):
    """A step of the user's own is keyed by its identity: the program
    built for the factory's step is not reused for it."""
    counted(lambda: lm_fit(lm))
    own = []

    def wrap(step):
        def mine(params, opt_state, batch):
            own.append(1)
            return step(params, opt_state, batch)
        return mine
    _, built = counted(lambda: lm_fit(lm, step=wrap))
    assert built == 1 and own


def test_make_train_step_returns_the_same_objects_for_equal_arguments():
    from repro.configs import get_arch
    from repro.train.steps import make_train_step
    cfg = get_arch("llama3-8b", smoke=True)
    a = make_train_step(cfg, "gal_residual", lr=1e-3, weight_decay=0.0)
    b = make_train_step(cfg, "gal_residual", lr=1e-3, weight_decay=0.0)
    c = make_train_step(cfg, "gal_residual", lr=2e-3, weight_decay=0.0)
    assert a[0] is b[0] and a[1] is b[1]
    assert c[0] is not a[0]
    # an unhashable learning rate still builds a working pair
    step, opt = make_train_step(cfg, "gal_residual", lr=jnp.asarray(1e-3))
    assert callable(step) and opt is not None
