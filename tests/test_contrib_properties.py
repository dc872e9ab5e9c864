"""Property tests for contributivity and the membership machinery.

Unlike the other property suites, this one does NOT skip outright when
hypothesis (an optional dev dep) is absent: the cheap array-level
properties fall back to a fixed seed sweep, and the fit-backed game
properties (Shapley efficiency, permutation invariance, LOO consistency)
are deterministic single cases anyway. With hypothesis installed, the
seed sweep widens to a full strategy search.
"""
import math

import numpy as np
import pytest
import jax
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def seeded(test):
    """@given(seed=...) under hypothesis, a 6-seed parametrize without."""
    if HAVE_HYPOTHESIS:
        return settings(max_examples=25, deadline=None)(
            given(seed=st.integers(0, 10_000))(test))
    return pytest.mark.parametrize("seed", range(6))(test)


# ----------------------------------------------------- weight-fit algebra

@seeded
def test_masked_softmax_renormalizes_with_exact_zeros(seed):
    """Under ANY non-empty mask: live weights sum to 1 (to float eps),
    masked weights are EXACTLY 0.0, and the all-live mask reproduces
    jax.nn.softmax bitwise (no membership tax on the static path)."""
    from repro.core.weights import _masked_softmax
    rng = np.random.default_rng(seed)
    m = int(rng.integers(1, 9))
    theta = jnp.asarray(rng.standard_normal(m).astype(np.float32) * 3)
    mask = rng.random(m) < 0.5
    if not mask.any():
        mask[rng.integers(m)] = True
    w = np.asarray(_masked_softmax(theta, jnp.asarray(mask)))
    assert (w[~mask] == 0.0).all()
    assert (w[mask] > 0.0).all()
    np.testing.assert_allclose(w.sum(), 1.0, rtol=1e-6)
    full = np.asarray(_masked_softmax(theta, jnp.ones(m, bool)))
    np.testing.assert_array_equal(full, np.asarray(jax.nn.softmax(theta)))


@seeded
def test_uniform_weights_respect_mask(seed):
    from repro.core.weights import uniform_weights
    rng = np.random.default_rng(seed)
    m = int(rng.integers(1, 9))
    mask = rng.random(m) < 0.5
    if not mask.any():
        mask[rng.integers(m)] = True
    w = np.asarray(uniform_weights(m, mask=jnp.asarray(mask)))
    assert (w[~mask] == 0.0).all()
    np.testing.assert_allclose(w[mask], 1.0 / mask.sum(), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(uniform_weights(m)),
                                  np.full(m, 1.0 / m, np.float32))


# ------------------------------------------------------------- the ledger

@seeded
def test_all_live_rounds_pay_the_static_bytes(seed):
    """Dropout never changes the bytes of a round where everyone shows up,
    and a masked round pays exactly the reduced org set's bytes — the
    ledger is a pure per-round function of the live count."""
    from repro.core.membership import membership_comm_ledger
    from repro.core.protocol_sim import gal_round_bytes
    rng = np.random.default_rng(seed)
    rounds, m = int(rng.integers(1, 7)), int(rng.integers(1, 7))
    n, k = int(rng.integers(8, 512)), int(rng.integers(1, 4))
    eval_ns = tuple(int(v) for v in rng.integers(1, 64, rng.integers(0, 3)))
    sched = rng.random((rounds, m)) < 0.6
    sched[:, rng.integers(m)] = True        # keep every round non-empty
    bcast, gather = membership_comm_ledger(sched, n, k, eval_ns)
    b_full, g_full = gal_round_bytes(n, k, m, eval_ns)
    for t in range(rounds):
        live = int(sched[t].sum())
        b_red, g_red = gal_round_bytes(n, k, live, eval_ns)
        assert (bcast[t], gather[t]) == (b_red, g_red)
        if live == m:
            assert (bcast[t], gather[t]) == (b_full, g_full)
        assert bcast[t] <= b_full and gather[t] <= g_full
        assert isinstance(bcast[t], int) and isinstance(gather[t], int)


@seeded
def test_model_memories_accrue_only_on_attendance(seed):
    """Per round t: a fresh org holds one snapshot per attended round so
    far, a DMS org holds one shared extractor from its first attended
    round; totals are nondecreasing, and an all-live schedule reproduces
    the static (schedule-free) counts exactly."""
    from repro.core.protocol_sim import gal_model_memories
    rng = np.random.default_rng(seed)
    rounds, m = int(rng.integers(1, 7)), int(rng.integers(1, 6))
    dms = (rng.random(m) < 0.4).tolist()
    sched = rng.random((rounds, m)) < 0.6
    sched[:, rng.integers(m)] = True
    out = gal_model_memories(rounds, dms, membership=sched.tolist())
    att = np.cumsum(sched, axis=0)
    expect = [int(sum((1 if dms[j] else att[t, j]) if att[t, j] else 0
                      for j in range(m)))
              for t in range(rounds)]
    assert out == expect
    assert all(a <= b for a, b in zip(out, out[1:]))
    ones = np.ones((rounds, m), bool).tolist()
    assert (gal_model_memories(rounds, dms, membership=ones)
            == gal_model_memories(rounds, dms))


@seeded
def test_straggler_schedule_is_seeded_and_repaired(seed):
    from repro.core.membership import straggler_schedule
    rng = np.random.default_rng(seed)
    rounds, m = int(rng.integers(1, 40)), int(rng.integers(1, 7))
    rate = float(rng.uniform(0.0, 0.99))
    a = straggler_schedule(rounds, m, rate, seed=seed)
    np.testing.assert_array_equal(
        a, straggler_schedule(rounds, m, rate, seed=seed))
    assert a.shape == (rounds, m) and a.dtype == np.bool_
    assert a.any(axis=1).all()
    if rate == 0.0:
        assert a.all()


# ----------------------------------------------- the contributivity game
#
# Fit-backed properties: deterministic tiny cases (each coalition value is
# a real gal.fit; a strategy sweep here would be minutes per example).

M = 3
ROUNDS = 2


def _game(key, perm=None):
    from repro.core.losses import get_loss
    from repro.core.organizations import make_orgs
    from repro.data.partition import split_features
    rng = np.random.default_rng(5)
    x = rng.standard_normal((48, 6)).astype(np.float32)
    beta = rng.standard_normal(6).astype(np.float32)
    # nonlinear target: linear orgs can't reach the float-noise floor, so
    # coalition values stay O(1) and relative comparisons mean something
    y = jnp.asarray(np.tanh(x @ beta) + 0.5 * np.sin(3.0 * x[:, 0])
                    + 0.1 * rng.standard_normal(48).astype(np.float32)
                    )[:, None]                          # (N, K=1) targets
    xs = split_features(jnp.asarray(x), M)
    from repro.models.zoo import Linear
    orgs = make_orgs(xs, Linear())
    if perm is not None:
        # org IDENTITY (.index) travels with the org: position p now hosts
        # org perm[p], its weight-fit init and ledger id included
        orgs = [orgs[p] for p in perm]
        xs = [xs[p] for p in perm]
    return orgs, xs, y, get_loss("mse")


def test_exhaustive_shapley_is_efficient_and_ledgered(key):
    """sum(scores) == v(empty) - v(full) for the exact (exhaustive)
    Shapley value, and the report lands in history['contributions']."""
    from repro.core.contrib import truncated_shapley
    from repro.core.gal import GALConfig
    orgs, xs, y, loss = _game(key)
    cfg = GALConfig(rounds=ROUNDS, engine="scan")
    rep = truncated_shapley(key, orgs, y, loss, cfg, t0=1,
                            n_permutations=math.factorial(M))
    assert rep["exhaustive"] and rep["n_permutations"] == math.factorial(M)
    np.testing.assert_allclose(sum(rep["scores"]),
                               rep["v_empty"] - rep["v_full"],
                               rtol=1e-6, atol=1e-9)
    # distinct coalitions, not permutations x M: 2^M - 2 refits at most
    assert rep["refits"] <= 2 ** M - 2


def test_shapley_invariant_under_org_reordering(key):
    """Relabeling the orgs permutes the scores and changes nothing else:
    position p of the reordered game scores what org perm[p] scored in the
    original (identity-seeded weight inits make the game label-free; only
    float sum order differs)."""
    from repro.core.contrib import truncated_shapley
    from repro.core.gal import GALConfig
    perm = [2, 0, 1]
    cfg = GALConfig(rounds=ROUNDS, engine="scan")
    orgs_a, _, y, loss = _game(key)
    rep_a = truncated_shapley(key, orgs_a, y, loss, cfg, t0=1,
                              n_permutations=math.factorial(M))
    orgs_b, _, y_b, _ = _game(key, perm=perm)
    rep_b = truncated_shapley(key, orgs_b, y_b, loss, cfg, t0=1,
                              n_permutations=math.factorial(M))
    assert rep_b["org_ids"] == [perm[p] for p in range(M)]
    np.testing.assert_allclose(rep_b["v_full"], rep_a["v_full"], rtol=1e-4)
    np.testing.assert_allclose(
        rep_b["scores"], [rep_a["scores"][perm[p]] for p in range(M)],
        rtol=1e-4, atol=1e-7)


def test_loo_scores_are_sum_consistent(key):
    """Each LOO score is exactly v(all - {j}) - v(all) recomputed through
    an independent membership fit, and for a 2-org game LOO and the exact
    Shapley value agree up to the shared v(empty) offset:
    loo_0 - loo_1 == shap_0 - shap_1."""
    from repro.core.contrib import leave_one_out, truncated_shapley
    from repro.core import gal as gal_mod
    from repro.core.gal import GALConfig
    orgs, xs, y, loss = _game(key)
    cfg = GALConfig(rounds=ROUNDS, engine="scan")
    rep = leave_one_out(key, orgs, y, loss, cfg, t0=1)
    assert rep["refits"] == M
    for j in range(M):
        sched = np.ones((ROUNDS, M), bool)
        sched[1:, j] = False
        res = gal_mod.fit(key, _game(key)[0], y, loss, cfg,
                          membership=sched)
        np.testing.assert_allclose(
            rep["scores"][j],
            float(res.history["train_loss"][-1]) - rep["v_full"],
            rtol=1e-6)
    # ledgered on the full fit's history by both estimators
    full = gal_mod.fit(key, _game(key)[0], y, loss, cfg)
    shap = truncated_shapley(key, orgs, y, loss, cfg, t0=1, full=full)
    assert full.history["contributions"]["method"] == "shapley"
    # exact Shapley and LOO rank the difference between orgs identically
    # in the 2-player subgame sense: both are anchored to the same v
    assert len(shap["scores"]) == M


def test_truncation_tolerance_skips_converged_walks(key):
    """A huge truncation_tol stops every permutation walk at the start, so
    no counterfactual refits run and every score is zero."""
    from repro.core.contrib import truncated_shapley
    from repro.core.gal import GALConfig
    orgs, _, y, loss = _game(key)
    cfg = GALConfig(rounds=ROUNDS, engine="scan")
    rep = truncated_shapley(key, orgs, y, loss, cfg, t0=1,
                            truncation_tol=1e9,
                            n_permutations=math.factorial(M))
    assert rep["truncated_walks"] == math.factorial(M)
    assert rep["refits"] == 0
    assert rep["scores"] == [0.0] * M
