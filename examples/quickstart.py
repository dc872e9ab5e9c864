"""Quickstart: the full GAL lifecycle on 4 collaborating organizations.

Nobody shares data, models, or objective functions: org 0 (Alice) holds the
labels; orgs hold disjoint vertical feature slices and *different* private
model classes (the paper's model autonomy). The walk-through covers the
whole production lifecycle:

  fit (6 rounds) -> save artifact -> load in a "fresh process" -> serve
  -> resume the collaboration to 10 rounds without refitting rounds 0-5

Run: PYTHONPATH=src python examples/quickstart.py
"""
import tempfile

import numpy as np
import jax

from repro.checkpoint import load_artifact, save_artifact
from repro.core import boosting, gal
from repro.core.gal import GALConfig
from repro.core.losses import get_loss
from repro.core.organizations import make_orgs
from repro.data.partition import split_features
from repro.data.synthetic import make_regression, train_test_split
from repro.metrics.metrics import mad
from repro.models.zoo import KernelRidge, Linear, MLP, StumpBoost
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)

    ds = make_regression(rng, n=440, d=12)
    train, test = train_test_split(ds, rng)
    xs = split_features(train.x, 4)         # vertical split across 4 orgs
    xs_te = split_features(test.x, 4)
    loss = get_loss("mse")                   # Alice's overarching L1

    # model autonomy: every org picks its own private model class; the org
    # execution planner fuses the whole mix into one compiled round loop
    models = [Linear(), StumpBoost(n_stumps=40), KernelRidge(), MLP((32,))]
    make = lambda: make_orgs(xs, models)                        # noqa: E731

    print("== GAL: 6 assistance rounds ==")
    result = gal.fit(key, make(), train.y, loss, GALConfig(rounds=6),
                     eval_sets={"test": (xs_te, test.y)}, metrics=("mad",))
    for t, (eta, w) in enumerate(zip(result.etas, result.weights)):
        w_str = "[" + " ".join(f"{v:.2f}" for v in np.asarray(w)) + "]"
        print(f" round {t}: eta={eta:5.2f}  weights={w_str}  "
              f"test MAD={result.history['test_mad'][t + 1]:.3f}")

    alone = boosting.fit_alone(
        key, xs[0], train.y, loss, Linear(), GALConfig(rounds=6),
        eval_sets={"test": ([xs_te[0]], test.y)}, metric_fn=mad)
    joint = boosting.fit_joint(
        key, xs, train.y, loss, Linear(), GALConfig(rounds=6),
        eval_sets={"test": (xs_te, test.y)}, metric_fn=mad)

    print("\n== final test MAD ==")
    print(f" Alone (org 0 only) : {alone.history['test_metric'][-1]:.3f}")
    print(f" GAL (decentralized): {result.history['test_mad'][-1]:.3f}")
    print(f" Joint (oracle)     : {joint.history['test_metric'][-1]:.3f}")

    with tempfile.TemporaryDirectory() as tmp:
        # fit once ... the artifact captures the plan, stacked round
        # params, etas/weights, history, and the round-scan resume carry
        path = save_artifact(result, tmp + "/gal-demo")
        print(f"\n== artifact saved ({result.engine} engine) ==")

        # ... serve forever: a fresh process loads and predicts with NO
        # refit and NO Organization objects — bitwise-identical outputs
        art = load_artifact(path)
        preds_mem = result.predict(xs_te)
        preds_art = art.predict(xs_te)
        print(f" loaded predict MAD : "
              f"{float(mad(test.y, preds_art)):.3f} "
              f"(bitwise == in-memory: "
              f"{bool(np.array_equal(np.asarray(preds_mem), np.asarray(preds_art)))})")

        # ... and resume: extend the collaboration to 10 rounds — rounds
        # 0-5 are NOT refit, and the curve is draw-for-draw what a
        # one-shot 10-round fit would produce
        result10 = gal.fit(key, make(), train.y, loss,
                           GALConfig(rounds=10),
                           eval_sets={"test": (xs_te, test.y)},
                           metrics=("mad",), resume_from=path)
        print(f" resumed 6 -> {result10.rounds} rounds: "
              f"test MAD {result.history['test_mad'][-1]:.3f} -> "
              f"{result10.history['test_mad'][-1]:.3f}")

    # prediction-stage API (paper Alg. 1, Prediction Stage)
    preds = result10.predict(xs_te)
    print(f" predict() MAD      : {float(mad(test.y, preds)):.3f}")


if __name__ == "__main__":
    main()
