"""A run with the timed path broken underneath must come out not correct.

Each case drives a whole benchmark run on the CPU at a tiny size (the look
for a chip skipped), first sound, then with one fault planted in the
program where it does its work: a step that leaves the state unchanged,
half of the batch left out, an answer altered where it is produced."""
from __future__ import annotations

import json

import jax.numpy as jnp
import pytest


def run(capsys, workload):
    import bench.run as bench_run
    args = bench_run.parse(["--workload", workload, "--seed", "3000000001",
                            "--seconds", "0.5", "--trace", "0"])
    assert bench_run.run_cell(args, require_tpu=False) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def tabular_state_unchanged(mp):
    import repro.core.engine as engine
    mp.setattr(engine, "line_search",
               lambda *a, **k: jnp.asarray(0.0, jnp.float32))


def tabular_half_batch(mp):
    from repro.models.zoo import Linear
    fit = Linear.fit

    def half(self, rng, x, r, local_loss, data_axis=None):
        n = x.shape[0] // 2
        return fit(self, rng, x[:n], r[:n], local_loss, data_axis)
    mp.setattr(Linear, "fit", half)


def tabular_answer_altered(mp):
    import repro.core.engine as engine
    predict = engine.grouped_predict
    mp.setattr(engine, "grouped_predict",
               lambda *a, **k: predict(*a, **k).at[0].add(1.0))


def serve_answer_altered(mp):
    from repro.serve.batcher import BucketedPredict
    call = BucketedPredict.__call__
    mp.setattr(BucketedPredict, "__call__",
               lambda self, xs: call(self, xs).at[0].add(1.0))


def serve_half_batch(mp):
    from repro.serve.batcher import BucketedPredict
    call = BucketedPredict.__call__

    def half(self, xs):
        out = call(self, xs)
        keep = (out.shape[0] + 1) // 2
        return out.at[keep:].set(0.0)
    mp.setattr(BucketedPredict, "__call__", half)


def lm_state_unchanged(mp):
    import repro.core.gal_lm as gal_lm
    mp.setattr(gal_lm, "run_local_steps",
               lambda step, params, opt, batch, steps: (params, opt, None))


def lm_half_batch(mp):
    import repro.core.gal_lm as gal_lm
    local = gal_lm.run_local_steps

    def half(step, params, opt, batch, steps):
        s = batch["residual"].shape[-2] // 2
        return local(step, params, opt,
                     {"tokens": batch["tokens"][..., :s],
                      "residual": batch["residual"][..., :s, :]}, steps)
    mp.setattr(gal_lm, "run_local_steps", half)


def lm_answer_altered(mp):
    import types
    import repro.core.gal_lm as gal_lm
    tfm = gal_lm.tfm

    def apply(params, cfg, tokens, **kw):
        logits, aux = tfm.apply(params, cfg, tokens, **kw)
        return logits.at[..., 0, :].set(logits[..., 0, ::-1]), aux
    mp.setattr(gal_lm, "tfm", types.SimpleNamespace(apply=apply))


CASES = {
    "linear-m8-fit": (tabular_state_unchanged, tabular_half_batch,
                      tabular_answer_altered),
    "linear-m8-serve": (serve_answer_altered, serve_half_batch),
    "stablelm-m2-fit": (lm_state_unchanged, lm_half_batch,
                        lm_answer_altered),
}


@pytest.mark.parametrize("workload", sorted(CASES))
def test_sound_run_is_correct(small_cells, capsys, workload):
    assert run(capsys, workload)["correct"] is True


@pytest.mark.parametrize("workload,fault", [
    (w, f) for w, faults in sorted(CASES.items()) for f in faults],
    ids=lambda v: v if isinstance(v, str) else v.__name__)
def test_broken_run_is_not_correct(small_cells, capsys, monkeypatch,
                                   workload, fault):
    fault(monkeypatch)
    out = run(capsys, workload)
    assert out["correct"] is False, out["checks"]
