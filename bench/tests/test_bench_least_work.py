"""Each least-work count, reckoned by hand at small shapes."""
from __future__ import annotations

from pathlib import Path

import pytest

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def reader_module(name):
    import importlib.util
    path = Path(__file__).resolve().parents[1] / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location("m_" + name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_tabular_round_work_by_hand():
    fit_mfu = reader_module("fit_mfu")
    # N=10 rows, D=4 features over M=2 orgs (d=2 each), K=1
    flops, nbytes = fit_mfu.least_round_work(10, 4, 2)
    assert flops == 2 * 10 * 2 * (3 * 3 + 3 * 1) == 480
    assert nbytes == 10 * 4 * 4 + 10 * 1 * 4 * (3 + 4) == 440
    cfg = {"n_rows": 10, "n_features": 4, "n_orgs": 2, "rounds": 3}
    assert fit_mfu.least_fit_seconds(cfg, PEAKS) == pytest.approx(
        3 * 440 / 819e9)


def test_tabular_share_of_a_bandwidth_bound_fit():
    fit_mfu = reader_module("fit_mfu")
    cfg = {"n_rows": 1 << 23, "n_features": 128, "n_orgs": 8, "rounds": 10}
    least = fit_mfu.least_fit_seconds(cfg, PEAKS)
    assert least == pytest.approx(10 * ((1 << 23) * 128 * 4
                                        + (1 << 23) * 4 * 19) / 819e9)
    ctx = {"counters": {"fits": 2, "elapsed_s": 4 * least},
           "config": cfg, "peaks": PEAKS}
    assert fit_mfu.read(ctx) == pytest.approx(50.0)


def test_lm_fit_flops_by_hand():
    lm_mfu = reader_module("lm_mfu")
    c = {"n_layers": 1, "d_model": 4, "n_heads": 2, "n_kv_heads": 1,
         "d_ff": 8, "vocab": 10, "seq": 3, "batch": 1, "n_orgs": 2,
         "rounds": 1, "local_steps": 1}
    # hd=2: q and o 4x4 each, k and v 4x2 each, MLP 3*4*8, head 4x10
    p = 16 + 16 + 8 + 8 + 96 + 40
    assert lm_mfu.matmul_params(c) == p
    attn = 2 * 3 * 3 * 4                 # causal half of QK^T and PV
    per_org = (6 * p * 3 + 3 * attn) + (2 * p * 3 + attn)
    assert lm_mfu.fit_flops(c) == 2 * per_org


def test_stablelm_cell_matmul_params():
    lm_mfu = reader_module("lm_mfu")
    c = {"n_layers": 2, "d_model": 2048, "n_heads": 32, "n_kv_heads": 32,
         "d_ff": 5632, "vocab": 33450}
    assert lm_mfu.matmul_params(c) == 2 * (4 * 2048 ** 2
                                           + 3 * 2048 * 5632) + 2048 * 33450


def test_residual_kernel_bytes_by_hand():
    roof = reader_module("residual_xent_roofline")
    assert roof.least_call_bytes(3, 5) == 2 * 3 * 5 * 4 + 4 * 3


def test_residual_kernel_found_by_its_chip_names():
    """The two passes as a v5e trace names them; the slice that reads the
    kernel's output is another operation."""
    from bench.lib import trace as tr
    roof = reader_module("residual_xent_roofline")
    call = 'custom-call(f32[1024,33792]{1,0:T(8,128)} %pad.102), ' \
        'custom_call_target="tpu_custom_call"'
    ops = [tr.Op(f"%residual_xent_kernel.24 = (f32[1024,1]) {call}",
                 "jit_run", 0.0, 1e-3),
           tr.Op(f"%residual_xent_kernel.25 = f32[1024,33792] {call}",
                 "jit_run", 1e-3, 3e-3),
           tr.Op("%slice.73 = f32[1024,33450] slice(f32[1024,33792] "
                 "%residual_xent_kernel.25)", "jit_run", 3e-3, 5e-3)]
    trace = tr.Trace(window=(0.0, 1.0), ops={0: ops})
    cfg = {"batch": 1, "seq": 1024, "vocab": 33450}
    ctx = {"trace": trace, "config": cfg, "counters": {"rounds": 1},
           "peaks": PEAKS}
    least = roof.least_call_bytes(1024, 33450) / PEAKS["hbm_bytes_per_s"]
    assert roof.read(ctx) == pytest.approx(100.0 * least / 3e-3)
    ctx["trace"] = tr.Trace(window=(0.0, 1.0), ops={0: ops[2:]})
    assert roof.read(ctx) is None
