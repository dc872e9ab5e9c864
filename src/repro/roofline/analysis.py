"""Roofline analysis from the compiled dry-run artifact (no real hardware).

Three terms per (arch x shape x mesh), in seconds:
    compute    = HLO_FLOPs / (chips * peak_FLOP/s)
    memory     = HLO_bytes / (chips * HBM_bw)
    collective = collective_bytes / (chips * link_bw)

Sources: compiled.cost_analysis() for FLOPs/bytes; collective bytes parsed
from the compiled HLO text (all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute operand sizes). XLA's cost analysis of a
GSPMD-partitioned module is per-partition, so terms divide by per-chip rates
only — verified in tests/test_roofline.py.

CAVEAT (scan trip counts): XLA's cost model counts a while-loop body ONCE.
Layer-stacked models run L layers via lax.scan, so raw HLO FLOPs undercount
by ~L. We report both the raw numbers and trip-count-corrected numbers using
the known layer count (``scan_correction``), and cross-check against the
analytic 6*N*D MODEL_FLOPS.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Dict

import numpy as np

from repro.configs.base import InputShape, ModelConfig
from repro.roofline.hlo_stats import result_part


@dataclass(frozen=True)
class Hardware:
    name: str = "tpu-v5e"
    peak_flops: float = 197e12          # bf16 FLOP/s per chip
    hbm_bw: float = 819e9               # bytes/s per chip
    link_bw: float = 50e9               # bytes/s per ICI link


HW = Hardware()

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"(pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64)"
                       r"\[([0-9,]*)\]")
_COLLECTIVE_RE = re.compile(
    r"=\s*(?:\([^)]*\)\s*)?[a-z0-9\[\],{}\s]*?"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(", re.IGNORECASE)


def _line_result_bytes(line: str) -> int:
    """Sum the byte sizes of the collective's result shape(s)."""
    head = line.split("=", 1)
    if len(head) != 2:
        return 0
    total = 0
    for dt, dims in _SHAPE_RE.findall(result_part(head[1])):
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind result bytes in the (per-partition) module."""
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if not m:
            continue
        if "-done(" in line:
            continue  # async pair: count the -start only
        kind = m.group(1).lower()
        out[kind] = out.get(kind, 0) + _line_result_bytes(line)
    return out


def gal_shard_round_collectives(n: int, k: int, m: int, rounds: int,
                                eval_ns=(), weight_epochs: int = 100,
                                block_size: int = 1, data_shards: int = 1,
                                dtype_bytes: int = 4,
                                alice_quadratic: bool = True
                                ) -> Dict[str, int]:
    """Expected per-partition collective bytes of the compiled org-sharded
    GAL fit (``core.engine.lower_shard_round`` -> ``hlo_stats.analyze``),
    decomposed so tests can reconcile the compiler's traffic with the
    protocol ledger (``core.protocol_sim.gal_round_bytes``):

      all_gather            step-3 fitted-value gather, (M, N/ds, K) result
                            per round. EXACT under every placement. The
                            ledger's train-set gather is the same tensor
                            counted once per data shard:
                            ``ledger_train_gather == data_shards * all_gather``.
      all_reduce_broadcast  step-2 residual psum from Alice's device,
                            (N/ds, K) per round. The ledger's broadcast is
                            per-receiver-link: ``ledger_broadcast ==
                            (m - 1) * data_shards * all_reduce_broadcast``
                            at fp32. NOTE ``residual_dtype="bf16"`` does NOT
                            shrink this number: XLA folds the bf16 upcast
                            into the all-reduce producer, so the simulated
                            collective stays f32 — the 2-byte width is a
                            wire-protocol (ledger) property of real
                            cross-org links, not of the single-host psum.
      all_reduce_direction  step-6 weighted org-sum of fitted values.
      all_reduce_evals      per-eval-set combines (weighted sums, so
                            (N_e, K) — the ledger instead books the
                            protocol's M per-org shipments, M * N_e * K).
      all_reduce_weight_fit step-4 distributed assistance-weight fit. For
                            block placement with the quadratic alice loss
                            (the alice_q=2 default) the fit runs on
                            per-block Gram statistics, so each epoch moves
                            ONLY the (M,) gradient psum per sharded mesh
                            axis — no (N, K) tensor crosses the mesh inside
                            the epoch loop. A non-quadratic alice loss
                            (``alice_quadratic=False``) keeps the
                            combine-and-psum objective: one forward (N/ds,
                            K) psum per epoch (its backward transpose is
                            eliminated by a stop_gradient identity) plus
                            the (M,) psums. Zero for 1:1 placement on an
                            un-sharded data axis — the weight fit is then
                            replicated.
      all_reduce            sum of the above. EXACT when data_shards == 1;
                            a LOWER bound when the data axis is sharded
                            (the psum'd global-mean loss adds a few bytes
                            of scalar sync per line-search/loss call that
                            we do not model).
      all_reduce_exact      whether ``all_reduce`` is exact or a bound.

    Verified against the compiled HLO in tests/test_roofline_engine.py."""
    if data_shards < 1 or n % data_shards:
        raise ValueError(f"data_shards {data_shards} must divide n {n}")
    db = dtype_bytes
    n_l = n // data_shards
    axes = (1 if block_size > 1 else 0) + (1 if data_shards > 1 else 0)
    if block_size > 1:
        if alice_quadratic and data_shards == 1:
            # Gram fast path: the epoch loop is collective-free except for
            # the per-axis (M,) gradient psum
            wfit_round = weight_epochs * (axes * m * db)
        else:
            wfit_round = weight_epochs * (n_l * k * db + axes * m * db)
    elif data_shards > 1:
        wfit_round = weight_epochs * (m * db)   # (M,) grad psum over "data"
    else:
        wfit_round = 0
    out = {
        "all_gather": rounds * m * n_l * k * db,
        "all_reduce_broadcast": rounds * n_l * k * db,
        "all_reduce_direction": rounds * n_l * k * db,
        "all_reduce_evals": rounds * sum(int(ne) * k * db for ne in eval_ns),
        "all_reduce_weight_fit": rounds * wfit_round,
        "all_reduce_exact": data_shards == 1,
    }
    out["all_reduce"] = (out["all_reduce_broadcast"]
                         + out["all_reduce_direction"]
                         + out["all_reduce_evals"]
                         + out["all_reduce_weight_fit"])
    return out


def model_flops(cfg: ModelConfig, shape: InputShape, train: bool = True) -> float:
    """Analytic MODEL_FLOPS: 6*N_active*D for training, 2*N_active*D for
    inference forward (D = tokens processed)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    tokens = shape.global_batch * 1       # decode: one token
    return 2.0 * n * tokens


def roofline_terms(cost: Dict[str, float], collectives: Dict[str, int],
                   n_chips: int, hw: Hardware = HW,
                   scan_correction: float = 1.0) -> Dict[str, float]:
    """cost: compiled.cost_analysis() dict (per-partition module).
    Returns the three terms in seconds plus raw inputs."""
    flops = float(cost.get("flops", 0.0)) * scan_correction
    bytes_acc = float(cost.get("bytes accessed", 0.0)) * scan_correction
    coll = float(sum(collectives.values())) * scan_correction
    return {
        "hlo_flops_per_chip": flops,
        "hlo_bytes_per_chip": bytes_acc,
        "collective_bytes_per_chip": coll,
        "t_compute": flops / hw.peak_flops,
        "t_memory": bytes_acc / hw.hbm_bw,
        "t_collective": coll / hw.link_bw,
        "n_chips": n_chips,
    }


def dominant_term(terms: Dict[str, float]) -> str:
    three = {k: terms[k] for k in ("t_compute", "t_memory", "t_collective")}
    return max(three, key=three.get)
