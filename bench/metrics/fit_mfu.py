"""The whole tabular fit against the chip's roofline: the least time the
work of a fit needs, over the measured wall time per fit, in percent.

The tabular fit is bound by bandwidth, so the least time of a round is the
larger of its least FLOPs over the peak FLOP/s and its least bytes over the
peak HBM bandwidth. The counts are of the work itself, whatever does it:

  bytes per round  N*D*4            each organization's features read once
                 + N*K*4*(3 + 2M)   y and F read, F written; each of the M
                                    organizations' fitted values written and
                                    read once (the weight fit at its least:
                                    one pass over the (M, N, K) stack)
  FLOPs per round  2*N*M*((d+1)^2 + (d+1)*K)   normal equations and fitted
                                    values, d = D / M columns per org
"""


def least_round_work(n, d_total, m, k=1):
    """(FLOPs, bytes) that one GAL round needs at least."""
    d = d_total // m
    flops = 2 * n * m * ((d + 1) ** 2 + (d + 1) * k)
    nbytes = n * d_total * 4 + n * k * 4 * (3 + 2 * m)
    return flops, nbytes


def least_fit_seconds(cfg, peaks):
    flops, nbytes = least_round_work(cfg["n_rows"], cfg["n_features"],
                                     cfg["n_orgs"])
    per_round = max(flops / peaks["bf16_flops_per_s"],
                    nbytes / peaks["hbm_bytes_per_s"])
    return cfg["rounds"] * per_round


def read(ctx):
    c = ctx["counters"]
    if not c.get("fits"):
        return None
    wall_per_fit = c["elapsed_s"] / c["fits"]
    return 100.0 * least_fit_seconds(ctx["config"], ctx["peaks"]) / wall_per_fit
