"""``kernels/residual_xent.py`` against its roofline: the least time of the
kernel's work over its summed device time in the trace, in percent.

One call turns (T, V) f32 logits and T int32 labels into the (T, V) f32
residual. Its least work reads the logits once and writes the residual
once, 2 T V 4 + 4 T bytes, with about 5 T V FLOPs, so it is bound by HBM
bandwidth. The kernel's device time is the sum of its two Pallas passes'
events; the calls are one per round. On a TPU each pass is a
``tpu_custom_call`` operation that XLA names after the kernel's function,
``%residual_xent_kernel.<n> = ... custom-call(...)``; the padding before
it and the slice after it are other operations and not counted.
"""
import re

KERNEL = re.compile(
    r'^%residual_xent_kernel(\.\d+)? = .*custom_call_target="tpu_custom_call"')


def least_call_bytes(tokens, vocab):
    return 2 * tokens * vocab * 4 + 4 * tokens


def read(ctx):
    trace, c = ctx["trace"], ctx["config"]
    rounds = ctx["counters"].get("rounds")
    if trace is None or not rounds:
        return None
    seconds = trace.op_seconds(pred=lambda o: KERNEL.match(o.name))
    if seconds <= 0:
        return None
    tokens = c["batch"] * c["seq"]
    least = least_call_bytes(tokens, c["vocab"]) / ctx["peaks"][
        "hbm_bytes_per_s"]
    return 100.0 * least * rounds / seconds
