"""Roofline share of the rule-LHS kernel (``kernels/ops.batched_diff_sq_norm``
-> ``_batched_diff_sq_kernel``): the least time its bytes take at the chip's
HBM bandwidth (``flops.lhs_bytes_per_chip``) over its mean device time per
call in the trace. Memory bound: no arithmetic to speak of."""
from bench import flops, traces

PATTERN = r"batched_diff_sq_norm(\.\d+)?$"


def read(view):
    if view.summary is None or view.peaks is None:
        return None
    secs, calls = traces.op_calls(view.trace, PATTERN)
    if calls == 0 or secs <= 0:
        return None
    least = flops.lhs_bytes_per_chip(
        view.cfg, int(view.traffic["workers"]), view.chips,
        view.state_shards) / float(view.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (secs / calls)
