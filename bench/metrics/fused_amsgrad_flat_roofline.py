"""Roofline share of the server update kernel (``kernels/ops.fused_amsgrad_flat``
-> ``_amsgrad_kernel``): the least time its bytes take at the chip's HBM
bandwidth (``flops.amsgrad_bytes_per_chip``: the chip's shard of θ, h, v̂,
∇̄ read and θ', h', v̂' written, fp32) over its mean device time per call."""
from bench import flops, traces

PATTERN = r"fused_amsgrad_flat(\.\d+)?$"


def read(view):
    if view.summary is None or view.peaks is None:
        return None
    secs, calls = traces.op_calls(view.trace, PATTERN)
    if calls == 0 or secs <= 0:
        return None
    least = flops.amsgrad_bytes_per_chip(view.cfg, view.state_shards) \
        / float(view.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (secs / calls)
