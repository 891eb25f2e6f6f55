"""Model FLOP/s utilization of the whole step: model operations per
training token (``flops.model_flops_per_token``: no remat, no second
evaluation) times the traced window's tokens per second, over the chips'
bf16 peak (``bench/peaks.json``)."""
from bench import flops


def read(view):
    if view.peaks is None:
        return None
    per_token = flops.model_flops_per_token(view.cfg, int(view.traffic["seq"]))
    peak = view.chips * float(view.peaks["bf16_flops_per_s"])
    return 100.0 * per_token * view.tokens_per_s / peak
