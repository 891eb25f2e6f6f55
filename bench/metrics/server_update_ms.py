"""Device time per completed window step, in ms, of the ops the compiled
step names ``cada.server_update``: the fused AMSGrad kernel
(``fused_amsgrad_flat``) and the write of ‖Δθ‖² into the RHS history. From
the traced window's device ops (``bench/scopes.py``)."""
from bench import scopes


def read(view):
    return scopes.phase_ms(view, "cada.server_update")
