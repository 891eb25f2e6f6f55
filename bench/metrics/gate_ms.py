"""Device time per completed window step, in ms, of the ops the compiled
step names ``cada.gate``: the rule's LHS (for cada2 the batched
``batched_diff_sq_norm`` kernel), its RHS and the upload mask. From the
traced window's device ops (``bench/scopes.py``)."""
from bench import scopes


def read(view):
    return scopes.phase_ms(view, "cada.gate")
