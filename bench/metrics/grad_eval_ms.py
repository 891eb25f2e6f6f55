"""Device time per completed window step, in ms, of the ops the compiled
step names ``cada.grad_eval``: the workers' forward and backward passes.
The fresh and the second evaluation (cada1, cada2) count together: on the
stacked route they are rows of one vmapped call. From the traced window's
device ops (``bench/scopes.py``)."""
from bench import scopes


def read(view):
    return scopes.phase_ms(view, "cada.grad_eval")
