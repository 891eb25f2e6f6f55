"""Device time per completed window step, in ms, of the ops the compiled
step names ``cada.eq3``: the eq. (3) aggregate, from the innovation delta
and the wire through ``eq3_row_mean`` to the ∇̄ and worker-row updates (for
``always``, the mean over workers). From the traced window's device ops
(``bench/scopes.py``)."""
from bench import scopes


def read(view):
    return scopes.phase_ms(view, "cada.eq3")
