"""Device time per completed window step, in ms, of the ops the compiled
step names ``cada.rule_state``: the rule's evaluation-point state; for
cada2, the stale-iterate ring's gather ``ring[slot]`` and its write after
the upload decision. From the traced window's device ops
(``bench/scopes.py``)."""
from bench import scopes


def read(view):
    return scopes.phase_ms(view, "cada.rule_state")
