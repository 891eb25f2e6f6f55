"""Device time per completed window step, in ms, of the ops the compiled
step names ``cada.pack``: the moves between pytrees and flat planes
(``FlatLayout.pack``, ``pack_worker``, ``unpack``, ``unpack_worker``,
``cast_roundtrip``), wherever they are called. From the traced window's
device ops (``bench/scopes.py``)."""
from bench import scopes


def read(view):
    return scopes.phase_ms(view, "cada.pack")
