"""Exposed collective time per completed window step, in ms: the device time
of the collective ops (``traces.COLLECTIVE``: all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all, their async starts and
dones) during which no other op runs on the same chip, inside the window,
mean over chips (``traces.exposed_collective_s``). Nothing on a trace with
no collective in the window, as a one-chip step's."""
from bench import traces


def read(view):
    if view.summary is None or view.steps <= 0:
        return None
    lo, hi = traces.window_of(view.trace)
    if not any(traces.is_collective(e) and e[1] < hi and e[1] + e[2] > lo
               for evs in view.trace["devices"].values() for e in evs):
        return None
    return traces.exposed_collective_s(view.trace) / view.steps * 1e3
