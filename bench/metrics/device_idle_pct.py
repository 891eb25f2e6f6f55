"""Share of the traced window in which no operation ran on the device,
mean over the chips used: 100 · (1 − busy / window), from the profiler
trace (``traces.summarize``)."""


def read(view):
    if view.summary is None or view.summary.window_s <= 0:
        return None
    return 100.0 * (1.0 - view.summary.busy_s / view.summary.window_s)
