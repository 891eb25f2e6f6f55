"""Share of worker-steps in the window whose gradient was uploaded, from
each step's own ``upload_mask``."""


def read(view):
    if view.masks is None or view.masks.size == 0:
        return None
    return 100.0 * float(view.masks.mean())
