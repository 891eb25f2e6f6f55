"""The compiled step's own count of its peak device memory, in GB (1e9 B):
``memory_analysis().peak_memory_in_bytes``, per chip. The runtime's
``peak_bytes_in_use`` leaves the step's temporaries out."""


def read(view):
    peak = getattr(view.memory, "peak_memory_in_bytes", 0) or 0
    return peak / 1e9 if peak > 0 else None
