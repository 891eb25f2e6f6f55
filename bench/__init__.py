"""On-chip benchmark of the CADA trainer step (see ``bench/run.py``)."""
