"""The on-chip benchmark of the adaptive-batch trainer (see ../run.py)."""
