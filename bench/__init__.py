"""Chip benchmark of the low-bit CNN training step (see ``run.py``)."""
