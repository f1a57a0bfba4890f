"""Chip benchmark of the DistServe serving program (see run.py)."""
