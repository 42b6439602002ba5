"""Chip benchmark of the hybrid-parallel training step (see run.py)."""
