"""Chip benchmark of the flow engine (see run.py)."""
