"""Chip benchmark of M-AVG training (see bench/run.py)."""
