"""Chip benchmark of the weight-shared CNN stack: see ``run.py``."""
