"""Chip benchmark of the EcoFlow conv stack: `python3 bench/run.py`."""
