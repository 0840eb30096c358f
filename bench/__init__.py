"""The on-chip benchmark of the BAD engine: ``python3 bench/run.py``."""
