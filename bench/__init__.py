"""On-chip benchmark of the selection engine: see ``bench/run.py``."""
