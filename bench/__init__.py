"""On-chip benchmark of the served DyPe stack; ``python3 bench/run.py``."""
