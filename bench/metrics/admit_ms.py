"""Traced runs: the mean of the program span ``engine.admit`` in the
window (``Engine._admit``: DP lookup or solve, evictions,
``backend.prepare``); in milliseconds."""
from bench.program_spans import window_metric


def read(w):
    return window_metric(w, "admit_ms")
