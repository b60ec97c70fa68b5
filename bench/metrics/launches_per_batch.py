"""Traced runs: device programs launched in the window (the change of the
backend's ``launches`` counter) over the batches dispatched in it."""
from bench.program_spans import window_metric


def read(w):
    return window_metric(w, "launches_per_batch")
