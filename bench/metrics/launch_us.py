"""Traced runs: seconds in the program span ``backend.dispatch`` over the
device programs launched in the window (the backend's ``launches``
counter); in microseconds."""
from bench.program_spans import window_metric


def read(w):
    return window_metric(w, "launch_us")
