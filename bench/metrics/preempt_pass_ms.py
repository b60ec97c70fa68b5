"""Traced runs: seconds in the program span ``router.preempt_pass`` per
``router.step`` span in the window; in milliseconds. Only a tenancy
layer with preemption opens that span."""
from bench.program_spans import window_metric


def read(w):
    return window_metric(w, "preempt_pass_ms")
