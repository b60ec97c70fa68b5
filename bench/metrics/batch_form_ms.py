"""Traced runs: seconds in the program span ``batcher.next_batch`` (each
call in ``Router.step``'s loop) over the batches dispatched in the window;
in milliseconds."""
from bench.program_spans import window_metric


def read(w):
    return window_metric(w, "batch_form_ms")
