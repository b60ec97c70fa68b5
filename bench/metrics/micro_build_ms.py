"""Traced runs: seconds in the program span ``backend.microbatches`` (the
microbatch build and copy in ``PallasPipelineBackend.submit``) over the
batches dispatched in the window; in milliseconds."""
from bench.program_spans import window_metric


def read(w):
    return window_metric(w, "micro_build_ms")
