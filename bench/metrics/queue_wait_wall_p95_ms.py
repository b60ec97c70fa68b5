"""Traced runs: 95th percentile (nearest rank) of the wall wait from
``Router.submit`` to a request's first dispatch, over the requests first
dispatched in the window (``ServingMetrics.queue_wait_s``, kept while a
tracer times spans); in milliseconds."""
from bench.program_spans import window_metric


def read(w):
    return window_metric(w, "queue_wait_wall_p95_ms")
