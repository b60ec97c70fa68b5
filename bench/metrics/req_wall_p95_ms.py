"""95th percentile (nearest rank) of the wall latency, submit to the step
that returned the request completed, over every request completed in the
window; in milliseconds."""
import math


def read(w):
    if not w.latencies_s:
        return None
    s = sorted(w.latencies_s)
    return s[max(0, math.ceil(0.95 * len(s)) - 1)] * 1e3
