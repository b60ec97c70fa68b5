"""``DynamicScheduler.events`` appended in the window (drift, objective,
resize; cached schedule or fresh solve) per 1,000 requests completed."""


def read(w):
    return 1e3 * w.reschedules / w.completed if w.completed else None
