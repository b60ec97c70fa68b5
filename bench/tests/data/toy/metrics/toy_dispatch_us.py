"""Traced runs: seconds in the toy backend's program span ``toy.dispatch``
over its device programs launched in the window; in microseconds."""


def read(w):
    if not w.span_totals or "toy.dispatch" not in w.span_totals:
        return None
    launches = w.counters.get("launches", 0)
    return w.span_totals["toy.dispatch"][0] / launches * 1e6 \
        if launches else None
