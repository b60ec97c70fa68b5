"""Traced runs: the union of device-operation intervals in the traced
window over the batches dispatched in it; in milliseconds."""


def read(w):
    if not w.busy_s or not w.batches:
        return None
    return w.busy_s * 1e3 / w.batches
