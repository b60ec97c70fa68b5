"""Requests completed in the window over batches dispatched in it."""


def read(w):
    return w.completed / w.batches if w.batches else None
