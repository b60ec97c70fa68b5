"""Requests completed in the window over the window's wall seconds."""


def read(w):
    return w.completed / w.seconds if w.seconds > 0 else None
