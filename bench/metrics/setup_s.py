"""Process start to the window's start: imports, device init, compile or
cache reads, stack build and warm-up serving; in seconds."""


def read(w):
    return w.setup_s
