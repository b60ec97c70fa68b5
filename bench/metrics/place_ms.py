"""Mean over the window's batches of the placement wall that
``Router._dispatch`` times around ``Engine.submit`` (DP lookup or solve,
cell acquire, backend enqueue); in milliseconds."""


def read(w):
    return sum(w.place_s) / len(w.place_s) * 1e3 if w.place_s else None
