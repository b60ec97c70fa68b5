"""Traced runs: the share of the traced window in which no operation ran
on the device; in percent."""


def read(w):
    if not w.busy_s or not w.window_s:
        return None
    return 100.0 * (1.0 - w.busy_s / w.window_s)
