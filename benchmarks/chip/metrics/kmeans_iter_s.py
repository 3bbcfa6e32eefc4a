"""Seconds per Lloyd iteration: the whole window over every iteration of
the jobs run in it (host clock)."""


def read(view):
    r = view.records
    return r["window_s"] / r["iterations"] if r["iterations"] else None
