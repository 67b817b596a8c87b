"""Simulated requests per second: every request the window completed, over
the window's whole wall time (first dispatch to the last result on the
host)."""


def read(run):
    w = run.window
    return w["requests"] / w["wall_s"] if w["wall_s"] > 0 and w["requests"] else None
