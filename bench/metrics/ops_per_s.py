"""Ops completed in the window over the whole window (host clock)."""


def read(run):
    ops = sum(len(c.ids) for c in run.calls)
    return ops / run.window_s, ops
