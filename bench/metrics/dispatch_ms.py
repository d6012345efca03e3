"""Mean time of a store call in ``repro.api`` until it returns, before
the client waits for its result: the ``bench.dispatch.<op>`` host spans
of the profiler trace."""

PREFIX = "bench.dispatch."


def read(run):
    t = run.trace
    if t is None:
        return None
    lo, hi = t.window
    ds = [e - s for n, s, e in t.spans if n.startswith(PREFIX) and lo <= s < hi]
    if not ds:
        return None
    return sum(ds) / len(ds) / 1e6, len(ds)
