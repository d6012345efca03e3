"""Share of the window in which no op ran on the device (profiler trace),
averaged over the devices traced."""


def read(run):
    t = run.trace
    if t is None or not t.ops or t.busy_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s), sum(len(d) for d in t.ops)
