"""Mean time per lookup call of the API's own Python around its launch:
each ``api.lookup`` host span (``repro.api``'s ``_ModuleStore.lookup``,
the whole call) less the ``api.lookup.launch`` span inside it (the
jitted call alone, ``launch_ms``)."""

import bisect

import program_trace

program_trace.install()

CALL, LAUNCH = "api.lookup", "api.lookup.launch"


def read(run):
    t = run.trace
    spans = getattr(t, "program_spans", None)
    if not spans:
        return None
    lo, hi = t.window
    calls = [(s, e) for n, s, e, _ in spans if n == CALL and lo <= s < hi]
    if not calls:
        return None
    starts = [s for s, _ in calls]
    launched = 0
    for n, s, e, _ in spans:
        i = bisect.bisect_right(starts, s) - 1
        if n == LAUNCH and i >= 0 and e <= calls[i][1]:
            launched += e - s
    return (sum(e - s for s, e in calls) - launched) / len(calls) / 1e6, \
        len(calls)
