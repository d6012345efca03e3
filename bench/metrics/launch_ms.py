"""Mean time of the program's launch of a store call: the
``api.<op>.launch`` host spans that ``repro.api`` writes into the
profiler trace around its jitted call alone (static-argument hashing,
argument flattening, the executable's launch and its output-buffer
allocation), inside ``dispatch_ms``'s span."""

import program_trace

program_trace.install()

PREFIX, SUFFIX = program_trace.API_PREFIX, ".launch"


def read(run):
    t = run.trace
    spans = getattr(t, "program_spans", None)
    if not spans:
        return None
    lo, hi = t.window
    ds = [e - s for n, s, e, _ in spans
          if n.startswith(PREFIX) and n.endswith(SUFFIX) and lo <= s < hi]
    if not ds:
        return None
    return sum(ds) / len(ds) / 1e6, len(ds)
