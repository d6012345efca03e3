"""Device time of the lookup program per call: the executions of its XLA
module in the profiler trace.  ``store.lookup`` runs the jitted
``repro.api.stores._jit_lookup``, whose module XLA names ``MODULE``."""

MODULE = "jit__jit_lookup"


def read(run):
    if run.trace is None:
        return None
    runs = run.trace.module_runs(MODULE)
    if not runs:
        return None
    return sum(runs) / len(runs) / 1e6, len(runs)
