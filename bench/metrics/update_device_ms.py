"""Device time of the update program per call: the executions of its XLA
module in the profiler trace.  ``store.update`` runs the jitted
``repro.core.continuity.update``, whose module XLA names ``MODULE``."""

MODULE = "jit_update"


def read(run):
    if run.trace is None:
        return None
    runs = run.trace.module_runs(MODULE)
    if not runs:
        return None
    return sum(runs) / len(runs) / 1e6, len(runs)
