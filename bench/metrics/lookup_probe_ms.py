"""Device time per lookup call of the ops under the named scope
``lookup.probe``: ``locate``, the candidate gather, match and select
(``continuity.lookup``, or ``kernels.ops.probe_lookup`` when the Pallas
probe runs).

Each leaf op of the lookup program counts under the innermost of the
scopes in ``SCOPES`` on its ``op_name`` (``program_trace.scope_ms``);
``lookup_stash_ms`` and ``lookup_plan_ms`` read the other two.
"""

import program_trace
from metrics.lookup_device_ms import MODULE

program_trace.install()

SCOPES = ("lookup.probe", "lookup.stash", "lookup.plan")


def scope_read(run, scope):
    if run.trace is None:
        return None
    got = program_trace.scope_ms(run.trace, MODULE, SCOPES)
    if got is None:
        return None
    per, n = got
    return per[scope], n


def read(run):
    return scope_read(run, "lookup.probe")
