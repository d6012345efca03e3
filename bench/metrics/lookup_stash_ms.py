"""Device time per lookup call of the ops under the named scope
``lookup.stash``: the stash scan, its chunk loop included
(``continuity._stash_tail``).  Counted as ``lookup_probe_ms`` counts."""

from metrics.lookup_probe_ms import scope_read


def read(run):
    return scope_read(run, "lookup.stash")
