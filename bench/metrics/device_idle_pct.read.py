"""``device_idle_pct`` in the cells whose end-to-end metric is the lookup
tail, ``read_p95_ms``: the same reduction."""

from metrics.device_idle_pct import read  # noqa: F401
