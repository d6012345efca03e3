"""``launch_ms`` in the cells whose end-to-end metric is the lookup tail,
``read_p95_ms``: the same reduction."""

from metrics.launch_ms import read  # noqa: F401
