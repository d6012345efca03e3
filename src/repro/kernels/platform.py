"""The one place that decides how a Pallas kernel runs.

The mode follows the platform the enclosing computation is lowered for,
which is where its arrays live: the Pallas interpreter on CPU (tests and
CPU-only runs), the compiled Mosaic kernel on TPU, and a lowering error
on any other platform.  No caller chooses; a TPU run can never fall back
to the interpreter.
"""

from __future__ import annotations

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel, **kwargs):
    """``pl.pallas_call(kernel, **kwargs)`` with the platform-derived mode."""
    def call(*args):
        return jax.lax.platform_dependent(
            *args,
            cpu=pl.pallas_call(kernel, interpret=True, **kwargs),
            tpu=pl.pallas_call(kernel, interpret=False, **kwargs))
    return call
