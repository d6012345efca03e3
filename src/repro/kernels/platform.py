"""The one place that decides by platform.

The platform is the one the enclosing computation is lowered for, which
is where its arrays live.  It decides how a Pallas kernel runs: the
Pallas interpreter on CPU (tests and CPU-only runs), the compiled Mosaic
kernel on TPU, and a lowering error on any other platform.  It also
decides whether a path that has a kernel takes it at all
(`kernels.ops.lookup`: the kernel on a TPU, the jnp gather elsewhere).
No caller chooses; a TPU run can never fall back to the interpreter.
"""

from __future__ import annotations

import jax
from jax.experimental import pallas as pl


def by_platform(*args, **per_platform):
    """``per_platform[p](*args)`` for the platform ``p`` the computation is
    lowered for (``default`` for any platform not named); only that
    branch is lowered."""
    return jax.lax.platform_dependent(*args, **per_platform)


def pallas_call(kernel, **kwargs):
    """``pl.pallas_call(kernel, **kwargs)`` with the platform-derived mode."""
    def call(*args):
        return by_platform(
            *args,
            cpu=pl.pallas_call(kernel, interpret=True, **kwargs),
            tpu=pl.pallas_call(kernel, interpret=False, **kwargs))
    return call
