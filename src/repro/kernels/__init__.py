"""Pallas TPU kernels of the hot path, with pure-jnp oracles in ``ref.py``.

Every kernel entry point takes ``interpret: bool | None`` and runs its
``pallas_call`` through :func:`call_kernel`, the one place that decides
between compiled Mosaic and the Pallas interpreter.
"""
from __future__ import annotations

import jax


def call_kernel(build, *args, interpret: bool | None = None):
    """Run ``build(interpret)(*args)``, where ``build`` makes a pallas_call.

    ``interpret=None`` chooses per the platform the program is lowered for:
    the compiled Mosaic kernel on a TPU, the interpreter elsewhere (the CPU
    test path).  The choice is made at lowering, not from the host's default
    backend, so a program compiled ahead of time for a TPU holds the Mosaic
    kernel even on a host whose default backend is the CPU.
    """
    if interpret is None:
        return jax.lax.platform_dependent(*args, tpu=build(False),
                                          default=build(True))
    return build(interpret)(*args)
