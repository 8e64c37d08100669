"""Pallas TPU kernels, each a ``kernel.py`` / ``ref.py`` / ``ops.py`` triplet.

Every kernel entry takes ``interpret: bool | None = None``; ``None`` picks
the mode from the platform through :func:`interpret_mode`, so the same call
runs compiled on a TPU and in the Pallas interpreter everywhere else.
"""

from __future__ import annotations

import jax


def interpret_mode(interpret: bool | None = None) -> bool:
    """Resolve a kernel's ``interpret`` argument.

    An explicit bool wins (tests and compile checks force a mode); ``None``
    means interpret unless JAX's default backend is a TPU."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"
