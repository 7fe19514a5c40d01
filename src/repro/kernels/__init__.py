"""Pallas TPU kernels. Each ``<name>/`` holds ``kernel.py`` (the
``pallas_call``), ``ops.py`` (pytree/layout wrappers) and ``ref.py`` (the
pure-jnp oracle the tests compare against)."""
from __future__ import annotations

import jax


def interpret_mode(interpret=None) -> bool:
    """The ``pallas_call(interpret=...)`` flag for a kernel being traced.

    ``None`` picks it from the backend at trace time: the Pallas
    interpreter runs only on the CPU backend (the test suite), so every
    other backend compiles the kernel — a TPU never silently interprets,
    and a backend the kernel cannot compile for fails loudly."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)
