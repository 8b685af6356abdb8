"""Pallas TPU kernels and the one decision of where they compile.

Every caller that asks "does Pallas compile here, or run in interpret
mode?" asks :func:`pallas_compiled`: the kernel wrappers (``ops``,
``bocd_step``, ``cell_reduce``) for their ``interpret`` default, and the
screening / reduction backend registries for their ``"auto"`` choice.
"""
from __future__ import annotations

import jax


def pallas_compiled() -> bool:
    """True when jax's default backend is a TPU, where the kernels of this
    package (written against ``pltpu`` memory spaces) compile to Mosaic.

    Everywhere else they run in interpret mode, which is correct but slow,
    so auto-selection picks the numpy paths there. This initializes jax's
    default backend if nothing has yet; it swallows no error.
    """
    return jax.default_backend() == "tpu"
