"""Pallas TPU kernels for the paper's compute hot spots.

Each kernel package holds ``kernel.py`` (the Pallas body), ``ops.py``
(the jitted public wrappers) and ``ref.py`` (the plain-jnp oracle).
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """The one interpret-mode rule: ``None`` compiles the kernel on a TPU
    and runs it in the Pallas interpreter on every other backend; only an
    explicit ``True`` interprets on a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
