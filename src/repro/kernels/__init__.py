"""Pallas TPU kernels for the paper's hot loops: the map-major OLP conv and
the blocked matmul, each with a pure-jnp oracle (``ref.py``) and a jitted
wrapper that registers it as the ``pallas_mapmajor`` implementation
(``ops.py``)."""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Pallas TPU kernels compile on a TPU and only interpret elsewhere;
    ``None`` picks by the backend, so the chip never interprets."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
