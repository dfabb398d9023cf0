"""Pallas TPU kernels, each with a pure-jnp oracle (ref.py) and a
jitted wrapper (ops.py)."""

from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Whether a kernel runs in the Pallas interpreter: an explicit
    argument wins; otherwise the platform decides — compiled on a TPU,
    interpreted everywhere else.  Nothing else can force the
    interpreter onto a chip."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
