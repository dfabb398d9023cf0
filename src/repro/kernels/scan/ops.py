"""Jitted public wrapper for the selective-scan kernel."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.scan.selective_scan import selective_scan


@partial(jax.jit, static_argnames=("chunk", "d_block"))
def selective_scan_op(da: jnp.ndarray, dbx: jnp.ndarray,
                      c: jnp.ndarray, *, chunk: int = 128,
                      d_block: int = 256) -> jnp.ndarray:
    return selective_scan(da, dbx, c, chunk=chunk, d_block=d_block)
