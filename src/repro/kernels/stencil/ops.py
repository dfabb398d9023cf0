"""Jitted public wrapper for the stencil kernel.

`stencil_rk3_step` is what amr/compiled.py calls when
CompiledAMRConfig.use_pallas is set: it adapts the pool layout
(slots, 3, g+2H) + broadcast masks to the kernel's (nb, ...) layout.
The kernel compiles on a TPU and runs in the Pallas interpreter
everywhere else (repro.kernels.interpret_mode).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.stencil.stencil import stencil_rk3


@partial(jax.jit, static_argnames=("dr", "dt", "p"))
def stencil_rk3_step(pool_ext: jnp.ndarray, r_ext: jnp.ndarray,
                     left_phys: jnp.ndarray, right_phys: jnp.ndarray,
                     *, dr: float, dt: float, p: int) -> jnp.ndarray:
    """(slots, 3, g+2H) -> (slots, 3, g); masks broadcast (slots,1,1)."""
    nb = pool_ext.shape[0]
    flags = jnp.stack(
        [left_phys.reshape(nb).astype(jnp.int32),
         right_phys.reshape(nb).astype(jnp.int32)], axis=-1)
    return stencil_rk3(pool_ext, r_ext, flags, dr=dr, dt=dt, p=p)
