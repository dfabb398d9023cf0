"""Pallas TPU kernel: fused RK3 semilinear-wave block step.

The paper's task body (one AMR block update) as a TPU kernel: all three
RK stages execute on a block resident in VMEM, so HBM traffic per task
is exactly one read of (3, g+2H) and one write of (3, g) — the
communication-avoiding property that motivated fusing the stages in the
first place (amr/wave.py).

Tiling: grid = (n_blocks,); each program owns one block.
  in  : u_ext (1, 3, g+2H) VMEM   r_ext (1, g+2H) VMEM
        flags (1, 2) VMEM (left_phys, right_phys as 0/1)
  out : (1, 3, g) VMEM

The physics matches amr/wave.fused_rk3_block bit-for-bit in interpret
mode (tests/test_kernels.py sweeps shapes and dtypes against ref.py).
TPU target notes: g should be a multiple of 128 (lane width); the three
stages are elementwise + shifts, so the kernel is VPU-bound — the win
is HBM avoidance, not MXU utilization.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

H = 3  # halo width (1 stencil radius x 3 RK stages)


def _rhs(u, r, dr, p):
    """RHS on a (3, W) VMEM block via rolls.

    The two wrap-around edge cells per stage are garbage; they reach at
    most `stage` cells inward, all discarded by the final [H:-H] slice
    (or overwritten by the physical-ghost refresh) — the same validity
    argument as the fused jnp version.  Roll keeps the kernel free of
    captured array constants (a pallas_call restriction).
    """
    chi, phi, pi = u[0], u[1], u[2]

    def ctr(v):
        return (jnp.roll(v, -1) - jnp.roll(v, 1)) / (2.0 * dr)

    near = jnp.abs(r) < 0.5 * dr
    safe = jnp.where(near, 1.0, r * r)
    mono = jnp.where(near, 3.0 * ctr(phi), ctr(r * r * phi) / safe)
    return jnp.stack([pi, ctr(pi), mono + chi ** p])


def _refresh(u, left, right):
    w = u.shape[-1]
    # mirror about index H (r=0): ghost columns [0:H] <- columns
    # [H+1 : 2H+1] reversed, with (+, -, +) parity.
    mir = u[:, H + 1:2 * H + 1][:, ::-1]
    lvals = jnp.stack([mir[0], -mir[1], mir[2]])
    u = jnp.where(left, jnp.concatenate([lvals, u[:, H:]], axis=-1), u)
    last = u[:, w - H - 1]
    slope = last - u[:, w - H - 2]
    rvals = jnp.stack([last + (k + 1.0) * slope for k in range(H)],
                      axis=-1)
    u = jnp.where(right,
                  jnp.concatenate([u[:, :w - H], rvals], axis=-1), u)
    return u


def _kernel(u_ref, r_ref, flags_ref, o_ref, *, dr, dt, p):
    u = u_ref[0]                       # (3, W)
    r = r_ref[0]                       # (W,)
    left = flags_ref[0, 0] > 0
    right = flags_ref[0, 1] > 0
    u0 = _refresh(u, left, right)
    u1 = u0 + dt * _rhs(u0, r, dr, p)
    u1 = _refresh(u1, left, right)
    u2 = 0.75 * u0 + 0.25 * (u1 + dt * _rhs(u1, r, dr, p))
    u2 = _refresh(u2, left, right)
    u3 = u0 / 3.0 + (2.0 / 3.0) * (u2 + dt * _rhs(u2, r, dr, p))
    u3 = _refresh(u3, left, right)
    o_ref[0] = u3[:, H:-H]


def stencil_rk3(u_ext: jnp.ndarray, r_ext: jnp.ndarray,
                flags: jnp.ndarray, *, dr: float, dt: float, p: int,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """u_ext: (nb, 3, g+2H); r_ext: (nb, g+2H); flags: (nb, 2) int32.

    Returns (nb, 3, g).
    """
    nb, _, w = u_ext.shape
    g = w - 2 * H
    kern = functools.partial(_kernel, dr=u_ext.dtype.type(dr),
                             dt=u_ext.dtype.type(dt), p=p)
    return pl.pallas_call(
        kern,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, 3, w), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, w), lambda i: (i, 0)),
            pl.BlockSpec((1, 2), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 3, g), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, 3, g), u_ext.dtype),
        interpret=interpret_mode(interpret),
    )(u_ext, r_ext, flags)
