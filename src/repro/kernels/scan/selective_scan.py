"""Pallas TPU kernel: Mamba-1 selective scan (chunked, channel-parallel).

The recurrence h_t = da_t * h_{t-1} + dbx_t ; y_t = <h_t, C_t> is
sequential in time but embarrassingly parallel over the d_inner
channels — the TPU-native layout keeps a (bd, n) state tile resident in
VMEM and walks time in chunks:

  grid = (B, n_dblocks, n_chunks); LAST axis sequential.
  in  : da, dbx (1, chunk, bd, n) VMEM;  c (1, chunk, n) VMEM
  out : y (1, chunk, bd) VMEM
  scratch : h (bd, n) f32 — persists across the chunk axis (the chunk
  carry is the dataflow future between chunk tasks, DESIGN.md §4).

HBM traffic is one read of (da, dbx, c) and one write of y — the
(S, d, n) state history never materializes, which is the point of the
Mamba scan kernel; the jnp oracle (ref.py) is the lax.scan recurrence.
d-block size bd should be a multiple of 8 (sublane) and n is the small
state dim (16); time steps inside a chunk run in a fori_loop over VMEM.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _kernel(da_ref, dbx_ref, c_ref, y_ref, h_ref, *, chunk):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def step(t, h):
        da_t = da_ref[0, t]          # (bd, n)
        dbx_t = dbx_ref[0, t]
        c_t = c_ref[0, t]            # (n,)
        h = da_t * h + dbx_t
        y_ref[0, t] = jnp.sum(h * c_t[None, :], axis=-1).astype(
            y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_ref[...])
    h_ref[...] = h


def selective_scan(da: jnp.ndarray, dbx: jnp.ndarray, c: jnp.ndarray,
                   *, chunk: int = 128, d_block: int = 256,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """da/dbx: (B, S, D, N) f32; c: (B, S, N) f32 -> y (B, S, D) f32."""
    b, s, d, n = da.shape
    chunk = min(chunk, s)
    d_block = min(d_block, d)
    nch = s // chunk
    ndb = d // d_block
    kern = functools.partial(_kernel, chunk=chunk)
    # layout: (B, S, D, N) -> blocks (1, chunk, d_block, n)
    return pl.pallas_call(
        kern,
        grid=(b, ndb, nch),
        in_specs=[
            pl.BlockSpec((1, chunk, d_block, n),
                         lambda bi, di, ci: (bi, ci, di, 0)),
            pl.BlockSpec((1, chunk, d_block, n),
                         lambda bi, di, ci: (bi, ci, di, 0)),
            pl.BlockSpec((1, chunk, n),
                         lambda bi, di, ci: (bi, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, d_block),
                               lambda bi, di, ci: (bi, ci, di)),
        out_shape=jax.ShapeDtypeStruct((b, s, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((d_block, n), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(da, dbx, c)
