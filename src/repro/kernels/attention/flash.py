"""Pallas TPU kernel: flash attention (causal + sliding window + GQA).

Tiling (TPU-idiomatic): grid = (BH, nq, nk) with the LAST axis the
sequential one; online-softmax statistics (m, l) and the output
accumulator persist in VMEM scratch across the nk steps of one (BH, nq)
tile and are flushed on the final step.

  q tile  : (1, bq, D) VMEM        k/v tile: (1, bk, D) VMEM
  scratch : acc (bq, D) f32, m (bq,) f32, l (bq,) f32

GQA is handled in the k/v index_map: query row bh = b*H + h reads kv
row b*KV + h // (H/KV) — no materialized head repetition, which is the
memory win over the jnp oracle (models/attention.flash_jnp).

Block pruning: fully-masked (q, k) tiles are skipped via @pl.when on
the block indices (causal upper triangle; outside the sliding window),
so compute scales with the touched area, matching the cost model's
S_eff accounting.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1.0e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            bq, bk, nk, causal, window, q_offset, scale):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos0 = q_offset + qi * bq
    k_pos0 = ki * bk
    # Block-level pruning: skip tiles with no unmasked element.
    live = jnp.bool_(True)
    if causal:
        live &= q_pos0 + bq - 1 >= k_pos0
    if window > 0:
        live &= q_pos0 - (k_pos0 + bk - 1) < window

    @pl.when(live)
    def _body():
        q = q_ref[0]                          # (bq, D)
        k = k_ref[0]                          # (bk, D)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        q_pos = q_pos0 + jax.lax.broadcasted_iota(jnp.int32,
                                                  (bq, bk), 0)
        k_pos = k_pos0 + jax.lax.broadcasted_iota(jnp.int32,
                                                  (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= q_pos >= k_pos
        if window > 0:
            mask &= q_pos - k_pos < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot(
            p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_bhsd(q: jnp.ndarray, k: jnp.ndarray,
                         v: jnp.ndarray, *, causal: bool = True,
                         window: int = 0, q_offset: int = 0,
                         n_rep: int = 1, bq: int = 128, bk: int = 128,
                         interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: (BH, Sq, D); k/v: (BKV, Sk, D) with BH = BKV * n_rep."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq = min(bq, sq)
    bk = min(bk, sk)
    nq = sq // bq
    nk = sk // bk
    kern = functools.partial(
        _kernel, bq=bq, bk=bk, nk=nk, causal=causal, window=window,
        q_offset=q_offset, scale=d ** -0.5)
    kv_map = lambda b, i, j: (b // n_rep, j, 0)
    return pl.pallas_call(
        kern,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(q, k, v)
