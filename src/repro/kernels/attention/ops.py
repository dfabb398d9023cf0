"""Jitted public wrapper: (B, S, H, D) layout -> kernel layout."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.attention.flash import flash_attention_bhsd
from repro.kernels.attention.paged import (paged_attention_bhd,
                                           paged_prefill_attention_btd)


@partial(jax.jit,
         static_argnames=("causal", "window", "q_offset", "bq", "bk"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    *, causal: bool = True, window: int = 0,
                    q_offset: int = 0, bq: int = 128,
                    bk: int = 128) -> jnp.ndarray:
    """q: (B, Sq, H, D); k/v: (B, Sk, KV, D) (GQA without repetition).

    models/attention.attention() repeats kv before calling (it serves
    the jnp path too); the kernel undoes nothing — if KV == H the
    index map is identity, so both call patterns are valid.
    """
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    n_rep = h // kvh
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kvh, k.shape[1], d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kvh, v.shape[1], d)
    out = flash_attention_bhsd(
        qf, kf, vf, causal=causal, window=window, q_offset=q_offset,
        n_rep=n_rep, bq=bq, bk=bk)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("window",))
def paged_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                    v_pages: jnp.ndarray, block_tables: jnp.ndarray,
                    positions: jnp.ndarray, *,
                    window: int = 0) -> jnp.ndarray:
    """q: (B, 1, H, D); k/v_pages: (N, ps, KV, D) (GQA without
    repetition) or (S, R, ps, KV, D) for a locality-sharded pool;
    block_tables: (B, P) physical page rows (``locality * R + slot``
    encoded when sharded); positions: (B,) per-slot absolute position
    of the token being decoded.  Same contract as
    kernels.attention.ref.paged_attention_ref."""
    out = paged_attention_bhd(
        q[:, 0], k_pages, v_pages, block_tables, positions,
        window=window)
    return out[:, None]


@partial(jax.jit, static_argnames=("window",))
def paged_prefill_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                            v_pages: jnp.ndarray,
                            block_tables: jnp.ndarray,
                            start: jnp.ndarray, *,
                            window: int = 0) -> jnp.ndarray:
    """q: (B, T, H, D) chunk queries; k/v_pages: (N, ps, KV, D) (GQA
    without repetition) or (S, R, ps, KV, D) for a locality-sharded
    pool; block_tables: (B, P) physical page rows (``locality * R +
    slot`` encoded when sharded); start: (B,) absolute position of
    each chunk's first query.  Same contract as
    kernels.attention.ref.paged_prefill_attention_ref."""
    return paged_prefill_attention_btd(
        q, k_pages, v_pages, block_tables, start, window=window)
