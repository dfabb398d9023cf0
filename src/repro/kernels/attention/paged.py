"""Pallas TPU kernels: paged attention over block tables.

Two entry points share the scheme:

* `paged_attention_bhd` — decode: ONE query token per sequence attends
  a KV cache scattered across fixed-size pages.
* `paged_prefill_attention_btd` — chunked prefill: a CHUNK of T query
  tokens (absolute positions start..start+T-1) attends the pages
  already written for earlier chunks plus the chunk's own freshly
  written pages, causal within the chunk (DESIGN.md §4b).

The block table is a *scalar-prefetch* operand
(pltpu.PrefetchScalarGridSpec): it is available before the kernel body
runs, so the k/v index maps dereference it to pick the physical page
row each grid step DMAs into VMEM — the AGAS lookup compiled into an
index map, with no gather materialized in HBM.

Tiling: grid = (B, nP) with the page axis LAST (sequential);
online-softmax statistics (m, l) and the output accumulator persist in
VMEM scratch across the nP steps of one sequence and are flushed on the
final step (same scheme as flash.py).  Every block spans whole trailing
dimensions, which is what the TPU lowering requires of a block whose
trailing dims are not (8, 128)-aligned:

  k/v tile: (1, ps, KV, D) — one page, ALL kv heads, DMAed once
  decode  : q/out (1, H, D); scratch acc (H, D), m/l (H, 1) f32
  prefill : q/out (1, T, H, D); scratch acc (H, T, D), m/l (H, T, 1)

GQA is handled inside the body: kv head g serves query heads
g*n_rep .. (g+1)*n_rep - 1, so each page is read once per sequence no
matter how many query heads share it (decode groups those n_rep heads
into one (n_rep, D) x (D, ps) matmul).  Pages entirely outside the
slot's valid range — beyond its per-slot position counter (or the
chunk's last query) or behind its sliding window — are skipped via
@pl.when, so compute scales with the tokens actually resident, not
with the table width.

`interpret=None` runs the compiled kernel on a TPU and the Pallas
interpreter everywhere else; the platform alone decides.
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


def _kv_spec(k_pages: jnp.ndarray):
    """BlockSpec of one page (all kv heads) picked through the block
    table: (N, ps, KV, D) flat pools, or (S, R, ps, KV, D) sharded
    pools whose rows encode ``locality * R + slot`` — the index map
    performs the AGAS (locality, slot) decode."""
    ps, kvh, d = k_pages.shape[-3:]
    if k_pages.ndim == 5:
        rps = k_pages.shape[1]             # rows per shard

        def kv_map(bi, pi, bt, _):
            row = bt[bi, pi]
            return (row // rps, row % rps, 0, 0, 0)
        return pl.BlockSpec((1, 1, ps, kvh, d), kv_map)
    return pl.BlockSpec((1, ps, kvh, d),
                        lambda bi, pi, bt, _: (bt[bi, pi], 0, 0, 0))


def _page(ref, g: int, sharded: bool) -> jnp.ndarray:
    """kv head g of the page in VMEM: (ps, D)."""
    return ref[0, 0, :, g] if sharded else ref[0, :, g]


def _init_stats(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _online_softmax(q, k, v, mask, scale, acc_ref, m_ref, l_ref, at):
    """One page's contribution to the running softmax of the query
    rows `q` (n, D) against k/v (ps, D); `at` indexes their scratch."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (n, ps)
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[at]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    pr = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[at] = l_ref[at] * corr + jnp.sum(pr, axis=-1, keepdims=True)
    acc_ref[at] = acc_ref[at] * corr + jax.lax.dot(
        pr.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[at] = m_new


def _decode_kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, ps, kvh, n_rep, n_pages,
                   window, scale, sharded):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        _init_stats(acc_ref, m_ref, l_ref)

    pos = pos_ref[b]
    base = p * ps
    live = base <= pos
    if window > 0:
        live &= pos - (base + ps - 1) < window

    @pl.when(live)
    def _body():
        j = base + jax.lax.broadcasted_iota(jnp.int32, (n_rep, ps), 1)
        mask = j <= pos
        if window > 0:
            mask &= pos - j < window
        for g in range(kvh):
            rows = slice(g * n_rep, (g + 1) * n_rep)
            _online_softmax(q_ref[0, rows], _page(k_ref, g, sharded),
                            _page(v_ref, g, sharded), mask, scale,
                            acc_ref, m_ref, l_ref, rows)

    @pl.when(p == n_pages - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_attention_bhd(q: jnp.ndarray, k_pages: jnp.ndarray,
                        v_pages: jnp.ndarray,
                        block_tables: jnp.ndarray,
                        positions: jnp.ndarray, *,
                        window: int = 0,
                        interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: (B, H, D); k/v_pages: (N, ps, KV, D) — or (S, R, ps, KV, D)
    for a locality-sharded pool, where block-table rows encode
    ``locality * R + slot`` and the index map performs the AGAS
    (locality, slot) decode; block_tables: (B, P) int32 physical rows;
    positions: (B,) int32 per-slot clocks.  Returns (B, H, D)."""
    b, h, d = q.shape
    ps, kvh = k_pages.shape[-3], k_pages.shape[-2]
    n_tables = block_tables.shape[1]
    kern = functools.partial(
        _decode_kernel, ps=ps, kvh=kvh, n_rep=h // kvh,
        n_pages=n_tables, window=window, scale=d ** -0.5,
        sharded=k_pages.ndim == 5)
    kv_spec = _kv_spec(k_pages)
    q_spec = pl.BlockSpec((1, h, d), lambda bi, pi, bt, _: (bi, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_tables),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((h, d), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret_mode(interpret),
    )(block_tables.astype(jnp.int32), positions.astype(jnp.int32),
      q, k_pages, v_pages)


def _prefill_kernel(bt_ref, start_ref, q_ref, k_ref, v_ref, o_ref,
                    acc_ref, m_ref, l_ref, *, t, ps, h, n_rep, n_pages,
                    window, scale, sharded):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        _init_stats(acc_ref, m_ref, l_ref)

    start = start_ref[b]
    base = p * ps
    # some query in the chunk can see this page: the last query sits at
    # start + t - 1; under a window the earliest query (at `start`)
    # bounds how far back any key can still be visible
    live = base <= start + (t - 1)
    if window > 0:
        live &= start - (base + ps - 1) < window

    @pl.when(live)
    def _body():
        j = base + jax.lax.broadcasted_iota(jnp.int32, (t, ps), 1)
        qpos = start + jax.lax.broadcasted_iota(jnp.int32, (t, ps), 0)
        mask = j <= qpos                   # causal across + within chunk
        if window > 0:
            mask &= qpos - j < window
        for hi in range(h):
            g = hi // n_rep
            _online_softmax(q_ref[0, :, hi], _page(k_ref, g, sharded),
                            _page(v_ref, g, sharded), mask, scale,
                            acc_ref, m_ref, l_ref, hi)

    @pl.when(p == n_pages - 1)
    def _flush():
        for hi in range(h):
            l = jnp.maximum(l_ref[hi], 1e-30)
            o_ref[0, :, hi] = (acc_ref[hi] / l).astype(o_ref.dtype)


def paged_prefill_attention_btd(q: jnp.ndarray, k_pages: jnp.ndarray,
                                v_pages: jnp.ndarray,
                                block_tables: jnp.ndarray,
                                start: jnp.ndarray, *,
                                window: int = 0,
                                interpret: Optional[bool] = None
                                ) -> jnp.ndarray:
    """Chunked-prefill attention over block tables.

    q: (B, T, H, D) chunk queries; k/v_pages: (N, ps, KV, D) — or
    (S, R, ps, KV, D) for a locality-sharded pool with
    ``locality * R + slot`` row encoding (the AGAS decode lives in the
    index map, exactly as in the decode kernel); block_tables: (B, P)
    int32 physical rows; start: (B,) int32 absolute position of
    q[:, 0].  The chunk's own K/V must already be written into its
    pages; query t attends keys at positions <= start + t (and within
    the sliding window when set).  Returns (B, T, H, D).
    """
    b, t, h, d = q.shape
    ps, kvh = k_pages.shape[-3], k_pages.shape[-2]
    n_tables = block_tables.shape[1]
    kern = functools.partial(
        _prefill_kernel, t=t, ps=ps, h=h, n_rep=h // kvh,
        n_pages=n_tables, window=window, scale=d ** -0.5,
        sharded=k_pages.ndim == 5)
    kv_spec = _kv_spec(k_pages)
    q_spec = pl.BlockSpec((1, t, h, d),
                          lambda bi, pi, bt, _: (bi, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_tables),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((h, t, d), jnp.float32),
            pltpu.VMEM((h, t, 1), jnp.float32),
            pltpu.VMEM((h, t, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, t, h, d), q.dtype),
        interpret=interpret_mode(interpret),
    )(block_tables.astype(jnp.int32), start.astype(jnp.int32),
      q, k_pages, v_pages)
