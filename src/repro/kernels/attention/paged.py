"""Pallas TPU kernels: paged attention over block tables.

Two entry points:

* `paged_attention_bhd` — decode: ONE query token per sequence attends
  a KV cache scattered across fixed-size pages.
* `paged_prefill_attention_btd` — chunked prefill: a CHUNK of T query
  tokens (absolute positions start..start+T-1) attends the pages
  already written for earlier chunks plus the chunk's own freshly
  written pages, causal within the chunk (DESIGN.md §4b).

Decode reads only the live pages, each once for all its query heads.
The pools stay in HBM, viewed as (rows, ps * KV, D): a page's tokens
with their kv heads interleaved, a free reshape of the flat
(N, ps, KV, D) pool and of the sharded (S, R, ps, KV, D) one, whose
``locality * R + slot`` rows are then plain row numbers.  One program
serves the whole batch: for each slot it walks the 128-token blocks
(`BLOCK_TOKENS`, ppb pages) that hold a position it may see, up to its
clock and back no further than its sliding window, and copies each
page of a block with one DMA into a double-buffered VMEM scratch.  The
next block's copies, the next slot's first block included, start
before the current block is waited for and computed.  A slot costs at
least one block, so an idle one (null table row, position 0) costs
exactly one.  All H query heads score a block's ppb * ps * KV rows in
one (H, D) x (D, ppb * ps * KV) matmul; a row of kv head g counts only
for query heads g * n_rep .. (g + 1) * n_rep - 1, so nothing is
widened.  Scores and the online softmax are float32, P·V takes bf16
inputs and accumulates in float32: the oracle's precision.

Prefill keeps the block table as a *scalar-prefetch* operand whose
k/v index maps pick the physical page row each grid step DMAs into
VMEM.  Its grid is (B, nP) with the page axis LAST (sequential);
online-softmax statistics (m, l) and the output accumulator persist in
VMEM scratch across the nP steps of one sequence and are flushed on the
final step (same scheme as flash.py):

  k/v tile: (1, ps, KV, D) — one page, ALL kv heads, DMAed once
  q/out (1, T, H, D); scratch acc (H, T, D), m/l (H, T, 1)

Pages entirely outside the chunk's valid range — past its last query
or behind its sliding window — are skipped via @pl.when.

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
    """One page's (prefill) or block's (decode) contribution to the
    running softmax of the query rows `q` (n, D) against k/v (t, D);
    `at` indexes their scratch."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # (n, t)
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[at]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    pr = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[at] = l_ref[at] * corr + jnp.sum(pr, axis=-1, keepdims=True)
    acc_ref[at] = acc_ref[at] * corr + jax.lax.dot(
        pr.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[at] = m_new


BLOCK_TOKENS = 128          # tokens a decode block brings, all heads
LANES = 128                 # a TPU vector register's lanes


def decode_fits(pages: jnp.ndarray) -> bool:
    """Whether the decode kernel compiles for pages of this shape: its
    DMAs and matmuls take whole 128-lane rows, so the head dim must be
    a multiple of 128."""
    return pages.shape[-1] % LANES == 0


def _decode_kernel(tables_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
                   k_buf, v_buf, sems, acc_ref, m_ref, l_ref, *,
                   n_slots, n_cols, ppb, ps, kvh, n_rep, window, scale):
    bk = ppb * ps                        # tokens a block
    rows = bk * kvh                      # (token, kv head) rows a block
    h, d = acc_ref.shape

    def bounds(b):
        """[lo, hi): the blocks of slot b that hold a visible position,
        at least one and none past the table, so that every copy
        started is waited for."""
        pos = pos_ref[b]
        hi = jnp.minimum(pos // bk + 1, n_cols // ppb)
        if window <= 0:
            return 0, hi
        return jnp.minimum(jnp.maximum(pos - window + 1, 0) // bk,
                           hi - 1), hi

    def copies(b, i, s):
        """Slot b's block i into buffer s: one DMA per page and pool."""
        base = b * n_cols + i * ppb
        out = []
        for j in range(ppb):
            row = jnp.clip(tables_ref[base + j], 0, k_hbm.shape[0] - 1)
            out.append(pltpu.make_async_copy(k_hbm.at[row], k_buf.at[s, j],
                                             sems.at[0, s]))
            out.append(pltpu.make_async_copy(v_hbm.at[row], v_buf.at[s, j],
                                             sems.at[1, s]))
        return out

    # row r of a block is token r // kvh of kv head r % kvh
    col = jax.lax.broadcasted_iota(jnp.int32, (h, rows), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (h, rows), 0)
    tok = col // kvh
    g = col - tok * kvh
    own = (g * n_rep <= head) & (head < (g + 1) * n_rep)

    for c in copies(0, bounds(0)[0], 0):
        c.start()

    def slot(b, s):
        lo, hi = bounds(b)
        pos = pos_ref[b]
        q = q_ref[b]
        _init_stats(acc_ref, m_ref, l_ref)

        def block(i, s):
            last = i + 1 >= hi

            @pl.when(jnp.logical_not(last) | (b + 1 < n_slots))
            def _prefetch():
                nb = jnp.where(last, b + 1, b)
                ni = jnp.where(last, bounds(jnp.minimum(nb, n_slots - 1))[0],
                               i + 1)
                for c in copies(nb, ni, 1 - s):
                    c.start()

            for c in copies(b, i, s):
                c.wait()
            j = i * bk + tok
            mask = own & (j <= pos)
            if window > 0:
                mask &= pos - j < window
            _online_softmax(q, k_buf[s].reshape(rows, d),
                            v_buf[s].reshape(rows, d), mask, scale,
                            acc_ref, m_ref, l_ref, Ellipsis)
            return 1 - s

        s = jax.lax.fori_loop(lo, hi, block, s)
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[b] = (acc_ref[...] / l).astype(o_ref.dtype)
        return s

    jax.lax.fori_loop(0, n_slots, slot, 0)


def paged_attention_bhd(q: jnp.ndarray, k_pages: jnp.ndarray,
                        v_pages: jnp.ndarray,
                        block_tables: jnp.ndarray,
                        positions: jnp.ndarray, *,
                        window: int = 0,
                        interpret: Optional[bool] = None) -> jnp.ndarray:
    """q: (B, H, D); k/v_pages: (N, ps, KV, D) — or (S, R, ps, KV, D)
    for a locality-sharded pool, where block-table rows encode
    ``locality * R + slot``; block_tables: (B, P) int32 physical rows,
    any valid row past a slot's position; positions: (B,) int32
    per-slot clocks.  Returns (B, H, D).  Compiled, D must be a
    multiple of 128 (`decode_fits`); the interpreter takes any D."""
    b, h, d = q.shape
    ps, kvh = k_pages.shape[-3], k_pages.shape[-2]
    n_tables = block_tables.shape[1]
    ppb = max(1, min(n_tables, BLOCK_TOKENS // ps))
    n_cols = -(-n_tables // ppb) * ppb
    # pad the table to whole blocks with row 0: read, never seen
    tables = jnp.pad(block_tables.astype(jnp.int32),
                     ((0, 0), (0, n_cols - n_tables)))
    kern = functools.partial(
        _decode_kernel, n_slots=b, n_cols=n_cols, ppb=ppb, ps=ps, kvh=kvh,
        n_rep=h // kvh, window=window, scale=d ** -0.5)
    whole = pl.BlockSpec((b, h, d), lambda i, *_: (0, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    buf = pltpu.VMEM((2, ppb, ps * kvh, d), k_pages.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[whole, hbm, hbm],
        out_specs=whole,
        scratch_shapes=[
            buf, buf,
            pltpu.SemaphoreType.DMA((2, 2)),
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
        name="paged_decode",
    )(tables.reshape(-1), positions.astype(jnp.int32), q,
      k_pages.reshape(-1, ps * kvh, d), v_pages.reshape(-1, ps * kvh, d))


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
    index map); block_tables: (B, P)
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
