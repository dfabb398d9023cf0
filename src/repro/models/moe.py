"""Mixture-of-Experts: one routing rule, two dispatches.

Routing (`route`, Mixtral's rule for any k): router logits x Wr in
float32, softmax over all E experts, the k largest kept and their
weights renormalised to sum to 1.

* `moe_dropless` (serving: whole-prompt and chunked prefill, decode)
  sorts the N*k (token, expert) pairs by expert and runs each expert's
  rows through grouped matmuls (`grouped_matmul`).  There is no
  capacity: no token is dropped, and a token's output depends on that
  token alone, never on which requests share its step.
* `moe_apply` (training `forward`) keeps GShard grouped dense
  dispatch.  The dispatch/combine path is the LM-side incarnation of
  ParalleX *parcels*: a token routed to a remote expert is exactly
  "move the work to the data" — under the production sharding (groups
  over "data", experts over "model") the dispatch einsums lower to the
  all-to-all / slice collectives the roofline's collective term
  measures.  Capacity: per group of `group_size` tokens, each (virtual)
  expert owns C = k * group_size * capacity_factor / n_virtual slots;
  overflow tokens are dropped (standard GShard behaviour), so this
  path is for training only.

EP x TP composition (DESIGN.md §6): when n_experts < the model-axis
size M, each expert is split into tp = M / n_experts *virtual experts*
along d_ff (mixtral: 8 experts x 2 TP -> 16 virtual).  A token visits
every slice of its expert; the slices' partial outputs add up to the
full output under the same gate weight, so the sum over virtual
experts IS the tensor-parallel psum.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

from repro.models.config import ArchConfig
from repro.models.layers import Params, _init_dense

#: (rows, contraction, output columns) of one tile of the TPU kernel
GMM_TILE = (128, 512, 1024)


def moe_init(key, cfg: ArchConfig, tp: int = 1) -> Params:
    """tp = virtual-expert split factor (model_axis / n_experts at the
    production mesh; 1 on CPU smoke tests)."""
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    ev, ffv = e * tp, ff // tp
    return {
        "router": _init_dense(ks[0], d, e, jnp.float32),
        # virtual-expert stacked weights: (EV, d, ff/tp) / (EV, ff/tp, d)
        "wi": _init_dense(ks[1], d, ffv * ev, dt).reshape(d, ev, ffv)
              .swapaxes(0, 1),
        "wg": _init_dense(ks[2], d, ffv * ev, dt).reshape(d, ev, ffv)
              .swapaxes(0, 1),
        "wdown": _init_dense(ks[3], ffv * ev, d, dt).reshape(ev, ffv, d),
    }


def route(router: jnp.ndarray, x: jnp.ndarray, k: int
          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x (..., D) -> (weights (..., k) f32 summing to 1, experts
    (..., k) int32, probs (..., E) f32 over all experts)."""
    logits = jnp.dot(x.astype(jnp.float32), router,
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, k)
    return w / jnp.sum(w, axis=-1, keepdims=True), idx, probs


def capacity_dispatch(w: jnp.ndarray, idx: jnp.ndarray, probs: jnp.ndarray,
                      capacity: int
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """GShard dispatch of routed choices within token groups.

    w, idx: (G, T, k) from `route`; probs (G, T, E).  Earlier choices
    claim an expert's slots first.  Returns (dispatch (G,T,E,C) 0/1,
    combine (G,T,E,C) f32, aux_loss ())."""
    e = probs.shape[-1]
    dispatch = combine = 0.0
    taken = 0.0                                 # (G,1,E) slots claimed
    for j in range(idx.shape[-1]):
        mask = jax.nn.one_hot(idx[..., j], e, dtype=jnp.float32)
        pos = (jnp.cumsum(mask, axis=1) - mask + taken) * mask
        keep = mask * (pos < capacity)
        oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                            dtype=jnp.float32) * \
            keep[..., None]
        dispatch = dispatch + oh
        combine = combine + oh * w[..., j, None, None]
        taken = taken + jnp.sum(mask, axis=1, keepdims=True)
    # load-balancing aux loss (Switch/GShard form) on the first choice
    first = jax.nn.one_hot(idx[..., 0], e, dtype=jnp.float32)
    aux = jnp.mean(jnp.mean(probs, axis=1) * jnp.mean(first, axis=1)) * \
        (e * e)
    return dispatch, combine, aux


def moe_apply(params: Params, x: jnp.ndarray, cfg: ArchConfig,
              tp: int = 1) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (y, aux_loss).  Training dispatch (GShard).

    Tokens are folded into groups of cfg.moe_group_size; each group
    routes and disperses independently.  With groups sharded over
    "data" and (virtual) experts over "model", all einsums below are
    local except the final combine's psum over the expert axis.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    tokens = x.reshape(b * s, d)
    gs = min(cfg.moe_group_size, tokens.shape[0])
    n_groups = tokens.shape[0] // gs
    xt = tokens[:n_groups * gs].reshape(n_groups, gs, d)

    w, idx, probs = route(params["router"], xt, k)        # (G,T,k)
    cap = max(int(k * gs * cfg.capacity_factor / e), 4)
    dispatch, combine, aux = capacity_dispatch(w, idx, probs, cap)
    if tp > 1:
        # Each real expert's token set goes to ALL of its tp virtual
        # splits (same slot); the combine-sum over virtual experts adds
        # the partial wdown outputs — i.e. the TP psum.
        dispatch = jnp.repeat(dispatch, tp, axis=2)
        combine = jnp.repeat(combine, tp, axis=2)

    # dispatch is a 0/1 routing tensor: its cotangent is useless (the
    # router learns through `combine`), and killing it removes one
    # activation-sized all-reduce per layer per microbatch (§Perf F2a).
    dsp = jax.lax.stop_gradient(dispatch).astype(x.dtype)
    # combine in param dtype: its (G,T,E,C) cotangent is psum'd across
    # the expert shards every layer; f32 doubles those bytes (F2c).
    combine = combine.astype(x.dtype)
    from repro.models.layers import constrain_spec
    expert_in = jnp.einsum("gtec,gtd->gecd", dsp, xt)     # (G,EV,C,D)
    # Pin expert buffers to EP sharding (e -> "model"); without this
    # the partitioner contracted over a model-sharded d and emitted
    # f32 all-reduces of the (G,EV,C,F) hidden per layer (§Perf F2b).
    expert_in = constrain_spec(expert_in, "DP", "model", "U", "U")
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", expert_in,
                               params["wg"])) * \
        jnp.einsum("gecd,edf->gecf", expert_in, params["wi"])
    h = constrain_spec(h, "DP", "model", "U", "U")
    expert_out = jnp.einsum("gecf,efd->gecd", h, params["wdown"])
    y = jnp.einsum("gtec,gecd->gtd", combine, expert_out)
    y = y.reshape(n_groups * gs, d)
    if n_groups * gs < tokens.shape[0]:
        y = jnp.concatenate(
            [y, jnp.zeros((tokens.shape[0] - n_groups * gs, d),
                          y.dtype)], axis=0)
    return y.reshape(b, s, d), aux


def grouped_matmul(x: jnp.ndarray, w: jnp.ndarray, groups: jnp.ndarray
                   ) -> jnp.ndarray:
    """x (M, K) rows sorted by group, w (G, K, N), groups (G,) int32
    row counts -> (M, N): each group's rows times its matrix; rows past
    the groups' total are left undefined.

    On a TPU the megablox Pallas kernel (ops named `gmm` in the trace),
    which visits only the (row tile, group) pairs that hold rows: a
    decode step's 128 rows read each expert once.  XLA's `ragged_dot`
    took 2.9 ms an op there for a chunk's 512 rows (TPU v5e, Mixtral's
    widths), whose expert weights read in 1.15 ms at the HBM peak.
    Elsewhere `jax.lax.ragged_dot`, the same contract."""
    return jax.lax.platform_dependent(x, w, groups, tpu=_gmm,
                                      default=jax.lax.ragged_dot)


def _gmm(x, w, groups, interpret: bool = False):
    """The TPU branch of `grouped_matmul`: rows padded to whole tiles."""
    m, tm = x.shape[0], GMM_TILE[0]
    xp = jnp.pad(x, ((0, -m % tm), (0, 0)))
    tiling = (tm, min(GMM_TILE[1], x.shape[1]), min(GMM_TILE[2], w.shape[2]))
    return gmm(xp, w, groups, preferred_element_type=x.dtype, tiling=tiling,
               interpret=interpret)[:m]


def moe_dropless(params: Params, x: jnp.ndarray, k: int, layer, *,
                 live: Optional[jnp.ndarray] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dropless top-k experts.  x (N, D) -> (y (N, D), load (E,) int32:
    the (token, expert) pairs each expert computed).

        w, top = route(x)              (k experts, weights sum to 1)
        y      = sum_{e in top} w_e * (silu(x Wg_e) * (x Wi_e)) Wdown_e

    The N*k pairs are sorted by expert and each expert's rows run
    through `grouped_matmul`, so the work is N*k rows, not N*E.
    Rows where `live` (N,) is False (padding, idle slots) are routed to
    no expert and give 0.

    `params` holds this layer's router (D, E) and every layer's expert
    weights, stacked (L, EV, ...); this layer's groups sit at `layer`
    (a traced index) * EV of the stack, so the grouped matmuls read it
    in place, where a slice of it taken inside a layer scan is copied
    out first (a whole layer's experts per layer).  Virtual experts
    (tp > 1, `moe_init`) are inferred from the stack's expert count; a
    pair visits every slice of its expert.
    """
    n, d = x.shape
    router = params["router"]
    e = router.shape[-1]
    ev = params["wi"].shape[1]
    wi, wg, wd = (params[w].reshape((-1,) + params[w].shape[2:])
                  for w in ("wi", "wg", "wdown"))
    tp = ev // e
    with jax.named_scope("moe"):
        w, idx, _ = route(router, x, k)                   # (N, k)
        if tp > 1:
            idx = (idx[..., None] * tp + jnp.arange(tp)).reshape(n, -1)
            w = jnp.repeat(w, tp, axis=-1)
        kk = idx.shape[1]
        valid = jnp.ones((n, kk), bool) if live is None else \
            jnp.broadcast_to(live[:, None], (n, kk))
        flat = jnp.where(valid, idx, ev).reshape(-1)      # dead: no group
        order = jnp.argsort(flat, stable=True)            # by expert
        sizes = jnp.sum(flat[:, None] == jnp.arange(ev), axis=0,
                        dtype=jnp.int32)
        groups = jax.lax.dynamic_update_slice(
            jnp.zeros(wi.shape[0], jnp.int32), sizes, (layer * ev,))
        xs = x[order // kk]
        h = jax.nn.silu(grouped_matmul(xs, wg, groups)) * \
            grouped_matmul(xs, wi, groups)
        out = grouped_matmul(h.astype(x.dtype), wd, groups)
        out = jnp.zeros_like(out).at[order].set(out).reshape(n, kk, d)
        out = jnp.where(valid[..., None], out.astype(jnp.float32), 0.0)
        # the weighted sum elementwise in float32 (a dot would round
        # the weights to bf16 on a TPU)
        y = jnp.sum(w[..., None] * out, axis=1)
        load = sizes.reshape(e, tp)[:, 0] if tp > 1 else sizes
    return y.astype(x.dtype), load
