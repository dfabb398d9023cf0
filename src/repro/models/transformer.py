"""Model assembly for all assigned families.

Families and their layer stacks (all scan-over-layers with stacked
params, remat-wrapped when cfg.remat):

  dense / audio   L x [norm, GQA-attn, norm, SwiGLU]
  moe             L x [norm, GQA-attn, norm, top-k MoE]
                  (training: GShard dispatch; serving: dropless)
  ssm             L x [norm, mamba1]
  hybrid (zamba2) G groups x [E mamba2 layers, shared attn block] + tail
                  — ONE shared attention block's weights reused by all
                  groups (an AGAS single-object/many-refs pattern), with
                  a per-group output adapter.
  vlm             G groups x [(k-1) self layers, 1 gated cross-attn
                  layer over stub patch embeddings]

Entry points:
  init_params(key, cfg, tp)                 -> params
  forward(params, batch, cfg, mode)         -> (hidden, aux)
  loss_fn(params, batch, cfg)               -> scalar (chunked-CE)
  init_cache(cfg, batch, cache_len)         -> cache pytree
  decode_step(params, cache, batch, cfg)    -> (logits, cache)
  init_paged_cache(cfg, n_rows, page_size)  -> {"k","v"} page arrays
  decode_step_paged(params, pages, batch, cfg) -> (logits, pages)
                                            (per-slot position clocks
                                            over AGAS block tables,
                                            DESIGN.md §4a)
  decode_step_paged_load(...)               -> (logits, pages, load)
                                            (the same step, also
                                            returning the tokens each
                                            expert of each layer
                                            computed: (L, E) int32, or
                                            None for dense families)
  prefill_chunk(params, pages, batch, cfg)  -> (logits, pages)
  prefill_chunk_load(...)                   -> (logits, pages, load)
                                            (resumable chunked prefill:
                                            one page-aligned chunk of a
                                            prompt attends the pages of
                                            earlier chunks and extends
                                            the paged cache, §4b;
                                            all_hidden=True returns the
                                            chunk's post-norm hidden
                                            states instead of logits —
                                            the activation checkpoints
                                            compute skip stores, §4e)
  resume_prefill(params, hidden)            -> logits
                                            (prefix-cache compute skip,
                                            §4e: first-token logits
                                            from a cached last-position
                                            activation checkpoint — a
                                            fully-covered prompt runs
                                            no transformer pass at all)

`batch` is a dict: tokens (B,S) int32; labels (B,S) for train;
patch_embeds (B,Nimg,Df) for vlm; frame_embeds (B,S,D) for audio;
cache_len () int32 for decode.  The modality frontends are STUBS per
the task statement: input_specs() (launch/dryrun.py) fabricates the
precomputed embeddings.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention as att
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.config import ArchConfig
from repro.models.layers import (Params, _init_dense, constrain_spec,
                                 cross_entropy_chunked, embed_init,
                                 embed_lookup, rmsnorm, rmsnorm_init,
                                 swiglu, swiglu_init)


import os

# Megatron-style sequence parallelism for the residual stream.
# MEASURED AND REFUTED on this partitioner (EXPERIMENTS.md §Perf, F4):
# instead of folding the TP psums into reduce-scatter/all-gather pairs,
# GSPMD reshards around every attention/MoE boundary — command-r train
# collective seconds went 30 -> 104.  Kept as an opt-in flag for
# documentation; default OFF.
_SEQ_SHARD_RESIDUAL = os.environ.get(
    "REPRO_SEQ_SHARD_RESIDUAL", "0") not in ("0", "false")


def _cres(x):
    """Pin the residual stream: batch on dp, seq optionally sharded
    over "model" (F4), D replicated.

    Stops the SPMD partitioner from speculatively resharding (B, S, D)
    activations onto "model" between blocks, which showed up as paired
    all-gather+all-reduce of activation tensors in every layer.  The
    batch dim must be pinned too — left unconstrained, the partitioner
    answered the D-replication constraint by all-gathering the batch
    (EXPERIMENTS.md §Perf, fix F1).
    """
    seq = "model" if _SEQ_SHARD_RESIDUAL else "U"
    return constrain_spec(x, "DP", seq, None)


# ---------------------------------------------------------------------------
# Per-layer init
# ---------------------------------------------------------------------------

def _dense_layer_init(key, cfg: ArchConfig) -> Params:
    k1, k2 = jax.random.split(key)
    p = {
        "attn_norm": rmsnorm_init(cfg.d_model, jnp.dtype(cfg.dtype)),
        "attn": att.attn_init(k1, cfg),
        "mlp_norm": rmsnorm_init(cfg.d_model, jnp.dtype(cfg.dtype)),
    }
    p["mlp"] = swiglu_init(k2, cfg.d_model, cfg.d_ff, jnp.dtype(cfg.dtype))
    return p


def _moe_layer_init(key, cfg: ArchConfig, tp: int) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "attn_norm": rmsnorm_init(cfg.d_model, jnp.dtype(cfg.dtype)),
        "attn": att.attn_init(k1, cfg),
        "mlp_norm": rmsnorm_init(cfg.d_model, jnp.dtype(cfg.dtype)),
        "moe": moe_mod.moe_init(k2, cfg, tp),
    }


def _ssm_layer_init(key, cfg: ArchConfig) -> Params:
    init = ssm_mod.mamba1_init if cfg.mamba_version == 1 \
        else ssm_mod.mamba2_init
    return {
        "norm": rmsnorm_init(cfg.d_model, jnp.dtype(cfg.dtype)),
        "ssm": init(key, cfg),
    }


def _shared_attn_init(key, cfg: ArchConfig) -> Params:
    """zamba2 shared block: attends over concat(x, x0) (width 2d)."""
    wide = dataclasses.replace(
        cfg, head_dim=2 * cfg.d_model // cfg.n_heads)
    k1, k2 = jax.random.split(key)
    return {
        "norm": rmsnorm_init(2 * cfg.d_model, jnp.dtype(cfg.dtype)),
        "attn": att.attn_init(k1, wide, d_in=2 * cfg.d_model),
        "mlp_norm": rmsnorm_init(cfg.d_model, jnp.dtype(cfg.dtype)),
        "mlp": swiglu_init(k2, cfg.d_model, cfg.d_ff,
                           jnp.dtype(cfg.dtype)),
    }


def _cross_layer_init(key, cfg: ArchConfig) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "attn_norm": rmsnorm_init(cfg.d_model, jnp.dtype(cfg.dtype)),
        "attn": att.attn_init(k1, cfg),
        "gate": jnp.zeros((), jnp.float32),     # gated residual, init 0
        "mlp_norm": rmsnorm_init(cfg.d_model, jnp.dtype(cfg.dtype)),
        "mlp": swiglu_init(k2, cfg.d_model, cfg.d_ff,
                           jnp.dtype(cfg.dtype)),
    }


@partial(jax.jit, donate_argnums=0)
def _put_layer(stack: Params, layer: Params, idx) -> Params:
    return jax.tree.map(lambda s, l: s.at[tuple(idx)].set(l), stack,
                        layer)


def _stack_init(key, dims: Tuple[int, ...], fn) -> Params:
    """Layer params stacked over `dims` (one axis, or two for grouped
    stacks).  Each layer is drawn from the key `vmap` over
    ``random.split(key, dims[0])`` (split again per group) would hand
    it, with the same eager ops, so the values are bit-identical to
    that whole-stack draw.  Layers are drawn one at a time into a
    preallocated stack, so the largest temporary is one layer's
    float32 draw, never the whole stack's (a 32-layer d_ff stack in
    float32 alone would not fit a 16 GB chip)."""
    keys = jax.random.split(key, dims[0])
    if len(dims) == 2:
        keys = jax.vmap(lambda k: jax.random.split(k, dims[1]))(keys)
    stack = None
    for idx in np.ndindex(*dims):
        layer = fn(keys[idx])
        if stack is None:
            stack = jax.tree.map(
                lambda a: jnp.zeros(dims + a.shape, a.dtype), layer)
        stack = _put_layer(stack, layer, jnp.asarray(idx, jnp.int32))
    return stack


def init_params(key, cfg: ArchConfig, tp: int = 1) -> Params:
    ks = jax.random.split(key, 8)
    dt = jnp.dtype(cfg.dtype)
    params: Params = {
        "embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model, dt),
        "final_norm": rmsnorm_init(cfg.d_model, dt),
    }
    if not cfg.tie_embeddings:
        params["out_embed"] = embed_init(ks[1], cfg.vocab_size,
                                         cfg.d_model, dt)
    fam = cfg.family
    if fam in ("dense", "audio"):
        params["layers"] = _stack_init(
            ks[2], (cfg.n_layers,), lambda k: _dense_layer_init(k, cfg))
    elif fam == "moe":
        params["layers"] = _stack_init(
            ks[2], (cfg.n_layers,), lambda k: _moe_layer_init(k, cfg, tp))
    elif fam == "ssm":
        params["layers"] = _stack_init(
            ks[2], (cfg.n_layers,), lambda k: _ssm_layer_init(k, cfg))
    elif fam == "hybrid":
        every = cfg.shared_attn_every
        n_groups = cfg.n_layers // every
        tail = cfg.n_layers - n_groups * every
        params["groups"] = _stack_init(
            ks[2], (n_groups, every), lambda k: _ssm_layer_init(k, cfg))
        params["shared_attn"] = _shared_attn_init(ks[3], cfg)
        params["adapters"] = _stack_init(
            ks[4], (n_groups,),
            lambda k: {"w": _init_dense(k, cfg.d_model, cfg.d_model, dt)
                       * 0.1})
        if tail:
            params["tail"] = _stack_init(
                ks[5], (tail,), lambda k: _ssm_layer_init(k, cfg))
    elif fam == "vlm":
        every = cfg.cross_attn_every
        n_groups = cfg.n_layers // every
        params["groups_self"] = _stack_init(
            ks[2], (n_groups, every - 1),
            lambda k: _dense_layer_init(k, cfg))
        params["groups_cross"] = _stack_init(
            ks[3], (n_groups,), lambda k: _cross_layer_init(k, cfg))
        params["patch_proj"] = {
            "w": _init_dense(ks[4], _frontend_dim(cfg), cfg.d_model, dt)}
    else:
        raise ValueError(f"unknown family {fam}")
    return params


def _frontend_dim(cfg: ArchConfig) -> int:
    return 1280 if cfg.d_model >= 1024 else 32


# ---------------------------------------------------------------------------
# Layer bodies (shared by forward and decode)
# ---------------------------------------------------------------------------

def _attn_block(lp: Params, x, cfg: ArchConfig, cos, sin, *,
                use_pallas=False, kv_override=None, causal=True):
    h = rmsnorm(lp["attn_norm"], x, cfg.norm_eps)
    q, k, v = att.qkv(lp["attn"], h, cfg)
    if kv_override is None:
        q = att.apply_rope(q, cos, sin, cfg.rope_fraction)
        k = att.apply_rope(k, cos, sin, cfg.rope_fraction)
        o = att.attention(q, k, v, cfg, causal=causal,
                          use_pallas=use_pallas)
    else:
        # cross-attention: keys/values from the frontend embeddings
        kx, vx = kv_override
        o = att.attention(q, kx, vx, cfg, causal=False,
                          use_pallas=use_pallas)
    b, s, _, _ = o.shape
    return o.reshape(b, s, -1) @ lp["attn"]["wo"], (k, v)


def _cross_kv(lp: Params, embeds, cfg: ArchConfig):
    b, n, _ = embeds.shape
    k = (embeds @ lp["attn"]["wk"]).reshape(b, n, cfg.n_kv_heads,
                                            cfg.head_dim)
    v = (embeds @ lp["attn"]["wv"]).reshape(b, n, cfg.n_kv_heads,
                                            cfg.head_dim)
    return k, v


def _mlp_block(lp: Params, x, cfg: ArchConfig):
    return swiglu(lp["mlp"], rmsnorm(lp["mlp_norm"], x, cfg.norm_eps))


def _ffn(params: Params, cfg: ArchConfig):
    """(the layer stack to scan, ffn(lp, x, live=None) -> (update,
    load)): the SwiGLU block, load None; for the moe family the
    dropless expert layer, load (E,) int32, with `live` (B, S) marking
    the rows that are tokens.  The expert weights of every layer stay
    out of the scanned stack, which carries each layer's index instead:
    the grouped matmuls read them in place, where a slice of them
    inside the scan is copied out first (a whole layer's experts, 2.8
    GB at Mixtral's widths, every layer)."""
    if cfg.family != "moe":
        return params["layers"], \
            lambda lp, x, live=None: (_mlp_block(lp, x, cfg), None)
    moe = params["layers"]["moe"]
    experts = {k: moe[k] for k in ("wi", "wg", "wdown")}
    layers = dict(params["layers"], moe={
        "router": moe["router"],
        "layer": jnp.arange(cfg.n_layers, dtype=jnp.int32)})

    def ffn(lp, x, live=None):
        h = rmsnorm(lp["mlp_norm"], x, cfg.norm_eps)
        y, load = moe_mod.moe_dropless(
            dict(experts, router=lp["moe"]["router"]),
            h.reshape(-1, h.shape[-1]), cfg.top_k, lp["moe"]["layer"],
            live=None if live is None else live.reshape(-1))
        return y.reshape(x.shape), load
    return layers, ffn


def _shared_attn_apply(sp: Params, adapter: Params, x, x0,
                       cfg: ArchConfig, positions, use_pallas=False):
    wide = dataclasses.replace(
        cfg, head_dim=2 * cfg.d_model // cfg.n_heads)
    rot = max(int(wide.head_dim * cfg.rope_fraction), 2)
    cos, sin = att.rope_angles(positions, rot, cfg.rope_theta)
    xx = jnp.concatenate([x, x0], axis=-1)
    h = rmsnorm(sp["norm"], xx, cfg.norm_eps)
    q, k, v = att.qkv(sp["attn"], h, wide)
    q = att.apply_rope(q, cos, sin, cfg.rope_fraction)
    k = att.apply_rope(k, cos, sin, cfg.rope_fraction)
    o = att.attention(q, k, v, wide, causal=True, use_pallas=use_pallas)
    b, s, _, _ = o.shape
    o = o.reshape(b, s, -1) @ sp["attn"]["wo"]
    x = x + o @ adapter["w"]
    x = x + swiglu(sp["mlp"], rmsnorm(sp["mlp_norm"], x, cfg.norm_eps))
    return x, (k, v)


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def _maybe_remat(fn, cfg: ArchConfig):
    return jax.checkpoint(fn) if cfg.remat else fn


def forward(params: Params, batch: Dict[str, Any], cfg: ArchConfig,
            mode: str = "train", use_pallas: bool = False,
            tp: int = 1) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward.  Returns (hidden (B,S,D), aux_loss)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = embed_lookup(params["embed"], tokens)
    if cfg.family == "audio" and "frame_embeds" in batch:
        x = x + batch["frame_embeds"].astype(x.dtype)
    pos = jnp.arange(s)
    rot = int(cfg.head_dim * cfg.rope_fraction) if cfg.n_heads else 0
    cos, sin = att.rope_angles(pos, max(rot, 2), cfg.rope_theta)
    aux = jnp.float32(0.0)
    fam = cfg.family

    if fam in ("dense", "audio"):
        def layer(x, lp):
            o, _ = _attn_block(lp, x, cfg, cos, sin,
                               use_pallas=use_pallas)
            x = _cres(x + o)
            x = _cres(x + _mlp_block(lp, x, cfg))
            return x, None

        G = cfg.remat_group_size
        if G > 1 and cfg.n_layers % G == 0:
            # F5: checkpoint k-layer groups — the backward saves one
            # residual per GROUP (stack memory / k) and re-runs the
            # inner k-layer scan during the group's backward.
            grouped = jax.tree.map(
                lambda p: p.reshape((cfg.n_layers // G, G)
                                    + p.shape[1:]), params["layers"])

            def group(x, gp):
                # nested remat: the group's backward replays layer by
                # layer with only one inner residual live at a time
                x, _ = jax.lax.scan(_maybe_remat(layer, cfg), x, gp)
                return x, None

            x, _ = jax.lax.scan(_maybe_remat(group, cfg), x, grouped)
        else:
            x, _ = jax.lax.scan(_maybe_remat(layer, cfg), x,
                                params["layers"])
    elif fam == "moe":
        def layer(carry, lp):
            x, aux = carry
            o, _ = _attn_block(lp, x, cfg, cos, sin,
                               use_pallas=use_pallas)
            x = _cres(x + o)
            h = rmsnorm(lp["mlp_norm"], x, cfg.norm_eps)
            mo, a = moe_mod.moe_apply(lp["moe"], h, cfg, tp)
            return (_cres(x + mo), aux + a), None
        (x, aux), _ = jax.lax.scan(_maybe_remat(layer, cfg), (x, aux),
                                   params["layers"])
    elif fam == "ssm":
        ssm_mode = "chunked" if mode != "ref" else "ref"
        def layer(x, lp):
            h = rmsnorm(lp["norm"], x, cfg.norm_eps)
            y, _ = ssm_mod.ssm_block_apply(lp["ssm"], h, cfg,
                                           mode=ssm_mode)
            return _cres(x + y), None
        x, _ = jax.lax.scan(_maybe_remat(layer, cfg), x,
                            params["layers"])
    elif fam == "hybrid":
        x0 = x
        ssm_mode = "chunked" if mode != "ref" else "ref"

        def mamba_layer(x, lp):
            h = rmsnorm(lp["norm"], x, cfg.norm_eps)
            y, _ = ssm_mod.ssm_block_apply(lp["ssm"], h, cfg,
                                           mode=ssm_mode)
            return x + y, None

        sp = params["shared_attn"]

        def group(x, g):
            gp, ad = g
            x, _ = jax.lax.scan(mamba_layer, x, gp)
            x, _ = _shared_attn_apply(sp, ad, x, x0, cfg, pos,
                                      use_pallas=use_pallas)
            return x, None

        x, _ = jax.lax.scan(_maybe_remat(group, cfg), x,
                            (params["groups"], params["adapters"]))
        if "tail" in params:
            x, _ = jax.lax.scan(_maybe_remat(mamba_layer, cfg), x,
                                params["tail"])
    elif fam == "vlm":
        pe = batch["patch_embeds"].astype(x.dtype)
        pe = pe @ params["patch_proj"]["w"]

        def self_layer(x, lp):
            o, _ = _attn_block(lp, x, cfg, cos, sin,
                               use_pallas=use_pallas)
            x = _cres(x + o)
            return _cres(x + _mlp_block(lp, x, cfg)), None

        def group(x, g):
            sl, cl = g
            # nested remat (F5): without it the group backward holds
            # every inner self-layer's internals live at once
            x, _ = jax.lax.scan(_maybe_remat(self_layer, cfg), x, sl)
            kx, vx = _cross_kv(cl, pe, cfg)
            h = rmsnorm(cl["attn_norm"], x, cfg.norm_eps)
            q = (h @ cl["attn"]["wq"]).reshape(
                x.shape[0], x.shape[1], cfg.n_heads, cfg.head_dim)
            o = att.attention(q, kx, vx, cfg, causal=False,
                              use_pallas=use_pallas)
            o = o.reshape(x.shape[0], x.shape[1], -1) @ cl["attn"]["wo"]
            x = _cres(x + jnp.tanh(cl["gate"]).astype(x.dtype) * o)
            return _cres(x + _mlp_block(cl, x, cfg)), None

        x, _ = jax.lax.scan(
            _maybe_remat(group, cfg), x,
            (params["groups_self"], params["groups_cross"]))
    else:
        raise ValueError(fam)

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, aux


def loss_fn(params: Params, batch: Dict[str, Any], cfg: ArchConfig,
            use_pallas: bool = False, tp: int = 1) -> jnp.ndarray:
    x, aux = forward(params, batch, cfg, "train", use_pallas, tp)
    out_w = params.get("out_embed", params["embed"])["embedding"]
    ce = cross_entropy_chunked(x, out_w, batch["labels"],
                               cfg.loss_chunk)
    return ce + 0.01 * aux


def logits_fn(params: Params, hidden: jnp.ndarray) -> jnp.ndarray:
    out_w = params.get("out_embed", params["embed"])["embedding"]
    return (hidden @ out_w.T.astype(hidden.dtype)).astype(jnp.float32)


def prefill(params: Params, batch: Dict[str, Any], cfg: ArchConfig,
            use_pallas: bool = False, tp: int = 1,
            full_kv: bool = False, last_index=None,
            all_hidden: bool = False
            ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Full-sequence forward that also builds the decode cache.

    Returns (last-position hidden (B, D), cache).  SWA archs keep only
    the trailing `window` keys (ring reset so the cursor wraps onto the
    oldest slot) unless `full_kv` — the paged cache keeps every
    position and enforces the window as an absolute-position mask
    instead of by trimming.  `last_index` (a traced int32) selects
    which position's hidden state is returned instead of the final
    one — used by right-padded prefills, where the real sequence ends
    before the padded buffer does, without recompiling per length.
    `all_hidden=True` returns the full post-norm hidden (B, S, D)
    instead (`last_index` ignored) — callers index it themselves and
    checkpoint page-boundary positions for compute skip (§4e).

    Layout contract: the paged/chunked engines run PAD-FREE — real
    tokens occupy positions 0..R-1 and any padding is RIGHT-padding
    in the compute buffer only (junk positions are causally masked
    from the real ones and never attached to the KV cache), so the
    same prompt produces the same per-position KV regardless of which
    bucket it compiled into.  That position normalization is what the
    §4e prefix keys hash over; only the dense engine left-pads (its
    single shared clock needs aligned ends), which is why its caches
    never interoperate with the paged prefix index.
    """
    tokens = batch["tokens"]
    b, s = tokens.shape
    x = embed_lookup(params["embed"], tokens)
    if cfg.family == "audio" and "frame_embeds" in batch:
        x = x + batch["frame_embeds"].astype(x.dtype)
    pos = jnp.arange(s)
    rot = int(cfg.head_dim * cfg.rope_fraction) if cfg.n_heads else 0
    cos, sin = att.rope_angles(pos, max(rot, 2), cfg.rope_theta)
    fam = cfg.family
    win = cfg.sliding_window
    eff = min(s, win) if win else s

    def trim(k):   # keep trailing window for SWA ring buffers
        return k[..., -eff:, :, :] if (win and not full_kv) else k

    # len = valid cache slots; cursor = next ring write slot (slot 0 is
    # the oldest after a trim); abs = absolute next position (RoPE
    # phase continuity for ring-buffer SWA caches where len < abs).
    cache: Dict[str, Any] = {
        "len": jnp.asarray(eff, jnp.int32),
        "cursor": jnp.asarray(0 if win else s, jnp.int32),
        "abs": jnp.asarray(s, jnp.int32),
    }

    if fam in ("dense", "audio", "moe"):
        layers, ffn = _ffn(params, cfg)

        def layer(x, lp):
            o, (k, v) = _attn_block(lp, x, cfg, cos, sin,
                                    use_pallas=use_pallas)
            x = x + o
            return x + ffn(lp, x)[0], (trim(k), trim(v))
        x, (ks, vs) = jax.lax.scan(_maybe_remat(layer, cfg), x, layers)
        cache["k"], cache["v"] = ks, vs
    elif fam == "ssm":
        def layer(x, lp):
            h = rmsnorm(lp["norm"], x, cfg.norm_eps)
            y, st = ssm_mod.ssm_block_apply(lp["ssm"], h, cfg,
                                            mode="chunked")
            return x + y, (st["ssm"], st["conv"])
        x, (hs, cs) = jax.lax.scan(_maybe_remat(layer, cfg), x,
                                   params["layers"])
        cache["ssm"], cache["conv"] = hs, cs
    elif fam == "hybrid":
        x0 = x
        sp = params["shared_attn"]

        def mamba_layer(x, lp):
            h = rmsnorm(lp["norm"], x, cfg.norm_eps)
            y, st = ssm_mod.ssm_block_apply(lp["ssm"], h, cfg,
                                            mode="chunked")
            return x + y, (st["ssm"], st["conv"])

        def group(x, g):
            gp, ad = g
            x, (hs, cs) = jax.lax.scan(mamba_layer, x, gp)
            x, (k, v) = _shared_attn_apply(sp, ad, x, x0, cfg, pos,
                                           use_pallas=use_pallas)
            return x, (hs, cs, trim(k), trim(v))

        x, (hs, cs, ks, vs) = jax.lax.scan(
            _maybe_remat(group, cfg), x,
            (params["groups"], params["adapters"]))
        cache.update(ssm=hs, conv=cs, k=ks, v=vs)
        if "tail" in params:
            x, (th, tc) = jax.lax.scan(_maybe_remat(mamba_layer, cfg),
                                       x, params["tail"])
            cache["tail_ssm"], cache["tail_conv"] = th, tc
    elif fam == "vlm":
        pe = batch["patch_embeds"].astype(x.dtype)
        pe = pe @ params["patch_proj"]["w"]

        def self_layer(x, lp):
            o, (k, v) = _attn_block(lp, x, cfg, cos, sin,
                                    use_pallas=use_pallas)
            x = x + o
            return x + _mlp_block(lp, x, cfg), (trim(k), trim(v))

        def group(x, g):
            sl, cl = g
            x, (k, v) = jax.lax.scan(self_layer, x, sl)
            kx, vx = _cross_kv(cl, pe, cfg)
            h = rmsnorm(cl["attn_norm"], x, cfg.norm_eps)
            q = (h @ cl["attn"]["wq"]).reshape(
                x.shape[0], x.shape[1], cfg.n_heads, cfg.head_dim)
            o = att.attention(q, kx, vx, cfg, causal=False,
                              use_pallas=use_pallas)
            o = o.reshape(x.shape[0], x.shape[1], -1) @ cl["attn"]["wo"]
            x = x + jnp.tanh(cl["gate"]).astype(x.dtype) * o
            return x + _mlp_block(cl, x, cfg), (k, v)

        x, (ks, vs) = jax.lax.scan(
            _maybe_remat(group, cfg), x,
            (params["groups_self"], params["groups_cross"]))
        cache["k"], cache["v"] = ks, vs
    else:
        raise ValueError(fam)

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if all_hidden:
        return x, cache
    if last_index is None:
        return x[:, -1], cache
    out = jax.lax.dynamic_index_in_dim(x, last_index, axis=1,
                                       keepdims=False)
    return out, cache


# ---------------------------------------------------------------------------
# KV / state caches and decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch_size: int, cache_len: int,
               dtype=None) -> Dict[str, Any]:
    """Allocate the decode cache.  Sliding-window archs cap the cache
    at their window (the sub-quadratic property)."""
    dt = dtype or jnp.dtype(cfg.dtype)
    eff = min(cache_len, cfg.sliding_window) if cfg.sliding_window \
        else cache_len
    cache: Dict[str, Any] = {"len": jnp.zeros((), jnp.int32),
                             "cursor": jnp.zeros((), jnp.int32),
                             "abs": jnp.zeros((), jnp.int32)}
    kvshape = (cfg.n_layers, batch_size, eff, cfg.n_kv_heads,
               cfg.head_dim)
    fam = cfg.family
    if fam in ("dense", "audio", "moe", "vlm"):
        n_attn = cfg.n_layers
        if fam == "vlm":
            n_attn = cfg.n_layers - cfg.n_layers // cfg.cross_attn_every
            kvshape = (cfg.n_layers // cfg.cross_attn_every,
                       cfg.cross_attn_every - 1, batch_size, eff,
                       cfg.n_kv_heads, cfg.head_dim)
        else:
            kvshape = (n_attn, batch_size, eff, cfg.n_kv_heads,
                       cfg.head_dim)
        cache["k"] = jnp.zeros(kvshape, dt)
        cache["v"] = jnp.zeros(kvshape, dt)
    if fam == "ssm":
        cache["ssm"] = jnp.zeros(
            (cfg.n_layers, batch_size, cfg.d_inner, cfg.ssm_state),
            jnp.float32)
        cache["conv"] = jnp.zeros(
            (cfg.n_layers, batch_size, cfg.ssm_conv - 1, cfg.d_inner),
            dt)
    if fam == "hybrid":
        every = cfg.shared_attn_every
        n_groups = cfg.n_layers // every
        tail = cfg.n_layers - n_groups * every
        nh = cfg.d_inner // cfg.ssm_head_dim
        cache["ssm"] = jnp.zeros(
            (n_groups, every, batch_size, nh, cfg.ssm_head_dim,
             cfg.ssm_state), jnp.float32)
        cache["conv"] = jnp.zeros(
            (n_groups, every, batch_size, cfg.ssm_conv - 1,
             cfg.d_inner + 2 * cfg.ssm_state), dt)
        if tail:
            cache["tail_ssm"] = jnp.zeros(
                (tail, batch_size, nh, cfg.ssm_head_dim, cfg.ssm_state),
                jnp.float32)
            cache["tail_conv"] = jnp.zeros(
                (tail, batch_size, cfg.ssm_conv - 1,
                 cfg.d_inner + 2 * cfg.ssm_state), dt)
        wide_hd = 2 * cfg.d_model // cfg.n_heads
        cache["k"] = jnp.zeros(
            (n_groups, batch_size, eff, cfg.n_kv_heads, wide_hd), dt)
        cache["v"] = jnp.zeros(
            (n_groups, batch_size, eff, cfg.n_kv_heads, wide_hd), dt)
    return cache


def _decode_attn(lp, x, cfg, cos, sin, k_c, v_c, cache_len, pos):
    """One-token attention against (and update of) one layer's cache."""
    h = rmsnorm(lp["attn_norm"] if "attn_norm" in lp else lp["norm"],
                x, cfg.norm_eps)
    q, k, v = att.qkv(lp["attn"], h, cfg)
    q = att.apply_rope(q, cos, sin, cfg.rope_fraction)
    k = att.apply_rope(k, cos, sin, cfg.rope_fraction)
    k_c = jax.lax.dynamic_update_slice_in_dim(k_c, k, pos, axis=1)
    v_c = jax.lax.dynamic_update_slice_in_dim(v_c, v, pos, axis=1)
    o = att.decode_attention(q, k_c, v_c, cache_len + 1, cfg)
    b = x.shape[0]
    return o.reshape(b, 1, -1) @ lp["attn"]["wo"], k_c, v_c


def decode_step(params: Params, cache: Dict[str, Any],
                batch: Dict[str, Any], cfg: ArchConfig,
                tp: int = 1) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """One decode step for the whole batch.

    batch: tokens (B, 1).  Returns (logits (B, V) f32, new cache).
    For sliding-window caches the write position wraps (ring buffer).
    """
    tokens = batch["tokens"]
    b = tokens.shape[0]
    x = embed_lookup(params["embed"], tokens)
    cache_len = cache["len"]
    pos_abs = cache["abs"]                    # absolute position (RoPE)
    eff = cache["k"].shape[-3] if "k" in cache else 0
    # SWA caches are ring buffers of size `window`: the write cursor
    # wraps; masking is by valid-slot count (order-free softmax).
    pos_write = (cache["cursor"] % jnp.int32(eff)) \
        if (eff and cfg.sliding_window > 0) else cache["cursor"]
    rot = int(cfg.head_dim * cfg.rope_fraction) if cfg.n_heads else 2
    cos, sin = att.rope_angles(pos_abs[None], max(rot, 2),
                               cfg.rope_theta)
    aux_len = jnp.minimum(cache_len, eff - 1) if eff else cache_len
    fam = cfg.family

    if fam in ("dense", "audio", "moe"):
        layers, ffn = _ffn(params, cfg)

        def layer(x, lkv):
            lp, k_c, v_c = lkv
            o, k_c, v_c = _decode_attn(lp, x, cfg, cos, sin, k_c, v_c,
                                       aux_len, pos_write)
            x = x + o
            return x + ffn(lp, x)[0], (k_c, v_c)
        x, (k_new, v_new) = jax.lax.scan(
            layer, x, (layers, cache["k"], cache["v"]))
        cache = dict(cache, k=k_new, v=v_new)
    elif fam == "ssm":
        def layer(x, lst):
            lp, h0, c0 = lst
            h = rmsnorm(lp["norm"], x, cfg.norm_eps)
            y, st = ssm_mod.ssm_block_apply(
                lp["ssm"], h, cfg, mode="decode",
                state={"ssm": h0, "conv": c0})
            return x + y, (st["ssm"], st["conv"])
        x, (ssm_new, conv_new) = jax.lax.scan(
            layer, x, (params["layers"], cache["ssm"], cache["conv"]))
        cache = dict(cache, ssm=ssm_new, conv=conv_new)
    elif fam == "hybrid":
        x0 = x
        sp = params["shared_attn"]
        wide = dataclasses.replace(
            cfg, head_dim=2 * cfg.d_model // cfg.n_heads)
        rot_w = max(int(wide.head_dim * cfg.rope_fraction), 2)
        cos_w, sin_w = att.rope_angles(pos_abs[None], rot_w,
                                       cfg.rope_theta)

        def mamba_layer(x, lst):
            lp, h0, c0 = lst
            h = rmsnorm(lp["norm"], x, cfg.norm_eps)
            y, st = ssm_mod.ssm_block_apply(
                lp["ssm"], h, cfg, mode="decode",
                state={"ssm": h0, "conv": c0})
            return x + y, (st["ssm"], st["conv"])

        def group(x, g):
            gp, ad, h0, c0, k_c, v_c = g
            x, (h_new, c_new) = jax.lax.scan(mamba_layer, x,
                                             (gp, h0, c0))
            xx = jnp.concatenate([x, x0], axis=-1)
            hh = rmsnorm(sp["norm"], xx, cfg.norm_eps)
            q, k, v = att.qkv(sp["attn"], hh, wide)
            q = att.apply_rope(q, cos_w, sin_w, cfg.rope_fraction)
            k = att.apply_rope(k, cos_w, sin_w, cfg.rope_fraction)
            k_c = jax.lax.dynamic_update_slice_in_dim(
                k_c, k, pos_write, axis=1)
            v_c = jax.lax.dynamic_update_slice_in_dim(
                v_c, v, pos_write, axis=1)
            o = att.decode_attention(q, k_c, v_c, aux_len + 1, wide)
            o = o.reshape(b, 1, -1) @ sp["attn"]["wo"]
            x = x + o @ ad["w"]
            x = x + swiglu(sp["mlp"],
                           rmsnorm(sp["mlp_norm"], x, cfg.norm_eps))
            return x, (h_new, c_new, k_c, v_c)

        x, (ssm_new, conv_new, k_new, v_new) = jax.lax.scan(
            group, x,
            (params["groups"], params["adapters"], cache["ssm"],
             cache["conv"], cache["k"], cache["v"]))
        cache = dict(cache, ssm=ssm_new, conv=conv_new, k=k_new,
                     v=v_new)
        if "tail" in params:
            x, (th, tc) = jax.lax.scan(
                mamba_layer, x,
                (params["tail"], cache["tail_ssm"], cache["tail_conv"]))
            cache = dict(cache, tail_ssm=th, tail_conv=tc)
    elif fam == "vlm":
        pe = batch["patch_embeds"].astype(x.dtype)
        pe = pe @ params["patch_proj"]["w"]

        def self_layer(x, lkv):
            lp, k_c, v_c = lkv
            o, k_c, v_c = _decode_attn(lp, x, cfg, cos, sin, k_c, v_c,
                                       aux_len, pos_write)
            x = x + o
            return x + _mlp_block(lp, x, cfg), (k_c, v_c)

        def group(x, g):
            sl, cl, k_c, v_c = g
            x, (k_n, v_n) = jax.lax.scan(self_layer, x,
                                         (sl, k_c, v_c))
            kx, vx = _cross_kv(cl, pe, cfg)
            h = rmsnorm(cl["attn_norm"], x, cfg.norm_eps)
            q = (h @ cl["attn"]["wq"]).reshape(b, 1, cfg.n_heads,
                                               cfg.head_dim)
            o = att.attention(q, kx, vx, cfg, causal=False)
            o = o.reshape(b, 1, -1) @ cl["attn"]["wo"]
            x = x + jnp.tanh(cl["gate"]).astype(x.dtype) * o
            return x + _mlp_block(cl, x, cfg), (k_n, v_n)

        x, (k_new, v_new) = jax.lax.scan(
            group, x,
            (params["groups_self"], params["groups_cross"],
             cache["k"], cache["v"]))
        cache = dict(cache, k=k_new, v=v_new)
    else:
        raise ValueError(fam)

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = logits_fn(params, x[:, 0])
    cache = dict(cache, len=cache["len"] + 1,
                 cursor=cache["cursor"] + 1, abs=cache["abs"] + 1)
    return logits, cache


# ---------------------------------------------------------------------------
# Paged KV cache decode (serving/kvcache.py block tables)
# ---------------------------------------------------------------------------

PAGED_FAMILIES = ("dense", "audio", "moe")


def init_paged_cache(cfg: ArchConfig, n_rows: int, page_size: int,
                     dtype=None, n_shards: int = 1) -> Dict[str, Any]:
    """Allocate the page-pool KV arrays.

    Single locality (``n_shards == 1``): (L, n_rows, ps, KV, D), where
    `n_rows` counts physical rows (the pool passes capacity + 1 so the
    last row can serve as the null page idle slots write into).

    Sharded pool (``n_shards > 1``, DESIGN.md §4c): one AGAS locality
    per KV shard — (L, n_shards, n_rows, ps, KV, D) with `n_rows` rows
    PER SHARD (pages_per_shard + 1; each shard carries its own local
    null page so an idle write never crosses localities).  Axis 1 is
    the locality axis the serving mesh shards over "kv".
    """
    if cfg.family not in PAGED_FAMILIES:
        raise ValueError(
            f"paged decode supports {PAGED_FAMILIES}, not {cfg.family!r}")
    dt = dtype or jnp.dtype(cfg.dtype)
    if n_shards > 1:
        shape = (cfg.n_layers, n_shards, n_rows, page_size,
                 cfg.n_kv_heads, cfg.head_dim)
    else:
        shape = (cfg.n_layers, n_rows, page_size, cfg.n_kv_heads,
                 cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def paged_decode_attention(pages: jnp.ndarray):
    """The paged decode attention for a pool: the Pallas kernel on a
    TPU when the pool lives on one device and its head dim is whole
    128-lane rows, the jnp oracle everywhere else.  A pool partitioned
    over a mesh keeps the oracle, whose gather GSPMD lowers to
    collectives; a custom call would not partition."""
    from repro.kernels.attention.paged import decode_fits
    if jax.default_backend() == "tpu" and decode_fits(pages) and \
            jax.typeof(pages).sharding.mesh.size <= 1:
        from repro.kernels.attention.ops import paged_attention
        return paged_attention
    from repro.kernels.attention.ref import paged_attention_ref
    return paged_attention_ref


def decode_step_paged(params: Params, pages: Dict[str, Any],
                      batch: Dict[str, Any], cfg: ArchConfig,
                      tp: int = 1) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """`decode_step_paged_load` without the expert load."""
    return decode_step_paged_load(params, pages, batch, cfg, tp)[:2]


def decode_step_paged_load(params: Params, pages: Dict[str, Any],
                           batch: Dict[str, Any], cfg: ArchConfig,
                           tp: int = 1
                           ) -> Tuple[jnp.ndarray, Dict[str, Any],
                                      Optional[jnp.ndarray]]:
    """One decode step over block tables with per-slot position clocks.

    batch: tokens (B, 1); block_tables (B, P) int32 physical page
    rows; positions (B,) int32 per-slot absolute position of the new
    token (replaces the dense cache's shared len/cursor/abs clock);
    write_rows/write_offs (B,) int32 page slot the new K/V lands in
    (idle slots point at the pool's null row, which no mask ever
    reads).  Sliding windows are enforced as absolute-position masks —
    pages are never trimmed, so RoPE phases baked at write time stay
    valid.  Returns (logits (B, V) f32, new pages, load): for the moe
    family `load` (L, E) int32 counts the decoding slots each expert of
    each layer computed (slots writing the null row — idle, or prefill
    passengers — are routed to no expert); None for the others.

    Pages may be sharded across AGAS localities (DESIGN.md §4c):
    6-d ``pages["k"]`` of (L, n_shards, R, ps, KV, D) with block-table
    rows encoded ``locality * R + slot`` — the scatter and the gather
    both decode (locality, slot) so every page resolves on the shard
    that owns it.
    """
    if cfg.family not in PAGED_FAMILIES:
        raise ValueError(
            f"paged decode supports {PAGED_FAMILIES}, not {cfg.family!r}")
    paged_attention = paged_decode_attention(pages["k"])
    tokens = batch["tokens"]
    tables = batch["block_tables"]
    positions = batch["positions"]
    write_rows = batch["write_rows"]
    write_offs = batch["write_offs"]
    b = tokens.shape[0]
    x = embed_lookup(params["embed"], tokens)
    rot = int(cfg.head_dim * cfg.rope_fraction) if cfg.n_heads else 2
    # per-slot RoPE phases: (B, 1, rot/2) broadcasting over heads
    cos, sin = att.rope_angles(positions[:, None], max(rot, 2),
                               cfg.rope_theta)
    layers, ffn = _ffn(params, cfg)
    live = None
    if cfg.family == "moe":
        rows = pages["k"].shape[-4]          # a shard's null row: rows-1
        live = (write_rows % rows != rows - 1)[:, None]

    def layer(x, lkv):
        lp, kp, vp = lkv
        h = rmsnorm(lp["attn_norm"], x, cfg.norm_eps)
        q, k, v = att.qkv(lp["attn"], h, cfg)
        q = att.apply_rope(q, cos, sin, cfg.rope_fraction)
        k = att.apply_rope(k, cos, sin, cfg.rope_fraction)
        # scatter the new token's K/V into each slot's write page
        if kp.ndim == 5:                 # sharded: (S, R, ps, KV, D)
            rps = kp.shape[1]
            wloc, wslot = write_rows // rps, write_rows % rps
            kp = kp.at[wloc, wslot, write_offs].set(k[:, 0])
            vp = vp.at[wloc, wslot, write_offs].set(v[:, 0])
        else:
            kp = kp.at[write_rows, write_offs].set(k[:, 0])
            vp = vp.at[write_rows, write_offs].set(v[:, 0])
        o = paged_attention(q, kp, vp, tables, positions,
                            window=cfg.sliding_window)
        x = x + o.reshape(b, 1, -1) @ lp["attn"]["wo"]
        mo, load = ffn(lp, x, live)
        return x + mo, (kp, vp, load)

    x, (k_new, v_new, load) = jax.lax.scan(
        layer, x, (layers, pages["k"], pages["v"]))
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = logits_fn(params, x[:, 0])
    return logits, dict(pages, k=k_new, v=v_new), load


def prefill_chunk(params: Params, pages: Dict[str, Any],
                  batch: Dict[str, Any], cfg: ArchConfig,
                  tp: int = 1, use_pallas: bool = False,
                  all_hidden: bool = False
                  ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """`prefill_chunk_load` without the expert load."""
    return prefill_chunk_load(params, pages, batch, cfg, tp, use_pallas,
                              all_hidden)[:2]


def prefill_chunk_load(params: Params, pages: Dict[str, Any],
                       batch: Dict[str, Any], cfg: ArchConfig,
                       tp: int = 1, use_pallas: bool = False,
                       all_hidden: bool = False
                       ) -> Tuple[jnp.ndarray, Dict[str, Any],
                                  Optional[jnp.ndarray]]:
    """Resumable chunked prefill: one page-aligned chunk of a prompt
    consumes and extends the paged KV cache (DESIGN.md §4b).

    batch: tokens (B, C) chunk tokens, right-padded to the fixed chunk
    width; block_tables (B, P) int32 physical page rows (pages of
    earlier chunks plus this chunk's freshly acquired pages); start
    (B,) int32 absolute position of tokens[:, 0] — page-aligned, equal
    to the tokens already resident for the slot; chunk_rows (B, C/ps)
    int32 physical rows this chunk's K/V pages are scattered into, with
    the pool's null row substituted for prefix-shared pages (their
    content already exists and must not be rewritten) and for pages
    past a partial final chunk; last_index () int32 chunk-local index
    of the chunk's last token, whose hidden state feeds the returned
    logits (only a prompt's final chunk reads them).  Rows past it are
    right-padding, which the expert layer (moe family) routes to no
    expert.

    Query t attends key positions <= start + t: causal over every
    earlier chunk's pages and within the chunk itself — the chunk's
    K/V is scattered into its pages *before* the gather, so one paged
    attention covers both.  Junk K/V from right-padding lands inside
    the final partial page beyond the slot's clock; masks never read
    it, and the first decode write overwrites it (same invariant as
    the whole-prompt attach path).  Returns (logits (B, V) f32, new
    pages, load); with ``all_hidden=True`` the post-norm hidden
    (B, C, D) replaces the logits — callers index the last position
    themselves and checkpoint the chunk's page-boundary activations for
    compute skip (§4e).  `load` is as in `decode_step_paged_load`, over
    the chunk's tokens.
    """
    if cfg.family not in PAGED_FAMILIES:
        raise ValueError(
            f"paged prefill supports {PAGED_FAMILIES}, not {cfg.family!r}")
    if use_pallas:
        from repro.kernels.attention.ops import paged_prefill_attention
    else:
        from repro.kernels.attention.ref import \
            paged_prefill_attention_ref as paged_prefill_attention
    tokens = batch["tokens"]
    tables = batch["block_tables"]
    start = batch["start"]
    chunk_rows = batch["chunk_rows"]
    last_index = batch["last_index"]
    b, c = tokens.shape
    sharded = pages["k"].ndim == 6       # (L, S, R, ps, KV, D)
    ps = pages["k"].shape[3 if sharded else 2]
    assert c % ps == 0, f"chunk width {c} not page-aligned (ps={ps})"
    cp = c // ps
    x = embed_lookup(params["embed"], tokens)
    positions = start[:, None] + jnp.arange(c)[None, :]    # (B, C)
    rot = int(cfg.head_dim * cfg.rope_fraction) if cfg.n_heads else 2
    cos, sin = att.rope_angles(positions, max(rot, 2), cfg.rope_theta)
    layers, ffn = _ffn(params, cfg)
    live = None
    if cfg.family == "moe":
        live = jnp.broadcast_to(jnp.arange(c) <= last_index, (b, c))

    def layer(x, lkv):
        lp, kp, vp = lkv
        h = rmsnorm(lp["attn_norm"], x, cfg.norm_eps)
        q, k, v = att.qkv(lp["attn"], h, cfg)
        q = att.apply_rope(q, cos, sin, cfg.rope_fraction)
        k = att.apply_rope(k, cos, sin, cfg.rope_fraction)
        # scatter the chunk's K/V as whole pages (shared pages and the
        # tail of a partial chunk point at the null row)
        kw = k.reshape(b, cp, ps, *k.shape[2:]).astype(kp.dtype)
        vw = v.reshape(b, cp, ps, *v.shape[2:]).astype(vp.dtype)
        if sharded:
            rps = kp.shape[1]
            cloc, cslot = chunk_rows // rps, chunk_rows % rps
            kp = kp.at[cloc, cslot].set(kw)
            vp = vp.at[cloc, cslot].set(vw)
        else:
            kp = kp.at[chunk_rows].set(kw)
            vp = vp.at[chunk_rows].set(vw)
        o = paged_prefill_attention(q, kp, vp, tables, start,
                                    window=cfg.sliding_window)
        x = x + o.reshape(b, c, -1) @ lp["attn"]["wo"]
        mo, load = ffn(lp, x, live)
        return x + mo, (kp, vp, load)

    x, (k_new, v_new, load) = jax.lax.scan(
        layer, x, (layers, pages["k"], pages["v"]))
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    pages = dict(pages, k=k_new, v=v_new)
    if all_hidden:
        return x, pages, load
    out = jax.lax.dynamic_index_in_dim(x, last_index, axis=1,
                                       keepdims=False)
    return logits_fn(params, out), pages, load


def resume_prefill(params: Params, hidden: jnp.ndarray) -> jnp.ndarray:
    """First-token logits from a cached last-position activation
    checkpoint (prefix-cache compute skip, DESIGN.md §4e).

    ``hidden`` (B, D) is the post-final-norm hidden state of a
    prompt's last position, checkpointed by an earlier prefill of the
    identical pad-free token sequence and stored in the page pool's
    prefix index
    alongside the KV pages.  A fully-covered prompt needs no
    transformer pass at all: its KV is resident in shared pages, and
    this one vocab projection reproduces the logits its own prefill
    would have computed.  Partial covers need no checkpoint —
    `prefill_chunk` is resumable from any page-aligned position given
    only the prefix KV pages.
    """
    return logits_fn(params, hidden)
