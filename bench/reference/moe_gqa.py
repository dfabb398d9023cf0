"""Plain float32 reference of a decoder whose MLP is a mixture of
SwiGLU experts (Mixtral): grouped-query attention, RMSNorm, rotary
embeddings, and a softmax router that keeps the top k experts.

It imports nothing of the program; the attention, norm, rotary and
matmul pieces are the dense reference's (`dense_gqa`).  It reads the
weights the benchmark made (bench/weights.py) in their stored precision
and computes in float32 at `Precision.HIGHEST`, one layer per call and,
inside a layer, one expert at a time: a whole layer's experts in
float32 (5.6 GB at Mixtral's widths) would not fit beside the bf16
weights on one chip.

Equations (per layer, x the residual stream, E experts, top k):
    h = rmsnorm(x) * attn_norm
    q, k, v = h Wq, h Wk, h Wv;  rotary on the first rope_fraction of
        each head's dims, pairs (2i, 2i+1), theta ** (-2i / rot)
    x += softmax(q k^T / sqrt(head_dim), causal) v Wo   (head h reads
        KV head h // (n_heads / n_kv_heads))
    u = rmsnorm(x) * mlp_norm
    p = softmax(u Wr)                     over all E experts
    T = the k experts of largest p;  g_e = p_e / sum_{j in T} p_j
        for e in T, g_e = 0 otherwise
    x += sum_e g_e * (silu(u Wg_e) * (u Wi_e)) Wdown_e
        (every expert computed for every token; the unchosen ones
        weigh 0)
logits = rmsnorm(x) * final_norm  out_embed^T.

The number of experts is read from the router's width, k from the
configuration's `top_k`.  `quant` is the dense reference's: "fp8" the
control, "bf16" the configuration's own precision as a witness.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import dense_gqa as D


def _expert(h, moe, idx, e, quant):
    """Expert e of layer idx applied to every row of h."""
    def take(a):
        return jax.lax.dynamic_slice(
            a, (idx, e, 0, 0), (1, 1) + a.shape[2:])[0, 0]
    g = D._mm(h, take(moe["wg"]), quant)
    return D._mm(jax.nn.silu(g) * D._mm(h, take(moe["wi"]), quant),
                 take(moe["wdown"]), quant)


@partial(jax.jit, static_argnames=("cfg", "quant"))
def _layer(x, layers, idx, *, cfg, quant):
    (n_heads, n_kv, hd, frac, theta, eps, top_k) = cfg
    lw = jax.tree.map(lambda a: a[idx],
                      {k: v for k, v in layers.items() if k != "moe"})
    moe = layers["moe"]
    s = x.shape[0]
    pos = jnp.arange(s)
    h = D._rmsnorm(x, lw["attn_norm"]["scale"], eps)
    at = lw["attn"]
    q = D._rope(D._mm(h, at["wq"], quant).reshape(s, n_heads, hd), pos,
                frac, theta)
    k = D._rope(D._mm(h, at["wk"], quant).reshape(s, n_kv, hd), pos, frac,
                theta)
    v = D._mm(h, at["wv"], quant).reshape(s, n_kv, hd)
    x = D._residual(x + D._mm(D._attention(q, k, v, n_kv), at["wo"],
                              quant), quant)
    h = D._rmsnorm(x, lw["mlp_norm"]["scale"], eps)
    router = moe["router"][idx]
    n_exp = router.shape[-1]
    p = jax.nn.softmax(D._mm(h, router, quant), axis=-1)
    top_p, top = jax.lax.top_k(p, top_k)
    g = jnp.sum(jax.nn.one_hot(top, n_exp) * (
        top_p / jnp.sum(top_p, axis=-1, keepdims=True))[..., None], axis=1)

    def add(e, acc):
        return acc + g[:, e, None] * _expert(h, moe, idx, e, quant)
    y = jax.lax.fori_loop(0, n_exp, add, jnp.zeros_like(x))
    return D._residual(x + y, quant)


def _static(cfg: dict) -> tuple:
    return D._static(cfg) + (int(cfg["top_k"]),)


def hidden(weights: dict, cfg: dict, tokens: np.ndarray,
           quant=None) -> jnp.ndarray:
    """Residual stream after the last layer, (bucket(S), d_model) f32.
    Right padding cannot reach earlier positions (causal)."""
    s = D.bucket(len(tokens))
    toks = np.zeros(s, np.int32)
    toks[:len(tokens)] = tokens
    x = D._embed(weights["embed"]["embedding"], jnp.asarray(toks))
    st = _static(cfg)
    for i in range(cfg["n_layers"]):
        x = _layer(x, weights["layers"], jnp.int32(i), cfg=st, quant=quant)
    return x


logits_at = D.logits_at
