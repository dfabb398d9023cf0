"""Plain float32 reference of a dense decoder with grouped-query
attention, RMSNorm, SwiGLU and (partial) rotary embeddings.

It imports nothing of the program.  It reads the weights the benchmark
made (bench/weights.py) in their stored precision and computes in
float32 at `Precision.HIGHEST`, one layer per call and attention in
blocks of queries, so that it fits beside the weights on one chip.

Equations (per layer, x the residual stream):
    h = rmsnorm(x) * attn_norm
    q, k, v = h Wq, h Wk, h Wv;  rotary on the first rope_fraction of
        each head's dims, pairs (2i, 2i+1), theta ** (-2i / rot)
    x += softmax(q k^T / sqrt(head_dim), causal) v Wo   (head h reads
        KV head h // (n_heads / n_kv_heads))
    x += (silu(rmsnorm(x) * mlp_norm Wg) * (rmsnorm(x) * mlp_norm Wi)) Wdown
logits = rmsnorm(x) * final_norm  out_embed^T.

`quant="fp8"` is the control: every matmul's weight (per output column)
and input (per row) scaled into the range of 8-bit floats of 4 exponent
and 3 mantissa bits and rounded to 3 mantissa bits before a float32
product.  The rounding works on the bits of the float32 values, which no
compiler may fold away as excess precision, as it may a round trip
through a float8 type.
`quant="bf16"` rounds every matmul's input and the residual stream to
bfloat16 instead: the configuration's own precision, a witness of how
far that precision alone moves the logits (bench.diagnose).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256                 # queries per attention block
FP8_MAX = 240.0               # largest finite e4m3 value with IEEE inf


def _round_mantissa(a, bits: int):
    """Round float32 `a` to `bits` mantissa bits, half away from zero,
    on its bit pattern."""
    drop = 23 - bits
    u = jax.lax.bitcast_convert_type(a, jnp.uint32)
    u = (u + jnp.uint32(1 << (drop - 1))) & jnp.uint32(
        (0xFFFFFFFF >> drop) << drop)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def _round_fp8(a, axis):
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale == 0, 1.0, scale)
    q = a / scale
    # e4m3 holds nothing below 2**-9: smaller values flush to zero
    q = jnp.where(jnp.abs(q) < 2.0 ** -9, 0.0, _round_mantissa(q, 3))
    return q * scale


def _round_bf16(a):
    return _round_mantissa(a, 7)


def _mm(x, w, quant):
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x = _round_fp8(x, -1)
        w = _round_fp8(w, 0)
    elif quant == "bf16":
        x = _round_bf16(x)
    return jnp.dot(x, w, precision=HIGHEST)


def _residual(x, quant):
    return _round_bf16(x) if quant == "bf16" else x


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, pos, frac, theta):
    """x (S, H, D); rotate pairs (2i, 2i+1) of the first rot dims."""
    d = x.shape[-1]
    rot = int(d * frac)
    rot -= rot % 2
    freqs = theta ** (-(jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = pos[:, None].astype(jnp.float32) * freqs          # (S, rot/2)
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    r = jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1)
    return jnp.concatenate([r.reshape(*x.shape[:-1], rot), x[..., rot:]],
                           -1)


def _attention(q, k, v, n_kv):
    """Causal attention; q (S, H, D), k/v (S, KV, D), in query blocks."""
    s, h, d = q.shape
    rep = h // n_kv
    qb = min(Q_BLOCK, s)
    qg = q.reshape(s // qb, qb, n_kv, rep, d)
    keys = jnp.arange(s)

    def block(args):
        i, qi = args
        sc = jnp.einsum("qgrd,kgd->grqk", qi, k, precision=HIGHEST)
        sc = sc / np.sqrt(d)
        qpos = i * qb + jnp.arange(qb)
        sc = jnp.where(keys[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (jnp.arange(s // qb), qg))
    return out.reshape(s, h * d)


@partial(jax.jit, static_argnames=("cfg", "quant"))
def _layer(x, layers, idx, *, cfg, quant):
    (n_heads, n_kv, hd, frac, theta, eps) = cfg
    lw = jax.tree.map(lambda a: a[idx], layers)
    s = x.shape[0]
    pos = jnp.arange(s)
    h = _rmsnorm(x, lw["attn_norm"]["scale"], eps)
    at = lw["attn"]
    q = _rope(_mm(h, at["wq"], quant).reshape(s, n_heads, hd), pos, frac,
              theta)
    k = _rope(_mm(h, at["wk"], quant).reshape(s, n_kv, hd), pos, frac,
              theta)
    v = _mm(h, at["wv"], quant).reshape(s, n_kv, hd)
    x = _residual(x + _mm(_attention(q, k, v, n_kv), at["wo"], quant),
                  quant)
    h = _rmsnorm(x, lw["mlp_norm"]["scale"], eps)
    m = lw["mlp"]
    g = _mm(h, m["wg"], quant)
    x = x + _mm(jax.nn.silu(g) * _mm(h, m["wi"], quant), m["wdown"], quant)
    return _residual(x, quant)


@jax.jit
def _embed(table, tokens):
    return jnp.take(table, tokens, axis=0).astype(jnp.float32)


@partial(jax.jit, static_argnames=("eps", "quant"))
def _logits(x, final_scale, out_embed, *, eps, quant):
    h = _rmsnorm(x, final_scale, eps)
    return _mm(h, out_embed.T, quant)


def _static(cfg: dict) -> tuple:
    return (cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"],
            float(cfg["rope_fraction"]), float(cfg["rope_theta"]),
            float(cfg["norm_eps"]))


def bucket(n: int) -> int:
    """Sequence lengths run padded to a power of two (at least one
    query block), so a handful of programs serve every request."""
    b = Q_BLOCK
    while b < n:
        b *= 2
    return b


def hidden(weights: dict, cfg: dict, tokens: np.ndarray,
           quant=None) -> jnp.ndarray:
    """Residual stream after the last layer, (bucket(S), d_model) f32.
    Right padding cannot reach earlier positions (causal)."""
    s = bucket(len(tokens))
    toks = np.zeros(s, np.int32)
    toks[:len(tokens)] = tokens
    x = _embed(weights["embed"]["embedding"], jnp.asarray(toks))
    st = _static(cfg)
    for i in range(cfg["n_layers"]):
        x = _layer(x, weights["layers"], jnp.int32(i), cfg=st, quant=quant)
    return x


def logits_at(weights: dict, cfg: dict, x, rows: np.ndarray,
              quant=None) -> jnp.ndarray:
    """Float32 logits at positions `rows` of a `hidden` result."""
    out = weights.get("out_embed", weights["embed"])["embedding"]
    r = np.zeros(bucket(len(rows)), np.int32)
    r[:len(rows)] = rows
    lg = _logits(x[jnp.asarray(r)], weights["final_norm"]["scale"], out,
                 eps=float(cfg["norm_eps"]), quant=quant)
    return lg[:len(rows)]
