"""Operations and bytes of a serving step of a mixture-of-experts
decoder (family "moe"), from the configuration and the counted tokens
and experts, never from what an implementation does.  The dense
counterpart is `bench.work`, whose KV, attention and head terms this
shares.

Forward only, 2 FLOPs per matmul parameter per token: attention
projections and the router for every token, and k experts' SwiGLU per
token (never all E).  Bytes: every weight outside the experts read once
(of the input embedding, the rows looked up), each expert that computed
any token read once (`moe_experts_hit`, counted by the program), and
the KV read and written.
"""

from __future__ import annotations

from typing import Iterable, Tuple

from bench import work

ROUTER_ITEMSIZE = 4          # the router's weights are float32


def attn_params(cfg: dict) -> int:
    d, hd = cfg["d_model"], cfg["head_dim"]
    h, kv = cfg["n_heads"], cfg["n_kv_heads"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d


def expert_bytes(cfg: dict) -> int:
    """One expert's SwiGLU weights (Wg, Wi, Wdown)."""
    return 3 * cfg["d_model"] * cfg["d_ff"] * work._itemsize(cfg)


def nonexpert_bytes(cfg: dict, rows: int) -> int:
    """Weights outside the experts one step reads to embed `rows`
    tokens: attention, router and norms of every layer, the final norm,
    the output head, and the input embedding's rows looked up (all of
    it where it is tied to the head)."""
    d, it = cfg["d_model"], work._itemsize(cfg)
    layer = (attn_params(cfg) + 2 * d) * it + \
        d * cfg["n_experts"] * ROUTER_ITEMSIZE
    table = cfg["vocab_size"] * d * it
    embed = table if cfg["tie_embeddings"] else \
        table + min(rows, cfg["vocab_size"]) * d * it
    return cfg["n_layers"] * layer + d * it + embed


def weight_bytes(cfg: dict) -> int:
    """Every stored weight."""
    return nonexpert_bytes(cfg, cfg["vocab_size"]) + \
        cfg["n_layers"] * cfg["n_experts"] * expert_bytes(cfg)


def token_flops(cfg: dict) -> float:
    """Matmul FLOPs of one token through every layer: attention
    projections, router and k experts."""
    d = cfg["d_model"]
    layer = attn_params(cfg) + d * cfg["n_experts"] + \
        cfg["top_k"] * 3 * d * cfg["d_ff"]
    return 2.0 * cfg["n_layers"] * layer


def decode_work(cfg: dict, contexts: Iterable[int], experts_hit: float
                ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode step whose decoding slots hold
    `contexts` tokens each (the new one included) and whose experts
    computed for `experts_hit` (expert, layer) pairs."""
    ctx = list(contexts)
    flops = sum(token_flops(cfg) + work.head_flops(cfg)
                + work.attn_flops(cfg, c) for c in ctx)
    nbytes = nonexpert_bytes(cfg, len(ctx)) + \
        experts_hit * expert_bytes(cfg) + \
        work.kv_bytes_per_token(cfg) * sum(ctx)
    return float(flops), float(nbytes)


def prefill_work(cfg: dict, start: int, take: int, experts_hit: float
                 ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one prefill chunk of `take` tokens at positions
    start..start+take-1 (query t attends start + t + 1 keys), one
    position's logits, `experts_hit` (expert, layer) pairs read."""
    keys = take * start + take * (take + 1) / 2.0
    flops = token_flops(cfg) * take + work.head_flops(cfg) + \
        work.attn_flops(cfg, keys)
    nbytes = nonexpert_bytes(cfg, take) + \
        experts_hit * expert_bytes(cfg) + \
        work.kv_bytes_per_token(cfg) * (start + take)
    return float(flops), float(nbytes)


def gmm_work(cfg: dict, routed: float, experts_hit: float
             ) -> Tuple[float, float]:
    """(FLOPs, bytes) of one program's grouped matmuls over all layers:
    `routed` (token, expert) pairs through Wg, Wi and Wdown, each expert
    hit read once, each pair's rows read and written once (x in; the
    two up projections out and the hidden back in; the output)."""
    d, ff = cfg["d_model"], cfg["d_ff"]
    flops = routed * 6.0 * d * ff
    nbytes = experts_hit * expert_bytes(cfg) + \
        routed * (2 * d + 3 * ff) * work._itemsize(cfg)
    return float(flops), float(nbytes)
