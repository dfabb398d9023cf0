"""Operations and bytes a serving step needs, from the configuration and
the tokens counted, never from what an implementation does.

The per-token formulas follow the analytic cost model of the serving
stack (forward only: 2 FLOPs per matmul parameter per token; attention
scores and values 4 * context * heads * head_dim per token and layer),
copied here so that no program change can move the yardstick.  A
kernel that skips dead pages or the GQA widening is read against the
same work, so no share can pass 100%.
"""

from __future__ import annotations

from typing import Iterable, Tuple


def _itemsize(cfg: dict) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[cfg["dtype"]]


def embed_params(cfg: dict) -> int:
    return cfg["vocab_size"] * cfg["d_model"] * \
        (1 if cfg["tie_embeddings"] else 2)


def layer_matmul_params(cfg: dict) -> int:
    d, hd = cfg["d_model"], cfg["head_dim"]
    h, kv = cfg["n_heads"], cfg["n_kv_heads"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * cfg["d_ff"]


def param_count(cfg: dict) -> int:
    """Every stored parameter: embeddings, layers, norms."""
    d = cfg["d_model"]
    return embed_params(cfg) + cfg["n_layers"] * (
        layer_matmul_params(cfg) + 2 * d) + d


def weight_bytes(cfg: dict) -> int:
    return param_count(cfg) * _itemsize(cfg)


def step_weight_bytes(cfg: dict, rows: int) -> int:
    """Weight bytes one step must read to embed `rows` tokens: every
    stored parameter except the input embedding rows it does not look
    up (an untied table is read whole only by the output head)."""
    if cfg["tie_embeddings"]:
        return weight_bytes(cfg)
    skipped = (cfg["vocab_size"] - min(rows, cfg["vocab_size"])) * \
        cfg["d_model"]
    return weight_bytes(cfg) - skipped * _itemsize(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    return 2 * cfg["n_layers"] * cfg["n_kv_heads"] * cfg["head_dim"] * \
        _itemsize(cfg)


def matmul_flops_per_token(cfg: dict) -> float:
    return 2.0 * cfg["n_layers"] * layer_matmul_params(cfg)


def head_flops(cfg: dict) -> float:
    """The output projection of one position."""
    return 2.0 * cfg["vocab_size"] * cfg["d_model"]


def attn_flops(cfg: dict, keys: float) -> float:
    """Scores and values of one query over `keys` keys, all layers."""
    return 4.0 * keys * cfg["n_heads"] * cfg["head_dim"] * cfg["n_layers"]


def decode_work(cfg: dict, contexts: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode step whose decoding slots hold
    `contexts` tokens each, the new one included: every weight read
    once (of the input embedding, the slots' rows), each slot's KV read
    once and its new K/V written."""
    ctx = list(contexts)
    flops = sum(matmul_flops_per_token(cfg) + head_flops(cfg)
                + attn_flops(cfg, c) for c in ctx)
    nbytes = step_weight_bytes(cfg, len(ctx)) + \
        kv_bytes_per_token(cfg) * sum(ctx)
    return flops, float(nbytes)


def prefill_work(cfg: dict, start: int, take: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one prefill chunk of `take` tokens at absolute
    positions start..start+take-1: query t attends start + t + 1 keys;
    one position's logits; weights read once (of the input embedding,
    the chunk's rows), the context's KV read once and the chunk's K/V
    written."""
    keys = take * start + take * (take + 1) / 2.0
    flops = matmul_flops_per_token(cfg) * take + head_flops(cfg) + \
        attn_flops(cfg, keys)
    nbytes = step_weight_bytes(cfg, take) + \
        kv_bytes_per_token(cfg) * (start + take)
    return float(flops), float(nbytes)


def model_flops_decode(cfg: dict, context: int) -> float:
    """Model FLOPs of one decoded token at `context` keys (for MFU)."""
    return matmul_flops_per_token(cfg) + head_flops(cfg) + \
        attn_flops(cfg, context)


def model_flops_prefill(cfg: dict, start: int, take: int) -> float:
    """Model FLOPs of `take` prompt tokens from position `start`: every
    position's layers, and one position's logits."""
    return prefill_work(cfg, start, take)[0]


def least_time(flops: float, nbytes: float, peaks: dict) -> Tuple[float, str]:
    """Least seconds the chip needs, and which peak bounds it."""
    t_c = flops / peaks["flops_bf16"]
    t_m = nbytes / peaks["hbm_bytes_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
