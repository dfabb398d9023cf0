"""Kernels (the Pallas paged decode attention, ops named
`paged_decode`): least time of the traced decode steps' attention work,
each decoding slot's KV read once (bench.work.kv_bytes_per_token times
its tokens) and its scores and values (bench.work.attn_flops), times
the decode program's calls, over the device time of the decode
program's ops named `paged_decode`, %.  The work counts live tokens
only, so a kernel that reads whole blocks reads under 100%.  None where
no such op ran: the jnp oracle, which the program takes off a TPU."""

from __future__ import annotations

from typing import Optional

import numpy as np

from bench import work
from bench.metrics._common import DECODE_PROGRAM, program, traced_steps

KERNEL = "paged_decode"


def attention_work(cfg: dict, contexts) -> tuple:
    """(FLOPs, bytes) of one decode step's attention over `contexts`
    tokens a slot."""
    ctx = list(contexts)
    return (sum(work.attn_flops(cfg, c) for c in ctx),
            float(work.kv_bytes_per_token(cfg) * sum(ctx)))


def read(ctx) -> Optional[float]:
    p = program(ctx, DECODE_PROGRAM)
    if p is None or ctx.get("peaks") is None:
        return None
    dev = sum(v for k, v in ctx["trace"]["ops"].items()
              if k.startswith(f"{DECODE_PROGRAM}/{KERNEL}"))
    least = [work.least_time(*attention_work(ctx["cfg"], s["decode_ctx"]),
                             ctx["peaks"])[0]
             for s in traced_steps(ctx) if s["decode_ctx"]]
    if not dev or not least:
        return None
    return float(np.mean(least)) * p[0] / dev * 100.0
