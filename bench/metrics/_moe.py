"""Arithmetic shared by the readers of the moe family's cells: the
work of the traced steps from `bench.work_moe` and the expert loads the
engine counted (engine.counters `moe_*`, serving/engine.py MOE_KEYS).
Every reader returns None where the rows lack those counts."""

from __future__ import annotations

import bisect
from typing import Optional

import numpy as np

from bench import work, work_moe
from bench.metrics._common import (CHUNK_PROGRAM, DECODE_PROGRAM, program,
                                   traced_steps, window_steps)

HIT = "moe_experts_hit"
DECODE_HIT = "moe_decode_experts_hit"


def _rows_by_step(ctx, steps):
    """The counter row each step wrote (None where it wrote none)."""
    rows = [c for c in ctx["run"]["counters"] if DECODE_HIT in c]
    ts = [c["t"] for c in rows]
    out = []
    for s in steps:
        i = bisect.bisect_left(ts, s["t0"])
        out.append(rows[i] if i < len(rows) and ts[i] <= s["t1"] else None)
    return out


def roofline(ctx, name: str, kind: str) -> Optional[float]:
    """Least time of the traced steps' work over their device time, %:
    mean least time per call over mean device time per call of program
    `name`.  A decode step reads the experts its decode program hit; a
    step's chunks share the rest of its hits evenly."""
    p = program(ctx, name)
    if p is None or ctx.get("peaks") is None:
        return None
    cfg, peaks = ctx["cfg"], ctx["peaks"]
    steps = traced_steps(ctx)
    least = []
    for s, row in zip(steps, _rows_by_step(ctx, steps)):
        if row is None:
            continue
        if kind == "decode" and s["decode_ctx"]:
            least.append(work.least_time(*work_moe.decode_work(
                cfg, s["decode_ctx"], row[DECODE_HIT]), peaks)[0])
        elif kind == "prefill" and s["chunks"]:
            hit = (row[HIT] - row[DECODE_HIT]) / len(s["chunks"])
            least += [work.least_time(*work_moe.prefill_work(
                cfg, a, b, hit), peaks)[0] for a, b in s["chunks"]]
    if not least:
        return None
    return float(np.mean(least)) / (p[1] / p[0]) * 100.0


def gmm_roofline(ctx, kernel: str = "gmm") -> Optional[float]:
    """The grouped-matmul kernel's share of its roofline, %: per program
    (decode, chunk), the mean least time of the traced steps' grouped
    matmul work (bench.work_moe.gmm_work: routed pairs and experts hit,
    from the counters) times the program's calls, over the device time
    of the program's ops named `kernel`."""
    if not ctx.get("trace") or ctx.get("peaks") is None:
        return None
    cfg, peaks = ctx["cfg"], ctx["peaks"]
    kl = cfg["top_k"] * cfg["n_layers"]
    steps = traced_steps(ctx)
    least = {DECODE_PROGRAM: [], CHUNK_PROGRAM: []}
    for s, row in zip(steps, _rows_by_step(ctx, steps)):
        if row is None:
            continue
        dec = len(s["decode_ctx"]) * kl
        if dec:
            least[DECODE_PROGRAM].append(work.least_time(*work_moe.gmm_work(
                cfg, dec, row[DECODE_HIT]), peaks)[0])
        if s["chunks"]:
            n = len(s["chunks"])
            least[CHUNK_PROGRAM] += n * [work.least_time(*work_moe.gmm_work(
                cfg, (row["moe_routed"] - dec) / n,
                (row[HIT] - row[DECODE_HIT]) / n), peaks)[0]]
    total_least = total_dev = 0.0
    for prog, ls in least.items():
        p = program(ctx, prog)
        dev = sum(v for k, v in ctx["trace"]["ops"].items()
                  if k.startswith(prog + "/" + kernel))
        if p is None or not ls or not dev:
            continue
        total_least += float(np.mean(ls)) * p[0]
        total_dev += dev
    if not total_dev:
        return None
    return total_least / total_dev * 100.0


def mfu(ctx) -> Optional[float]:
    """Model FLOPs (k experts a token) of every prefill and decode token
    the steps inside the window served, over window x peak bf16
    FLOP/s, %."""
    if ctx.get("peaks") is None:
        return None
    cfg, run = ctx["cfg"], ctx["run"]
    flops = 0.0
    for s in window_steps(ctx):
        if s["decode_ctx"]:
            flops += work_moe.decode_work(cfg, s["decode_ctx"], 0)[0]
        flops += sum(work_moe.prefill_work(cfg, a, b, 0)[0]
                     for a, b in s["chunks"])
    if not flops:
        return None
    return flops / ((run["w1"] - run["w0"]) * ctx["peaks"]["flops_bf16"]) \
        * 100.0


def load_max_over_mean(ctx) -> Optional[float]:
    """Over the window's rows: the busiest expert's load summed over
    layers and programs, over the mean expert's (routed pairs / E)."""
    rows = [c for c in ctx["run"]["counters"] if "moe_load_max" in c]
    routed = sum(c["moe_routed"] for c in rows)
    if not routed:
        return None
    return sum(c["moe_load_max"] for c in rows) * \
        ctx["cfg"]["n_experts"] / routed
