"""Arithmetic shared by the per-layer readers."""

from __future__ import annotations

from typing import Optional

import numpy as np

from bench import work

# Names of the program's jitted step programs as the trace shows them
# (HLO module names): the decode step is jitted from a lambda in
# PagedServingEngine, the chunk step from `chunk_fn` in
# ChunkedPagedServingEngine.
DECODE_PROGRAM = "jit__lambda"
CHUNK_PROGRAM = "jit_chunk_fn"


def program(ctx, name: str):
    """(calls, device seconds) of program `name` in the trace, or None."""
    tr = ctx.get("trace")
    if not tr:
        return None
    hit = [v for k, v in tr["programs"].items()
           if k == name or k.startswith(name + ".")]
    calls = sum(v[0] for v in hit)
    if not calls:
        return None
    return calls, sum(v[1] for v in hit)


def mean_ms(ctx, name: str) -> Optional[float]:
    p = program(ctx, name)
    return None if p is None else p[1] / p[0] * 1e3


def traced_steps(ctx):
    """Steps the harness ran inside the traced interval."""
    iv = ctx["run"].get("trace_interval")
    if not iv:
        return []
    return [s for s in ctx["run"]["steps"] if iv[0] <= s["t0"] and
            s["t1"] <= iv[1]]


def roofline(ctx, name: str, kind: str) -> Optional[float]:
    """Least time of the traced steps' work over their device time, %:
    mean least time per call (from bench.work) over mean device time per
    call of program `name`."""
    p = program(ctx, name)
    if p is None or ctx.get("peaks") is None:
        return None
    cfg, peaks = ctx["cfg"], ctx["peaks"]
    least = []
    for s in traced_steps(ctx):
        if kind == "decode" and s["decode_ctx"]:
            least.append(work.least_time(
                *work.decode_work(cfg, s["decode_ctx"]), peaks)[0])
        elif kind == "prefill":
            least += [work.least_time(*work.prefill_work(cfg, a, b),
                                      peaks)[0] for a, b in s["chunks"]]
    if not least:
        return None
    return float(np.mean(least)) / (p[1] / p[0]) * 100.0


def window_steps(ctx):
    run = ctx["run"]
    return [s for s in run["steps"] if run["w0"] <= s["t0"] and
            s["t1"] <= run["w1"]]


def mfu(ctx) -> Optional[float]:
    """Model FLOPs of every prefill and decode token the steps inside
    the window served, over window x peak bf16 FLOP/s, %."""
    if ctx.get("peaks") is None:
        return None
    cfg, run = ctx["cfg"], ctx["run"]
    flops = 0.0
    for s in window_steps(ctx):
        flops += sum(work.model_flops_decode(cfg, c) for c in s["decode_ctx"])
        flops += sum(work.model_flops_prefill(cfg, a, b)
                     for a, b in s["chunks"])
    if not flops:
        return None
    return flops / ((run["w1"] - run["w0"]) * ctx["peaks"]["flops_bf16"]) \
        * 100.0
