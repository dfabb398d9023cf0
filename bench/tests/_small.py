"""A cell of the benchmark cut to CPU test size: the configuration's
family at the program's reduced widths (served in bf16, as on the
chip), a 4-slot deployment and short prompts, driven through the same
functions as a chip run."""

from __future__ import annotations

import dataclasses
import time

from bench import run as R, spec, weights as W
from bench.peaks import PEAKS

# At this size a served token is off when the reference ranks it more
# than OFF_TOL below its best (bf16 rounding through 4 layers at d_model
# 64 moves a logit by at most 0.008 on the CPU, fp8 by up to 0.2).
OFF_TOL = 0.01
OFF_SHARE_LIMIT = 0.01


def small_work(name: str, bench=None) -> dict:
    bench = bench if bench is not None else spec.benchmark()
    work = spec.workload(name, bench)
    work["bench"] = bench
    cell = dict(work["cell"], slots=4, max_len=256, chunk_size=32,
                step_tokens=36, ramp_s=0.5)
    cell["check"] = dict(cell["check"], min_requests=3, max_requests=4,
                         min_served_tokens=40,
                         off_tol=OFF_TOL, off_share_limit=OFF_SHARE_LIMIT)
    mix = dict(work["traffic"])
    for k in ("prompt", "output", "document", "question", "user", "answer"):
        if k in mix:
            d = dict(mix[k], max=48, min=8)
            if "median" in d:
                d["median"] = 24
            mix[k] = d
    if "system_len" in mix:
        mix["system_len"] = 32
    if "session_rate_per_s" in cell["load"]:
        cell["load"] = {"session_rate_per_s": 4.0}
    work["cell"], work["traffic"] = cell, mix
    return work


def small_arch(cfg: dict):
    return dataclasses.replace(W.arch_config(cfg).reduced(),
                               dtype="bfloat16")


def run_small(name: str, *, seed: int = 2 ** 31 + 5, seconds: float = 1.5,
              trace: bool = False, work: dict = None) -> dict:
    work = work or small_work(name)
    return R.run_cell(work, name=name, seed=seed, seconds=seconds,
                      trace=trace, t_start=time.perf_counter(),
                      arch=small_arch(work["config"]), n_pages=64,
                      peaks=PEAKS["TPU v5 lite"])
