"""The moe cell's benchmark parts on the CPU: the work arithmetic at
Mixtral's published widths, the four readers on a synthetic trace, and
`run_cell` end to end at a reduced float32 size, where the program
matches the reference and a planted fault does not."""

from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np
import pytest

from bench import harness, run as R, spec, work, work_moe
from bench.peaks import PEAKS
from bench.tests import _small

NAME = "mixtral-8x7b.chat"
PK = PEAKS["TPU v5 lite"]
READERS = ("moe_decode_roofline", "moe_prefill_roofline", "mfu_moe",
           "expert_load_max_over_mean", "gmm_roofline")


def test_work_at_published_widths():
    cfg = spec.config("mixtral-8x7b")
    # 4 x (2.818 GB of experts + 84 MB of attention) + 0.52 GB embeddings
    assert work_moe.weight_bytes(cfg) == pytest.approx(12.13e9, rel=1e-3)
    assert 8 * work_moe.expert_bytes(cfg) == pytest.approx(2.818e9,
                                                           rel=1e-3)
    assert work.kv_bytes_per_token(cfg) == 16384
    # a 64-slot decode step that hits every expert reads all weights
    # but the 64 - 32000 input embedding rows it does not look up
    f, b = work_moe.decode_work(cfg, [100] * 64, 32)
    skipped = (32000 - 64) * 4096 * 2
    assert b == work_moe.weight_bytes(cfg) - skipped + 6400 * 16384
    t, bound = work.least_time(f, b, PK)
    assert bound == "memory" and t == pytest.approx(14.6e-3, rel=0.01)
    # fewer experts hit, fewer bytes; FLOPs count k experts a token
    assert work_moe.decode_work(cfg, [100] * 64, 31)[1] == \
        b - work_moe.expert_bytes(cfg)
    d, ff = 4096, 14336
    per_tok = 2 * 4 * (work_moe.attn_params(cfg) + 8 * d + 2 * 3 * d * ff)
    assert work_moe.token_flops(cfg) == per_tok
    # a 256-token chunk: 0.81 TFLOP, about 4.1 ms at peak, bound by the
    # 12 GB read
    f, b = work_moe.prefill_work(cfg, 0, 256, 32)
    assert f == pytest.approx(0.81e12, rel=0.01)
    assert f / PK["flops_bf16"] == pytest.approx(4.1e-3, rel=0.02)
    assert work.least_time(f, b, PK)[1] == "memory"


def _ctx(counters=True):
    """A synthetic traced window: 10 steps of 50 decoding slots, every
    other one with a 200-token chunk, each step 40 ms on the device."""
    cfg = spec.config("mixtral-8x7b")
    steps, rows = [], []
    for i in range(10):
        t0 = 100.0 + 0.05 * i
        chunks = [(64, 200)] if i % 2 else []
        steps.append({"t0": t0, "t1": t0 + 0.045, "decode_ctx": [300] * 50,
                      "chunks": chunks})
        row = {"t": t0 + 0.04, "decode_tokens": 50}
        if counters:
            routed = (50 + sum(n for _, n in chunks)) * 2 * 4
            row.update(moe_routed=routed, moe_experts_hit=32 + 32 * len(chunks),
                       moe_load_max=4 * (18 + 60 * len(chunks)),
                       moe_decode_experts_hit=32)
        rows.append(row)
    trace = {"programs": {"jit__lambda": [10, 0.30],
                          "jit_chunk_fn": [5, 0.20]},
             "ops": {"jit__lambda/gmm.1 bf16[128,14336]": 0.12,
                     "jit__lambda/gmm.2 bf16[128,4096]": 0.06,
                     "jit_chunk_fn/gmm.1 bf16[512,14336]": 0.10,
                     "jit__lambda/fusion.3 bf16[64,4096]": 0.05}}
    run = {"steps": steps, "counters": rows, "w0": 100.0, "w1": 100.5,
           "trace_interval": (99.9, 100.6)}
    return {"cfg": cfg, "cell": {}, "peaks": PK, "run": run, "trace": trace}


def _read(name, ctx):
    return spec.load_module("metrics", name).read(ctx)


def test_readers_on_a_synthetic_trace():
    ctx = _ctx()
    vals = {n: _read(n, ctx) for n in READERS}
    assert all(v is not None and 0 < v <= 100 for v in vals.values()), vals
    cfg = ctx["cfg"]
    least = work.least_time(*work_moe.decode_work(cfg, [300] * 50, 32),
                            PK)[0]
    assert vals["moe_decode_roofline"] == pytest.approx(least / 0.030 * 100)
    least = work.least_time(*work_moe.prefill_work(cfg, 64, 200, 32),
                            PK)[0]
    assert vals["moe_prefill_roofline"] == pytest.approx(least / 0.040 * 100)
    flops = 10 * work_moe.decode_work(cfg, [300] * 50, 0)[0] + \
        5 * work_moe.prefill_work(cfg, 64, 200, 0)[0]
    assert vals["mfu_moe"] == pytest.approx(flops / (0.5 * 197e12) * 100)
    dec = work.least_time(*work_moe.gmm_work(cfg, 50 * 8, 32), PK)[0]
    chunk = work.least_time(*work_moe.gmm_work(cfg, 200 * 8, 32), PK)[0]
    assert vals["gmm_roofline"] == pytest.approx(
        (10 * dec + 5 * chunk) / 0.28 * 100)
    routed = sum(r["moe_routed"] for r in ctx["run"]["counters"])
    top = sum(r["moe_load_max"] for r in ctx["run"]["counters"])
    assert vals["expert_load_max_over_mean"] == pytest.approx(
        top * 8 / routed)


def test_readers_are_silent_without_the_counts():
    """A program that counts no expert load (the parent of this cell)
    gives the device readers nothing; MFU needs only served tokens."""
    ctx = _ctx(counters=False)
    for n in ("moe_decode_roofline", "moe_prefill_roofline",
              "expert_load_max_over_mean", "gmm_roofline"):
        assert _read(n, ctx) is None
    assert _read("mfu_moe", ctx) > 0
    assert _read("moe_decode_roofline", dict(_ctx(), trace=None)) is None


def _work_and_arch():
    """The cell cut to CPU size in float32, its configuration named as
    the reduced architecture (so that run_cell keeps every key)."""
    work_ = _small.small_work(NAME)
    arch = dataclasses.replace(_small.small_arch(work_["config"]),
                               dtype="float32")
    work_["config"] = dict(work_["config"], **{
        f: getattr(arch, f) for f in (
            "name", "n_layers", "d_model", "n_heads", "n_kv_heads",
            "head_dim", "d_ff", "n_experts", "top_k", "vocab_size",
            "dtype")})
    work_["cell"]["load"] = {"rate_per_s": 40.0}
    work_["cell"]["check"].update(min_requests=16, max_requests=16)
    return work_, arch


def _run(work_, arch):
    return R.run_cell(work_, name=NAME, seed=2 ** 31 + 3, seconds=2.0,
                      trace=False, t_start=time.perf_counter(), arch=arch,
                      n_pages=64, peaks=PK)


@pytest.mark.parametrize("fault", [None, "expert_negated", "top1"])
def test_run_cell_reads_the_program_against_the_reference(fault,
                                                          monkeypatch):
    work_, arch = _work_and_arch()
    if fault == "expert_negated":
        build = harness.build_engine

        def faulty(a, params, *rest, **kw):
            moe = params["layers"]["moe"]
            bad = dict(moe, wdown=moe["wdown"].at[:, 0].multiply(-1))
            layers = dict(params["layers"], moe=bad)
            return build(a, dict(params, layers=layers), *rest, **kw)
        monkeypatch.setattr(harness, "build_engine", faulty)
    elif fault == "top1":
        build = harness.build_engine

        def faulty(a, *rest, **kw):
            return build(dataclasses.replace(a, top_k=1), *rest, **kw)
        monkeypatch.setattr(harness, "build_engine", faulty)
    res = _run(work_, arch)
    off = res["check"]["off_share"]["value"]
    assert res["failed"] == 0 and res["compared"]["tokens"] >= 40
    if fault is None:
        assert res["correct"] and off == 0.0, res["check"]
    else:
        assert not res["correct"] and off > 0.1, res["check"]
