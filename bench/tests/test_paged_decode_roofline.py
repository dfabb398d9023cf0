"""`paged_decode_roofline` on a synthetic trace: the paged decode
kernel's live-token attention work over its ops' device time, and
silence where the kernel did not run."""

from __future__ import annotations

import pytest

from bench import spec
from bench.peaks import PEAKS

PK = PEAKS["TPU v5 lite"]


def _ctx(ops):
    """10 traced decode steps of 14 slots holding 300 tokens each."""
    steps = [{"t0": 100.0 + 0.05 * i, "t1": 100.045 + 0.05 * i,
              "decode_ctx": [300] * 14, "chunks": []} for i in range(10)]
    run = {"steps": steps, "counters": [], "w0": 100.0, "w1": 100.5,
           "trace_interval": (99.9, 100.6)}
    trace = {"programs": {"jit__lambda": [10, 0.50],
                          "jit_chunk_fn": [2, 0.07]},
             "ops": ops}
    return {"cfg": spec.config("yi-6b"), "cell": {}, "peaks": PK,
            "run": run, "trace": trace}


def _read(ctx):
    return spec.load_module("metrics", "paged_decode_roofline").read(ctx)


def test_kernel_work_over_its_device_time():
    ctx = _ctx({"jit__lambda/paged_decode.8 bf16[32,32,128]": 0.015,
                "jit__lambda/paged_decode.9 bf16[32,32,128]": 0.005,
                "jit__lambda/fusion.198 bf16[32,11008]": 0.2,
                "jit_chunk_fn/paged_decode.2 bf16[1,256,32,128]": 0.5})
    # 14 x 300 tokens at 64 KiB each (2 x 32 layers x 4 heads x 128 x
    # 2 bytes): 275 MB, 336 us at 819 GB/s; 2.2 GFLOP is 11 us at peak
    least = 14 * 300 * 65536 / 819e9
    assert _read(ctx) == pytest.approx(least * 10 / 0.020 * 100)
    assert 0 < _read(ctx) <= 100


@pytest.mark.parametrize("case", ["oracle", "untraced", "off_chip"])
def test_silent_without_the_kernel(case):
    ctx = _ctx({"jit__lambda/fusion.193 f32[32,1024,32]": 0.3})
    if case == "untraced":
        ctx = dict(_ctx({"jit__lambda/paged_decode.8 bf16[32,32,128]":
                         0.02}), trace=None)
    elif case == "off_chip":
        ctx = dict(_ctx({"jit__lambda/paged_decode.8 bf16[32,32,128]":
                         0.02}), peaks=None)
    assert _read(ctx) is None
