"""Trace reduction, checked on a small trace recorded on a TPU v5e
(bench/tests/record_fixture.py: yi-6b widths at 2 layers, 4 steps of
the chunked engine inside the benchmark's host annotations)."""

from __future__ import annotations

import os

import numpy as np
import pytest

from bench import spec, xplane

FIXTURE = os.path.join(spec.BENCH, "fixtures", "yi-6b-2layer.xplane.pb")
LABELS = ("engine.step", "submit", "wait_arrival")


@pytest.fixture(scope="module")
def pd():
    return xplane.load(FIXTURE)


@pytest.fixture(scope="module")
def summary(pd):
    return xplane.reduce(pd, LABELS,
                         window=xplane.host_window(pd, "trace_window"))


def test_window_and_busy_time_by_hand(pd, summary):
    """Busy time against a 10 ns timeline filled op by op: a different
    algorithm from the interval union."""
    lo, hi = xplane.host_window(pd, "trace_window")
    assert (hi - lo) * 1e-9 == pytest.approx(0.033785519)
    dev = [p for p in pd.planes if p.name == "/device:TPU:0"][0]
    ops = [(e.start_ns, e.start_ns + e.duration_ns) for line in dev.lines
           if line.name == "XLA Ops" for e in line.events]
    assert len(ops) == 1209
    busy = np.zeros(int((hi - lo) / 10) + 1, bool)
    for a, b in ops:
        busy[int((max(a, lo) - lo) // 10):
             int(np.ceil((min(b, hi) - lo) / 10))] = True
    assert summary["devices"] == 1
    assert summary["window_s"] == pytest.approx(0.033785519)
    assert summary["busy_s"] == pytest.approx(busy.sum() * 1e-8, abs=2e-6)
    assert summary["busy_s"] == pytest.approx(0.0110458, abs=1e-6)


def test_per_program_time(summary):
    progs = summary["programs"]
    assert progs["jit_chunk_fn"] == pytest.approx([4, 0.007362754])
    assert progs["jit__lambda"] == pytest.approx([2, 0.00366019])
    assert progs["jit__argmax"][0] == 5
    # leaf ops only: their sum is the busy time, less overlaps
    total_ops = sum(summary["ops"].values())
    assert total_ops == pytest.approx(summary["busy_s"], rel=0.05)
    assert not any("/while" in k for k in summary["ops"])


def test_readers_on_the_fixture(summary):
    ctx = {"trace": summary}
    ms = {m: spec.load_module("metrics", m).read(ctx) for m in (
        "decode_step_ms", "prefill_chunk_ms", "device_idle_share")}
    assert ms["decode_step_ms"] == pytest.approx(3.66019 / 2)
    assert ms["prefill_chunk_ms"] == pytest.approx(7.362754 / 4)
    assert ms["device_idle_share"] == pytest.approx(
        100 * (1 - 0.0110458 / 0.033785519), abs=0.01)


def test_breakdown(summary):
    b = xplane.breakdown(summary)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0].startswith(("jit_chunk_fn/", "jit__lambda/"))
    idle = sum(s for _, s in summary["idle"])
    assert idle == pytest.approx(summary["window_s"] - summary["busy_s"])
    labels = {k.split(" (")[0] for k, _ in b["idle_gaps"]}
    assert labels <= set(LABELS) | {"none"} and "engine.step" in labels


def test_union_and_clip():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 7)]
    assert xplane.clip([(0, 4), (5, 9)], 2, 6) == [(2, 4), (5, 6)]
    assert xplane.program_name("jit_chunk_fn(182030146482233004)") == \
        "jit_chunk_fn"
    assert xplane.op_name("%fusion.175 = bf16[32,32,128]{2,1,0:T(8,128)} "
                          "fusion(...)") == "fusion.175 bf16[32,32,128]"
    assert xplane.op_name("%while.4 = (s32[], bf16[1]) while(...)") == \
        "while.4 tuple"
