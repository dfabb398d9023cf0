"""tools/idle_by_span.py on a synthetic stand-in for ProfileData: one
device plane and one host plane, times in ns."""

import importlib.util
import os
from types import SimpleNamespace as NS

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tool():
    spec = importlib.util.spec_from_file_location(
        "idle_by_span", os.path.join(ROOT, "tools", "idle_by_span.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _profile():
    """Device ops at 0-100, 200-300, 500-600 and 900-1000; the host's
    step spans leave the gap 100-200 in engine.fetch inside
    engine.sample, 300-500 in engine.sample's own time, and 600-900
    outside any step (its middle, 750, follows the step's end)."""
    ops = [_ev("fusion.1 = bf16[4] fusion(x)", a, 100)
           for a in (0, 200, 500, 900)]
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit_step(1)", 0, 1000)]),
        NS(name="XLA Ops", events=ops)])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("trace_window", 0, 1000),
        _ev("engine.step", 0, 700),
        _ev("engine.decode_batch", 50, 600),
        _ev("engine.sample", 90, 500),
        _ev("engine.fetch", 100, 120),
        _ev("unrelated", 300, 400)])])
    return NS(planes=[device, host])


def test_gaps_take_the_innermost_step_span():
    summary = _tool().idle_by_span(_profile())
    assert summary["window_s"] == pytest.approx(1000e-9)
    assert summary["busy_s"] == pytest.approx(400e-9)
    got = dict(summary["idle_gaps"])
    assert got == pytest.approx({"engine.sample (1 gaps)": 200e-9,
                                 "none (1 gaps)": 300e-9,
                                 "engine.fetch (1 gaps)": 100e-9})
    assert [k for k, _ in summary["idle_gaps"]][0] == "none (1 gaps)"


def test_usage_without_a_path(capsys):
    assert _tool().main([]) == 2
    assert "idle_by_span.py" in capsys.readouterr().err
