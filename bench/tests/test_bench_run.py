"""The harness end to end on the CPU at a reduced size, by calling its
functions directly: every traffic kind, the traced path with every
per-layer reader, a mix and a metric added by files alone, the control
and the planted faults that `correct` must catch."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, spec, weights as W
from bench.tests import _small

CELLS = [w["name"] for w in spec.benchmark()["workloads"]]


def test_run_exits_nonzero_off_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "yi-6b.chat",
         "--seed", str(2 ** 31 + 1), "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


@pytest.mark.parametrize("name", CELLS)
def test_cell_end_to_end(name):
    res = _small.run_small(name, seconds=2.0)
    assert res["correct"], res["check"]
    assert res["run"]["compiles_in_window"] == 0
    assert res["attempted"] > 0 and res["failed"] == 0
    want = {m["name"] for m in spec.metrics_for(name, "end_to_end")}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["check"]["off_share"]["limit"] == _small.OFF_SHARE_LIMIT


def test_traced_run_reads_every_host_metric():
    res = _small.run_small("yi-6b.chat", seconds=2.0, trace=True)
    assert res["correct"]
    m = res["metrics"]
    # off a chip the trace holds no device plane, so the device readers
    # find nothing and stay silent; the host counters are read
    for k in ("decode_occupancy", "queue_wait_p90_ms", "prefix_page_share",
              "mfu", "ttft_tail_p90_ms"):
        assert k in m, k
    for k in ("decode_step_ms", "decode_roofline", "device_idle_share"):
        assert k not in m
    assert 0 < m["decode_occupancy"]["value"] <= 100
    # every finished prompt was prefilled in chunks the steps recorded,
    # also those admitted and prefilled within one step
    run = res["run"]
    chunked = sum(n for s in run["steps"] for _, n in s["chunks"])
    assert chunked >= sum(len(run["prompts"][r]) for r in run["completions"])
    assert m["prefix_page_share"]["value"] == 0.0      # nothing shared


DUMMY_KIND = '''
import numpy as np
from bench.traffic._draw import Arrival, tokens


class Traffic:
    def __init__(self, mix, load, *, vocab, seed, horizon_s, max_len):
        rng = np.random.default_rng(seed)
        self._a = [Arrival(i, tokens(rng, mix["prompt_len"], vocab),
                           mix["output_len"], 0.05 * i) for i in range(60)]

    def due(self, now):
        out = [a for a in self._a if a.due <= now]
        self._a = [a for a in self._a if a.due > now]
        return out

    def next_due(self):
        return self._a[0].due if self._a else None

    def finished(self, rid, tokens_out, now):
        pass
'''
DUMMY_METRIC = '''
def read(ctx):
    return float(len(ctx["run"]["counters"]))
'''


def test_mix_and_metric_added_by_files_alone(tmp_path, monkeypatch):
    """A new traffic kind, mix, cell and per-layer metric are files and
    BENCHMARK.json entries only; no harness file changes."""
    root = tmp_path / "repo"
    bench_dir = root / "bench"
    for sub in ("configs", "cells", "traffic", "metrics", "reference"):
        (bench_dir / sub).mkdir(parents=True)
        for f in os.listdir(os.path.join(spec.BENCH, sub)):
            src = os.path.join(spec.BENCH, sub, f)
            if os.path.isfile(src):
                os.symlink(src, bench_dir / sub / f)
    (bench_dir / "traffic" / "steady.py").write_text(DUMMY_KIND)
    (bench_dir / "traffic" / "steady.json").write_text(json.dumps(
        {"kind": "steady", "prompt_len": 20, "output_len": 12}))
    (bench_dir / "metrics" / "steps_in_window.py").write_text(DUMMY_METRIC)
    cell = spec.cell_settings("yi-6b.chat")
    (bench_dir / "cells" / "yi-6b.steady.json").write_text(json.dumps(cell))
    bench = spec.benchmark()
    bench["workloads"].append({"name": "yi-6b.steady", "config": "yi-6b",
                               "traffic": "steady", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "steps_in_window", "unit": "steps",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "scheduler",
                               "moves": "output_tok_s",
                               "workloads": ["yi-6b.steady"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(spec, "BENCH", str(bench_dir))
    monkeypatch.setattr(spec, "ROOT", str(root))
    work = _small.small_work("yi-6b.steady")
    res = _small.run_small("yi-6b.steady", work=work, trace=True,
                           seconds=1.5)
    assert res["correct"]
    assert res["metrics"]["steps_in_window"]["value"] > 0
    assert res["attempted"] == sum(
        1 for i in range(60) if 0.5 <= 0.05 * i < 2.0)


def _faulty_engine(fault, monkeypatch):
    build = harness.build_engine

    def faulty(*a, **kw):
        eng = build(*a, **kw)
        if fault == "token_altered":
            sample = eng._sample

            def altered(logits, req, n_gen):
                tok = sample(logits, req, n_gen)
                if req.rid < harness.WARM_RID and n_gen == 2:
                    tok = (tok + 1) % logits.shape[-1]
                return tok
            eng._sample = altered
        elif fault in ("state_unchanged", "half_batch"):
            decode = eng._decode

            def broken(p, pages, b):
                if fault == "state_unchanged":
                    # the step's KV write is lost: the pool comes back
                    # as it went in (a copy, since the step donates it)
                    kept = jax.tree.map(jnp.copy, pages)
                    return decode(p, pages, b)[0], kept
                logits, new = decode(p, pages, b)
                half = logits.shape[0] // 2
                return logits.at[half:].set(logits[:half]), new
            eng._decode = broken
        return eng
    monkeypatch.setattr(harness, "build_engine", faulty)


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged",
                                   "half_batch"])
def test_planted_fault_is_not_correct(fault, monkeypatch):
    _faulty_engine(fault, monkeypatch)
    work = _small.small_work("yi-6b.chat")
    # every slot busy, as in the cells on the chip, and every finished
    # request compared
    work["cell"]["load"] = {"rate_per_s": 40.0}
    work["cell"]["check"].update(min_requests=16, max_requests=16)
    res = _small.run_small("yi-6b.chat", seconds=2.0, work=work)
    assert not res["correct"], res["check"]
    assert res["check"]["off_share"]["value"] > _small.OFF_SHARE_LIMIT


def test_control_fails_the_limit_and_the_program_passes():
    """The reference in fp8 (the precision below the bf16 the
    configuration states) in the program's place reads above the limit
    on the same requests; the program reads below it."""
    name, seed = "yi-6b.chat", 2 ** 31 + 11
    work = _small.small_work(name)
    arch = _small.small_arch(work["config"])
    res = _small.run_small(name, seed=seed, work=work)
    cfg = dict(work["config"], **{k: getattr(arch, k) for k in (
        "n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
        "vocab_size", "dtype")})
    rids = harness.sample(res["run"], work["cell"]["check"], seed)
    check = work["cell"]["check"]
    c = harness.compare(W.build(arch, seed), cfg, res["run"], rids,
                        check["off_tol"], control=True)
    assert harness.passes(c["off_share"], check)
    assert not harness.passes(c["control_off_share"], check)
