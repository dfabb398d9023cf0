"""The engines' per-step fetch count and host times, and the two
scheduler readers that average them (host_syncs_per_step,
step_host_ms)."""

from __future__ import annotations

import jax
import numpy as np
import pytest

import repro.configs as configs
from repro.models import transformer as T
from repro.serving.engine import Request, make_engine

from bench import spec
from bench.tests import _small

ENGINES = {
    "paged": dict(engine="paged"),
    "chunked": dict(engine="chunked", chunk_size=16),
    "disagg": dict(engine="chunked", chunk_size=16, disagg=True,
                   kv_shards=2),
}


@pytest.fixture(scope="module")
def model():
    cfg = configs.get_reduced("yi-6b")
    return cfg, T.init_params(jax.random.PRNGKey(0), cfg)


def _tokens(eng) -> dict:
    """Tokens each request holds so far, wherever it is."""
    n = {it["req"].rid: len(it["gen"]) for it in eng.queue}
    n.update({c.rid: len(c.tokens) for c in eng.completions})
    n.update({st["req"].rid: len(st["tokens"])
              for st in eng.active.values()})
    return n


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_host_syncs_count_every_sampled_token(model, kind):
    """Greedy requests: a step's fetches are its decoding slots plus the
    first tokens it sampled, one read each."""
    cfg, params = model
    eng = make_engine(params, cfg, slots=3, max_len=96,
                      prefill_buckets=(32, 64), page_size=8, n_pages=48,
                      **ENGINES[kind])
    rng = np.random.default_rng(1)
    for rid, (n, out) in enumerate(((40, 5), (9, 3), (23, 1), (30, 4),
                                    (12, 6))):
        eng.submit(Request(rid, rng.integers(0, cfg.vocab_size, n)
                           .astype(np.int32), max_new_tokens=out))
    rows = 0
    while eng.active or eng.queue:
        before = _tokens(eng)
        eng.step()
        if len(eng.counters) == rows:
            continue                         # the step ran no batch
        rows = len(eng.counters)
        c = eng.counters[-1]
        after = _tokens(eng)
        sampled = sum(after[r] - before.get(r, 0) for r in after)
        first = sum(1 for r in after if after[r] and not before.get(r))
        assert c["host_syncs"] == sampled, (kind, rows, c)
        if "decode_tokens" in c:
            assert c["host_syncs"] == c["decode_tokens"] + first
        assert 0.0 <= c["fetch_ms"] <= c["step_ms"]
    assert rows and len(eng.completions) == 5
    assert sum(c["host_syncs"] for c in eng.counters) == \
        sum(len(c.tokens) for c in eng.completions)


def _read(name, rows):
    return spec.load_module("metrics", name).read({"run": {"counters": rows}})


@pytest.mark.parametrize("name", ["host_syncs_per_step", "step_host_ms"])
def test_readers_are_silent_without_the_keys(name):
    assert _read(name, []) is None
    assert _read(name, [{"decode_tokens": 3, "decode_ms": 4.0}]) is None


def test_readers_average_the_rows():
    rows = [{"host_syncs": 21, "fetch_ms": 30.0, "step_ms": 80.0},
            {"host_syncs": 2, "fetch_ms": 1.5, "step_ms": 40.0},
            {"host_syncs": 0, "fetch_ms": 0.0, "step_ms": 9.0}]
    assert _read("host_syncs_per_step", rows) == pytest.approx(23 / 3)
    assert _read("step_host_ms", rows) == pytest.approx(
        (50.0 + 38.5 + 9.0) / 3)


def test_traced_run_reports_both_metrics():
    res = _small.run_small("yi-6b.chat", seconds=2.0, trace=True)
    m = res["metrics"]
    rows = res["run"]["counters"]
    assert m["host_syncs_per_step"]["value"] == pytest.approx(
        np.mean([c["host_syncs"] for c in rows]))
    assert m["host_syncs_per_step"]["unit"] == "syncs/step"
    assert 0 < m["step_host_ms"]["value"] <= max(c["step_ms"] for c in rows)
