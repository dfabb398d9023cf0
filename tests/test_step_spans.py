"""The serving step's spans on the profiler's clock.

A reduced chunked engine runs a few steps under `jax.profiler.trace`,
once with the null tracer and once with a bound `Tracer`.  Every name
of `STEP_SPANS` the steps exercise is a host annotation in the
`.xplane.pb`, nested in its parent as the tree in serving/engine.py
draws it, and the bound tracer's records pair one-to-one with those
annotations on one clock."""

import glob
import os

import jax
import numpy as np
import pytest

import repro.configs as configs
from repro.models import transformer as T
from repro.obs.trace import NULL_TRACER, Tracer
from repro.serving.engine import STEP_SPANS, Request, make_engine

# each span's parent in the chunked engine's step tree
PARENTS = {
    "engine.admit": ("engine.step",),
    "engine.prefetch": ("engine.step",),
    "engine.prefill_chunk": ("engine.step",),
    "engine.chunk_inputs": ("engine.prefill_chunk",),
    "engine.chunk_dispatch": ("engine.prefill_chunk",),
    "engine.first_token": ("engine.prefill_chunk",),
    "engine.prepare_writes": ("engine.step",),
    "engine.decode_batch": ("engine.step",),
    "engine.decode_inputs": ("engine.decode_batch",),
    "engine.decode_dispatch": ("engine.decode_batch",),
    "engine.sample": ("engine.decode_batch",),
    "engine.finish": ("engine.decode_batch",),
    "engine.fetch": ("engine.sample", "engine.first_token"),
}


@pytest.fixture(scope="module")
def setup():
    cfg = configs.get_reduced("yi-6b")
    return cfg, T.init_params(jax.random.PRNGKey(0), cfg)


def _traced_steps(setup, tracer, tmp_path):
    """Warm an engine, then profile a wave that prefills in several
    chunks, decodes, and finishes.  Returns (host events, engine)."""
    cfg, params = setup
    eng = make_engine(params, cfg, engine="chunked", slots=2, max_len=64,
                      page_size=8, n_pages=32, chunk_size=16,
                      tracer=tracer)
    rng = np.random.default_rng(0)

    def wave(rid0):
        for i, n in enumerate((20, 9)):
            eng.submit(Request(rid0 + i, rng.integers(
                0, cfg.vocab_size, n).astype(np.int32), max_new_tokens=3))
        eng.run_to_completion()

    wave(0)                              # compiles outside the trace
    tracer.clear()
    with jax.profiler.trace(str(tmp_path)):
        wave(10)
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    events = [(line.name, e.name, e.start_ns, e.duration_ns)
              for p in pd.planes if not p.name.startswith("/device:")
              for line in p.lines for e in line.events
              if e.name.startswith(("engine.", "kvcache."))]
    return events, eng


def _check_tree(events):
    """Every STEP_SPANS name is present and sits in its parent."""
    spans = sorted((ln, a, a + d, name) for ln, name, a, d in events
                   if name in STEP_SPANS)
    assert {s[3] for s in spans} == set(STEP_SPANS)
    for i, (ln, a, b, name) in enumerate(spans):
        enclosing = [s for s in spans[:i] if s[0] == ln and s[1] <= a
                     and b <= s[2]]
        if name == "engine.step":
            assert not enclosing, name
            continue
        # the innermost enclosing span: the latest to start before it
        assert enclosing, name
        assert enclosing[-1][3] in PARENTS[name], (name, enclosing[-1])


def test_null_tracer_spans_reach_the_profiler(setup, tmp_path):
    events, eng = _traced_steps(setup, NULL_TRACER, tmp_path)
    assert eng.trace.records() == []
    _check_tree(events)


def test_tracer_records_pair_with_profiler_events(setup, tmp_path):
    tr = Tracer(capacity=1 << 14)
    events, eng = _traced_steps(setup, tr, tmp_path)
    _check_tree(events)
    recs = [r for r in tr.records() if r.dur is not None]
    assert tr.dropped == 0 and recs
    by_name = {}
    for _, name, a, d in sorted(events, key=lambda e: e[2]):
        by_name.setdefault(name, []).append((a, d))
    rec_by_name = {}
    for r in sorted(recs, key=lambda r: r.t0):
        rec_by_name.setdefault(f"{r.subsystem}.{r.name}", []).append(r)
    assert {k: len(v) for k, v in rec_by_name.items()} == \
        {k: len(v) for k, v in by_name.items()}
    # one clock: the profiler's host events are perf_counter less one
    # constant, so offsets from the first step agree
    base_rec = rec_by_name["engine.step"][0].t0
    base_ev = by_name["engine.step"][0][0]
    for name, rs in rec_by_name.items():
        for r, (a, d) in zip(rs, by_name[name]):
            assert abs((r.t0 - base_rec) - (a - base_ev) * 1e-9) <= 200e-6
            assert abs(r.dur - d * 1e-9) <= 50e-6, name
