"""The serving expert layer (models/moe.py `moe_dropless`) and the moe
family's paged programs against plain references, on the CPU in
float32: per-token loops for the layer, bench/reference/moe_gqa.py for
the model.

Tolerances: the program and the references compute in float32 with
different summation orders, so logits agree to rounding, about 1e-6 at
these sizes; 1e-5 leaves room for that and nothing else.  A dropped
token or a wrong expert moves a logit by 1e-2 or more.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.models import moe as M
from repro.models import transformer as T
from repro.serving.engine import Request, make_engine

from bench.reference import moe_gqa as R

TOL = 1e-5


def _params(key, e=8, d=16, f=32, layers=None):
    ks = jax.random.split(key, 4)
    lead = () if layers is None else (layers,)
    n = lambda k, s: jax.random.normal(k, lead + s, jnp.float32) / np.sqrt(s[-2])
    return {"router": n(ks[0], (d, e)), "wi": n(ks[1], (e, d, f)),
            "wg": n(ks[2], (e, d, f)), "wdown": n(ks[3], (e, f, d))}


def _layer(p, x, k, **kw):
    """The expert layer over one layer's weights, as a stack of one."""
    stack = {w: p[w][None] for w in ("wi", "wg", "wdown")}
    return M.moe_dropless(dict(stack, router=p["router"]), x, k, 0, **kw)


def _loop(p, x, k):
    """Per token, in float64: softmax router, top k, renormalised
    weights, the chosen experts' SwiGLU."""
    p = {n: np.asarray(a, np.float64) for n, a in p.items()}
    x = np.asarray(x, np.float64)
    ys, chosen = [], []
    for t in x:
        lg = t @ p["router"]
        pr = np.exp(lg - lg.max())
        pr /= pr.sum()
        top = np.argsort(-pr, kind="stable")[:k]
        w = pr[top] / pr[top].sum()
        y = np.zeros_like(t)
        for e, we in zip(top, w):
            g = t @ p["wg"][e]
            h = g / (1 + np.exp(-g)) * (t @ p["wi"][e])
            y += we * (h @ p["wdown"][e])
        ys.append(y)
        chosen.append(top)
    return np.stack(ys), np.concatenate(chosen)


@pytest.mark.parametrize("k", [2, 4])
@pytest.mark.parametrize("skewed", [False, True])
def test_expert_layer_equals_per_token_loop(k, skewed):
    p = _params(jax.random.PRNGKey(k))
    x = jax.random.normal(jax.random.PRNGKey(10 + k), (24, 16))
    if skewed:
        # every token's logits rank experts 0, 1, 2, ... first: a
        # capacity of 1.25 k N / E would drop most of them
        x = jnp.abs(x) + 0.1
        p["router"] = p["router"].at[:, :4].set(
            jnp.array([40.0, 30.0, 20.0, 10.0]))
    y, load = _layer(p, x, k)
    want, chosen = _loop(p, x, k)
    np.testing.assert_allclose(np.asarray(y), want, atol=TOL, rtol=TOL)
    np.testing.assert_array_equal(np.asarray(load),
                                  np.bincount(chosen, minlength=8))
    if skewed:
        assert int(load[0]) == 24 and int(load[k - 1]) == 24


def test_token_count_not_a_multiple_of_any_group_is_fully_routed():
    p = _params(jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (37, 16))
    y, load = _layer(p, x, 2)
    want, _ = _loop(p, x, 2)
    assert int(load.sum()) == 37 * 2
    assert np.all(np.abs(np.asarray(y)).sum(-1) > 0)
    np.testing.assert_allclose(np.asarray(y), want, atol=TOL, rtol=TOL)


def test_dead_rows_route_to_no_expert():
    p = _params(jax.random.PRNGKey(5))
    x = jax.random.normal(jax.random.PRNGKey(6), (20, 16))
    live = jnp.arange(20) % 3 != 0
    y, load = _layer(p, x, 2, live=live)
    full, _ = _layer(p, x, 2)
    assert int(load.sum()) == 2 * int(live.sum())
    np.testing.assert_array_equal(np.asarray(y)[~np.asarray(live)], 0.0)
    np.testing.assert_allclose(np.asarray(y)[np.asarray(live)],
                               np.asarray(full)[np.asarray(live)],
                               atol=TOL, rtol=TOL)


def test_whole_stack_read_in_place_equals_the_layer_slice():
    stack = _params(jax.random.PRNGKey(7), layers=3)
    x = jax.random.normal(jax.random.PRNGKey(8), (12, 16))
    for layer in range(3):
        sl = jax.tree.map(lambda a: a[layer], stack)
        want, wload = _layer(sl, x, 2)
        got, load = M.moe_dropless(dict(stack, router=sl["router"]), x, 2,
                                   jnp.int32(layer))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=TOL, rtol=TOL)
        np.testing.assert_array_equal(np.asarray(load), np.asarray(wload))


# -- the model: paged programs against the plain reference ------------

PS, P = 8, 16                       # page size, pages per slot


def _cfg(k):
    return dataclasses.replace(configs.get_reduced("mixtral-8x7b"),
                               n_experts=8, top_k=k)


def _ref_cfg(cfg):
    return {f: getattr(cfg, f) for f in (
        "n_layers", "n_heads", "n_kv_heads", "head_dim", "rope_fraction",
        "rope_theta", "norm_eps", "top_k")}


def _prefill(params, cfg, pages, prompt, table, chunk=16):
    """Chunked prefill of one slot; the last chunk's logits and load."""
    null = pages["k"].shape[1] - 1
    for start in range(0, len(prompt), chunk):
        take = min(chunk, len(prompt) - start)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :take] = prompt[start:start + take]
        rows = np.full((1, chunk // PS), null, np.int32)
        n = -(-take // PS)
        rows[0, :n] = table[start // PS:start // PS + n]
        lg, pages, load = T.prefill_chunk_load(params, pages, {
            "tokens": jnp.asarray(toks),
            "block_tables": jnp.asarray(table[None]),
            "start": jnp.asarray([start], jnp.int32),
            "chunk_rows": jnp.asarray(rows),
            "last_index": jnp.int32(take - 1)}, cfg)
        assert int(load.sum()) == take * cfg.top_k * cfg.n_layers
    return np.asarray(lg)[0], pages


def _decode_batch(tokens, positions, tables, live, null):
    pos = np.asarray(positions)
    rows = np.array([t[p // PS] if a else null
                     for t, p, a in zip(tables, pos, live)], np.int32)
    return {"tokens": jnp.asarray(np.asarray(tokens, np.int32)[:, None]),
            "block_tables": jnp.asarray(np.where(
                np.asarray(live)[:, None], tables, null).astype(np.int32)),
            "positions": jnp.asarray(np.where(live, pos, 0), jnp.int32),
            "write_rows": jnp.asarray(rows),
            "write_offs": jnp.asarray(np.where(live, pos % PS, 0),
                                      jnp.int32)}


@pytest.mark.parametrize("k", [2, 4])
def test_paged_decode_and_chunked_prefill_match_the_reference(k):
    """Chunked prefill (three chunks, the last partial) then paged
    decode beside an idle slot: every step's logits are the reference's
    full forward over the prompt and the tokens before."""
    cfg = _cfg(k)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(k)
    prompt = rng.integers(0, cfg.vocab_size, 40).astype(np.int32)
    pages = T.init_paged_cache(cfg, 2 * P + 1, PS)
    null = 2 * P
    tables = np.stack([np.arange(P), np.arange(P, 2 * P)]).astype(np.int32)
    lg, pages = _prefill(params, cfg, pages, prompt, tables[0])
    seq = list(prompt)
    rc = _ref_cfg(cfg)
    for step in range(5):
        ref = np.asarray(R.logits_at(params, rc, R.hidden(
            params, rc, np.array(seq)), np.array([len(seq) - 1])))[0]
        np.testing.assert_allclose(lg, ref, atol=TOL, rtol=TOL)
        seq.append(int(np.argmax(lg)))
        out, pages, load = T.decode_step_paged_load(
            params, pages, _decode_batch([seq[-1], 0], [len(seq) - 1, 0],
                                         tables, [True, False], null), cfg)
        assert int(load.sum()) == k * cfg.n_layers      # the live slot
        lg = np.asarray(out)[0]


def test_a_requests_logits_do_not_depend_on_its_batch():
    """One decode step of a request served alone and beside 15 others:
    the same logits."""
    cfg = _cfg(2)
    params = T.init_params(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(2)
    b = 16
    pages = T.init_paged_cache(cfg, b * P + 1, PS)
    null = b * P
    tables = np.arange(b * P, dtype=np.int32).reshape(b, P)
    lens = rng.integers(9, 40, b)
    last = []
    for s in range(b):
        prompt = rng.integers(0, cfg.vocab_size, lens[s]).astype(np.int32)
        lg, pages = _prefill(params, cfg, pages, prompt, tables[s])
        last.append(int(np.argmax(lg)))
    pos = [int(n) for n in lens]
    alone = _decode_batch(last, pos, tables, [True] + [False] * 15, null)
    beside = _decode_batch(last, pos, tables, [True] * 16, null)
    a, _, la = T.decode_step_paged_load(params, pages, alone, cfg)
    c, _, lc = T.decode_step_paged_load(params, pages, beside, cfg)
    assert int(la.sum()) == 2 * cfg.n_layers
    assert int(lc.sum()) == 16 * 2 * cfg.n_layers
    np.testing.assert_allclose(np.asarray(a)[0], np.asarray(c)[0],
                               atol=1e-6, rtol=0)


def test_served_tokens_do_not_depend_on_the_batch():
    """Through the chunked engine: a request's greedy tokens alone and
    among 15 others admitted with it."""
    cfg = _cfg(2)
    params = T.init_params(jax.random.PRNGKey(2), cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in rng.integers(8, 60, 16)]

    def serve(ps):
        eng = make_engine(params, cfg, engine="chunked", slots=16,
                          max_len=96, page_size=PS, n_pages=16 * 12,
                          chunk_size=16)
        futs = [eng.submit(Request(i, p, max_new_tokens=6))
                for i, p in enumerate(ps)]
        eng.run_to_completion()
        return futs[0].get().tokens
    assert serve(prompts[:1]) == serve(prompts)


# -- the engine's expert-load counters ---------------------------------

def _tokens(eng) -> dict:
    n = {it["req"].rid: len(it["gen"]) for it in eng.queue}
    n.update({c.rid: len(c.tokens) for c in eng.completions})
    n.update({st["req"].rid: len(st["tokens"])
              for st in eng.active.values()})
    return n


def test_expert_load_counters_add_up_with_no_extra_read():
    """Every step's `moe_routed` is its tokens x k x layers (a chunk's
    load read in a later step is added to its own step's row); a step
    reads once for its decode batch and once per first token, as a
    dense model's does."""
    cfg = _cfg(2)
    params = T.init_params(jax.random.PRNGKey(3), cfg)
    eng = make_engine(params, cfg, engine="chunked", slots=3, max_len=96,
                      page_size=PS, n_pages=48, chunk_size=16)
    rng = np.random.default_rng(4)
    for rid, (n, out) in enumerate(((40, 5), (9, 3), (23, 1), (50, 4),
                                    (12, 6))):
        eng.submit(Request(rid, rng.integers(0, cfg.vocab_size, n)
                           .astype(np.int32), max_new_tokens=out))
    firsts = []
    while eng.active or eng.queue:
        before = _tokens(eng)
        rows = len(eng.counters)
        eng.step()
        after = _tokens(eng)
        if len(eng.counters) > rows:
            firsts.append(sum(1 for r in after
                              if after[r] and not before.get(r)))
    assert len(eng.completions) == 5 and not eng._held
    kl = cfg.top_k * cfg.n_layers
    for c, first in zip(eng.counters, firsts):
        tokens = c["decode_tokens"] + c["prefill_chunk_tokens"]
        assert c["moe_routed"] == tokens * kl, c
        assert c["moe_decode_experts_hit"] <= min(
            c["moe_experts_hit"], cfg.n_layers * cfg.n_experts)
        assert c["moe_load_max"] * cfg.n_experts >= c["moe_routed"]
        assert c["host_syncs"] == (c["decode_tokens"] > 0) + first, c
    m = eng.metrics
    assert m.counter("moe.routed").value == \
        sum(c["moe_routed"] for c in eng.counters)
    assert m.counter("moe.experts_hit").value == \
        sum(c["moe_experts_hit"] for c in eng.counters)


def test_tpu_grouped_matmul_in_the_interpreter():
    """The TPU branch (megablox gmm) on the Pallas interpreter: a layer
    stack of 3 x 4 groups with only the middle layer's nonzero, rows
    not a multiple of the tile and a dead tail past the groups."""
    w = jax.random.normal(jax.random.PRNGKey(9), (12, 16, 40))
    x = jax.random.normal(jax.random.PRNGKey(10), (37, 16))
    groups = jnp.array([0, 0, 0, 0, 9, 0, 13, 11, 0, 0, 0, 0], jnp.int32)
    got = M._gmm(x, w, groups, interpret=True)
    want = jax.lax.ragged_dot(x, w, groups)
    np.testing.assert_allclose(np.asarray(got)[:33], np.asarray(want)[:33],
                               atol=1e-5, rtol=1e-5)
