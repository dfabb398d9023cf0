"""The device sampler (serving/sampling.py) against the per-row sampler
it replaced: `jnp.argmax(row)` at temperature 0 and
`jax.random.categorical(jax.random.PRNGKey(rid * 7919 + n_gen),
row / temperature)` above it — alone on a mixed batch, and inside the
paged, chunked and disaggregated engines' programs."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.models import transformer as T
from repro.serving.engine import Request, make_engine
from repro.serving.sampling import (KEY_STRIDE, key_words, sample_rows,
                                    sampler_inputs)

# rid * 7919 passes 2**31 from this id on
BIG_RID = 2 ** 31 // KEY_STRIDE + 1


def per_row(row, rid: int, n_gen: int, temperature: float) -> int:
    """The sampler the engines ran per slot before it moved into their
    programs."""
    if temperature <= 0:
        return int(jnp.argmax(row))
    key = jax.random.PRNGKey(rid * KEY_STRIDE + n_gen)
    return int(jax.random.categorical(key, row / temperature))


@pytest.mark.parametrize("seed", [0, 5, 2 ** 31 - 1, 2 ** 31 + 5,
                                  2 ** 32 - 1, 2 ** 32 + 7,
                                  BIG_RID * KEY_STRIDE + 3])
def test_key_words_are_prngkeys(seed):
    assert list(key_words(seed)) == \
        np.asarray(jax.random.PRNGKey(seed)).tolist()


def test_mixed_batch_matches_the_per_row_sampler():
    rng = np.random.default_rng(0)
    vocab = 512
    # (rid, n_gen, temperature): greedy rows between hot ones, seeds
    # below and above 2**31
    rows = [(3, 0, 0.0), (3, 1, 0.7), (BIG_RID, 4, 1.0), (11, 2, 0.0),
            (BIG_RID + 9, 0, 1.6), (7, 5, 0.3), (BIG_RID + 1, 1, 0.0),
            (2, 9, 2.5)]
    logits = jnp.asarray(rng.normal(size=(len(rows), vocab)) * 4,
                         jnp.float32)
    reqs = [Request(rid, np.zeros(1, np.int32), temperature=t)
            for rid, _, t in rows]
    s = sampler_inputs(((i, reqs[i], n) for i, (_, n, _) in
                        enumerate(rows)), len(rows))
    got = np.asarray(sample_rows(logits, s["temperature"], s["key_data"]))
    assert got.dtype == np.int32 and got.shape == (len(rows),)
    want = [per_row(logits[i], rid, n, t)
            for i, (rid, n, t) in enumerate(rows)]
    assert got.tolist() == want
    # the draws differ from the argmax, so the hot rows were sampled
    assert any(got[i] != int(jnp.argmax(logits[i]))
               for i, (_, _, t) in enumerate(rows) if t > 0)


def test_rows_that_do_not_sample_are_greedy():
    """A row absent from `rows` (a prefill passenger, a free slot)
    carries temperature 0 and a zero key; a batch with no hot row is
    the argmax."""
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(4, 300)), jnp.float32)
    hot = Request(5, np.zeros(1, np.int32), temperature=1.0)
    s = sampler_inputs([(2, hot, 3)], 4)
    assert s["temperature"].tolist() == [0.0, 0.0, 1.0, 0.0]
    assert not s["key_data"][[0, 1, 3]].any()
    got = np.asarray(sample_rows(logits, s["temperature"], s["key_data"]))
    greedy = np.asarray(jnp.argmax(logits, axis=-1))
    assert got[[0, 1, 3]].tolist() == greedy[[0, 1, 3]].tolist()
    assert got[2] == per_row(logits[2], 5, 3, 1.0)
    cold = sampler_inputs([], 4)
    assert np.asarray(sample_rows(logits, cold["temperature"],
                                  cold["key_data"])).tolist() == \
        greedy.tolist()


ENGINES = {
    "paged": dict(engine="paged"),
    "chunked": dict(engine="chunked", chunk_size=16),
    "disagg": dict(engine="chunked", chunk_size=16, disagg=True,
                   kv_shards=2),
}
# (prompt length, new tokens, temperature)
REQUESTS = ((40, 5, 0.8), (9, 4, 0.0), (23, 3, 1.3), (30, 4, 0.0),
            (12, 6, 0.5))


@pytest.fixture(scope="module")
def model():
    cfg = configs.get_reduced("yi-6b")
    return cfg, T.init_params(jax.random.PRNGKey(0), cfg)


def _serve(model, kind, temperatures=True):
    cfg, params = model
    eng = make_engine(params, cfg, slots=3, max_len=96,
                      prefill_buckets=(32, 64), page_size=8, n_pages=48,
                      **ENGINES[kind])
    rng = np.random.default_rng(2)
    reqs = [Request(i if i % 2 else BIG_RID + i,
                    rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                    max_new_tokens=out,
                    temperature=t if temperatures else 0.0)
            for i, (n, out, t) in enumerate(REQUESTS)]
    futs = [eng.submit(r) for r in reqs]
    eng.run_to_completion()
    return reqs, [f.get().tokens for f in futs]


def _reference(model, req, tokens):
    """The per-row sampler over the un-paged forward's logits of the
    prompt and the tokens the engine served before each one."""
    cfg, params = model
    seq = np.concatenate([req.prompt, np.asarray(tokens[:-1], np.int32)])
    hidden = T.forward(params, {"tokens": jnp.asarray(seq[None])}, cfg)[0]
    logits = T.logits_fn(params, hidden[0, len(req.prompt) - 1:])
    return [per_row(logits[n], req.rid, n, req.temperature)
            for n in range(len(tokens))]


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_engine_tokens_match_the_per_row_sampler(model, kind):
    reqs, served = _serve(model, kind)
    assert any(r.temperature > 0 and r.rid * KEY_STRIDE >= 2 ** 31
               for r in reqs)
    for req, toks in zip(reqs, served):
        assert len(toks) == req.max_new_tokens
        assert toks == _reference(model, req, toks), (kind, req.rid)
    # some draw is not the argmax: the hot requests were sampled
    assert any(toks != _reference(model, dataclasses.replace(
        req, temperature=0.0), toks)
        for req, toks in zip(reqs, served) if req.temperature > 0)


def test_greedy_tokens_agree_across_engines(model):
    served = {kind: _serve(model, kind, temperatures=False)[1]
              for kind in ENGINES}
    assert served["paged"] == served["chunked"] == served["disagg"]


def _tokens(eng) -> dict:
    """Tokens each request holds so far, wherever it is."""
    n = {it["req"].rid: len(it["gen"]) for it in eng.queue}
    n.update({c.rid: len(c.tokens) for c in eng.completions})
    n.update({st["req"].rid: len(st["tokens"])
              for st in eng.active.values()})
    return n


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_host_syncs_one_read_per_decode_step(model, kind):
    """Greedy requests: a step's fetches are one read of the decode
    program's tokens, when any slot decodes, plus one read of each
    first token it sampled."""
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
        first = sum(1 for r in after if after[r] and not before.get(r))
        # the whole-prompt engine decodes every active slot
        decode_tokens = c.get("decode_tokens", c["active"])
        assert c["host_syncs"] == (1 if decode_tokens else 0) + first, \
            (kind, rows, c)
        assert 0.0 <= c["fetch_ms"] <= c["step_ms"]
    assert rows and len(eng.completions) == 5
    assert sum(c["host_syncs"] for c in eng.counters) < \
        sum(len(c.tokens) for c in eng.completions)


def test_benchmark_sees_a_token_altered_in_the_decode_program(monkeypatch):
    """The benchmark's comparison still catches a wrong token now that
    the decode program samples on the device: each request's third
    token is altered on its way out of the program."""
    from bench import harness
    from bench.tests import _small

    build = harness.build_engine

    def faulty(*a, **kw):
        eng = build(*a, **kw)
        decode = eng._decode

        def altered(p, pages, b):
            toks, new = decode(p, pages, b)
            for slot, st in eng.active.items():
                if st["req"].rid < harness.WARM_RID and \
                        len(st["tokens"]) == 2:
                    toks = toks.at[slot].set(
                        (toks[slot] + 1) % eng.cfg.vocab_size)
            return toks, new
        eng._decode = altered
        return eng
    monkeypatch.setattr(harness, "build_engine", faulty)
    work = _small.small_work("yi-6b.chat")
    work["cell"]["load"] = {"rate_per_s": 40.0}
    work["cell"]["check"].update(min_requests=16, max_requests=16)
    res = _small.run_small("yi-6b.chat", seconds=2.0, work=work)
    assert not res["correct"], res["check"]
    assert res["check"]["off_share"]["value"] > _small.OFF_SHARE_LIMIT
