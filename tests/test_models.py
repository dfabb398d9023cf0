"""Model zoo: per-arch smoke tests (reduced configs) + consistency."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import repro.configs as configs
from repro.models import transformer as T
from repro.models.config import SHAPES, shape_applicable

KEY = jax.random.PRNGKey(0)
B, S = 2, 64


def make_batch(cfg, b=B, s=S, train=True):
    ks = jax.random.split(KEY, 4)
    batch = {"tokens": jax.random.randint(ks[0], (b, s), 0,
                                          cfg.vocab_size)}
    if train:
        batch["labels"] = jax.random.randint(ks[1], (b, s), 0,
                                             cfg.vocab_size)
    if cfg.family == "vlm":
        batch["patch_embeds"] = jax.random.normal(
            ks[2], (b, cfg.n_frontend_tokens, 32))
    if cfg.family == "audio":
        batch["frame_embeds"] = 0.1 * jax.random.normal(
            ks[3], (b, s, cfg.d_model))
    return batch


@pytest.mark.parametrize("name", configs.ARCHS)
def test_arch_smoke_forward_loss_grad(name):
    """The assigned smoke test: reduced config, one forward/train step
    on CPU, output shapes + no NaNs."""
    cfg = configs.get_reduced(name)
    params = T.init_params(KEY, cfg)
    batch = make_batch(cfg)
    h, aux = T.forward(params, batch, cfg)
    assert h.shape == (B, S, cfg.d_model)
    assert np.all(np.isfinite(np.asarray(h, np.float32)))
    loss, grads = jax.value_and_grad(
        lambda p: T.loss_fn(p, batch, cfg))(params)
    assert np.isfinite(float(loss))
    gn = sum(float(jnp.sum(g.astype(jnp.float32) ** 2))
             for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0


@pytest.mark.parametrize("name", configs.ARCHS)
def test_arch_decode_smoke(name):
    cfg = configs.get_reduced(name)
    params = T.init_params(KEY, cfg)
    cache = T.init_cache(cfg, B, 32)
    batch = make_batch(cfg, s=1, train=False)
    batch["tokens"] = batch["tokens"][:, :1]
    logits, cache2 = T.decode_step(params, cache, batch, cfg)
    assert logits.shape == (B, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits)))
    assert int(cache2["len"]) == 1


@pytest.mark.parametrize("name", configs.ARCHS)
def test_prefill_decode_matches_forward(name):
    """prefill(S-1) + decode(token S) == forward(S) at the last pos."""
    cfg = configs.get_reduced(name)
    params = T.init_params(KEY, cfg)
    batch = make_batch(cfg, train=False)
    full, _ = T.forward(params, batch, cfg, mode="train")
    ref_logits = T.logits_fn(params, full[:, -1])

    pre_batch = dict(batch)
    pre_batch["tokens"] = batch["tokens"][:, :-1]
    if "frame_embeds" in pre_batch:
        pre_batch["frame_embeds"] = batch["frame_embeds"][:, :-1]
    _, cache = T.prefill(params, pre_batch, cfg)
    # pad cache seq to allow one more token
    def pad_seq(x):
        if x.ndim >= 3 and x.shape[-3] == S - 1:
            pads = [(0, 0)] * x.ndim
            pads[-3] = (0, 8)
            return jnp.pad(x, pads)
        return x
    cache = {k: (pad_seq(v) if k not in ("len", "cursor", "abs")
                 else v) for k, v in cache.items()}
    dec_batch = dict(batch)
    dec_batch["tokens"] = batch["tokens"][:, -1:]
    dec_batch.pop("labels", None)
    if "frame_embeds" in dec_batch:
        # decode path ignores frame embeds (conditioning was prefixed)
        dec_batch.pop("frame_embeds")
    logits, _ = T.decode_step(params, cache, dec_batch, cfg)
    # audio adds frame embeds in forward but not decode: skip exactness
    if cfg.family == "audio":
        return
    if cfg.family == "moe":
        # capacity-based top-2 routing depends on a token's group
        # companions, which differ between prefill and decode batches
        # (a real property of GShard-style MoE) — compare decisions.
        assert (np.argmax(np.asarray(logits), -1) ==
                np.argmax(np.asarray(ref_logits), -1)).all()
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(ref_logits), atol=0.15)
        return
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(ref_logits),
                               atol=2e-3, rtol=2e-3)


def test_param_counts_match_published():
    expect = {
        "h2o-danube-3-4b": 4.0e9, "chatglm3-6b": 6.2e9,
        "command-r-plus-104b": 104e9, "yi-6b": 6.1e9,
        "falcon-mamba-7b": 7.3e9, "zamba2-7b": 7.0e9,
        "mixtral-8x7b": 46.7e9, "phi3.5-moe-42b-a6.6b": 41.9e9,
        "musicgen-large": 3.3e9, "llama-3.2-vision-90b": 88e9,
    }
    for name, target in expect.items():
        got = configs.get(name).param_count()
        assert abs(got - target) / target < 0.12, \
            f"{name}: {got / 1e9:.1f}B vs {target / 1e9:.1f}B"


def test_moe_active_params():
    assert abs(configs.get("mixtral-8x7b").active_param_count()
               - 12.9e9) / 12.9e9 < 0.05
    assert abs(configs.get("phi3.5-moe-42b-a6.6b").active_param_count()
               - 6.6e9) / 6.6e9 < 0.05


def test_long500k_applicability():
    runnable = [a for a in configs.ARCHS if shape_applicable(
        configs.get(a), SHAPES["long_500k"])[0]]
    assert sorted(runnable) == sorted(
        ["falcon-mamba-7b", "zamba2-7b", "h2o-danube-3-4b",
         "mixtral-8x7b"])


def test_swa_ring_buffer_decode():
    """Decoding past the window keeps only `window` live keys."""
    cfg = configs.get_reduced("h2o-danube-3-4b")
    assert cfg.sliding_window == 32
    params = T.init_params(KEY, cfg)
    cache = T.init_cache(cfg, 1, 128)
    assert cache["k"].shape[-3] == 32       # capped at window
    batch = {"tokens": jnp.zeros((1, 1), jnp.int32)}
    for i in range(40):                      # wrap the ring
        logits, cache = T.decode_step(params, cache, batch, cfg)
    assert np.all(np.isfinite(np.asarray(logits)))
    assert int(cache["cursor"]) == 40


def _vmap_stack(key, n, fn):
    """The eager whole-stack draw init_params builds one layer at a
    time: every layer's values must equal what this gives."""
    return jax.vmap(fn)(jax.random.split(key, n))


@pytest.mark.parametrize("name", ["yi-6b", "mixtral-8x7b",
                                  "falcon-mamba-7b", "zamba2-7b",
                                  "llama-3.2-vision-90b"])
def test_layer_stacks_bit_identical_to_vmapped_draw(name):
    cfg = configs.get_reduced(name)
    key = jax.random.PRNGKey(7)
    got = T.init_params(key, cfg)
    ks = jax.random.split(key, 8)
    L, fam = cfg.n_layers, cfg.family
    dense = lambda k: T._dense_layer_init(k, cfg)
    ssm = lambda k: T._ssm_layer_init(k, cfg)
    if fam == "dense":
        want = {"layers": _vmap_stack(ks[2], L, dense)}
    elif fam == "moe":
        want = {"layers": _vmap_stack(
            ks[2], L, lambda k: T._moe_layer_init(k, cfg, 1))}
    elif fam == "ssm":
        want = {"layers": _vmap_stack(ks[2], L, ssm)}
    elif fam == "hybrid":
        every = cfg.shared_attn_every
        n = L // every
        want = {"groups": _vmap_stack(
                    ks[2], n, lambda k: _vmap_stack(k, every, ssm)),
                "adapters": _vmap_stack(ks[4], n, lambda k: {
                    "w": T._init_dense(k, cfg.d_model, cfg.d_model,
                                       jnp.dtype(cfg.dtype)) * 0.1})}
    else:
        every = cfg.cross_attn_every
        n = L // every
        want = {"groups_self": _vmap_stack(
                    ks[2], n, lambda k: _vmap_stack(k, every - 1, dense)),
                "groups_cross": _vmap_stack(
                    ks[3], n, lambda k: T._cross_layer_init(k, cfg))}
    assert set(want) <= set(got)
    flat_w = jax.tree_util.tree_leaves_with_path(want)
    flat_g = dict(jax.tree_util.tree_leaves_with_path(
        {k: got[k] for k in want}))
    assert len(flat_w) == len(flat_g)
    for path, w in flat_w:
        g = flat_g[path]
        assert g.dtype == w.dtype and g.shape == w.shape, path
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
