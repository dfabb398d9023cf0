"""FLOP and byte arithmetic at both configurations' published widths,
and the table of peaks."""

from __future__ import annotations

import pytest

from bench import peaks, spec, work


@pytest.mark.parametrize("name,params,kv", [
    ("yi-6b", 6.061e9, 65536),            # 32 x 2 x 4 x 128 x 2 bytes
    ("chatglm3-6b", 6.244e9, 28672),      # 28 x 2 x 2 x 128 x 2 bytes
])
def test_sizes(name, params, kv):
    cfg = spec.config(name)
    assert work.param_count(cfg) == pytest.approx(params, rel=1e-3)
    assert work.weight_bytes(cfg) == 2 * work.param_count(cfg)
    assert work.kv_bytes_per_token(cfg) == kv


@pytest.mark.parametrize("name", ["yi-6b", "chatglm3-6b"])
def test_decode_and_prefill_work(name):
    cfg = spec.config(name)
    pk = peaks.peaks("TPU v5 lite")
    f, b = work.decode_work(cfg, [100, 300])
    per_tok = 2 * cfg["n_layers"] * work.layer_matmul_params(cfg) + \
        2 * cfg["vocab_size"] * cfg["d_model"]
    attn = 4 * 400 * cfg["n_heads"] * cfg["head_dim"] * cfg["n_layers"]
    assert f == pytest.approx(2 * per_tok + attn)
    # the input embedding is read only at the two slots' rows
    table = (cfg["vocab_size"] - 2) * cfg["d_model"] * 2
    assert b == work.weight_bytes(cfg) - table + \
        400 * work.kv_bytes_per_token(cfg)
    assert work.step_weight_bytes(dict(cfg, tie_embeddings=True), 2) == \
        work.weight_bytes(cfg) - cfg["vocab_size"] * cfg["d_model"] * 2
    t, bound = work.least_time(f, b, pk)
    assert bound == "memory" and t == pytest.approx(b / 819e9)
    # weight reads alone: 14.8 ms for yi-6b, 15.2 ms for chatglm3-6b
    assert work.weight_bytes(cfg) / 819e9 == pytest.approx(
        {"yi-6b": 14.8e-3, "chatglm3-6b": 15.25e-3}[name], rel=0.01)
    f, b = work.prefill_work(cfg, 256, 256)
    keys = 256 * 256 + 256 * 257 / 2
    assert f == pytest.approx(
        2 * cfg["n_layers"] * work.layer_matmul_params(cfg) * 256
        + 2 * cfg["vocab_size"] * cfg["d_model"]
        + 4 * keys * cfg["n_heads"] * cfg["head_dim"] * cfg["n_layers"])
    # a 256-token chunk is about 2.9 TFLOP, 15 ms at peak: about as
    # long as reading the weights once, so neither bound dominates
    assert f == pytest.approx(2.9e12, rel=0.06)
    t_c, t_m = f / pk["flops_bf16"], b / pk["hbm_bytes_s"]
    assert t_c == pytest.approx(t_m, rel=0.15)
    assert work.least_time(f, b, pk)[0] == max(t_c, t_m)


def test_peaks_reject_unknown_device():
    assert peaks.peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
