"""Compile the serving path's kernels and steps for a described TPU v5e.

Nothing runs: the TPU compiler, installed beside JAX, compiles for a
chip that is described and not attached, and refuses what the chip
would refuse (block shapes, VMEM, HBM).  All at yi-6b's published
widths in bf16.  The topology is described inside a fixture, never at
import time, and every test of this kind stays in this one file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro.configs as configs
from repro.kernels.attention.flash import flash_attention_bhsd
from repro.kernels.attention.paged import (paged_attention_bhd,
                                           paged_prefill_attention_btd)
from repro.models import transformer as T
from repro.serving.sampling import sample_step

CFG = configs.get("yi-6b")
H, KV, D = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
PS, ROWS, TABLE, B, CHUNK = 16, 1024, 16, 8, 32
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(one_chip, *specs):
    return [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in specs]


def _pool(sharded: bool):
    return (4, ROWS // 4, PS, KV, D) if sharded else (ROWS, PS, KV, D)


# the cells' decode shapes: slots, table pages, KV heads, pool rows
DECODE_CELLS = {
    "yi-6b.chat": (32, 64, 4, 1515),
    "chatglm3-6b.chat": (32, 64, 2, 3065),
    "mixtral-8x7b.chat": (64, 64, 8, 2962),
    "yi-6b.sessions": (16, 128, 4, 1515),
}


@pytest.mark.parametrize("cell", sorted(DECODE_CELLS))
@pytest.mark.parametrize("sharded", [False, True],
                         ids=["flat", "sharded"])
def test_paged_decode_kernel_compiles(one_chip, sharded, cell):
    """The decode kernel at each cell's decode shape: 32 query heads of
    128 over the cell's KV heads (n_rep 8, 16, 4), 16-token pages."""
    b, table, kv, rows = DECODE_CELLS[cell]
    pool = (4, rows // 4, PS, kv, D) if sharded else (rows, PS, kv, D)
    args = _shapes(one_chip, ((b, H, D), BF16), (pool, BF16),
                   (pool, BF16), ((b, table), jnp.int32),
                   ((b,), jnp.int32))
    fn = jax.jit(lambda *a: paged_attention_bhd(*a, interpret=False))
    assert "tpu_custom_call" in fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["flat", "sharded"])
def test_paged_prefill_kernel_compiles(one_chip, sharded):
    args = _shapes(one_chip, ((1, CHUNK, H, D), BF16),
                   (_pool(sharded), BF16), (_pool(sharded), BF16),
                   ((1, TABLE), jnp.int32), ((1,), jnp.int32))
    fn = jax.jit(lambda *a: paged_prefill_attention_btd(
        *a, interpret=False))
    assert "tpu_custom_call" in fn.lower(*args).compile().as_text()


def test_flash_kernel_compiles(one_chip):
    s = 256
    args = _shapes(one_chip, ((H, s, D), BF16), ((KV, s, D), BF16),
                   ((KV, s, D), BF16))
    fn = jax.jit(lambda q, k, v: flash_attention_bhsd(
        q, k, v, n_rep=H // KV, interpret=False))
    assert "tpu_custom_call" in fn.lower(*args).compile().as_text()


def _step_inputs(one_chip):
    """One published-width layer: params, page pool and int operands
    as described-chip shapes."""
    cfg = CFG.with_depth(1)
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=one_chip),
        jax.eval_shape(lambda k: T.init_params(k, cfg),
                       jax.random.PRNGKey(0)))
    pool = (1, ROWS + 1, PS, KV, D)
    k, v = _shapes(one_chip, (pool, BF16), (pool, BF16))
    return cfg, params, {"k": k, "v": v}


def _i32(one_chip, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)


def test_decode_step_paged_compiles(one_chip):
    cfg, params, pages = _step_inputs(one_chip)
    batch = {"tokens": _i32(one_chip, B, 1),
             "block_tables": _i32(one_chip, B, TABLE),
             "positions": _i32(one_chip, B),
             "write_rows": _i32(one_chip, B),
             "write_offs": _i32(one_chip, B)}
    fn = jax.jit(lambda p, pg, b: T.decode_step_paged(p, pg, b, cfg),
                 donate_argnums=(1,))
    out = fn.lower(params, pages, batch).compile()
    logits = out.out_info[0]
    assert logits.shape == (B, cfg.vocab_size)


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
def test_decode_step_paged_places_the_kernel(one_chip, monkeypatch,
                                             backend):
    """The decode step at yi-6b.chat's 32 slots x 64 pages, lowered for
    the described chip.  Where the platform is a TPU (the test says so:
    the described chip is not this process's backend) the program has
    the `paged_decode` kernel and no gather of every page of every slot
    (bf16[2048,16,4,128]); elsewhere it has the oracle's gather."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg, params, _ = _step_inputs(one_chip)
    b, table, _, rows = DECODE_CELLS["yi-6b.chat"]
    pool = (1, rows, PS, KV, D)
    k, v = _shapes(one_chip, (pool, BF16), (pool, BF16))
    batch = {"tokens": _i32(one_chip, b, 1),
             "block_tables": _i32(one_chip, b, table),
             "positions": _i32(one_chip, b),
             "write_rows": _i32(one_chip, b),
             "write_offs": _i32(one_chip, b)}
    fn = jax.jit(lambda p, pg, bt: T.decode_step_paged(p, pg, bt, cfg),
                 donate_argnums=(1,))
    hlo = fn.lower(params, {"k": k, "v": v}, batch).compile().as_text()
    kernel = re.search(r"%paged_decode\S* = .*tpu_custom_call", hlo)
    every_page = re.search(
        rf"= bf16\[{b * table},{PS},{KV},{D}\]\S* (gather|fusion)", hlo)
    assert bool(kernel) == (backend == "tpu")
    assert bool(every_page) == (backend == "cpu")


def test_decode_step_with_sampler_compiles(one_chip):
    """The engines' decode program: the paged step with the device
    sampler folded in, returning (B,) int32 tokens."""
    cfg, params, pages = _step_inputs(one_chip)
    batch = {"tokens": _i32(one_chip, B, 1),
             "block_tables": _i32(one_chip, B, TABLE),
             "positions": _i32(one_chip, B),
             "write_rows": _i32(one_chip, B),
             "write_offs": _i32(one_chip, B),
             "temperature": jax.ShapeDtypeStruct((B,), jnp.float32,
                                                 sharding=one_chip),
             "key_data": jax.ShapeDtypeStruct((B, 2), jnp.uint32,
                                              sharding=one_chip)}
    fn = jax.jit(lambda p, pg, b: sample_step(T.decode_step_paged, p, pg,
                                              b, cfg),
                 donate_argnums=(1,))
    out = fn.lower(params, pages, batch).compile()
    tokens = out.out_info[0]
    assert tokens.shape == (B,) and tokens.dtype == jnp.int32


def test_prefill_chunk_compiles(one_chip):
    cfg, params, pages = _step_inputs(one_chip)
    batch = {"tokens": _i32(one_chip, 1, CHUNK),
             "block_tables": _i32(one_chip, 1, TABLE),
             "start": _i32(one_chip, 1),
             "chunk_rows": _i32(one_chip, 1, CHUNK // PS),
             "last_index": _i32(one_chip)}
    fn = jax.jit(lambda p, pg, b: T.prefill_chunk(p, pg, b, cfg),
                 donate_argnums=(1,))
    out = fn.lower(params, pages, batch).compile()
    logits = out.out_info[0]
    assert logits.shape == (1, cfg.vocab_size)


def test_moe_expert_stack_is_read_in_place(one_chip):
    """The dropless expert layer at Mixtral's widths inside a layer
    scan, as the paged programs run it: the grouped matmuls read every
    layer's experts from the stack in place, with no per-layer copy of
    a layer's experts (2.8 GB in bf16) among the temporaries."""
    from repro.models import moe as M
    cfg = configs.get("mixtral-8x7b")
    e, d, ff, n, layers = cfg.n_experts, cfg.d_model, cfg.d_ff, 128, 2
    wi, wg, wd, router, x = _shapes(
        one_chip, ((layers, e, d, ff), BF16), ((layers, e, d, ff), BF16),
        ((layers, e, ff, d), BF16), ((layers, d, e), jnp.float32),
        ((n, d), BF16))

    def step(wi, wg, wd, router, x):
        def layer(x, lr):
            i, r = lr
            y, load = M.moe_dropless(
                {"router": r, "wi": wi, "wg": wg, "wdown": wd}, x,
                cfg.top_k, layer=i)
            return x + y, load
        return jax.lax.scan(layer, x, (jnp.arange(layers), router))
    out = jax.jit(step).lower(wi, wg, wd, router, x).compile()
    assert out.out_info[1].shape == (layers, e)
    assert out.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20
