"""Sharded AGAS page pool (DESIGN.md §4c): locality-aware allocation,
(locality, slot) row encoding, migration name-stability, greedy-decode
parity across shard counts and across forced migrations, and the
device-backed mesh path (subprocess, 8 forced host devices)."""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import repro.configs as configs
from repro.models import transformer as T
from repro.serving.engine import ChunkedPagedServingEngine, Request
from repro.serving.kvcache import PageExhausted, PagePool

RNG = np.random.default_rng(17)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(name="yi-6b"):
    return configs.get_reduced(name)


# -- the sharded allocator ---------------------------------------------

def test_pool_least_loaded_alloc_and_row_encoding():
    pool = PagePool(_cfg(), n_pages=8, page_size=4, n_shards=2)
    assert pool.pages["k"].shape[1:3] == (2, 5)      # (S, R)
    addrs = [pool.alloc() for _ in range(6)]
    # least-loaded-first keeps the shards balanced as allocs arrive
    assert pool.shard_used() == [3, 3]
    for a in addrs:
        loc, slot = pool.agas.lookup(a)
        assert pool.row(a) == loc * pool.rows_per_shard + slot
        assert slot < pool.pages_per_shard       # never the null slot
    # global free count stays the admission signal
    assert pool.free_pages == 2
    [pool.alloc() for _ in range(2)]
    with pytest.raises(PageExhausted):
        pool.alloc()


def test_pool_rejects_indivisible_shard_count():
    with pytest.raises(ValueError, match="multiple"):
        PagePool(_cfg(), n_pages=10, page_size=4, n_shards=3)


def test_migration_keeps_global_name_and_moves_content():
    cfg = _cfg()
    pool = PagePool(cfg, n_pages=4, page_size=4, n_shards=2)
    addr = pool.alloc()
    L, kvh, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    span = jnp.full((L, 1, 4, kvh, hd), 7.0, pool.pages["k"].dtype)
    pool.write_pages([pool.row(addr)], span, span)
    gid, row0 = addr.gid, pool.row(addr)
    src = pool.agas.locality_of(addr)
    pool.migrate_pages({addr: 1 - src})
    # the AGAS promise: the name survives, only (locality, slot) moved
    assert addr.gid == gid
    assert pool.agas.locality_of(addr) == 1 - src
    assert pool.row(addr) != row0
    loc, slot = pool.agas.lookup(addr)
    got = np.asarray(pool.pages["k"])[0, loc, slot]
    np.testing.assert_array_equal(got, 7.0)
    assert pool.page_migrations == 1


def test_plan_rebalance_moves_only_unpinned_pages():
    pool = PagePool(_cfg(), n_pages=12, page_size=4, n_shards=2)
    # skew shard 0 with explicit-locality allocations
    skew = [pool.alloc(0) for _ in range(5)]
    pool.incref(skew[0])                 # shared -> pinned to owner
    assert pool.shard_used() == [5, 0]
    moves = pool.plan_rebalance(tolerance=1)
    assert skew[0] not in moves          # prefix-shared pages stay put
    pool.migrate_pages(moves)
    used = pool.shard_used()
    assert max(used) - min(used) <= 1
    assert pool.agas.locality_of(skew[0]) == 0


# -- kernels on the sharded layout -------------------------------------

@pytest.mark.parametrize("window", [0, 6])
def test_kernels_sharded_layout_matches_flat(window):
    """The (S, R, ps, KV, D) layout with locality*R+slot rows must
    reproduce the flat (N, ps, KV, D) layout bit for bit — in the jnp
    oracles and in the Pallas kernels."""
    from repro.kernels.attention.ops import (paged_attention,
                                             paged_prefill_attention)
    from repro.kernels.attention.ref import (
        paged_attention_ref, paged_prefill_attention_ref)
    b, h, kvh, d, ps, S, R = 3, 4, 2, 16, 8, 2, 5
    kp = jnp.asarray(RNG.normal(size=(S, R, ps, kvh, d)), jnp.float32)
    vp = jnp.asarray(RNG.normal(size=(S, R, ps, kvh, d)), jnp.float32)
    kp_f, vp_f = (x.reshape(S * R, ps, kvh, d) for x in (kp, vp))
    tables = jnp.asarray(RNG.integers(0, S * R, size=(b, 4)), jnp.int32)
    pos = jnp.asarray([3, 17, 30], jnp.int32)
    q = jnp.asarray(RNG.normal(size=(b, 1, h, d)), jnp.float32)
    ref = paged_attention_ref(q, kp_f, vp_f, tables, pos, window=window)
    got_ref = paged_attention_ref(q, kp, vp, tables, pos, window=window)
    np.testing.assert_array_equal(np.asarray(got_ref), np.asarray(ref))
    got_pl = paged_attention(q, kp, vp, tables, pos, window=window)
    np.testing.assert_allclose(np.asarray(got_pl), np.asarray(ref),
                               atol=1e-5)
    qq = jnp.asarray(RNG.normal(size=(b, 8, h, d)), jnp.float32)
    start = jnp.asarray([0, 8, 21], jnp.int32)
    pref = paged_prefill_attention_ref(qq, kp_f, vp_f, tables, start,
                                       window=window)
    pgot = paged_prefill_attention_ref(qq, kp, vp, tables, start,
                                       window=window)
    np.testing.assert_array_equal(np.asarray(pgot), np.asarray(pref))
    ppl = paged_prefill_attention(qq, kp, vp, tables, start,
                                  window=window)
    np.testing.assert_allclose(np.asarray(ppl), np.asarray(pref),
                               atol=1e-5)


# -- engine parity across shard counts and migrations ------------------

def _parity_requests(cfg, seed=3):
    rng = np.random.default_rng(seed)
    lens = [5, 40, 20, 12]               # < 1 page and > 1 chunk
    return [Request(rid, rng.integers(0, cfg.vocab_size, size=n)
                    .astype(np.int32), max_new_tokens=6)
            for rid, n in enumerate(lens)]


_KW = dict(slots=4, max_len=96, prefill_buckets=(64,), page_size=16,
           chunk_size=32)


def _run_engine(params, cfg, reqs, **kw):
    eng = ChunkedPagedServingEngine(params, cfg, **_KW, **kw)
    for r in reqs:
        eng.submit(r)
    eng.run_to_completion()
    return eng, {c.rid: c.tokens for c in eng.completions}


def test_greedy_parity_across_shard_counts():
    """Greedy decode is token-identical for n_shards in {1, 2, 4}: the
    shard layout relocates pages, never changes what a slot attends.
    (Same separately-compiled-executables seed caveat as the other
    parity tests.)"""
    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = _parity_requests(cfg)
    results = {}
    for ns in (1, 2, 4):
        eng, toks = _run_engine(params, cfg, reqs, kv_shards=ns)
        results[ns] = toks
        assert eng.kvc.pool.used_pages == 0
        s = eng.stats()
        assert s["kv_shards"] == ns
        assert len(s["shard_pages_used"]) == ns
    assert results[1] == results[2] == results[4]


def test_forced_mid_decode_migration_preserves_outputs():
    """Rotate every movable page to the next shard mid-decode: block
    tables re-resolve through the directory and every affected
    request's output is unchanged — the end-to-end rendering of the
    name-stability promise."""
    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = _parity_requests(cfg)
    _, baseline = _run_engine(params, cfg, reqs)
    eng = ChunkedPagedServingEngine(params, cfg, kv_shards=4, **_KW)
    futs = [eng.submit(r) for r in reqs]
    for _ in range(3):
        eng.step()                      # prompts resident, mid-decode
    assert eng.active
    moved = eng.force_migrate()
    assert moved > 0
    eng.run_to_completion()
    assert {c.rid: c.tokens for c in eng.completions} == baseline
    s = eng.stats()
    assert s["page_migrations"] >= moved
    for r, f in zip(reqs, futs):
        assert f.done() and f.get().rid == r.rid


def test_imbalance_triggers_rebalance_between_steps():
    """Pool-imbalance-triggered migration: skewing the shards past the
    tolerance makes the next step() migrate pages — and the trace's
    outputs stay identical to an undisturbed run."""
    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    reqs = _parity_requests(cfg)
    _, baseline = _run_engine(params, cfg, reqs)
    eng = ChunkedPagedServingEngine(params, cfg, kv_shards=2,
                                    rebalance_tolerance=2, **_KW)
    for r in reqs:
        eng.submit(r)
    for _ in range(3):
        eng.step()
    # skew shard 0 well past the tolerance with held pages
    held = [eng.kvc.pool.alloc(0) for _ in range(6)]
    assert eng.kvc.pool.page_migrations == 0
    eng.step()                           # rebalances before admitting
    assert eng.kvc.pool.page_migrations > 0
    eng.run_to_completion()
    assert {c.rid: c.tokens for c in eng.completions} == baseline
    for a in held:
        eng.kvc.pool.decref(a)
    assert eng.kvc.pool.used_pages == 0


def test_stats_report_per_shard_occupancy_mid_run():
    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    eng = ChunkedPagedServingEngine(params, cfg, kv_shards=2, **_KW)
    for r in _parity_requests(cfg):
        eng.submit(r)
    for _ in range(3):
        eng.step()
    s = eng.stats()
    pool = eng.kvc.pool
    assert sum(s["shard_pages_used"]) == pool.used_pages > 0
    assert len(s["shard_occupancy"]) == 2
    assert all(0.0 <= o <= 1.0 for o in s["shard_occupancy"])
    eng.run_to_completion()
    assert sum(eng.stats()["shard_pages_used"]) == 0


# -- the device-backed mesh path (8 forced host devices) ---------------

def run_sub(code: str, timeout=900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_mesh_backed_shards_parity_and_ppermute_migration():
    """One locality per device along the "kv" mesh axis: the page
    arrays carry a NamedSharding over 8 simulated host devices, forced
    migration executes as lax.ppermute legs under shard_map, and greedy
    outputs match the single-locality engine token for token."""
    out = run_sub("""
        import numpy as np, jax
        import repro.configs as configs
        from repro.models import transformer as T
        from repro.serving.engine import (ChunkedPagedServingEngine,
                                          Request)
        from repro.distributed.sharding import kv_pool_mesh

        cfg = configs.get_reduced('yi-6b')
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(3)
        reqs = [Request(rid, rng.integers(0, cfg.vocab_size, size=n)
                        .astype(np.int32), max_new_tokens=6)
                for rid, n in enumerate([5, 40, 20, 12])]
        kw = dict(slots=4, max_len=96, prefill_buckets=(64,),
                  page_size=16, chunk_size=32)

        base = ChunkedPagedServingEngine(params, cfg, **kw)
        for r in reqs: base.submit(r)
        base.run_to_completion()
        ref = {c.rid: c.tokens for c in base.completions}

        mesh = kv_pool_mesh(4)
        assert mesh is not None and mesh.shape['kv'] == 4
        eng = ChunkedPagedServingEngine(params, cfg, kv_shards=4,
                                        mesh=mesh, **kw)
        spec = eng.kvc.pool.pages['k'].sharding.spec
        assert spec[1] == 'kv', spec     # locality axis on the mesh
        for r in reqs: eng.submit(r)
        for _ in range(3): eng.step()
        moved = eng.force_migrate()      # lax.ppermute under shard_map
        assert moved > 0
        eng.run_to_completion()
        got = {c.rid: c.tokens for c in eng.completions}
        assert got == ref
        s = eng.stats()
        assert s['page_migrations'] >= moved
        assert len(s['shard_occupancy']) == 4
        print('MESH_SHARDED_OK', moved)
    """)
    assert "MESH_SHARDED_OK" in out


def test_decode_attention_follows_platform_mesh_and_head_dim():
    """`paged_decode_attention` picks the Pallas kernel only where the
    platform is a TPU (the test says so: this process runs on the CPU),
    the pool lives on one device, flat or with its localities on that
    device, and the head dim is whole 128-lane rows; a pool over a
    4-device mesh, a 64-wide head or the CPU takes the oracle.  Inside
    a jitted step the pool is a tracer: its sharding decides."""
    out = run_sub("""
        import jax, jax.numpy as jnp
        from repro.models import transformer as T
        from repro.kernels.attention.ops import paged_attention
        from repro.kernels.attention.ref import paged_attention_ref
        from repro.distributed.sharding import (kv_pool_mesh,
                                                page_pool_shardings)
        flat = jnp.zeros((1, 9, 16, 2, 128))
        local = jnp.zeros((1, 4, 9, 16, 2, 128))
        meshed = jax.device_put(local, page_pool_shardings(kv_pool_mesh(4)))
        narrow = jnp.zeros((1, 9, 16, 2, 64))

        def pick(pool):
            got = []
            jax.jit(lambda p: got.append(T.paged_decode_attention(p))
                    or p)(pool)
            return {paged_attention: 'kernel',
                    paged_attention_ref: 'oracle'}[got[0]]
        seen = [pick(p) for p in (flat, local, meshed, narrow)]
        jax.default_backend = lambda: 'tpu'
        seen += [pick(p) for p in (flat, local, meshed, narrow)]
        print('PICKED', ' '.join(seen))
    """)
    assert "PICKED oracle oracle oracle oracle " \
        "kernel kernel oracle oracle" in out
