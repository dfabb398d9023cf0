"""Distributed-runtime tests that need >1 device: run in a subprocess
with 8 forced host devices (the main pytest process keeps 1 device per
the dry-run contract)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, timeout=600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_compiled_amr_multidevice_matches_reference():
    out = run_sub("""
        import jax, numpy as np
        from jax.sharding import AxisType
        from repro.amr import wave, compiled as cp
        mesh = jax.make_mesh((4, 2), ('data', 'model'),
                             axis_types=(AxisType.Auto,) * 2)
        prob = wave.WaveProblem(rmax=20.0, amplitude=0.005)
        cfg = cp.CompiledAMRConfig(grain=32, slots=4, n_steps=6)
        step, mk, init, to_g, shd, info = cp.make_uniform_step(
            prob, cfg, mesh, ('data','model'))
        pool = jax.device_put(init(), shd)
        u = to_g(jax.jit(step)(pool))
        ref = cp.reference_uniform(prob, info['n_points'], 6,
                                   info['dr'], info['dt'])
        np.testing.assert_allclose(np.asarray(u), np.asarray(ref),
                                   atol=1e-6)
        print('AMR_OK')
    """)
    assert "AMR_OK" in out


def test_hierarchical_psum_exact():
    out = run_sub("""
        import jax, numpy as np, jax.numpy as jnp
        from jax.sharding import AxisType
        from jax.sharding import PartitionSpec as P
        from repro.distributed.collectives import hierarchical_psum
        mesh = jax.make_mesh((2, 4), ('pod', 'data'),
                             axis_types=(AxisType.Auto,) * 2)
        x = jnp.arange(8.0)
        fn = jax.shard_map(
            lambda v: hierarchical_psum(v, 'pod', 'data'),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False)
        got = fn(x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(x) * 8)
        print('HIER_OK')
    """)
    assert "HIER_OK" in out


def test_compressed_psum_error_feedback():
    out = run_sub("""
        import jax, numpy as np, jax.numpy as jnp
        from jax.sharding import AxisType
        from jax.sharding import PartitionSpec as P
        from repro.distributed.collectives import (
            compressed_cross_pod_psum)
        mesh = jax.make_mesh((8,), ('pod',),
                             axis_types=(AxisType.Auto,))
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(size=(64,)).astype(np.float32))
        def one(x, err):
            return compressed_cross_pod_psum(x, err, 'pod')
        fn = jax.shard_map(one, mesh=mesh, in_specs=(P(), P()),
                        out_specs=(P(), P()), check_vma=False)
        err = jnp.zeros_like(g)
        # accumulated compressed sums converge to accumulated true sums
        acc_c, acc_t = jnp.zeros_like(g), jnp.zeros_like(g)
        for i in range(20):
            s, err = fn(g * (1.0 + 0.01 * i), err)
            acc_c = acc_c + s
            acc_t = acc_t + 8 * g * (1.0 + 0.01 * i)
        rel = float(jnp.linalg.norm(acc_c - acc_t) /
                    jnp.linalg.norm(acc_t))
        assert rel < 0.01, rel
        print('COMP_OK', rel)
    """)
    assert "COMP_OK" in out


def test_sharded_train_step_runs():
    out = run_sub("""
        import jax, numpy as np
        from jax.sharding import AxisType
        import repro.configs as configs
        from repro.launch import steps as S
        from repro.launch.train import make_state
        from repro.models.config import ShapeConfig
        from repro.optim.adamw import AdamWConfig
        from repro.data.pipeline import DataConfig, SyntheticCorpus
        mesh = jax.make_mesh((4, 2), ('data', 'model'),
                             axis_types=(AxisType.Auto,) * 2)
        arch = configs.get_reduced('yi-6b')
        shape = ShapeConfig('t', 64, 8, 'train')
        opt_cfg = AdamWConfig(total_steps=50, warmup_steps=1, lr=5e-3)
        step, n_accum = S.make_train_step(arch, shape, mesh, opt_cfg)
        params, opt = make_state(arch, mesh, opt_cfg)
        corpus = SyntheticCorpus(DataConfig(arch.vocab_size, 64, 8))
        jstep = jax.jit(step, donate_argnums=(0, 1))
        losses = []
        with mesh:
            for i in range(10):
                params, opt, m = jstep(params, opt, corpus.batch_fast(i))
                losses.append(float(m['loss']))
        assert all(np.isfinite(l) for l in losses)
        assert np.mean(losses[-3:]) < np.mean(losses[:3])
        print('TRAIN_OK', losses[0], losses[-1])
    """)
    assert "TRAIN_OK" in out


def test_elastic_checkpoint_across_meshes(tmp_path):
    out = run_sub(f"""
        import jax, numpy as np, jax.numpy as jnp
        from jax.sharding import AxisType
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.checkpoint import Checkpointer
        mesh_a = jax.make_mesh((4, 2), ('data', 'model'),
                             axis_types=(AxisType.Auto,) * 2)
        mesh_b = jax.make_mesh((2, 4), ('data', 'model'),
                             axis_types=(AxisType.Auto,) * 2)
        x = jnp.arange(64.0).reshape(8, 8)
        xa = jax.device_put(x, NamedSharding(mesh_a,
                                             P('data', 'model')))
        ck = Checkpointer({str(tmp_path)!r})
        ck.save(1, {{'w': xa}})
        shard_b = {{'w': NamedSharding(mesh_b, P('model', 'data'))}}
        got, _ = ck.restore(1, {{'w': x}}, shardings=shard_b)
        np.testing.assert_array_equal(np.asarray(got['w']),
                                      np.asarray(x))
        assert got['w'].sharding.spec == P('model', 'data')
        print('ELASTIC_OK')
    """)
    assert "ELASTIC_OK" in out


def test_param_shardings_consistent_on_production_mesh():
    """Rule table produces valid, divisible specs for every arch on a
    small stand-in production mesh."""
    out = run_sub("""
        import jax
        from jax.sharding import AxisType
        import repro.configs as configs
        from repro.launch import steps as S
        mesh = jax.make_mesh((2, 4), ('data', 'model'),
                             axis_types=(AxisType.Auto,) * 2)
        for name in configs.ARCHS:
            arch = configs.get_reduced(name)
            pa = S.abstract_params(arch, mesh)   # raises if indivisible
            n = len(jax.tree.leaves(pa))
            assert n > 0
        print('SPECS_OK')
    """)
    assert "SPECS_OK" in out
