"""chip_smoke.py on the CPU: it refuses to run, and its phases pass at
the reduced size (the rehearsal of the chip run)."""

import importlib.util
import os
import subprocess
import sys
import textwrap

import repro.configs as configs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _module():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(args, env_extra, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable] + args, capture_output=True,
                          text=True, timeout=timeout, env=env, cwd=REPO)


def test_refuses_to_run_without_a_tpu():
    out = _run([SCRIPT], {})
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_serve_and_kernel_phases_at_reduced_size():
    smoke = _module()
    cfg = configs.get_reduced(smoke.ARCH)
    r = smoke.phase_serve(cfg, n_requests=4, new_tokens=4,
                          prompt_lens=(16, 60), n_pages=64)
    assert r["tokens"] == 16
    assert r["token_gap"] < 1e-4               # float32 at this size
    k = smoke.phase_kernels(cfg, interpret=True, batch=2,
                            table=4, n_rows=32)
    assert set(k) == {"decode_flat", "prefill_flat", "decode_sharded",
                      "prefill_sharded"}


def test_mesh_phase_on_four_virtual_devices():
    out = _run(["-c", textwrap.dedent(f"""
        import importlib.util, sys
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {SCRIPT!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        import repro.configs as configs
        r = smoke.phase_mesh(configs.get_reduced(smoke.ARCH),
                             new_tokens=6, prompt_lens=(16, 60))
        assert r["moved"] > 0 and r["compared"] >= 4, r
        print("MESH_PHASE_OK", r)
    """)], {"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "PYTHONPATH": os.path.join(REPO, "src")})
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MESH_PHASE_OK" in out.stdout


def test_compile_cache_placed_from_outside(monkeypatch):
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        jax.config.update("jax_compilation_cache_dir", before)
        # set from outside: JAX reads it, the code sets no other path
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
