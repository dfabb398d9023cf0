"""Training driver: config -> mesh -> sharded train loop with
checkpointing, failure recovery, and straggler monitoring.

Host-scale runs (this container) use the reduced arch configs on a
(n_devices, 1) mesh; at pod scale the same driver takes the production
mesh — nothing in the loop changes, which is the point of keeping
sharding in specs rather than code.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch yi-6b --reduced \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np


def make_state(arch, mesh, opt_cfg):
    from repro.distributed import sharding as shd
    from repro.launch import steps as S
    from repro.models import transformer as T
    from repro.optim.adamw import init_opt_state

    tp = S.model_tp(arch, mesh)
    params_abs = S.abstract_params(arch, mesh)
    shardings = jax.tree.map(lambda a: a.sharding, params_abs)
    params = jax.jit(
        lambda k: T.init_params(k, arch, tp),
        out_shardings=shardings)(jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    return params, opt


def train(arch, steps: int, batch: int, seq: int,
          ckpt_dir: Optional[str] = None, ckpt_every: int = 20,
          log_every: int = 10, mesh=None, opt_cfg=None,
          fail_plan=None, resume: bool = True):
    from repro.checkpoint.checkpoint import Checkpointer
    from repro.data.pipeline import DataConfig, SyntheticCorpus
    from repro.ft.failures import FailurePlan
    from repro.ft.straggler import StragglerMonitor
    from repro.launch import steps as S
    from repro.launch.mesh import make_host_mesh
    from repro.models.config import ShapeConfig
    from repro.optim.adamw import AdamWConfig

    mesh = mesh or make_host_mesh()
    opt_cfg = opt_cfg or AdamWConfig(total_steps=steps, warmup_steps=max(steps // 20, 1))
    shape = ShapeConfig("host_train", seq, batch, "train")
    corpus = SyntheticCorpus(DataConfig(arch.vocab_size, seq, batch))
    step_fn, n_accum = S.make_train_step(arch, shape, mesh, opt_cfg)
    jstep = jax.jit(step_fn, donate_argnums=(0, 1))
    monitor = StragglerMonitor(mesh.devices.size)
    fail_plan = fail_plan or FailurePlan()
    already_failed: set = set()

    ckpt = Checkpointer(ckpt_dir) if ckpt_dir else None
    params, opt = make_state(arch, mesh, opt_cfg)
    start = 0
    if ckpt and resume and ckpt.latest_step() is not None:
        (params, opt), extra = ckpt.restore(
            ckpt.latest_step(), (params, opt))
        start = int(extra.get("next_step", 0))
        print(f"[train] resumed from step {start}")

    losses = []
    t0 = time.perf_counter()
    i = start
    while i < steps:
        try:
            fail_plan.check(i, already_failed)
            b = corpus.batch_fast(i)
            with mesh:
                params, opt, metrics = jstep(params, opt, b)
            loss = float(metrics["loss"])
            losses.append((i, loss))
            t1 = time.perf_counter()
            monitor.observe([t1 - t0] * mesh.devices.size)
            t0 = t1
            if i % log_every == 0:
                print(f"[train] step {i:5d} loss {loss:.4f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"gnorm {float(metrics['grad_norm']):.3f}",
                      flush=True)
            i += 1
            if ckpt and i % ckpt_every == 0:
                ckpt.save_async(i, (params, opt),
                                extra={"next_step": i})
        except Exception as e:
            from repro.ft.failures import InjectedFailure
            if not isinstance(e, InjectedFailure) or ckpt is None:
                raise
            print(f"[train] FAILURE at step {i}: {e}; restarting")
            ckpt.wait()
            latest = ckpt.latest_step()
            params, opt = make_state(arch, mesh, opt_cfg)
            if latest is not None:
                (params, opt), extra = ckpt.restore(latest,
                                                    (params, opt))
                i = int(extra.get("next_step", 0))
            else:
                i = 0
    if ckpt:
        ckpt.wait()
    return params, opt, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir")
    ap.add_argument("--ckpt-every", type=int, default=20)
    args = ap.parse_args()

    import repro.configs as configs
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    arch = configs.get_reduced(args.arch) if args.reduced \
        else configs.get(args.arch)
    _, _, losses = train(arch, args.steps, args.batch, args.seq,
                         args.ckpt_dir, args.ckpt_every)
    first = np.mean([l for _, l in losses[:5]])
    last = np.mean([l for _, l in losses[-5:]])
    print(f"[train] loss {first:.4f} -> {last:.4f}")


if __name__ == "__main__":
    main()
