"""Paper Fig 9: thread-management overhead and its amortization.

Two measurements:

1. MEASURED host-engine overhead: wall-clock per task of the dataflow
   executor (core/lco.DependencyCounter firing through
   execute_topologically) on zero-work tasks — our analogue of the
   HPX-thread 3-5 us management cost, measured on this machine.

2. The Fig 9 sweep on the execution model: average per-task overhead
   vs worker count for artificial workloads of 0/15/45/115 us, one
   chain-free graph of N tasks; reports the scaling factor at 44
   workers for the 115 us load (paper: ~23x).

3. COMPILED-engine overhead: per-task cost of the compiled wavefront
   (rounds lowered to one XLA program) — scheduling decisions are
   compile-time constants, so the per-task runtime overhead is the
   amortized launch cost only (DESIGN.md §2/§5).
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.common import emit
from repro.core import TaskGraph, execute_topologically, list_schedule


def measured_dispatch_overhead(n_tasks=20000):
    g = TaskGraph()
    prev = None
    for i in range(n_tasks):
        deps = [prev] if prev is not None and i % 7 == 0 else []
        tid = g.add(0.0, phase=i, deps=deps)
        prev = tid
    sink = [0]

    def run(task):
        sink[0] += 1

    t0 = time.perf_counter()
    execute_topologically(g, run)
    dt = time.perf_counter() - t0
    assert sink[0] == n_tasks
    return dt / n_tasks


def fig9_sweep(n_tasks=100_000, verbose=True, sigma=None):
    if sigma is None:
        sigma = measured_dispatch_overhead()
    workloads = [0.0, 15e-6, 45e-6, 115e-6]
    workers = [2, 4, 8, 16, 32, 44, 48]
    out = {}
    for w_us in workloads:
        g = TaskGraph()
        for i in range(n_tasks // 10):   # model is per-task: scale ok
            g.add(w_us, phase=0)
        row = []
        for p in workers:
            r = list_schedule(g, p, overhead=sigma)
            # average overhead per thread, as plotted in Fig 9:
            # (makespan*P - useful work) / n_tasks
            avg_ovh = (r.makespan * p - g.work()) / len(g)
            row.append((p, r.makespan, avg_ovh))
        out[w_us] = row
        if verbose:
            print(f"# fig9 load={w_us * 1e6:5.1f}us  " + " ".join(
                f"P{p}:{o * 1e6:.2f}us" for p, _, o in row))
    # scaling factor at 44 workers for the heaviest load
    heavy = out[115e-6]
    t1 = [m for p, m, _ in heavy if p == 2][0] * 2   # serial estimate
    t44 = [m for p, m, _ in heavy if p == 44][0]
    scaling = t1 / t44
    return sigma, scaling, out


def compiled_overhead():
    """Per-task overhead of the compiled engine: one jitted step over
    a pool of blocks vs the same compute as per-block python calls."""
    import jax
    import jax.numpy as jnp

    from repro.amr.compiled import CompiledAMRConfig, make_uniform_step
    from repro.amr.wave import WaveProblem

    from jax.sharding import AxisType

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    prob = WaveProblem(rmax=20.0, amplitude=0.005)
    cfg = CompiledAMRConfig(grain=64, slots=32, n_steps=8)
    step, mk, init, to_g, shd, info = make_uniform_step(
        prob, cfg, mesh, ("data", "model"))
    jstep = jax.jit(step)
    pool = init()
    jstep(pool)[0].block_until_ready() if hasattr(
        jstep(pool), '__getitem__') else None
    t0 = time.perf_counter()
    reps = 20
    for _ in range(reps):
        pool = jstep(pool)
    jax.block_until_ready(pool)
    dt = time.perf_counter() - t0
    n_task_execs = cfg.slots * cfg.n_steps * reps
    return dt / n_task_execs


def run(verbose=True, out=None):
    from benchmarks.common import emit_registry
    from repro.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    disp = reg.histogram("fig9.host_dispatch_us")
    for _ in range(3):
        disp.record(measured_dispatch_overhead(n_tasks=5000) * 1e6)
    sigma = disp.quantile(50) * 1e-6   # median of the repeats
    _, scaling, _ = fig9_sweep(verbose=verbose, sigma=sigma)
    comp = compiled_overhead()
    reg.gauge("fig9.scaling_factor_44w_115us").set(scaling)
    reg.gauge("fig9.compiled_per_task_us").set(comp * 1e6)
    if verbose:
        print(f"# fig9 measured host dispatch overhead: "
              f"{sigma * 1e6:.2f} us/task (paper: 3-5 us)")
        print(f"# fig9 scaling factor at 44 workers, 115us load: "
              f"{scaling:.1f} (paper: ~23)")
        print(f"# fig9 compiled-engine per-task time: "
              f"{comp * 1e6:.2f} us (scheduling overhead ~0, "
              f"amortized launch only)")
    emit("fig9_host_dispatch_overhead", sigma * 1e6, "us_per_task")
    emit("fig9_scaling_factor_44w_115us", scaling, "paper_23")
    emit("fig9_compiled_per_task", comp * 1e6, "us_per_task")
    emit_registry(reg)
    if out:
        import json
        with open(out, "w") as f:
            json.dump(reg.snapshot(), f, indent=1, sort_keys=True)
        if verbose:
            print(f"# fig9 registry snapshot -> {out}")
    return sigma, scaling, comp


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write the metrics-registry snapshot JSON")
    run(out=ap.parse_args().out)
