"""AMR application: physics convergence, engine equivalence, cone."""

import numpy as np
import pytest
import jax.numpy as jnp

from repro import amr
from repro.amr import hierarchy as hi
from repro.amr import taskgraph as tg
from repro.core.scheduler import barrier_schedule, list_schedule


@pytest.fixture(scope="module")
def prob():
    return amr.WaveProblem(n_points=128, rmax=20.0, amplitude=0.005)


def test_initial_data_shapes(prob):
    u = amr.initial_data(prob)
    assert u.shape == (3, prob.n_points)
    assert float(amr.linf(u)) > 0


def test_uniform_evolution_stable(prob):
    u = amr.initial_data(prob)
    r = amr.grid(prob)
    for _ in range(100):
        u = amr.global_step(u, r, prob.dr, prob.dt, prob.p)
    assert np.all(np.isfinite(np.asarray(u)))
    # the pulse disperses/propagates; energy stays bounded
    assert float(amr.energy(u, r, prob.dr)) < 10.0


def test_spatial_convergence_second_order():
    """RK3+FD2 at fixed CFL -> observed order ~2 as dr -> 0."""
    import jax
    errs = []
    for n in (129, 257, 513):
        p = amr.WaveProblem(n_points=n, rmax=16.0, amplitude=0.003,
                            dtype="float64", cfl=0.2)
        with jax.enable_x64(True):
            u = amr.initial_data(p)
            r = amr.grid(p)
            t_target = 0.5
            n_steps = int(round(t_target / p.dt))
            for _ in range(n_steps):
                u = amr.global_step(u, r, p.dr, p.dt, p.p)
            errs.append((p.dr, np.asarray(u)))
    # Richardson: compare coarse vs fine restricted
    e1 = np.abs(errs[0][1][0] - errs[1][1][0][::2]).max()
    e2 = np.abs(errs[1][1][0] - errs[2][1][0][::2]).max()
    order = np.log2(e1 / max(e2, 1e-300))
    assert order > 1.6, f"observed order {order}"


@pytest.mark.parametrize("grain", [4, 16, 64])
@pytest.mark.parametrize("levels", [1, 2, 3])
def test_dataflow_equals_lockstep(prob, grain, levels):
    specs = amr.default_specs(prob, levels)
    ref = hi.run_ops_lockstep(
        amr.make_hierarchy(prob, specs),
        hi.enumerate_window_ops(levels, 2), prob)
    wg = tg.build_window_graph(specs, 2, grain)
    out = tg.run_window(wg, amr.make_hierarchy(prob, specs), prob)
    for l in range(levels):
        a, b = specs[l].proper_extent
        np.testing.assert_allclose(
            np.asarray(out[l].arr[:, a:b]),
            np.asarray(ref[l].arr[:, a:b]), atol=1e-6)


def test_random_topological_order_determinism(prob):
    specs = amr.default_specs(prob, 2)
    wg = tg.build_window_graph(specs, 2, 16)
    g = wg.graph
    rng = np.random.default_rng(7)

    def random_order():
        indeg = [len(t.deps) for t in g.tasks]
        ready = [t.tid for t in g.tasks if not t.deps]
        order = []
        while ready:
            i = rng.integers(len(ready))
            tid = ready.pop(i)
            order.append(tid)
            for s in g.tasks[tid].succs:
                indeg[s] -= 1
                if indeg[s] == 0:
                    ready.append(s)
        return order

    outs = []
    for _ in range(3):
        st = amr.make_hierarchy(prob, specs)
        res = tg.run_window(wg, st, prob, order=random_order())
        outs.append(np.concatenate(
            [np.asarray(s.arr) for s in res], axis=-1))
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])


def test_engines_agree_and_dataflow_wins(prob):
    specs = amr.default_specs(prob, 3)
    cfg = amr.EngineConfig(grain=8, n_workers=8)
    df, ba = amr.compare_engines(prob, specs, 3, cfg)
    assert df.makespan <= ba.makespan
    # with multiple levels + workers the win should be substantial
    assert ba.makespan / df.makespan > 1.5


def test_single_worker_no_benefit(prob):
    """Paper: 'When computing on just one processor, removing the
    timestep barrier has no performance impact'."""
    specs = amr.default_specs(prob, 2)
    cfg = amr.EngineConfig(grain=16, n_workers=1, barrier_cost=0.0)
    df, ba = amr.compare_engines(prob, specs, 2, cfg)
    assert df.makespan == pytest.approx(ba.makespan, rel=1e-6)


def test_cone_shape(prob):
    """Fig 5: the timestep front dips at the refined region.

    Uses FIFO queue priority (the paper's HPX scheduler); the default
    critical-path priority deliberately inverts the cone by racing the
    fine region ahead — that is the beyond-paper scheduler, compared in
    benchmarks/fig5_cone.py.
    """
    specs = amr.default_specs(prob, 3)
    wg = tg.build_window_graph(specs, 4, 8)
    tg.assign_owners(wg, 4)
    r = list_schedule(wg.graph, 4, overhead=4e-6,
                      priority=lambda t: t.tid)
    front = tg.timestep_front(wg, r.finish, r.makespan * 0.5,
                              prob.n_points)
    assert front.min() >= 0 and front.max() <= 4 + 1e-9
    fine = specs[2]
    fine_pts = slice(fine.lo // 4 + 2, fine.hi // 4 - 2)
    coarse_only = np.r_[front[:specs[1].lo // 2 - 2]]
    if len(coarse_only) and front[fine_pts].size:
        assert front[fine_pts].mean() <= coarse_only.mean() + 1e-9


def test_regrid_tracks_pulse(prob):
    from repro.amr import regrid as rg
    specs = [hi.LevelSpec(0, 0, prob.n_points, True, True)]
    states = amr.make_hierarchy(prob, specs)
    new_specs = rg.propose_specs(states, prob, 1e-4, 3)
    assert len(new_specs) >= 2
    lvl1 = new_specs[1]
    pulse_idx = 2 * int(prob.r0 / prob.dr)
    assert lvl1.lo <= pulse_idx <= lvl1.hi
    states2 = rg.transfer(states, new_specs, prob)
    for s in states2:
        assert np.all(np.isfinite(np.asarray(s.arr)))


def test_barrier_phases_respect_deps(prob):
    specs = amr.default_specs(prob, 2)
    wg = tg.build_window_graph(specs, 2, 16)
    tg.assign_owners(wg, 4)
    barrier_schedule(wg.graph, 4)   # raises on phase violations
