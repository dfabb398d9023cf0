"""The compiled ParalleX engine: block pools + parcel halo exchange in
one XLA program (shard_map over the production mesh).

This is the TPU-native rendering of DESIGN.md §2: the dataflow LCO
graph of a window is erased into a static program where

  * AGAS placement  -> the (locality, slot) layout of the block pool
                       array (n_localities, slots, 3, grain);
  * parcels         -> `lax.ppermute` legs moving halo payloads between
                       localities (2 legs for contiguous placement);
  * LCO/dataflow    -> HLO data dependence between rounds;
  * HPX threads     -> vmap'd fused-RK3 block tasks (one batched kernel
                       launch per round — per-task overhead is zero).

The per-device pool axis is the "work queue": every round each locality
executes its `slots` resident tasks as one vectorized kernel.  With the
default contiguous AGAS placement only the pool-edge blocks exchange
inter-locality parcels, so the collective term is 2 * H * 3 * 4 bytes
per round per locality — the number the roofline analysis reports.

The uniform (single-level) configuration compiles for any mesh size and
is the AMR entry in the multi-pod dry-run; multi-level compiled
execution is represented by the measured-schedule engines (see
DESIGN.md §9 note 1).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.amr.wave import H, NFIELDS, WaveProblem, fused_rk3_block


@dataclasses.dataclass(frozen=True)
class CompiledAMRConfig:
    """Static layout: n_localities x slots blocks of `grain` points."""

    grain: int = 256
    slots: int = 8              # blocks resident per locality
    n_steps: int = 8            # steps fused into one program
    use_pallas: bool = False    # stencil kernel backend (kernels/stencil)
    # Communication-avoiding fusion (§Perf hillclimb, AMR cell): carry
    # a 3k-cell halo and take k RK3 steps per parcel exchange.  Parcels
    # per step drop k-fold and the block stays VMEM-resident across the
    # k steps (HBM term ~ 1/k); extra compute is the shrinking-halo
    # overlap, fraction ~ 3k(k+1)/grain.
    steps_per_exchange: int = 1

    def n_blocks(self, n_loc: int) -> int:
        return n_loc * self.slots

    def n_points(self, n_loc: int) -> int:
        return self.n_blocks(n_loc) * self.grain


def _block_step_vmapped(pool_ext: jnp.ndarray, r_ext: jnp.ndarray,
                        left_phys: jnp.ndarray, right_phys: jnp.ndarray,
                        dr: float, dt: float, p: int,
                        use_pallas: bool) -> jnp.ndarray:
    """(slots, 3, g+2H) -> (slots, 3, g), one fused RK3 per resident block."""
    if use_pallas:
        from repro.kernels.stencil.ops import stencil_rk3_step
        return stencil_rk3_step(pool_ext, r_ext, left_phys, right_phys,
                                dr=dr, dt=dt, p=p)
    fn = lambda u, r, lp, rp: fused_rk3_block(u, r, dr, dt, p, lp, rp)
    return jax.vmap(fn)(pool_ext, r_ext, left_phys, right_phys)


def make_uniform_step(prob: WaveProblem, cfg: CompiledAMRConfig,
                      mesh: Mesh, axis_names: Tuple[str, ...]):
    """Build the shard_map'd n-step evolution for a uniform grid.

    Returns (step_fn, make_inputs, sharding) where step_fn(pool) -> pool
    advances cfg.n_steps steps.  pool has shape
    (n_localities, slots, NFIELDS, grain) sharded over axis 0.
    """
    n_loc = int(np.prod([mesh.shape[a] for a in axis_names]))
    g = cfg.grain
    S = cfg.slots
    n_pts = cfg.n_points(n_loc)
    dr = prob.rmax / (n_pts - 1)
    dt = prob.cfl * dr
    dtype = prob.jnp_dtype()

    spec = P(axis_names)  # leading dim sharded over all given axes
    sharding = NamedSharding(mesh, spec)

    K = cfg.steps_per_exchange
    HK = H * K
    if cfg.n_steps % K:
        raise ValueError("n_steps must be a multiple of "
                         "steps_per_exchange")
    if HK > g:
        raise ValueError("halo exceeds grain: lower steps_per_exchange")

    def local_step(pool: jnp.ndarray) -> jnp.ndarray:
        """Per-locality body: one exchange + K fused RK3 steps.

        pool: (1, S, 3, g) (sharded block).
        """
        pool = pool[0]                       # (S, 3, g)
        loc = lax.axis_index(axis_names)     # flattened locality id

        # --- parcels: pool-edge halo exchange (2 ppermute legs) -------
        # Right-moving leg: my last block's right edge -> next locality.
        right_edge = pool[-1, :, -HK:]       # (3, HK)
        left_edge = pool[0, :, :HK]
        fwd = [(i, (i + 1) % n_loc) for i in range(n_loc)]
        bwd = [((i + 1) % n_loc, i) for i in range(n_loc)]
        from_left = lax.ppermute(right_edge, axis_names, fwd)
        from_right = lax.ppermute(left_edge, axis_names, bwd)

        # --- assemble extended blocks (S, 3, g+2HK) --------------------
        # Intra-locality halos come from pool neighbours (an AGAS-local
        # lookup); the pool boundary slots splice in the parcels.
        lefts = jnp.concatenate(
            [from_left[None], pool[:-1, :, -HK:]], axis=0)
        rights = jnp.concatenate(
            [pool[1:, :, :HK], from_right[None]], axis=0)
        u = jnp.concatenate([lefts, pool, rights], axis=-1)

        # --- physical-boundary masks ----------------------------------
        slot_ids = jnp.arange(S)
        left_phys = (loc == 0) & (slot_ids == 0)
        right_phys = (loc == n_loc - 1) & (slot_ids == S - 1)

        # --- radial coordinates per block -----------------------------
        blk0 = (loc * S + slot_ids) * g       # (S,) global start index
        r_full = (blk0[:, None] +
                  jnp.arange(-HK, g + HK, dtype=dtype)[None, :]) * dr

        # --- K fused steps, validity shrinking by H per side ----------
        for i in range(K):
            r_ext = r_full[:, H * i: r_full.shape[1] - H * i]
            u = _block_step_vmapped(
                u, r_ext, left_phys[:, None, None],
                right_phys[:, None, None], dr, dt, prob.p,
                cfg.use_pallas)
        return u[None]                        # (1, S, 3, g)

    inner = jax.shard_map(local_step, mesh=mesh, in_specs=(spec,),
                          out_specs=spec, check_vma=False)

    def step_fn(pool: jnp.ndarray) -> jnp.ndarray:
        def body(p_, _):
            return inner(p_), None
        out, _ = lax.scan(body, pool, None, length=cfg.n_steps // K)
        return out

    def make_inputs() -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct((n_loc, S, NFIELDS, g), dtype,
                                    sharding=sharding)

    def initial_pool() -> jnp.ndarray:
        """Concrete initial data laid out into the pool (host-side)."""
        from repro.amr.wave import initial_data
        u = initial_data(prob, level_dr=dr, n=n_pts)     # (3, n_pts)
        blocks = u.reshape(NFIELDS, n_loc, S, g)
        return jnp.transpose(blocks, (1, 2, 0, 3))

    def to_global(pool: jnp.ndarray) -> jnp.ndarray:
        return jnp.transpose(pool, (2, 0, 1, 3)).reshape(NFIELDS, n_pts)

    return step_fn, make_inputs, initial_pool, to_global, sharding, dict(
        n_loc=n_loc, grain=g, slots=S, n_points=n_pts, dr=dr, dt=dt)


def reference_uniform(prob: WaveProblem, n_pts: int, n_steps: int,
                      dr: float, dt: float) -> jnp.ndarray:
    """Global jnp oracle for the compiled engine (tests)."""
    from repro.amr.wave import global_step, initial_data

    u = initial_data(prob, level_dr=dr, n=n_pts)
    r = jnp.arange(n_pts, dtype=u.dtype) * dr
    for _ in range(n_steps):
        u = global_step(u, r, dr, dt, prob.p)
    return u
