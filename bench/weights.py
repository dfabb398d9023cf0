"""Random weights for a cell, made on the device from the seed in one
jitted call, in the precision they are served in.

The tree has the layout the program's `init_params` gives (found with
`jax.eval_shape`, which allocates nothing); every leaf is drawn here:
projections uniform with the Glorot variance 2 / (fan_in + fan_out),
embeddings with standard deviation 0.02, norm scales 1 +- 0.1.  The
same seed gives the same weights.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def arch_config(cfg: dict):
    """The program's ArchConfig for a configuration file."""
    from repro.models.config import ArchConfig
    fields = {f.name for f in dataclasses.fields(ArchConfig)}
    return ArchConfig(**{k: v for k, v in cfg.items() if k in fields})


def seed_key(seed: int):
    """A PRNG key from any whole seed (also beyond 32 bits)."""
    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


def _leaf(key, name: str, shape, dtype):
    if name == "scale":
        return (1.0 + jax.random.uniform(key, shape, jnp.float32, -0.1, 0.1)
                ).astype(dtype)
    std = 0.02 if name == "embedding" else \
        float(np.sqrt(2.0 / (shape[-2] + shape[-1])))
    a = std * np.sqrt(3.0)
    return jax.random.uniform(key, shape, dtype, -a, a)


def build(arch, seed: int) -> dict:
    """Weights for `arch` (an ArchConfig), one jitted call."""
    from repro.models import transformer as T
    abstract = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), arch))
    flat, tree = jax.tree_util.tree_flatten_with_path(abstract)
    specs = tuple((str(path[-1].key), tuple(a.shape), a.dtype)
                  for path, a in flat)

    @partial(jax.jit, static_argnums=1)
    def make(key, specs):
        return [_leaf(jax.random.fold_in(key, i), name, shape, dt)
                for i, (name, shape, dt) in enumerate(specs)]

    leaves = make(seed_key(seed), specs)
    return jax.tree_util.tree_unflatten(tree, leaves)
