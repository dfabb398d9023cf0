"""Sharding rules: parameter/activation PartitionSpecs by path name.

The production mesh is ("data", "model") single-pod or
("pod", "data", "model") multi-pod (launch/mesh.py).  Parallelism plan
(DESIGN.md §6):

  DP    batch over ("pod", "data")
  TP    heads / d_ff / vocab over "model"
  EP    (virtual) experts over "model"
  SP    decode KV caches: sequence over "model" (context parallelism)
  FSDP  for archs >= fsdp_threshold params: the non-"model" weight dim
        additionally sharded over "data"; optimizer states always
        follow the param spec (ZeRO via GSPMD).

Rules match on the last path segments of each parameter, so the same
table covers flat stacks (dense "layers/attn/wq") and nested stacks
(vlm "groups_self/attn/wq"); leading stack dims are unsharded (scan
slices them per layer).
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models.config import ArchConfig, ShapeConfig


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def axis_size(mesh: Mesh, *names: str) -> int:
    return int(np.prod([mesh.shape[n] for n in names]))


# Rule table: (path regex) -> spec for the TRAILING dims of the leaf.
# "F" is replaced by the fsdp axis ("data") or None.
_RULES = [
    # embeddings: input D-sharded (local lookup); output vocab-sharded.
    (r"out_embed/embedding$", ("model", "F")),
    (r"(^|/)embed/embedding$", ("VOCAB_OR_D",)),   # special-cased below
    (r"patch_proj/w$", (None, "model")),
    # attention
    (r"attn/wq$", ("F", "model")),
    (r"attn/wk$", ("F", "KV")),
    (r"attn/wv$", ("F", "KV")),
    (r"attn/wo$", ("model", "F")),
    # mlp
    (r"mlp/wi$", ("F", "model")),
    (r"mlp/wg$", ("F", "model")),
    (r"mlp/wdown$", ("model", "F")),
    # moe (virtual-expert stacked)
    (r"moe/router$", ("F", None)),
    (r"moe/wi$", ("model", "F", None)),
    (r"moe/wg$", ("model", "F", None)),
    (r"moe/wdown$", ("model", None, "F")),
    # ssm
    (r"ssm/in_proj$", ("F", "model")),
    (r"ssm/out_proj$", ("model", "F")),
    (r"ssm/conv_w$", ("model", None)),
    (r"ssm/x_proj$", ("model", None)),
    (r"ssm/dt_proj$", (None, "model")),
    (r"ssm/dt_bias$", ("model",)),
    (r"ssm/a_log$", ("model", None)),
    (r"ssm/d_skip$", ("model",)),
    # zamba2 per-group adapters
    (r"adapters/w$", ("F", "model")),
    # norms / scalars: replicated
    (r"norm/scale$", (None,)),
    (r"gate$", ()),
]


def _leaf_spec(path: str, ndim: int, arch: ArchConfig, mesh: Mesh,
               fsdp: bool) -> P:
    f = "data" if fsdp else None
    m = mesh.shape["model"]
    for pat, trailing in _RULES:
        if re.search(pat, path):
            if trailing == ("VOCAB_OR_D",):
                # tied embeddings serve as the output head too ->
                # vocab-parallel; untied input tables shard D (local
                # lookup, no gather).
                trailing = ("model", "F") if arch.tie_embeddings \
                    else (None, "model")
            spec = []
            for t in trailing:
                if t == "F":
                    spec.append(f)
                elif t == "KV":
                    # GQA: shard kv projections only when they divide
                    # the model axis; otherwise replicate kv heads.
                    kvdim = arch.n_kv_heads * arch.head_dim
                    spec.append("model" if kvdim % m == 0 else None)
                else:
                    spec.append(t)
            lead = [None] * (ndim - len(spec))
            return P(*lead, *spec)
    return P()   # replicate by default (safe fallback)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_shardings(params_shape: Any, arch: ArchConfig, mesh: Mesh,
                    fsdp: Optional[bool] = None) -> Any:
    """NamedSharding pytree matching a params (shape-)pytree."""
    if fsdp is None:
        fsdp = arch.param_count() >= 20e9
    def one(path, leaf):
        spec = _leaf_spec(_path_str(path), len(leaf.shape), arch, mesh,
                          fsdp)
        # Never shard a dim the leaf can't divide.
        fixed = []
        for d, ax in zip(leaf.shape,
                         list(spec) + [None] * (len(leaf.shape) -
                                                len(spec))):
            if ax is None:
                fixed.append(None)
            elif d % axis_size(mesh, ax) == 0:
                fixed.append(ax)
            else:
                fixed.append(None)
        return NamedSharding(mesh, P(*fixed))
    return jax.tree_util.tree_map_with_path(one, params_shape)


def batch_shardings(arch: ArchConfig, shape: ShapeConfig, mesh: Mesh
                    ) -> Dict[str, NamedSharding]:
    """Input-batch shardings per shape kind."""
    dp = dp_axes(mesh)
    ns = lambda *spec: NamedSharding(mesh, P(*spec))
    out: Dict[str, NamedSharding] = {}
    b = shape.global_batch
    shard_b = b % axis_size(mesh, *dp) == 0
    bspec = dp if shard_b else None
    out["tokens"] = ns(bspec, None)
    if shape.kind == "train":
        out["labels"] = ns(bspec, None)
    if arch.family == "vlm":
        out["patch_embeds"] = ns(bspec, None, None)
    if arch.family == "audio":
        out["frame_embeds"] = ns(bspec, None, None)
    return out


def cache_shardings(arch: ArchConfig, shape: ShapeConfig, mesh: Mesh,
                    cache_shape: Any) -> Any:
    """Decode-cache shardings (context parallelism).

    KV caches (..., B, S, KV, D): S over "model"; B over dp when it
    divides, else KV heads over "data" (the long_500k B=1 case); SSM
    states shard their channel dim over whatever divides.
    """
    dp = dp_axes(mesh)
    dpsz = axis_size(mesh, *dp)
    m = mesh.shape["model"]

    def one(path, leaf):
        p = _path_str(path)
        shp = leaf.shape
        if p in ("len", "cursor", "abs"):
            return NamedSharding(mesh, P())
        if p in ("k", "v"):
            nd = len(shp)
            b_i, s_i, kv_i = nd - 4, nd - 3, nd - 2
            spec = [None] * nd
            if shp[s_i] % m == 0:
                spec[s_i] = "model"
            if shp[b_i] % dpsz == 0:
                spec[b_i] = dp
            elif shp[kv_i] % dpsz == 0:
                spec[kv_i] = dp
            return NamedSharding(mesh, P(*spec))
        if p in ("ssm", "tail_ssm"):
            nd = len(shp)
            spec = [None] * nd
            # (..., B, di, st) mamba1 or (..., B, nh, hd, st) mamba2
            ch_i = nd - 2 if arch.mamba_version == 1 else nd - 3
            b_i = ch_i - 1
            if shp[ch_i] % m == 0:
                spec[ch_i] = "model"
            if shp[b_i] % dpsz == 0:
                spec[b_i] = dp
            elif shp[ch_i] % (dpsz * m) == 0:
                spec[ch_i] = (*dp, "model")
            return NamedSharding(mesh, P(*spec))
        if p in ("conv", "tail_conv"):
            nd = len(shp)
            spec = [None] * nd
            if shp[-1] % m == 0:
                spec[-1] = "model"
            b_i = nd - 3
            if shp[b_i] % dpsz == 0:
                spec[b_i] = dp
            return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())
    return jax.tree_util.tree_map_with_path(one, cache_shape)


def page_pool_pspec(kv_axis: str = "kv") -> P:
    """PartitionSpec for the sharded KV page arrays (DESIGN.md §4c):
    (L, n_shards, rows_per_shard, ps, KV, D) with the locality axis
    over the `kv_axis` mesh axis, everything else replicated."""
    return P(None, kv_axis, None, None, None, None)


def page_pool_shardings(mesh: Mesh, kv_axis: str = "kv"
                        ) -> NamedSharding:
    """NamedSharding placing one page-pool locality per device along
    the `kv_axis` mesh axis — the device-backed rendering of the AGAS
    LocalityDomain the serving allocator speaks."""
    return NamedSharding(mesh, page_pool_pspec(kv_axis))


def kv_pool_mesh(n_shards: int, kv_axis: str = "kv"):
    """Mesh with a trailing `kv_axis` of size n_shards, or None.

    Returns None when the runtime cannot back one locality per device
    (single shard, or the device count does not divide) — the pool
    then falls back to simulated localities on one device, which is
    bit-identical in results and lets the same engine config run in
    unit tests and on real meshes.
    """
    nd = jax.device_count()
    if n_shards <= 1 or nd < n_shards or nd % n_shards:
        return None
    return jax.make_mesh((nd // n_shards, n_shards), ("data", kv_axis),
                         axis_types=(AxisType.Auto,) * 2)


def constrain(x, mesh: Mesh, *spec):
    """with_sharding_constraint helper tolerant of absent axes."""
    spec = tuple(s if (s is None or
                       all(a in mesh.axis_names
                           for a in ((s,) if isinstance(s, str) else s)))
                 else None for s in spec)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*spec)))
