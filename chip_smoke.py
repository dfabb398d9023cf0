"""Drive the serving main path once on a TPU, at yi-6b's published widths.

    python chip_smoke.py             # one chip: serve, tokens, kernels
    python chip_smoke.py --chips 4   # four chips: sharded pool on a mesh

One process runs every phase; no phase catches an exception, so a
failure anywhere is the exit code.  On any platform but a TPU the
script exits non-zero before it runs anything.

Phases on one chip:

* serve — 8 requests (prompts of 16-200 tokens, 16 new tokens each)
  through make_engine -> ChunkedPagedServingEngine -> PagedKVCache, at
  every published width and all 32 layers in bf16, with a page pool
  sized from the HBM left after the weights.  Every completion future
  must resolve with its tokens, and every served token must be the
  best, or within a rounding gap of it, under the plain un-paged
  `T.forward` / `T.logits_fn` of the same weights over the prompt and
  the tokens served before it.
* kernels — the paged decode and prefill Pallas kernels, on flat and
  locality-sharded pools, compiled (interpret=False) and compared with
  the jnp oracles of kernels/attention/ref.py at yi-6b's head widths.

With ``--chips 4`` only the mesh phase runs: the engine with
``kv_shards=4`` over a real four-device `kv_pool_mesh`, with one forced
page migration (the shard_map / ppermute legs), against
``kv_shards=1`` on the same prompts, both runs' tokens held to the
un-paged forward as in the serve phase.

The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

import repro.configs as configs  # noqa: E402
from repro.distributed.sharding import kv_pool_mesh  # noqa: E402
from repro.kernels.attention import ref as kref  # noqa: E402
from repro.kernels.attention.paged import (  # noqa: E402
    paged_attention_bhd, paged_prefill_attention_btd)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.serving.engine import Request, make_engine  # noqa: E402
from repro.serving.kvcache import pages_that_fit  # noqa: E402

ARCH = "yi-6b"
SEED = 0                      # of the random weights, prompts and pools
PAGE_SIZE = 16
MAX_LEN = 256
# Device memory kept out of the page pool: step temporaries besides the
# pool's second copy (activations, the (B, V) float32 logits), the
# un-paged reference forward and the kernel phase's own small pools.
RESERVE_BYTES = 3 << 29                               # 1.5 GiB

# Served vs un-paged logits, both bf16 with float32 accumulation.  The
# two paths round at different points (32-token chunks against pages
# vs one full-sequence pass, different matmul shapes), so they agree
# only to bf16 precision carried through every layer: each layer adds
# a relative error of a few bf16 units (2^-8) to the residual stream.
# The bound is on the largest difference relative to the largest
# reference logit; a 32-layer bf16 run at d_model 512 on the CPU gave
# 0.018-0.019.  A causal mask off by one position moved it to 0.39-0.64
# in the same run, so the check sees a wrong page or position.
LOGIT_REL_TOL = 0.05
# The engine samples on the device and returns tokens only, so the
# check is on them: a served greedy token is the best of logits within
# LOGIT_REL_TOL of the reference's, so its reference logit lies at most
# twice that below the reference's best (relative to the row's largest
# |logit|).
TOKEN_GAP_TOL = 2 * LOGIT_REL_TOL
# Kernel vs oracle, same bf16 inputs: both take a convex combination
# of bf16 value rows, and differ only in where the probabilities and
# the output are rounded to bf16 (8 significant bits), so the outputs
# agree to a few bf16 units of the largest |v|.
KERNEL_REL_TOL = 4 * 2.0 ** -8


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def amax(x) -> float:
    return float(np.max(np.abs(np.asarray(x, np.float32))))


def rel_diff(got, want, scale=None) -> float:
    """max |got - want| / scale (default max |want|), in float32."""
    diff = np.asarray(got, np.float32) - np.asarray(want, np.float32)
    return amax(diff) / (amax(want) if scale is None else scale)


def token_gaps(params, cfg, prompts, served) -> list:
    """For each request, the largest gap by which a served token's
    logit lies below the best logit of the un-paged forward over the
    prompt and the tokens served before it, relative to the row's
    largest |logit|.  Every sequence is right-padded to one length
    (causal: the pad never reaches earlier positions), so the forward
    compiles once."""
    n = max(len(p) + len(t) for p, t in zip(prompts, served))
    seq = np.zeros(-(-n // PAGE_SIZE) * PAGE_SIZE, np.int32)
    forward = jax.jit(lambda p, toks: T.logits_fn(
        p, T.forward(p, {"tokens": toks}, cfg)[0][0]))
    gaps = []
    for prompt, toks in zip(prompts, served):
        full = np.concatenate([prompt, np.asarray(toks, np.int32)])
        seq[:] = 0
        seq[:len(full)] = full
        lg = np.asarray(forward(params, jnp.asarray(seq[None])),
                        np.float32)
        rows = lg[len(prompt) - 1:len(full) - 1]
        got = rows[np.arange(len(toks)), toks]
        gaps.append(float(np.max((rows.max(-1) - got)
                                 / np.abs(rows).max(-1))))
    return gaps


def make_prompts(cfg, seed: int, n: int, lo: int, hi: int):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=int(s)).astype(np.int32)
            for s in rng.integers(lo, hi + 1, size=n)]


def serve(engine, prompts, new_tokens: int):
    """Submit every prompt, run to completion, return the completions
    in request order (each future must resolve with its tokens)."""
    futs = [engine.submit(Request(rid, p, max_new_tokens=new_tokens))
            for rid, p in enumerate(prompts)]
    engine.run_to_completion()
    comps = [f.get() for f in futs]
    for c in comps:
        check(len(c.tokens) == new_tokens,
              f"request {c.rid} got {len(c.tokens)} tokens")
    return comps


def free_device_bytes() -> int:
    stats = jax.devices()[0].memory_stats()
    return stats["bytes_limit"] - stats["bytes_in_use"]


def phase_serve(cfg, *, n_requests: int = 8,
                new_tokens: int = 16, prompt_lens=(16, 200),
                n_pages=None) -> dict:
    t = time.perf_counter()
    params = T.init_params(jax.random.PRNGKey(SEED), cfg)
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t
    weight_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    if n_pages is None:
        n_pages = pages_that_fit(cfg, PAGE_SIZE,
                                 free_device_bytes() - RESERVE_BYTES)
    log(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"heads {cfg.n_heads}/{cfg.n_kv_heads} kv, head_dim "
        f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
        f"{cfg.dtype}; {weight_bytes / 1e9:.3f} GB of weights built in "
        f"{init_s:.1f}s")
    eng = make_engine(params, cfg, engine="chunked", slots=n_requests,
                      max_len=MAX_LEN, page_size=PAGE_SIZE,
                      n_pages=n_pages)
    log(f"page pool: {n_pages} pages of {PAGE_SIZE} tokens "
        f"({n_pages * PAGE_SIZE} tokens of KV)")
    # one short request compiles the chunk step and the decode step,
    # each with its sampler; the timed serve below then compiles nothing
    t = time.perf_counter()
    serve(eng, make_prompts(cfg, SEED + 1, 1, 16, 16), 2)
    compile_s = time.perf_counter() - t
    prompts = make_prompts(cfg, SEED, n_requests, *prompt_lens)
    t = time.perf_counter()
    comps = serve(eng, prompts, new_tokens)
    serve_s = time.perf_counter() - t
    n_tok = sum(len(c.tokens) for c in comps)
    log(f"compile (warm-up request) {compile_s:.2f}s; served "
        f"{len(comps)} requests, prompts {min(map(len, prompts))}-"
        f"{max(map(len, prompts))} tokens, {n_tok} new tokens in "
        f"{serve_s:.2f}s")
    gap = max(token_gaps(params, cfg, prompts, [c.tokens for c in comps]))
    log(f"served tokens vs un-paged forward ({n_tok} tokens): largest "
        f"gap below the best logit / max|ref| = {gap:.3e} (tolerance "
        f"{TOKEN_GAP_TOL})")
    check(gap <= TOKEN_GAP_TOL, "served tokens disagree with the forward")
    return {"compile_s": compile_s, "serve_s": serve_s, "tokens": n_tok,
            "token_gap": gap, "pages": n_pages}


def phase_kernels(cfg, *, interpret: bool, batch: int = 8,
                  table: int = 16, chunk: int = 32,
                  n_rows: int = 256) -> dict:
    h, kvh, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.dtype)
    rng = np.random.default_rng(SEED)
    out = {}
    for layout, pool in (("flat", (n_rows, PAGE_SIZE, kvh, d)),
                         ("sharded", (4, n_rows // 4, PAGE_SIZE, kvh, d))):
        kp, vp = (jnp.asarray(rng.normal(size=pool), dt) for _ in "kv")
        tables = jnp.asarray(rng.integers(0, n_rows, (batch, table)),
                             jnp.int32)
        vmax = amax(vp)
        q = jnp.asarray(rng.normal(size=(batch, h, d)), dt)
        pos = jnp.asarray(rng.integers(0, table * PAGE_SIZE, batch),
                          jnp.int32)
        got = jax.jit(lambda *a: paged_attention_bhd(
            *a, interpret=interpret))(q, kp, vp, tables, pos)
        want = kref.paged_attention_ref(q[:, None], kp, vp, tables,
                                        pos)[:, 0]
        out[f"decode_{layout}"] = rel_diff(got, want, vmax)
        qc = jnp.asarray(rng.normal(size=(batch, chunk, h, d)), dt)
        start = jnp.asarray(rng.integers(
            0, table - chunk // PAGE_SIZE + 1, batch) * PAGE_SIZE,
            jnp.int32)
        got = jax.jit(lambda *a: paged_prefill_attention_btd(
            *a, interpret=interpret))(qc, kp, vp, tables, start)
        want = kref.paged_prefill_attention_ref(qc, kp, vp, tables, start)
        out[f"prefill_{layout}"] = rel_diff(got, want, vmax)
    for name, rel in out.items():
        log(f"kernel {name} (H {h}, KV {kvh}, D {d}, page {PAGE_SIZE}, "
            f"{dt.name}, interpret={interpret}): max|diff|/max|v| = "
            f"{rel:.3e} (tolerance {KERNEL_REL_TOL:.3e})")
        check(rel <= KERNEL_REL_TOL, f"kernel {name} disagrees with ref")
    return out


def phase_mesh(cfg, *, n_shards: int = 4, n_requests: int = 4,
               new_tokens: int = 8, prompt_lens=(16, 120)) -> dict:
    mesh = kv_pool_mesh(n_shards)
    check(mesh is not None and mesh.shape["kv"] == n_shards,
          f"no {n_shards}-device kv mesh over {jax.device_count()} "
          "devices: the pool would not be mesh-backed")
    params = T.init_params(jax.random.PRNGKey(SEED), cfg)
    prompts = make_prompts(cfg, SEED, n_requests, *prompt_lens)
    kw = dict(engine="chunked", slots=n_requests, max_len=MAX_LEN,
              page_size=PAGE_SIZE,
              n_pages=n_shards * n_requests * (MAX_LEN // PAGE_SIZE))
    log(f"{cfg.name}: {cfg.n_layers} layers at published widths, "
        f"{cfg.dtype}; kv_shards=1 on one device vs kv_shards="
        f"{n_shards} over mesh {dict(mesh.shape)}")

    base = make_engine(params, cfg, **kw)
    t = time.perf_counter()
    ref = {c.rid: c.tokens for c in serve(base, prompts, new_tokens)}
    log(f"kv_shards=1: {time.perf_counter() - t:.2f}s")
    del base

    eng = make_engine(jax.device_put(params, NamedSharding(mesh, P())),
                      cfg, kv_shards=n_shards, mesh=mesh, **kw)
    sharding = eng.kvc.pool.pages["k"].sharding
    check(sharding.spec[1] == "kv"
          and len(sharding.device_set) == n_shards,
          f"page pool not sharded over the mesh: {sharding}")
    t = time.perf_counter()
    futs = [eng.submit(Request(rid, p, max_new_tokens=new_tokens))
            for rid, p in enumerate(prompts)]
    for _ in range(3):
        eng.step()
    moved = eng.force_migrate()          # lax.ppermute under shard_map
    check(moved > 0, "forced migration moved no page")
    eng.run_to_completion()
    got = {c.rid: c.tokens for c in (f.get() for f in futs)}
    log(f"kv_shards={n_shards}: {time.perf_counter() - t:.2f}s, "
        f"{moved} pages migrated between devices")
    gaps = token_gaps(params, cfg, prompts * 2,
                      [ref[r] for r in range(n_requests)]
                      + [got[r] for r in range(n_requests)])
    same = sum(got[r] == ref[r] for r in ref)
    log(f"served tokens vs un-paged forward, 1 shard and {n_shards}: "
        f"{len(gaps)} requests compared, largest gap below the best "
        f"logit / max|ref| = {max(gaps):.3e} (tolerance "
        f"{TOKEN_GAP_TOL}); {same}/{len(ref)} requests token-identical")
    check(len(gaps) == 2 * n_requests and max(gaps) <= TOKEN_GAP_TOL,
          "sharded or one-shard tokens disagree with the forward")
    return {"moved": moved, "token_gap": max(gaps),
            "compared": len(gaps), "identical": same}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded-pool mesh phase")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}")
    if jax.device_count() < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX found "
                 f"{jax.device_count()} device(s)")
    cache = enable_compile_cache()
    log(f"device {dev.device_kind} x{jax.device_count()}, jax "
        f"{jax.__version__}, compile cache {cache}")
    cfg = configs.get(ARCH)
    if args.chips == 4:
        # two copies of the weights share device 0 (the one-shard
        # baseline's and the mesh's replica), so cut depth; the pool's
        # collectives and ppermute legs do not depend on it
        phase_mesh(cfg.with_depth(4))
    else:
        phase_serve(cfg)
        phase_kernels(cfg, interpret=False)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))


if __name__ == "__main__":
    main()
