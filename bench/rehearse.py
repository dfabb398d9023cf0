"""Compile a cell's decode and chunk programs for a described TPU v5e
(no chip needed) and print what `memory_analysis()` says they hold.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m bench.rehearse --workload yi-6b.chat

The programs are built as the chunked engine builds them, at the cell's
slots, max_len, chunk and page sizes and the configuration's published
widths, over a pool of `--pages` pages (default: what `pages_that_fit`
gives for `--limit` bytes of device memory less the weights and the
cell's reserve).
"""

from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--limit", type=float, default=16.9e9,
                    help="device bytes the runtime lets a program use")
    ap.add_argument("--pages", type=int, default=None)
    args = ap.parse_args(argv)
    from bench import spec, work, weights as W
    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.models import transformer as T
    from repro.serving.kvcache import pages_that_fit

    w = spec.workload(args.workload)
    cfg, cell = w["config"], w["cell"]
    arch = W.arch_config(cfg)
    ps, slots, max_len = cell["page_size"], cell["slots"], cell["max_len"]
    chunk = cell["chunk_size"]
    wb = work.weight_bytes(cfg)
    n_pages = args.pages or pages_that_fit(
        arch, ps, int(args.limit - wb - cell["reserve_bytes"]))
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=dev), tree)
    params = sds(jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), arch)))
    pages = sds(jax.eval_shape(
        lambda: T.init_paged_cache(arch, n_pages + 1, ps)))
    i32 = jnp.int32
    P = max_len // ps
    dec_batch = sds({"tokens": jax.ShapeDtypeStruct((slots, 1), i32),
                     "block_tables": jax.ShapeDtypeStruct((slots, P), i32),
                     "positions": jax.ShapeDtypeStruct((slots,), i32),
                     "write_rows": jax.ShapeDtypeStruct((slots,), i32),
                     "write_offs": jax.ShapeDtypeStruct((slots,), i32)})
    decode = jax.jit(lambda p, pg, b: T.decode_step_paged(p, pg, b, arch),
                     donate_argnums=(1,))

    def chunk_fn(p, pg, toks, tables, start, rows, last):
        x, pg = T.prefill_chunk(p, pg, {
            "tokens": toks, "block_tables": tables, "start": start,
            "chunk_rows": rows, "last_index": last}, arch, all_hidden=True)
        out = jax.lax.dynamic_index_in_dim(x, last, axis=1, keepdims=False)
        return T.logits_fn(p, out), out, x[:, ps - 1::ps], pg
    chunk_args = sds((jax.ShapeDtypeStruct((1, chunk), i32),
                      jax.ShapeDtypeStruct((1, P), i32),
                      jax.ShapeDtypeStruct((1,), i32),
                      jax.ShapeDtypeStruct((1, chunk // ps), i32),
                      jax.ShapeDtypeStruct((), i32)))
    print(f"{args.workload}: weights {wb / 1e9:.3f} GB, pool {n_pages} "
          f"pages ({n_pages * ps} tokens, "
          f"{n_pages * work.kv_bytes_per_token(cfg) * ps / 1e9:.3f} GB)")
    for name, fn, a in (("decode", decode, (params, pages, dec_batch)),
                        ("chunk", jax.jit(chunk_fn, donate_argnums=(1,)),
                         (params, pages) + chunk_args)):
        m = fn.lower(*a).compile().memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 - m.alias_size_in_bytes + m.temp_size_in_bytes)
        print(f"  {name}: arguments {m.argument_size_in_bytes / 1e9:.3f} GB,"
              f" outputs {m.output_size_in_bytes / 1e9:.3f} GB, aliased "
              f"{m.alias_size_in_bytes / 1e9:.3f} GB, temporaries "
              f"{m.temp_size_in_bytes / 1e9:.3f} GB; total "
              f"{total / 1e9:.3f} GB of {args.limit / 1e9:.2f}", flush=True)


if __name__ == "__main__":
    main()
