"""Find a cell's knee: the highest offered rate it sustains without a
growing backlog, by one sweep of fixed rates in one process.

    python -m bench.sweep --workload yi-6b.chat --rates 2,4,6,8 --seconds 20

The weights are built once; each rate gets a fresh engine, the cell's
warm-up and ramp, and a window of --seconds.  One JSON line per rate:
requests due and finished, the queue at the start and end of the
window, tails from due time and tokens per second.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--set", default=None,
                    help="JSON merged into the cell's settings")
    args = ap.parse_args(argv)
    from bench import spec
    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    import jax
    from bench import harness, stats, weights as W
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    work = spec.workload(args.workload)
    cfg, cell, mix = work["config"], work["cell"], work["traffic"]
    if args.set:
        cell.update(json.loads(args.set))
    arch = W.arch_config(cfg)
    params = W.build(arch, args.seed)
    gen_cls = spec.load_module("traffic", mix["kind"]).Traffic
    key = next(iter(cell["load"]))
    for rate in (float(r) for r in args.rates.split(",")):
        eng = harness.build_engine(arch, params, cell, recorder=False)
        harness.warm_up(eng, cell, arch.vocab_size)
        ramp = float(cell["ramp_s"])
        gen = gen_cls(mix, {key: rate}, vocab=arch.vocab_size,
                      seed=args.seed, horizon_s=ramp + args.seconds + 5,
                      max_len=cell["max_len"])
        run = harness.drive(eng, gen, ramp_s=ramp, window_s=args.seconds)
        recs, w0, w1 = run["records"], run["w0"], run["w1"]
        c = run["counters"]
        ttft = stats.ttft_samples(recs, w0, w1)
        itl = stats.itl_samples(recs, w0, w1)
        pct = stats.percentile
        print(json.dumps({
            key: rate,
            "due": sum(1 for r in recs if w0 <= r.due < w1),
            "finished": sum(1 for r in recs if r.finished),
            "queue_start": c[0]["queue_depth"] if c else None,
            "queue_end": c[-1]["queue_depth"] if c else None,
            "ttft_p50_ms": pct(ttft, 50) and pct(ttft, 50) * 1e3,
            "ttft_p90_ms": pct(ttft, 90) and pct(ttft, 90) * 1e3,
            "itl_p95_ms": pct(itl, 95) and pct(itl, 95) * 1e3,
            "output_tok_s": stats.tokens_in(recs, w0, w1) / args.seconds,
            "preemptions": run["preemptions"]}), flush=True)
        eng.kvc.pool.pages = None
        del eng
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
