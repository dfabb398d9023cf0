"""Look behind a cell's logit gap: every finished request's gaps, and
for the worst ones two witnesses from the program itself.

    python -m bench.diagnose --workload yi-6b.chat --seed 12 --seconds 10 \
        [--set '{"load": {"rate_per_s": 5.0}, "ramp_s": 8}']

One run of the cell as `bench.run` makes it; then, per finished request
(at most --requests), the widest gap, where it lies (prompt length,
output index, chunk boundaries) and how many tokens exceed --tol.  For
the --worst requests: (a) the same prompt served alone through a fresh
engine of the cell's deployment, (b) the program's un-paged forward
(`T.forward`, bf16) over the served sequence, each read against the
reference, and (c) the reference itself in bf16 and in fp8 (the
control): whether the positions where the served logits leave the
float32 reference are the ones where bf16 alone moves them.  One JSON
line per request.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402


def gaps(ref, params, cfg, prompt, toks):
    seq = np.concatenate([prompt, toks[:-1]])
    rows = np.arange(len(prompt) - 1, len(seq))
    lg = np.asarray(ref.logits_at(params, cfg, ref.hidden(params, cfg, seq),
                                  rows))
    return lg, lg.max(1) - lg[np.arange(len(toks)), toks]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--worst", type=int, default=2)
    ap.add_argument("--tol", type=float, default=0.1)
    ap.add_argument("--set", default=None,
                    help="JSON merged into the cell's settings")
    args = ap.parse_args(argv)
    from bench import spec
    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    import jax
    import jax.numpy as jnp
    from bench import harness, run as R, weights as W
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import transformer as T
    from repro.serving.engine import Request
    enable_compile_cache()
    bench = spec.benchmark()
    work = spec.workload(args.workload, bench)
    work["bench"] = bench
    if args.set:
        work["cell"].update(json.loads(args.set))
    cfg, cell = work["config"], work["cell"]
    res = R.run_cell(work, name=args.workload, seed=args.seed,
                     seconds=args.seconds, trace=False,
                     t_start=time.perf_counter())
    run = res["run"]
    del res
    gc.collect()
    arch = W.arch_config(cfg)
    params = W.build(arch, args.seed)
    ref = spec.load_module("reference", cfg["reference"])
    rows = []
    for rid in sorted(run["completions"])[:args.requests]:
        c = run["completions"][rid]
        prompt = run["prompts"][rid]
        toks = np.asarray(c.tokens, np.int32)
        _, g = gaps(ref, params, cfg, prompt, toks)
        bad = np.nonzero(g > args.tol)[0]
        rows.append((float(g.max()), rid))
        print(json.dumps({"rid": rid, "prompt": len(prompt),
                          "out": len(toks), "preempts": c.preemptions,
                          "gap_max": float(g.max()),
                          "at": int(g.argmax()), "gap_first": float(g[0]),
                          "n_over_tol": int(len(bad)),
                          "first_over_tol": int(bad[0]) if len(bad) else None,
                          "gaps_head": [round(float(x), 4) for x in g[:8]]}),
              flush=True)
    fwd = jax.jit(lambda p, t: T.logits_fn(
        p, T.forward(p, {"tokens": t}, arch)[0][0]))
    for _, rid in sorted(rows, reverse=True)[:args.worst]:
        c = run["completions"][rid]
        prompt = run["prompts"][rid]
        toks = np.asarray(c.tokens, np.int32)
        lg, g = gaps(ref, params, cfg, prompt, toks)
        eng = harness.build_engine(arch, params, cell, recorder=False)
        fut = eng.submit(Request(rid, prompt, max_new_tokens=len(toks)))
        eng.run_to_completion()
        alone = np.asarray(fut.get().tokens, np.int32)
        eng.kvc.pool.pages = None
        del eng
        n_same = int(np.argmin(np.append(alone == toks, False)))
        _, ga = gaps(ref, params, cfg, prompt, alone)
        seq = np.concatenate([prompt, toks[:-1]])
        pad = ref.bucket(len(seq))
        tt = np.zeros(pad, np.int32)
        tt[:len(seq)] = seq
        pl = np.asarray(fwd(params, jnp.asarray(tt[None])))
        pl = pl[len(prompt) - 1:len(seq)]
        pf = lg.max(1) - lg[np.arange(len(toks)), pl.argmax(1)]
        top2 = np.sort(lg, axis=1)[:, -2:]
        wit = {}
        for q in ("bf16", "fp8"):
            lq = np.asarray(ref.logits_at(
                params, cfg, ref.hidden(params, cfg, seq, quant=q),
                np.arange(len(prompt) - 1, len(seq)), quant=q))
            d = np.abs(lq - lg).max(1)
            gq = lg.max(1) - lg[np.arange(len(toks)), lq.argmax(1)]
            wit[q] = {"maxdiff_q50_q99_max": [round(float(v), 4) for v in
                                              np.quantile(d, [.5, .99, 1])],
                      "spikes": np.nonzero(d > 1.0)[0].tolist()[:12],
                      "gap_max": float(gq.max()),
                      "off_0.1": int((gq > 0.1).sum())}
        dp = np.abs(pl - lg).max(1)
        print(json.dumps({
            "worst_rid": rid, "gap_served": float(g.max()),
            "served_spikes": np.nonzero(g > 0.5)[0].tolist()[:12],
            "forward_spikes": np.nonzero(dp > 1.0)[0].tolist()[:12],
            "forward_maxdiff_q50_q99": [round(float(v), 4) for v in
                                        np.quantile(dp, [.5, .99])],
            "ref_margin_at_spikes": [round(float(top2[i, 1] - top2[i, 0]),
                                           4) for i in
                                     np.nonzero(g > 0.5)[0][:6]],
            "witness": wit,
            "alone_tokens_same_prefix": n_same, "alone_len": len(alone),
            "alone_gap_max": float(ga.max()),
            "forward_gap_max": float(pf.max()),
            "forward_argmax_equals_served": int((pl.argmax(1) == toks).sum()),
            "forward_vs_ref_logit_maxdiff": float(np.abs(pl - lg).max()),
            "ref_logit_absmax": float(np.abs(lg).max()),
            "n": len(toks)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
