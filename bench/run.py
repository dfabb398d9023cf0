"""Run one benchmark cell once and print its result line.

    python -m bench.run --workload <config>.<mix> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, printing no result, unless JAX finds a TPU with as many
chips as the cell asks for.  The last line of standard output is one
JSON object: correct, attempted, failed, metrics, device, (with --trace
1) breakdown, and last `check`, each compared number beside its limit.
Everything else goes to earlier lines.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()          # process start, for setup_s

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

TRACE_S = 5.0            # profiled seconds at the end of a --trace 1 window


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


class Profiler:
    """jax.profiler around part of the window, into a temporary dir."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")

    def start(self):
        import jax
        jax.profiler.start_trace(self.dir)

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def reduce(self, labels):
        from bench import xplane
        try:
            path = sorted(glob.glob(os.path.join(
                self.dir, "**", "*.xplane.pb"), recursive=True))[-1]
            pd = xplane.load(path)
            return xplane.reduce(pd, labels,
                                 window=xplane.host_window(pd, "trace_window"))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def run_cell(work: dict, *, name: str, seed: int, seconds: float,
             trace: bool, t_start: float, arch=None, n_pages=None,
             peaks=None, control: bool = False) -> dict:
    """One run of cell `name`.  `arch` and `n_pages` default to the
    configuration's own and the pool that free device memory holds;
    tests pass a reduced ArchConfig and a pool size to run the same
    path on the CPU.  `control` also reads the fp8 control on the same
    requests (bench.calibrate)."""
    import jax
    from bench import harness, spec, stats, weights as W
    cfg, cell, mix = work["config"], work["cell"], work["traffic"]
    arch = arch or W.arch_config(cfg)
    if arch.name != cfg["name"]:                   # a reduced test size
        cfg = dict(cfg, **{k: getattr(arch, k) for k in (
            "n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "vocab_size", "dtype")})
    compiles = harness.CompileCounter()
    params = W.build(arch, seed)
    jax.block_until_ready(params)
    log(f"{cfg['name']}: weights built at "
        f"{time.perf_counter() - t_start:.2f}s after start")
    eng = harness.build_engine(arch, params, cell, recorder=trace,
                               n_pages=n_pages)
    harness.warm_up(eng, cell, arch.vocab_size)
    ramp = float(cell["ramp_s"])
    gen = spec.load_module("traffic", mix["kind"]).Traffic(
        mix, cell["load"], vocab=arch.vocab_size, seed=seed,
        horizon_s=ramp + seconds + 5.0, max_len=cell["max_len"])
    mem = jax.devices()[0].memory_stats() or {}
    log(f"pool {eng.kvc.pool.capacity} pages of {cell['page_size']}; "
        f"device bytes in use {mem.get('bytes_in_use')} of "
        f"{mem.get('bytes_limit')}; {compiles.n} programs compiled or "
        f"loaded in set-up")
    prof = Profiler() if trace else None
    annotate = jax.profiler.TraceAnnotation
    setup_s = time.perf_counter() - t_start
    run = harness.drive(eng, gen, ramp_s=ramp, window_s=seconds,
                        annotate=annotate, tracer=prof, trace_s=min(
                            TRACE_S, seconds), log_steps=trace,
                        compiles=compiles)
    mem = [d.memory_stats() for d in jax.local_devices()]
    peak = max((m or {}).get("peak_bytes_in_use", 0) for m in mem)
    summary = prof.reduce(harness.ANNOTATIONS) if prof else None
    # free the program's state before the reference runs
    eng.kvc.pool.pages = None
    del eng
    gc.collect()

    recs, w0, w1 = run["records"], run["w0"], run["w1"]
    due = [r for r in recs if w0 <= r.due < w1]
    ttft = stats.ttft_samples(recs, w0, w1)
    itl = stats.itl_samples(recs, w0, w1)
    late = stats.lateness(recs)
    log(f"window {seconds}s after a {ramp}s ramp: {len(due)} requests due, "
        f"{sum(r.finished for r in due)} finished, "
        f"{sum(r.failed for r in recs)} failed, "
        f"{stats.tokens_in(recs, w0, w1)} tokens, {len(ttft)} TTFT and "
        f"{len(itl)} ITL samples, {run['preemptions']} preemptions")
    log(f"compiles inside the window: {run['compiles_in_window']}")
    if late:
        log(f"generator lateness: p50 {stats.percentile(late, 50) * 1e3:.2f} ms,"
            f" p99 {stats.percentile(late, 99) * 1e3:.2f} ms, max "
            f"{max(late) * 1e3:.2f} ms")
    if ttft:
        log(f"TTFT from due time: p50 {stats.percentile(ttft, 50) * 1e3:.1f}"
            f" ms, p90 {stats.percentile(ttft, 90) * 1e3:.1f} ms "
            f"({len(ttft)} requests)")

    t = time.perf_counter()
    rids = harness.sample(run, cell["check"], seed)
    check = cell["check"]
    cmp = harness.compare(params, cfg, run, rids, float(check["off_tol"]),
                          control=control)
    correct = bool(rids) and harness.passes(cmp["off_share"], check)
    log(f"reference: {cmp['requests']} requests, {cmp['tokens']} served "
        f"tokens compared in {time.perf_counter() - t:.1f}s; widest gap "
        f"{cmp['logit_gap']}; tokens off by more than each tolerance "
        f"{json.dumps(cmp['off'])}")
    for r in cmp["per_request"]:
        log("compared " + " ".join(f"{k}={v}" for k, v in r.items()))

    values = {
        "ttft_p90_ms": None if not ttft else
        stats.percentile(ttft, 90) * 1e3,
        "itl_p95_ms": None if not itl else stats.percentile(itl, 95) * 1e3,
        "output_tok_s": stats.tokens_in(recs, w0, w1) / seconds,
        "setup_s": setup_s,
    }
    bench = work["bench"]
    metrics = {}
    if not trace:
        for m in spec.metrics_for(name, "end_to_end", bench):
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        ctx = {"cfg": cfg, "cell": cell, "peaks": peaks, "run": run,
               "trace": summary}
        for m in spec.metrics_for(name, "per_layer", bench):
            v = spec.load_module("metrics", m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return {"correct": correct, "attempted": len(due),
            "failed": sum(r.failed for r in due), "metrics": metrics,
            "memory_peak_bytes": int(peak), "trace": summary,
            "check": {"off_share": {"value": cmp["off_share"],
                                    "limit": float(check["off_share_limit"])}},
            "compared": cmp, "run": run,
            "control_correct": harness.passes(cmp["control_off_share"],
                                              check)
            if control else None}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import spec
    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    bench = spec.benchmark()
    work = spec.workload(args.workload, bench)
    work["bench"] = bench
    chips = int(work["entry"]["chips"])

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: cell {args.workload} needs {chips} TPU chip(s); JAX "
              f"found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 2
    from bench.peaks import peaks as peak_table
    pk = peak_table(devs[0].device_kind)
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    res = run_cell(work, name=args.workload, seed=args.seed,
                   seconds=args.seconds, trace=bool(args.trace),
                   t_start=T_START, peaks=pk)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": res["memory_peak_bytes"]}
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"], "metrics": res["metrics"],
           "device": device}
    if args.trace:
        from bench import xplane
        tr = res["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        out["breakdown"] = xplane.breakdown(tr)
    out["check"] = res["check"]
    for k, v in res["check"].items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
