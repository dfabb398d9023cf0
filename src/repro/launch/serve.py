"""Serving driver: batched decode demo, reduced config by default.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --requests 8
  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --published \
      --pages 1024           # published widths in bf16 (on a TPU; see
                             # chip_smoke.py for the checked chip run)
  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b \
      --engine paged --pages 24 --page-size 16   # oversubscribed pool
  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b \
      --engine chunked --chunk-size 32 --step-tokens 64
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m repro.launch.serve --arch yi-6b \
      --kv-shards 4          # sharded AGAS page pool (DESIGN.md §4c)
  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b \
      --pages 16 --tiering --host-pages 64   # two-tier percolation:
                             # preempted KV offloads to host DRAM and
                             # restores on re-admission (DESIGN.md §4d)
  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b \
      --tiering --prefix-cache-compute   # prefix-cache compute skip
                             # (DESIGN.md §4e): covered prompts admit
                             # straight to decode off cached
                             # activation checkpoints
  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b \
      --tiering --trace /tmp/serve.trace.json \
      --metrics-interval 8 --metrics-out /tmp/serve.metrics.jsonl
                             # causal trace (perfetto-viewable) +
                             # exporter-backed metrics snapshots:
                             # one {t, step, metrics, delta} JSON
                             # line per interval (DESIGN.md §10)
  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b \
      --ttft-slo-ms 200 --itl-slo-ms 50 \
      --slo-report /tmp/serve.slo.json \
      --metrics-prom /tmp/serve.prom
                             # SLO/goodput tracking (DESIGN.md §10):
                             # deadline-tracked requests, per-request
                             # lifecycle flight recorder, end-of-run
                             # goodput report with per-phase blame,
                             # Prometheus text exposition
  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b \
      --kv-shards 2 --disagg --prefill-workers 2 --decode-workers 1
                             # disaggregated prefill/decode
                             # (DESIGN.md §4f): prefill chunks
                             # parcel-dispatched to prefix-owner
                             # localities, finished KV handed to the
                             # decode role via percolation snapshots
  PYTHONPATH=src python -m repro.launch.serve --arch yi-6b \
      --kv-shards 2 --tiering --chaos-kill-shard 1 --chaos-at-step 4
                             # failure injection (DESIGN.md §4g):
                             # shard 1 dies at step 4; pages with a
                             # host-tier percolation copy rebuild on
                             # shard 0, the rest drain and re-prefill
                             # — every request still completes
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--published", action="store_true",
                    help="serve the architecture at its published "
                         "widths in its own dtype (configs/<arch>.py) "
                         "instead of the reduced CPU variant")
    ap.add_argument("--layers", type=int, default=0,
                    help="depth cut: keep the first N layers of the "
                         "chosen config (0 = all)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--engine",
                    choices=("auto", "chunked", "paged", "dense"),
                    default="auto")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--pages", type=int, default=0,
                    help="page-pool size (0 = dense-equivalent)")
    ap.add_argument("--chunk-size", type=int, default=0,
                    help="prefill chunk width (0 = 2 pages)")
    ap.add_argument("--step-tokens", type=int, default=0,
                    help="per-step token budget (0 = slots + chunk)")
    ap.add_argument("--kv-shards", type=int, default=1,
                    help="AGAS localities the page pool is sharded "
                         "over (device-backed when the runtime has "
                         "one device per shard, simulated otherwise)")
    ap.add_argument("--tiering", action="store_true",
                    help="two-tier page pool (DESIGN.md §4d): cold "
                         "prefix pages spill to host DRAM and a "
                         "preempted request's KV is written back and "
                         "restored instead of re-prefilled")
    ap.add_argument("--host-pages", type=int, default=0,
                    help="host-tier capacity in pages "
                         "(0 = 4x the device pool)")
    ap.add_argument("--prefix-cache-compute", action="store_true",
                    help="prefix-cache compute skip (DESIGN.md §4e): "
                         "prompts covered by cached prefix pages skip "
                         "the covered prefill compute; fully-covered "
                         "prompts admit straight to decode from the "
                         "cached activation checkpoint")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated prefill/decode (DESIGN.md "
                         "§4f): prefill chunks dispatch as parcels to "
                         "the locality owning the prompt's prefix "
                         "pages (least-loaded when cold) and finished "
                         "KV hands to the decode role through staged "
                         "percolation snapshots; requires the chunked "
                         "engine")
    ap.add_argument("--prefill-workers", type=int, default=0,
                    help="prefill-worker localities for --disagg "
                         "(0 = one per KV shard)")
    ap.add_argument("--decode-workers", type=int, default=1,
                    help="decode-worker localities for --disagg")
    ap.add_argument("--pin-threshold", type=int, default=4,
                    help="radix-index hits before a prefix page is "
                         "pinned hot — pinned pages are the LAST "
                         "tiering-eviction candidates (0 disables "
                         "pinning)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="causal tracing (DESIGN.md §10): record "
                         "parcel/LCO/page/engine events and write a "
                         "Chrome trace-event JSON to PATH (open in "
                         "https://ui.perfetto.dev), plus a per-step "
                         "overhead attribution line")
    ap.add_argument("--metrics-interval", type=int, default=0,
                    metavar="N",
                    help="metrics-registry snapshot every N engine "
                         "steps: a one-line console summary, plus a "
                         "JSONL record when --metrics-out is set "
                         "(0 = off; --metrics-out implies 8)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write interval snapshots of the unified "
                         "metrics registry as JSON lines — one "
                         "{t, step, metrics, delta} object per "
                         "interval, deltas against the previous "
                         "snapshot (obs/export.py)")
    ap.add_argument("--metrics-prom", default=None, metavar="PATH",
                    help="write the final metrics registry as "
                         "Prometheus text exposition (counters as "
                         "_total, histograms as summaries)")
    ap.add_argument("--slo-report", default=None, metavar="PATH",
                    help="end-of-run SLO/goodput report JSON: "
                         "met/missed per deadline-tracked request, "
                         "per-phase blame, lifecycle phase totals "
                         "(enables the flight recorder)")
    ap.add_argument("--ttft-slo-ms", type=float, default=0.0,
                    help="TTFT deadline attached to every request "
                         "(ms; 0 = untracked)")
    ap.add_argument("--itl-slo-ms", type=float, default=0.0,
                    help="inter-token p95 deadline attached to every "
                         "request (ms; 0 = untracked)")
    ap.add_argument("--chaos-kill-shard", type=int, default=-1,
                    metavar="SHARD",
                    help="failure injection (DESIGN.md §4g): kill KV "
                         "shard SHARD mid-run — pages with host-tier "
                         "copies rebuild on survivors, the rest drain "
                         "and re-prefill; every request still "
                         "finishes (-1 = off; requires --kv-shards>1)")
    ap.add_argument("--chaos-at-step", type=int, default=4,
                    metavar="N",
                    help="engine step at which --chaos-kill-shard "
                         "fires")
    ap.add_argument("--flight-recorder", action="store_true",
                    help="record per-request lifecycle timelines "
                         "(submit/bind/chunks/handoff/first-token/"
                         "preempt/finish) queryable via "
                         "engine.recorder (implied by --slo-report)")
    args = ap.parse_args()

    import repro.configs as configs
    from repro.distributed.sharding import kv_pool_mesh
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import transformer as T
    from repro.serving.engine import Request, make_engine

    enable_compile_cache()
    cfg = configs.get(args.arch) if args.published \
        else configs.get_reduced(args.arch)
    if args.layers:
        cfg = cfg.with_depth(args.layers)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    failure_plan = None
    if args.chaos_kill_shard >= 0:
        if args.kv_shards < 2:
            ap.error("--chaos-kill-shard requires --kv-shards > 1 "
                     "(a surviving shard must exist)")
        from repro.ft.failures import FailurePlan
        failure_plan = FailurePlan.kill_locality(
            args.chaos_kill_shard, at_step=args.chaos_at_step)
    kw = dict(slots=args.slots, max_len=args.max_len)
    engine = "chunked" if args.engine == "auto" else args.engine
    mesh = kv_pool_mesh(args.kv_shards)
    eng = make_engine(params, cfg, engine=engine,
                      page_size=args.page_size,
                      n_pages=args.pages or None,
                      chunk_size=args.chunk_size or None,
                      step_tokens=args.step_tokens or None,
                      kv_shards=args.kv_shards, mesh=mesh,
                      tiering=args.tiering,
                      host_pages=args.host_pages,
                      prefix_cache_compute=args.prefix_cache_compute,
                      pin_threshold=args.pin_threshold,
                      disagg=args.disagg,
                      prefill_workers=args.prefill_workers or None,
                      decode_workers=args.decode_workers,
                      flight_recorder=(args.flight_recorder
                                       or bool(args.slo_report)),
                      failure_plan=failure_plan,
                      **kw)
    if failure_plan is not None:
        print(f"[serve] chaos: shard {args.chaos_kill_shard} dies at "
              f"step {args.chaos_at_step} (§4g recovery on)")
    if args.disagg and hasattr(eng, "prefill_workers"):
        print(f"[serve] disaggregated roles: {eng.prefill_workers} "
              f"prefill worker(s) / {eng.decode_workers} decode "
              f"worker(s) over {eng.kvc.pool.n_shards} localit"
              f"{'ies' if eng.kvc.pool.n_shards > 1 else 'y'}")
    if args.tiering and hasattr(eng, "kvc"):
        pool = eng.kvc.pool
        print(f"[serve] two-tier pool: {pool.capacity} device pages "
              f"+ {pool.host_pages} host pages (percolation on)")
    if args.kv_shards > 1 and hasattr(eng, "kvc"):
        backing = "mesh" if mesh is not None else "simulated"
        print(f"[serve] kv page pool: {args.kv_shards} shards "
              f"({backing} localities), "
              f"{eng.kvc.pool.pages_per_shard} pages/shard")
    tracer = None
    if args.trace:
        from repro.obs.trace import Tracer, set_global
        tracer = Tracer(capacity=1 << 18)
        eng.set_tracer(tracer)
        set_global(tracer)

    # interval snapshots go through the exporter (obs/export.py) —
    # the full registry lands in the JSONL file; the console keeps a
    # one-line summary instead of the old hardcoded key list
    interval = args.metrics_interval
    if args.metrics_out and interval <= 0:
        interval = 8
    exporter = None
    if args.metrics_out:
        from repro.obs.export import JsonlExporter
        exporter = JsonlExporter(eng.metrics, args.metrics_out)
    on_step = None
    if interval > 0:
        def on_step(e, _every=interval):
            steps = e.metrics.counter("engine.steps").value
            if steps % _every:
                return
            if exporter is not None:
                rec = exporter.snap(step=steps)
                snap, delta = rec["metrics"], rec["delta"]
                sink = f" -> {args.metrics_out} " \
                       f"({len(snap)} series, {len(delta)} changed)"
            else:
                snap = e.metrics.snapshot()
                sink = ""
            print(f"[metrics] step={steps} "
                  f"resident={snap.get('engine.peak_resident', 0):g} "
                  f"decoded={snap.get('engine.decode_ms.count', 0):g} "
                  f"ttft_n={snap.get('engine.ttft_ms.count', 0):g}"
                  f"{sink}")

    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    futs = []
    try:
        for rid in range(args.requests):
            n = int(rng.integers(8, 48))
            futs.append(eng.submit(Request(rid, rng.integers(
                0, cfg.vocab_size, size=n).astype(np.int32),
                max_new_tokens=args.max_new,
                ttft_deadline_ms=args.ttft_slo_ms or None,
                itl_deadline_ms=args.itl_slo_ms or None)))
        eng.run_to_completion(on_step=on_step)
    finally:
        if tracer is not None:
            from repro.obs.trace import set_global
            set_global(None)
    dt = time.perf_counter() - t0
    total_new = sum(len(c.tokens) for c in eng.completions)
    print(f"[serve] {type(eng).__name__}: "
          f"{len(eng.completions)} completions, "
          f"{total_new} tokens in {dt:.2f}s "
          f"({total_new / dt:.1f} tok/s)")
    for f in futs[:4]:
        c = f.get()                       # the completion LCO
        print(f"  rid={c.rid} new={len(c.tokens)} "
              f"prefill={c.prefill_s * 1e3:.0f}ms "
              f"decode={c.decode_s * 1e3:.0f}ms "
              f"preempts={c.preemptions}")
    if hasattr(eng, "stats"):
        s = eng.stats()
        print(f"[serve] steps={s['steps']} "
              f"peak_active={s['peak_active']} "
              f"peak_page_occ={s['peak_page_occupancy']:.2f} "
              f"preemptions={s['preemptions']} "
              f"shares={s['page_shares']} cow={s['cow_copies']}")
        if s["kv_shards"] > 1:
            occ = ", ".join(f"{o:.2f}" for o in s["shard_occupancy"])
            print(f"[serve] shards={s['kv_shards']} "
                  f"occupancy=[{occ}] "
                  f"page_migrations={s['page_migrations']}")
        rec = s.get("recovery")
        if rec and rec.get("localities_killed"):
            print(f"[serve] recovery: "
                  f"killed={rec['localities_killed']} "
                  f"rebuilt={rec['pages_rebuilt']} "
                  f"lost={rec['pages_lost']} "
                  f"drained={rec['drained_slots']} "
                  f"re_prefills={rec['re_prefills']} "
                  f"(budget {rec['recovery_restarts']} restart(s))")
        if s.get("tiering"):
            print(f"[serve] tiering: resident={s['peak_resident']} "
                  f"offloads={s['offloads']} restores={s['restores']} "
                  f"offload_bytes={s['offload_bytes']} "
                  f"promote_bytes={s['promote_bytes']} "
                  f"overlap={s['copy_compute_overlap']:.2f}")
        if s.get("disagg"):
            print(f"[serve] disagg: parcels={s['prefill_parcels']} "
                  f"(owner={s['prefill_parcels_owner']} "
                  f"cold={s['prefill_parcels_cold']} "
                  f"affinity={s['prefill_parcel_affinity']:.0%}) "
                  f"handoffs={s['handoffs']} "
                  f"({s['handoff_bytes']}B, "
                  f"overlap={s['handoff_overlap']:.2f})")
        if s.get("prefix_cache_compute"):
            print(f"[serve] compute skip: "
                  f"full_skips={s['prefix_skips']} "
                  f"partial_hits={s['prefix_partial_hits']} "
                  f"prefill_tokens_skipped="
                  f"{s['prefill_tokens_skipped']}")
        if hasattr(eng, "kvc") and hasattr(eng.kvc.pool, "prefix"):
            p = eng.kvc.pool.prefix.metrics()
            print(f"[serve] radix index: nodes={p['prefix.nodes']} "
                  f"tombstones={p['prefix.tombstones']} "
                  f"walks={p['prefix.full_walks']}full/"
                  f"{p['prefix.partial_walks']}partial/"
                  f"{p['prefix.miss_walks']}miss "
                  f"pinned={p['prefix.pinned']} "
                  f"(pins={p['prefix.pins']} "
                  f"forced_unpins={p['prefix.forced_unpins']})")
        print(f"[serve] ttft_p50={s['ttft_p50_ms']:.0f}ms "
              f"ttft_p95={s['ttft_p95_ms']:.0f}ms "
              f"itl_p50={s['itl_p50_ms']:.1f}ms "
              f"itl_p95={s['itl_p95_ms']:.1f}ms")
        if s.get("slo"):
            slo = s["slo"]
            blame = " ".join(f"{k}={v}" for k, v in
                             slo["blame"].items() if v)
            print(f"[serve] slo: goodput={slo['goodput']:.0%} "
                  f"({slo['met']}/{slo['requests']} met, "
                  f"ttft_misses={slo['ttft_misses']} "
                  f"itl_misses={slo['itl_misses']})"
                  + (f" blame: {blame}" if blame else ""))
    if exporter is not None:
        exporter.snap(step=None)          # final state closes the file
        exporter.close()
        print(f"[metrics] {exporter.records} snapshots "
              f"-> {args.metrics_out}")
    if args.metrics_prom:
        from repro.obs.export import to_prometheus
        with open(args.metrics_prom, "w") as f:
            f.write(to_prometheus(eng.metrics))
        print(f"[metrics] Prometheus exposition -> {args.metrics_prom}")
    if args.slo_report:
        import json
        from repro.obs.slo import build_report
        rep = build_report(eng)
        with open(args.slo_report, "w") as f:
            json.dump(rep, f, indent=2)
        print(f"[slo] report ({rep['requests']} tracked, "
              f"goodput={rep['goodput']:.0%}) -> {args.slo_report}")
    if tracer is not None:
        from repro.obs.attribution import attribute, subsystems
        tracer.export_chrome(args.trace)
        recs = tracer.records()
        rep = attribute(recs)
        subs = ",".join(sorted(subsystems(recs)))
        print(f"[trace] {len(recs)} records ({subs}) -> {args.trace} "
              f"(open in https://ui.perfetto.dev)")
        if rep["steps"]:
            cats = " ".join(
                f"{k}={v:.1f}ms"
                for k, v in sorted(rep["categories_ms"].items())
                if v > 0)
            print(f"[trace] overhead: compute="
                  f"{rep['compute_fraction'] * 100:.0f}% "
                  f"runtime={rep['overhead_fraction'] * 100:.0f}% "
                  f"of {rep['wall_ms']:.1f}ms step wall ({cats})")


if __name__ == "__main__":
    main()
