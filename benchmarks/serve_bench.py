"""Serving benchmark: chunked prefill vs whole-prompt paged vs dense.

Two comparisons, each on the trace it is valid for:

* dense vs paged (PR 1): a short single-bucket trace — the dense
  engine's one shared ``len/cursor/abs`` clock is only correct when
  every concurrent request shares a prefill bucket and the cursor
  never outruns ``max_len``, so the bulk-ownership baseline is
  measured inside its own validity envelope.  At equal peak KV bytes
  the paged engine runs more concurrent requests, because short
  requests only hold the pages they touched.
* whole-prompt vs chunked prefill (DESIGN.md §4b): a mixed short/long
  trace with the long prompts queued FIRST — the head-of-line shape
  chunked prefill exists to break.  At EQUAL page budget, splitting
  prefill into page-aligned chunks under a per-step token budget must
  hold p50 time-to-first-token strictly below the whole-prompt engine
  at a total-throughput cost within 10%.

``--kv-shards N`` additionally serves the mixed trace from a pool
sharded over N AGAS localities (DESIGN.md §4c) — device-backed when
the runtime has one device per shard (run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` like
tests/test_distributed.py), simulated otherwise — with a forced
mid-trace page migration, and asserts the greedy outputs are
token-identical to the single-locality chunked engine.

``--tiering`` serves a pressure trace (long-ish prompts, more
requests than the device pool can hold) twice at the SAME device page
budget: once untiered — preemptions forfeit pages and re-prefill —
and once with the two-tier percolation pool (DESIGN.md §4d,
``--host-pages`` sizes the host tier), where preempted KV is written
back to host and restored on re-admission.  The tiered run must hold
>= 2x the concurrently resident requests outside ``--smoke``, stay
token-identical to an ample-pool reference, and reports the
offload/promote byte counters plus the copy/compute overlap
fraction.

``--prefix-heavy`` serves the dominant production shape — a long
shared system prompt with short per-request user suffixes — twice at
the SAME page budget on the tiered chunked engine: once with the
prefix cache saving memory only, and once with
``prefix_cache_compute=True`` (DESIGN.md §4e), where covered prompts
skip the covered prefill compute and fully-covered repeats admit
straight to decode from their cached activation checkpoint.  TWO
warm waves run back to back: a fixed-suffix-length wave (the
regression baseline — equal totals were the only shape the old
padded-layout keys could ever share) and a MIXED-suffix-length wave,
where every request has a different total length behind the same
head — the traffic the position-normalized keys and the radix
longest-prefix index exist for.  Outside ``--smoke`` the fixed wave
must show >= 3.5x lower p50 TTFT and >= 80% of its prefill tokens
skipped, the mixed wave >= 3x and >= 70% (the TTFT floors are set
~20% under the quietest-machine measurement: the skip-OFF numerator
swings with host load, and a floor that trips on scheduler noise
guards nothing); greedy outputs are asserted token-identical between
the two runs, both waves.

``--disagg`` serves the mixed trace a third time on the
disaggregated prefill/decode engine (DESIGN.md §4f): prefill chunks
dispatched as parcels to the prefix-owner locality over a 2-shard
pool, finished KV handed to the decode role through the percolation
snapshot machinery.  Greedy outputs must be token-identical to the
single-locality chunked engine, a warm shared-prefix wave must send
>= 90% of its prefill parcels to the prefix-owner locality (asserted
even under ``--smoke`` — dispatch is deterministic), and the run
reports handoff bytes moved plus the fraction of handoffs whose
staged copy overlapped a decode batch.  Outside ``--smoke`` the
disagg engine must hold >= 50% of the single-locality chunked
throughput on the same trace.  Calibration: repeated quiet-ish runs
measure the ratio at ~1.0x median with an observed 0.66-1.13x spread
(both numerator and denominator are short wall-clock timings, so
host load can hit either side) — the floor sits ~25% under the WORST
observed sample, per the PR 7 lesson that floors set near the quiet
median trip on scheduler noise and guard nothing.

``--chaos`` runs the locality-loss drill (DESIGN.md §4g): the
pressure trace on the disagg 2-shard tiered stack, once failure-free
and once with KV shard 1 killed mid-wave by a ``FailurePlan``.  Every
in-flight future must resolve with tokens identical to the
failure-free run — per rid over the whole wave, not sampled — via
host-tier page rebuild where a percolation copy exists and
drain + re-prefill where it does not; the dead shard then re-joins
and a second wave must be identical on the healed pool.

``--seed`` reseeds every trace generator, so mixed-trace runs are
reproducible (and comparable) across machines.

Engines are warmed up (prefill buckets, the chunk step, and the decode
step compiled) on a throwaway trace before timing, so the latency
split reflects scheduling, not XLA compilation.

Emits the run.py ``name,us_per_call,derived`` CSV contract plus one
``# json {...}`` line (and ``--out FILE`` to persist the JSON).
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from benchmarks.common import emit

ARCH = "yi-6b"

# -- dense vs paged (PR 1): short trace, one shared bucket -------------
SLOTS_DENSE = 4
DENSE_MAX_LEN = 96          # dense peak: 4 * 96 = 384 KV token rows
PAGE_SIZE = 16
DENSE_N_PAGES = SLOTS_DENSE * DENSE_MAX_LEN // PAGE_SIZE     # 24 pages
SLOTS_PAGED = 8             # paged runs 2x the decode width, same bytes

# -- whole-prompt vs chunked (PR 2): mixed trace, equal pages ----------
MIXED_MAX_LEN = 128
MIXED_N_PAGES = 32          # 512 KV token rows for both paged engines
CHUNK = 32
STEP_TOKENS = SLOTS_PAGED + 2 * CHUNK
N_SHORT = 14
N_LONG = 2
MAX_NEW = 16

# -- tiered percolation (DESIGN.md §4d): pressure trace, tiny device --
TIER_DEVICE_PAGES = 16      # 256 KV token rows of HBM
TIER_HOST_PAGES = 64        # the ~4x host DRAM tier behind it
SLOTS_TIERED = 16           # slot count beyond what the device holds
N_PRESSURE = 16             # long decode tails: ~6-7 pages each at
TIER_MAX_NEW = 48           # completion, vs a 16-page device pool

# -- disaggregated prefill/decode (DESIGN.md §4f) ---------------------
DISAGG_SHARDS = 2           # one prefill worker per KV shard
DISAGG_AFFINITY_FLOOR = 0.9
DISAGG_TPUT_FLOOR = 0.5     # vs single-locality chunked; see docstring

# -- prefix-heavy shared-system-prompt trace (DESIGN.md §4e) ----------
PREFIX_SYS = 112            # shared system prompt: exactly 7 full
                            # pages under the pad-free layout, so every
                            # warm request's covered head is 112 tokens
PREFIX_USER = 16            # fixed-length user suffix (the baseline
                            # wave): equal totals were the ONLY shape
                            # the old padded-layout keys could share,
                            # so this wave guards the original §4e win
PREFIX_USER_MIX = (4, 8, 12, 20, 28, 36, 44)
                            # mixed-length suffixes: different TOTAL
                            # lengths behind the same head — the
                            # traffic position-normalized keys exist
                            # for (112 + 44 stays under PREFIX_MAX_LEN)
PREFIX_N = 12               # warm wave (incl. PREFIX_REPEATS)
PREFIX_REPEATS = 2          # exact repeats of the seed prompt: fully
                            # covered, admit straight to decode
PREFIX_MAX_NEW = 8
PREFIX_PAGES = 64           # same page budget for both runs
PREFIX_HOST_PAGES = 64
PREFIX_MAX_LEN = 160


def _short_requests(cfg, n, max_new=MAX_NEW, rid0=0, seed=0):
    rng = np.random.default_rng(seed)
    from repro.serving.engine import Request
    return [Request(rid0 + i, rng.integers(
        0, cfg.vocab_size, size=int(rng.integers(8, 30)))
        .astype(np.int32), max_new_tokens=max_new)
        for i in range(n)]


def _mixed_requests(cfg, n_short=N_SHORT, n_long=N_LONG,
                    max_new=MAX_NEW, seed=0):
    """Long prompts FIRST, shorts queued behind them."""
    rng = np.random.default_rng(seed)
    from repro.serving.engine import Request
    longs = [Request(rid, rng.integers(
        0, cfg.vocab_size, size=int(rng.integers(80, 96)))
        .astype(np.int32), max_new_tokens=max_new)
        for rid in range(n_long)]
    return longs + _short_requests(cfg, n_short, max_new=max_new,
                                   rid0=n_long, seed=seed + 1)


def _pressure_requests(cfg, n=N_PRESSURE, max_new=TIER_MAX_NEW,
                       seed=0):
    """Medium prompts + LONG decode tails: every request grows to 6-7
    pages before finishing, so a 16-page device pool preempts
    constantly mid-decode — the shape write-back offload exists for."""
    rng = np.random.default_rng(seed + 7)
    from repro.serving.engine import Request
    return [Request(i, rng.integers(
        0, cfg.vocab_size, size=int(rng.integers(40, 64)))
        .astype(np.int32), max_new_tokens=max_new)
        for i in range(n)]


def _prefix_traces(cfg, n=PREFIX_N, repeats=PREFIX_REPEATS,
                   max_new=PREFIX_MAX_NEW, seed=0, mixed=False):
    """(seed request, warm wave): one cold request carrying the shared
    system prompt, then a wave of partial covers (same system prompt,
    fresh user suffixes) plus `repeats` exact repeats of the seed
    prompt (full covers).  ``mixed=True`` cycles the suffix lengths
    through PREFIX_USER_MIX, so every wave member has a different
    total length behind the shared head."""
    rng = np.random.default_rng(seed + 29)
    from repro.serving.engine import Request
    sys_p = rng.integers(0, cfg.vocab_size,
                         size=PREFIX_SYS).astype(np.int32)

    def req(rid, user):
        return Request(rid, np.concatenate([sys_p, user])
                       .astype(np.int32), max_new_tokens=max_new)

    seed_user = rng.integers(0, cfg.vocab_size, size=PREFIX_USER)
    seed_req = req(900, seed_user)
    lens = (PREFIX_USER_MIX if mixed
            else (PREFIX_USER,)) * (n - repeats)
    wave = [req(i, rng.integers(0, cfg.vocab_size, size=lens[i]))
            for i in range(n - repeats)]
    wave += [req(800 + j, seed_user) for j in range(repeats)]
    return seed_req, wave


def _warmup(eng, cfg, lens):
    """Compile every executable the timed trace will hit, then wipe
    the engine's telemetry so timings reflect scheduling only."""
    from repro.serving.engine import Request
    rng = np.random.default_rng(2)
    for rid, n in enumerate(lens):
        eng.submit(Request(-1 - rid, rng.integers(
            0, cfg.vocab_size, size=n).astype(np.int32),
            max_new_tokens=4))
    eng.run_to_completion()
    eng.completions.clear()
    eng.reset_metrics()
    if hasattr(eng, "counters"):
        eng.counters.clear()
        eng.preemptions = 0
        eng.prefix_skips = 0
        eng.prefix_partial_hits = 0
        eng.prefill_tokens_skipped = 0
        pool = eng.kvc.pool
        pool.allocs = pool.shares = pool.cow_copies = 0
        if getattr(pool, "tiered", False):
            # the timed trace starts from an empty pool, an empty
            # staging buffer, and clean percolation counters (warmup
            # prefixes would otherwise sit cold on device, and
            # warmup-staged promotions would clog the double buffer)
            from repro.core.percolation import TransferEngine
            pool.drop_all_cold()
            pool.evictions = pool.cold_drops = 0
            pool.offloaded = pool.promoted = 0
            pool.xfer = TransferEngine(
                max_inflight=pool.xfer.max_inflight)
            # the fresh transfer engine must keep tracing into the
            # pool's stream (set_tracer before warmup would be undone
            # here otherwise)
            pool.xfer.trace = pool.trace
            pool.xfer.queue.trace = pool.trace
            eng.offloads = eng.restores = 0
        if hasattr(eng, "handoff_queue"):
            # disagg (§4f): warmup handoffs/parcels are compilation
            # traffic, not the measured trace's
            eng.handoffs = eng.handoff_bytes = 0
            eng.handoff_overlapped = 0
            role = eng._prefill_role
            role.parcels = role.owner_parcels = 0
            role.cold_parcels = role.inter_locality = 0
            role.dispatch_sizes.clear()
            eng._port.sent = eng._port.local_applied = 0


def _serve(eng, reqs):
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    eng.run_to_completion()
    dt = time.perf_counter() - t0
    new_tokens = sum(len(c.tokens) for c in eng.completions)
    assert len(eng.completions) == len(reqs)
    return dt, new_tokens


def _eng_stats(st, slots, tok, wall):
    return {"slots": slots, "tok_s": tok / wall, "wall_s": wall,
            "peak_active": st["peak_active"],
            "peak_page_occupancy": st["peak_page_occupancy"],
            "preemptions": st["preemptions"],
            "page_shares": st["page_shares"],
            "cow_copies": st["cow_copies"],
            "ttft_p50_ms": st["ttft_p50_ms"],
            "ttft_p95_ms": st["ttft_p95_ms"],
            "ttft_p99_ms": st["ttft_p99_ms"],
            "itl_p50_ms": st["itl_p50_ms"],
            "itl_p95_ms": st["itl_p95_ms"],
            "itl_p99_ms": st["itl_p99_ms"]}


def _serve_sharded(params, cfg, kw_mixed, warm_lens, mixed, kv_shards,
                   baseline_tokens):
    """Mixed trace over a kv_shards-locality pool + a forced mid-trace
    migration; greedy outputs must match the single-locality engine
    token for token (the AGAS name-stability promise, end to end)."""
    from repro.distributed.sharding import kv_pool_mesh
    from repro.serving.engine import make_engine

    mesh = kv_pool_mesh(kv_shards)
    eng = make_engine(params, cfg, engine="chunked", chunk_size=CHUNK,
                      step_tokens=STEP_TOKENS, kv_shards=kv_shards,
                      mesh=mesh, **kw_mixed)
    _warmup(eng, cfg, warm_lens)
    eng.kvc.pool.page_migrations = 0
    for r in mixed:
        eng.submit(r)
    t0 = time.perf_counter()
    for _ in range(4):                  # into the trace, then force a
        eng.step()                      # mid-trace migration
    eng.force_migrate()
    eng.run_to_completion()
    dt = time.perf_counter() - t0
    new_tokens = sum(len(c.tokens) for c in eng.completions)
    st = eng.stats()
    toks = {c.rid: c.tokens for c in eng.completions}
    assert toks == baseline_tokens, (
        f"kv_shards={kv_shards} outputs diverge from the "
        "single-locality engine")
    out = _eng_stats(st, eng.slots, new_tokens, dt)
    out.update(kv_shards=kv_shards,
               backing="mesh" if mesh is not None else "simulated",
               shard_occupancy=st["shard_occupancy"],
               page_migrations=st["page_migrations"])
    return out


def _disagg_affinity_wave(eng, cfg, seed):
    """Warm shared-prefix wave on a drained disagg engine: one cold
    seed request plants the prefix and KEEPS DECODING (an untiered
    pool de-indexes prefix pages at refcount zero), then a wave
    sharing its head dispatches.  Returns (owner, total) prefill
    parcels over the wave alone — dispatch is deterministic, so the
    >= 90% affinity floor holds even under --smoke."""
    from repro.serving.engine import Request
    rng = np.random.default_rng(seed + 61)
    head = rng.integers(0, cfg.vocab_size, size=64)      # 4 pages
    eng.submit(Request(700, np.concatenate([
        head, rng.integers(0, cfg.vocab_size, size=8)])
        .astype(np.int32), max_new_tokens=32))
    while not eng.active or any(st["phase"] != "decode"
                                for st in eng.active.values()):
        eng.step()                     # seed resident, prefix planted
    before = eng.stats()
    wave = [Request(710 + i, np.concatenate([
        head, rng.integers(0, cfg.vocab_size, size=4 + 4 * i)])
        .astype(np.int32), max_new_tokens=4) for i in range(8)]
    for r in wave:
        eng.submit(r)
    eng.run_to_completion()
    after = eng.stats()
    return (after["prefill_parcels_owner"]
            - before["prefill_parcels_owner"],
            after["prefill_parcels"] - before["prefill_parcels"])


def _prefix_run(params, cfg, seed_req, wave, skip):
    """One warm shared-system-prompt wave at the standard page budget:
    seed the prefix cache with one cold request, then measure the wave
    with compute skip on or off.  Returns (metrics, rid -> tokens)."""
    from repro.serving.engine import Request, make_engine
    eng = make_engine(params, cfg, engine="chunked",
                      slots=SLOTS_PAGED, max_len=PREFIX_MAX_LEN,
                      prefill_buckets=(32,), page_size=PAGE_SIZE,
                      n_pages=PREFIX_PAGES, chunk_size=CHUNK,
                      step_tokens=STEP_TOKENS, tiering=True,
                      host_pages=PREFIX_HOST_PAGES,
                      prefix_cache_compute=skip)
    _warmup(eng, cfg, (156, 120, 33, 12))
    # seed the cache (the cold request the wave shares), then one
    # throwaway warm repeat so the resume executable compiles outside
    # the timed wave; telemetry resets but the cold pages STAY — warm
    # is the point
    eng.submit(seed_req)
    eng.run_to_completion()
    cold_ttft_ms = eng.completions[0].ttft_s * 1e3
    eng.submit(Request(901, seed_req.prompt, max_new_tokens=2))
    eng.run_to_completion()
    eng.completions.clear()
    eng.counters.clear()
    eng.reset_metrics()
    eng.prefix_skips = 0
    eng.prefix_partial_hits = 0
    eng.prefill_tokens_skipped = 0
    dt, tok = _serve(eng, wave)
    st = eng.stats()
    run_tok = sum(c.get("prefill_chunk_tokens", 0)
                  for c in eng.counters)
    skipped = st["prefill_tokens_skipped"]
    out = dict(_eng_stats(st, eng.slots, tok, dt),
               compute_skip=skip,
               cold_ttft_ms=cold_ttft_ms,
               prefix_skips=st["prefix_skips"],
               prefix_partial_hits=st["prefix_partial_hits"],
               prefill_tokens_skipped=skipped,
               prefill_tokens_run=run_tok,
               skip_fraction=skipped / max(skipped + run_tok, 1),
               radix=eng.kvc.pool.prefix.metrics())
    return out, {c.rid: c.tokens for c in eng.completions}


def _traced_run(params, cfg, trace_path, smoke, seed, verbose,
                disagg=False):
    """Tentpole measurement (DESIGN.md §10): serve a pressure trace on
    the full stack — chunked prefill, 2 KV shards, two-tier
    percolation, a forced mid-trace migration — twice from identical
    warmed engines: once untraced (the wall-clock baseline) and once
    with the causal tracer attached to every subsystem.  Exports the
    Chrome trace, validates span nesting + request->slot->page causal
    links, decomposes step wall-clock into compute vs runtime overhead,
    and bounds the tracer's own cost (<= 5% enabled outside --smoke;
    <= 1% disabled, estimated from the measured null-tracer call cost
    times the observed records-per-step rate)."""
    import os

    from repro.obs.attribution import (attribute, check_causal,
                                       check_nesting, subsystems)
    from repro.obs.trace import NULL_TRACER, Tracer, set_global
    from repro.serving.engine import make_engine

    kw = dict(slots=SLOTS_PAGED, max_len=MIXED_MAX_LEN,
              prefill_buckets=(32,), page_size=PAGE_SIZE,
              n_pages=TIER_DEVICE_PAGES, chunk_size=CHUNK,
              step_tokens=STEP_TOKENS, kv_shards=2, tiering=True,
              host_pages=48, disagg=disagg)
    reqs = _pressure_requests(cfg, n=6, max_new=8 if smoke else 48,
                              seed=seed)
    warm = (97, 90, 33, 12)
    reps = 3 if smoke else 5

    def _drive(eng, rid_off):
        """Submissions, steps, and a forced migration — identical for
        the baseline and traced engines, so wall-clocks compare.  rids
        are offset per repetition so futures never collide."""
        import dataclasses
        rs = [dataclasses.replace(r, rid=r.rid + rid_off)
              for r in reqs]
        n0 = len(eng.completions)
        for r in rs[:2]:
            eng.submit(r)
        for _ in range(3):
            eng.step()
        eng.force_migrate()            # parcels: plan + AGAS moves
        for r in rs[2:]:
            eng.submit(r)
        eng.run_to_completion()
        return {c.rid - rid_off: c.tokens
                for c in eng.completions[n0:]}

    def _timed_drive(eng, rid_off):
        t0 = time.perf_counter()
        toks = _drive(eng, rid_off)
        return time.perf_counter() - t0, toks

    # a scratch engine absorbs process-level compiles _warmup does not
    # cover (the forced migration's permutation program), so the two
    # timed drives below compare scheduling, not XLA compilation
    scratch = make_engine(params, cfg, engine="chunked", **kw)
    _warmup(scratch, cfg, warm)
    _drive(scratch, 0)

    base = make_engine(params, cfg, engine="chunked", **kw)
    _warmup(base, cfg, warm)

    tracer = Tracer(capacity=1 << 18)
    eng = make_engine(params, cfg, engine="chunked", **kw)
    _warmup(eng, cfg, warm)
    eng.set_tracer(tracer)             # engine + pool + xfer

    # interleaved pairs: each repetition times the untraced and traced
    # twins back to back under the same system state, so load/frequency
    # drift cancels; min wall per side is the noise-robust statistic
    # the enabled-cost budget is judged on (one GC pause or scheduler
    # hiccup dwarfs the tracer at these run lengths).  The module
    # global (lco / parcels / agas) is live only during traced drives
    # so the baseline stays untraced and the ring stays causally
    # self-contained.
    base_walls, traced_walls = [], []
    base_toks, traced_toks = [], []
    try:
        for k in range(reps):
            w, t = _timed_drive(base, 100 * k)
            base_walls.append(w)
            base_toks.append(t)
            set_global(tracer)
            w, t = _timed_drive(eng, 100 * k)
            traced_walls.append(w)
            traced_toks.append(t)
            set_global(None)
    finally:
        set_global(None)
    base_s, traced_s = min(base_walls), min(traced_walls)
    base_total_s = sum(base_walls)
    base_steps = max(len(base.counters), 1)
    assert traced_toks == base_toks, (
        "tracing changed the served tokens — instrumentation must be "
        "observation only")

    records = tracer.records()
    assert tracer.dropped == 0, (
        f"ring dropped {tracer.dropped} records; causal validation "
        "needs the complete stream (raise the tracer capacity)")
    subs = subsystems(records)
    need = {"engine", "kvcache", "percolation", "parcels", "lco"}
    assert need <= subs, f"trace missing subsystems: {need - subs}"
    nest = check_nesting(records)
    assert not nest, f"span nesting violations: {nest[:3]}"
    causal = check_causal(records)
    assert not causal, f"dangling causal links: {causal[:3]}"

    report = attribute(records)
    assert report["steps"] > 0
    assert report["sum_residual"] <= 0.05, (
        f"attribution does not reconcile with step wall-clock: "
        f"residual {report['sum_residual']:.3f}")
    if disagg:
        # §4f handoffs must land in the parcel/copy attribution
        # buckets, not vanish into the residual
        names = {r.name for r in records}
        assert {"handoff_stage", "handoff_commit"} <= names, (
            "disagg trace carries no handoff spans")
        assert report["categories_ms"].get("copy", 0.0) > 0.0

    # tracer cost, enabled: wall-clock vs the untraced twin
    enabled_frac = traced_s / base_s - 1.0
    if not smoke:
        assert enabled_frac <= 0.05, (
            f"enabled tracing costs {enabled_frac:.1%} throughput "
            "(budget 5%)")
    # tracer cost, disabled: the null tracer's measured per-call cost
    # times the records-per-step rate this run actually produced,
    # against the untraced per-step wall — an upper bound on what the
    # instrumentation costs every untraced serve
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        with NULL_TRACER.span("engine", "x", kind="compute"):
            NULL_TRACER.instant("engine", "y", rid=0)
    per_record_s = (time.perf_counter() - t0) / (2 * n)
    records_per_step = len(records) / max(report["steps"], 1)
    disabled_frac = (per_record_s * records_per_step
                     / (base_total_s / base_steps))
    assert disabled_frac <= 0.01, (
        f"disabled tracing costs {disabled_frac:.2%} of a step "
        "(budget 1%)")

    tracer.export_chrome(trace_path)
    overhead = {
        "records": len(records),
        "subsystems": sorted(subs),
        "overhead": report,
        "enabled_overhead_fraction": enabled_frac,
        "disabled_overhead_fraction": disabled_frac,
        "baseline_wall_s": base_s,
        "traced_wall_s": traced_s,
    }
    report_path = os.path.splitext(trace_path)[0] + ".report.json"
    with open(report_path, "w") as f:
        json.dump(overhead, f, indent=2)
    if verbose:
        c = report["categories_ms"]
        split = " ".join(f"{k}={c[k]:.1f}ms" for k in sorted(c)
                        if c[k] > 0.0)
        print(f"# serve_bench traced  {len(records)} records, "
              f"{len(subs)} subsystems, "
              f"compute={report['compute_fraction']:.0%} "
              f"overhead={report['overhead_fraction']:.0%} [{split}] "
              f"residual={report['sum_residual']:.1%} "
              f"cost on/off={enabled_frac:+.1%}/{disabled_frac:.2%} "
              f"-> {trace_path}")
    emit("serve_trace_records", len(records), "events")
    emit("serve_trace_overhead_fraction",
         report["overhead_fraction"], "of_step_wall")
    emit("serve_trace_cost_enabled", enabled_frac * 100, "percent")
    return overhead


def _slo_run(params, cfg, smoke, seed, verbose, report_path=None):
    """SLO/goodput observability run (DESIGN.md §10): serve a
    deadline-carrying pressure trace on the full stack — disaggregated
    prefill/decode over a 2-shard tiered pool — with the flight
    recorder on, and check the three promises the recorder makes:

    * verdicts stream into the ``slo.*`` registry names and the
      goodput report reconciles with them (every missed request gets
      exactly one blame bucket; goodput is deterministic because the
      deadline mix is load-independent: tight deadlines no machine
      can meet, loose ones none can miss);
    * the recorder's exec/handoff durations reconcile with the §10
      causal-trace attribution measured on the SAME drive (summed
      flight-recorder durs vs summed span durs of the same
      boundaries, residual <= 5% each);
    * recorder cost stays inside the tracing budgets — <= 5% enabled
      (recorder-on vs recorder-off twins, interleaved, min-wall,
      outside ``--smoke``) and <= 1% disabled (the measured
      null-recorder guard cost times the observed events-per-step
      rate, against the recorder-off per-step wall).

    Also round-trips the end-of-run registry through both exporters
    (Prometheus text and one JSONL snapshot) against ``snapshot()``.
    """
    import dataclasses
    import os
    import tempfile

    from repro.obs.attribution import attribute_roles
    from repro.obs.export import (JsonlExporter, read_jsonl,
                                  verify_roundtrip)
    from repro.obs.slo import (EXEC_EVENTS, HANDOFF_EVENTS,
                               NULL_RECORDER, build_report)
    from repro.obs.trace import Tracer, set_global
    from repro.serving.engine import make_engine

    kw = dict(slots=SLOTS_PAGED, max_len=MIXED_MAX_LEN,
              prefill_buckets=(32,), page_size=PAGE_SIZE,
              n_pages=TIER_DEVICE_PAGES, chunk_size=CHUNK,
              step_tokens=STEP_TOKENS, kv_shards=2, tiering=True,
              host_pages=48, disagg=True)
    base_reqs = _pressure_requests(cfg, n=6, max_new=8 if smoke else 24,
                                   seed=seed)
    # the deadline mix is load-independent so goodput is exact on any
    # machine: every 3rd request gets a TTFT deadline nothing can meet
    # (tighter than one decode step), the next a same-tight ITL
    # deadline, the rest 10-minute deadlines nothing can miss
    TIGHT_MS, LOOSE_MS = 0.05, 600_000.0
    n_loose = sum(1 for i in range(len(base_reqs)) if i % 3 == 2)

    def _with_deadlines(off):
        return [dataclasses.replace(
            r, rid=r.rid + off,
            ttft_deadline_ms=TIGHT_MS if i % 3 == 0 else LOOSE_MS,
            itl_deadline_ms=TIGHT_MS if i % 3 == 1 else LOOSE_MS)
            for i, r in enumerate(base_reqs)]

    warm = (97, 90, 33, 12)
    reps = 2 if smoke else 6

    def _drive(eng, rid_off):
        rs = _with_deadlines(rid_off)
        n0 = len(eng.completions)
        for r in rs[:2]:
            eng.submit(r)
        for _ in range(3):
            eng.step()
        eng.force_migrate()
        for r in rs[2:]:
            eng.submit(r)
        eng.run_to_completion()
        return {c.rid - rid_off: c.tokens
                for c in eng.completions[n0:]}

    def _timed_drive(eng, rid_off):
        t0 = time.perf_counter()
        toks = _drive(eng, rid_off)
        return time.perf_counter() - t0, toks

    # scratch engine absorbs process-level compiles (the forced
    # migration's permutation program) so the twins compare scheduling
    scratch = make_engine(params, cfg, engine="chunked", **kw)
    _warmup(scratch, cfg, warm)
    _drive(scratch, 0)

    base = make_engine(params, cfg, engine="chunked", **kw)
    _warmup(base, cfg, warm)
    eng = make_engine(params, cfg, engine="chunked",
                      flight_recorder=True, **kw)
    _warmup(eng, cfg, warm)

    # recorder cost, enabled: recorder-off vs recorder-on twins (both
    # classify — deadlines ride on every request — so the delta is the
    # recorder alone), interleaved back to back so each pair shares
    # system state.  The budget is judged on the min per-pair ratio,
    # not min(rec)/min(base): one lucky-fast baseline rep would
    # inflate the cross-pair ratio by the machine's full noise band
    # (±3-4% at these run lengths), while a real systematic cost
    # shows up in every pair and survives the min
    base_walls, rec_walls = [], []
    base_toks, rec_toks = [], []
    for k in range(reps):
        w, t = _timed_drive(base, 100 * k)
        base_walls.append(w)
        base_toks.append(t)
        w, t = _timed_drive(eng, 100 * k)
        rec_walls.append(w)
        rec_toks.append(t)
    assert rec_toks == base_toks, (
        "the flight recorder changed the served tokens — "
        "instrumentation must be observation only")
    base_s, rec_s = min(base_walls), min(rec_walls)
    enabled_frac = min(r / b for r, b in zip(rec_walls,
                                             base_walls)) - 1.0
    if not smoke:
        assert enabled_frac <= 0.05, (
            f"enabled flight recording costs {enabled_frac:.1%} "
            "throughput (budget 5%)")

    # recorder cost, disabled: every hook site is one attribute load +
    # branch on NULL_RECORDER.enabled; measure it and scale by the
    # events-per-step rate this run actually produced
    n_events = sum(len(eng.recorder.timeline(r))
                   for r in eng.recorder.rids())
    n_steps = max(len(eng.counters), 1)
    n = 200_000
    rec = NULL_RECORDER
    t0 = time.perf_counter()
    for _ in range(n):
        if rec.enabled:
            rec.event(0, "x", dur=0.0)
    per_guard_s = (time.perf_counter() - t0) / n
    base_step_s = sum(base_walls) / max(len(base.counters), 1)
    disabled_frac = per_guard_s * (n_events / n_steps) / base_step_s
    assert disabled_frac <= 0.01, (
        f"disabled flight recording costs {disabled_frac:.2%} of a "
        "step (budget 1%)")

    # reconciliation drive: tracer AND recorder on the same engine,
    # telemetry wiped first so both views cover exactly one drive
    eng.completions.clear()
    eng.reset_metrics()              # registry + recorder + verdicts
    eng.counters.clear()
    tracer = Tracer(capacity=1 << 18)
    eng.set_tracer(tracer)
    set_global(tracer)
    try:
        _drive(eng, 100 * reps)
    finally:
        set_global(None)
    records = tracer.records()
    assert tracer.dropped == 0

    # flight-recorder exec/handoff durs vs the causal-trace spans that
    # wrap the same boundaries: summed over the drive they must agree
    # (same clock, same edges — the budget absorbs the per-op hook
    # skew and stage copies the recorder skips on snapshot misses)
    fr_exec = fr_handoff = 0.0
    for rid in eng.recorder.rids():
        ph = eng.recorder.phases(rid)
        fr_exec += ph.get("prefill_exec", 0.0) \
            + ph.get("prefill_exec_post", 0.0)
        fr_handoff += ph.get("handoff", 0.0)
    # subsystem-filtered: kvcache/"restore" is the page-level child
    # nested INSIDE engine/"restore" and percolation handoff commits —
    # name-only summation would double-count it
    span_exec = sum(r.dur for r in records
                    if r.subsystem == "engine"
                    and r.name in EXEC_EVENTS and r.dur is not None)
    span_handoff = sum(r.dur for r in records
                       if r.subsystem == "percolation"
                       and r.name in HANDOFF_EVENTS
                       and r.dur is not None)
    # the FR hook brackets the span (two extra clock reads + the ring
    # append land inside the FR dur), so each op carries a small fixed
    # skew — for µs-scale ops (handoff commits are table rebuilds)
    # that fixed part dominates a purely relative budget, so each
    # bucket gets 5% relative OR 50µs-per-op absolute slack
    _SKEW_S = 50e-6
    n_exec_ops = sum(1 for r in records if r.subsystem == "engine"
                     and r.name in EXEC_EVENTS and r.dur is not None)
    n_hand_ops = sum(1 for r in records
                     if r.subsystem == "percolation"
                     and r.name in HANDOFF_EVENTS
                     and r.dur is not None)
    exec_residual = abs(fr_exec - span_exec) / max(span_exec, 1e-9)
    handoff_residual = (abs(fr_handoff - span_handoff)
                        / max(span_handoff, 1e-9))
    assert span_exec > 0.0 and span_handoff > 0.0
    assert (exec_residual <= 0.05
            or abs(fr_exec - span_exec) <= _SKEW_S * n_exec_ops), (
        f"flight-recorder prefill exec ({fr_exec * 1e3:.1f}ms) does "
        f"not reconcile with the traced {span_exec * 1e3:.1f}ms "
        f"(residual {exec_residual:.1%}, budget 5% or "
        f"{_SKEW_S * n_exec_ops * 1e3:.2f}ms)")
    assert (handoff_residual <= 0.05
            or abs(fr_handoff - span_handoff)
            <= _SKEW_S * n_hand_ops), (
        f"flight-recorder handoff ({fr_handoff * 1e3:.1f}ms) does "
        f"not reconcile with the traced {span_handoff * 1e3:.1f}ms "
        f"(residual {handoff_residual:.1%}, budget 5% or "
        f"{_SKEW_S * n_hand_ops * 1e3:.2f}ms)")
    roles = attribute_roles(records)
    assert roles["roles_ms"].get("prefill", 0.0) > 0.0
    assert set(roles["localities_ms"]) >= {"loc0", "loc1"}

    # goodput report vs the deterministic deadline mix
    report = build_report(eng)
    assert report["requests"] == len(base_reqs)
    assert report["met"] == n_loose
    assert abs(report["goodput"] - n_loose / len(base_reqs)) < 1e-9
    assert report["ttft_misses"] > 0 and report["itl_misses"] > 0
    blamed = sum(report["blame"].values())
    assert blamed == report["requests"] - report["met"], (
        "every missed request must land in exactly one blame bucket")
    assert report["blame"]["unattributed"] == 0, (
        "recorder was on: no miss should be unattributed")

    # exporter round-trips against the live registry
    problems = verify_roundtrip(eng.metrics)
    assert not problems, f"prometheus round-trip: {problems[:3]}"
    fd, jl_path = tempfile.mkstemp(suffix=".jsonl")
    os.close(fd)
    try:
        with JsonlExporter(eng.metrics, jl_path) as exp:
            exp.snap(step=n_steps)
        got = read_jsonl(jl_path)[-1]["metrics"]
        want = eng.metrics.snapshot()
        assert set(got) == set(want)
        assert all(abs(got[k] - want[k]) <= 1e-9 * max(
            1.0, abs(want[k])) for k in want)
    finally:
        os.unlink(jl_path)

    report["recorder"] = {
        "enabled_overhead_fraction": enabled_frac,
        "disabled_overhead_fraction": disabled_frac,
        "events": n_events,
        "events_per_step": n_events / n_steps,
        "exec_residual": exec_residual,
        "handoff_residual": handoff_residual,
        "roles_ms": roles["roles_ms"],
        "localities_ms": roles["localities_ms"],
        "baseline_wall_s": base_s,
        "recorded_wall_s": rec_s,
    }
    if report_path:
        with open(report_path, "w") as f:
            json.dump(report, f, indent=2)
    if verbose:
        b = report["blame"]
        blame_s = " ".join(f"{k}={b[k]}" for k in sorted(b) if b[k])
        print(f"# serve_bench slo     goodput={report['goodput']:.2f} "
              f"({report['met']}/{report['requests']} met, "
              f"ttft_miss={report['ttft_misses']} "
              f"itl_miss={report['itl_misses']}) [{blame_s}] "
              f"recon exec/handoff="
              f"{exec_residual:.1%}/{handoff_residual:.1%} "
              f"cost on/off={enabled_frac:+.1%}/{disabled_frac:.2%}"
              + (f" -> {report_path}" if report_path else ""))
    emit("serve_slo_goodput", report["goodput"], "fraction")
    emit("serve_slo_recorder_cost_enabled", enabled_frac * 100,
         "percent")
    emit("serve_slo_recorder_cost_disabled", disabled_frac * 100,
         "percent")
    emit("serve_slo_exec_residual", exec_residual * 100, "percent")
    return report


def _chaos_run(params, cfg, smoke, seed, verbose):
    """Chaos drill (DESIGN.md §4g): serve the pressure trace on the
    full stack — disaggregated prefill/decode over a 2-shard tiered
    pool — twice from identically warmed engines: once failure-free
    (the token ground truth), and once with a failure plan that kills
    KV shard 1 mid-wave.  EVERY future must resolve, and every
    request's greedy tokens must be identical to the failure-free run
    — asserted per rid over the full wave, not sampled.  Pages with a
    host-tier percolation copy rebuild on the survivor; the rest
    drain their slots and re-prefill from the retained prompt +
    position clock.  The dead shard then re-joins elastically and a
    second wave must come back token-identical on the healed pool."""
    import dataclasses

    from repro.ft.failures import FailurePlan
    from repro.serving.engine import make_engine

    kw = dict(slots=SLOTS_PAGED, max_len=MIXED_MAX_LEN,
              prefill_buckets=(32,), page_size=PAGE_SIZE,
              n_pages=TIER_DEVICE_PAGES, chunk_size=CHUNK,
              step_tokens=STEP_TOKENS, kv_shards=2, tiering=True,
              host_pages=48, disagg=True)
    reqs = _pressure_requests(cfg, n=6, max_new=8 if smoke else 24,
                              seed=seed)
    warm = (97, 90, 33, 12)

    ref_eng = make_engine(params, cfg, engine="chunked", **kw)
    _warmup(ref_eng, cfg, warm)
    ref_futs = [ref_eng.submit(r) for r in reqs]
    ref_eng.run_to_completion()
    truth = {f.get().rid: f.get().tokens for f in ref_futs}

    eng = make_engine(params, cfg, engine="chunked", **kw)
    _warmup(eng, cfg, warm)
    # armed AFTER warmup: the plan counts engine steps, and warmup
    # wipes the counters it counts against
    eng.failure_plan = FailurePlan.kill_locality(1, at_step=CHAOS_AT)
    futs = [eng.submit(dataclasses.replace(r)) for r in reqs]
    t0 = time.perf_counter()
    eng.run_to_completion()
    dt = time.perf_counter() - t0
    tok = sum(len(c.tokens) for c in eng.completions)

    unresolved = [f for f in futs if not f.done()]
    assert not unresolved, (
        f"{len(unresolved)} futures never resolved after the kill — "
        "recovery must re-admit, not error")
    got = {f.get().rid: f.get().tokens for f in futs}
    assert got == truth, (
        "chaos outputs diverge from the failure-free run — rebuild "
        "and re-prefill must not change a token")
    st = eng.stats()
    rec = st["recovery"]
    assert rec["localities_killed"] == 1, "the failure plan never fired"
    assert rec["drained_slots"] + rec["pages_rebuilt"] > 0, (
        "the kill landed on an idle pool — the drill proves nothing")
    assert eng.kvc.pool.used_pages == 0

    # elastic re-join, then a second wave on the healed 2-shard pool
    moved = eng.join_locality(1)
    assert eng.kvc.pool.agas.is_active(1)
    futs2 = [eng.submit(dataclasses.replace(r, rid=r.rid + 100))
             for r in reqs]
    eng.run_to_completion()
    got2 = {f.get().rid - 100: f.get().tokens for f in futs2}
    assert got2 == truth, (
        "post-rejoin outputs diverge — the healed pool must serve "
        "identically")
    assert eng.kvc.pool.used_pages == 0

    out = dict(_eng_stats(st, eng.slots, tok, dt),
               kill_shard=1, kill_step=CHAOS_AT,
               n_requests=len(reqs),
               localities_killed=rec["localities_killed"],
               pages_rebuilt=rec["pages_rebuilt"],
               pages_lost=rec["pages_lost"],
               drained_slots=rec["drained_slots"],
               re_prefills=rec["re_prefills"],
               recovery_restarts=rec["recovery_restarts"],
               rejoin_moves=moved)
    if verbose:
        print(f"# serve_bench chaos   {tok / dt:8.1f} tok/s "
              f"(pressure, shard 1 killed at step {CHAOS_AT}) "
              f"rebuilt={rec['pages_rebuilt']} "
              f"lost={rec['pages_lost']} "
              f"drained={rec['drained_slots']} "
              f"re_prefills={rec['re_prefills']} "
              "token-identical to failure-free run "
              "(and again after re-join)")
    emit("serve_chaos_tok_s", tok / dt, "tok_per_s")
    emit("serve_chaos_pages_rebuilt", rec["pages_rebuilt"], "pages")
    emit("serve_chaos_pages_lost", rec["pages_lost"], "pages")
    emit("serve_chaos_drained_slots", rec["drained_slots"], "slots")
    emit("serve_chaos_re_prefills", rec["re_prefills"], "requests")
    return out


#: Step the --chaos failure plan fires at: far enough in that the
#: wave is mid-flight (slots bound, handoffs staged), early enough
#: that nothing has finished.
CHAOS_AT = 4

#: Bench-trajectory identity: BENCH_<n>.json files carry this id so
#: tools/bench_compare.py can order them and diff against the
#: previous one.
BENCH_ID = 9

#: Floors embedded in the committed BENCH_9.json, checked by
#: tools/bench_compare.py on full (non ``--smoke``) runs.  Throughput
#: floors sit ~20% under the LOWEST of several full-run measurements
#: (the PR 7/8 lesson: floors near the quiet median trip on scheduler
#: noise and guard nothing; observed run-to-run spread on tok/s is
#: ~35%, e.g. tiered 415-628 tok/s over four runs on one machine);
#: skip fractions are deterministic at a fixed seed, and slo.goodput
#: is deterministic on any machine (the deadline mix is
#: load-independent), so those floors stay tight.
BENCH_FLOORS = {
    "chunked_mixed.tok_s": 900.0,
    "disagg_mixed.tok_s": 900.0,
    "tiered_pressure.tok_s": 300.0,
    "prefix_fixed.skip_fraction": 0.8,
    "prefix_mixed.skip_fraction": 0.7,
    "slo.goodput": 0.33,
}


def _bench_scenarios(result):
    """Flatten one serve_bench result dict into the schema'd scenario
    map BENCH_<n>.json carries: per-scenario latency percentiles,
    throughput, and the rates the floors guard.  Scenarios the run
    did not exercise are simply absent — bench_compare diffs the
    intersection."""
    def lat(d):
        return {k: d[k] for k in (
            "tok_s", "ttft_p50_ms", "ttft_p95_ms", "ttft_p99_ms",
            "itl_p50_ms", "itl_p95_ms", "itl_p99_ms", "preemptions")
            if k in d}

    sc = {}
    mt = result.get("mixed_trace")
    if mt:
        sc["paged_mixed"] = lat(mt["paged"])
        sc["chunked_mixed"] = lat(mt["chunked"])
        if "sharded" in mt:
            sc["sharded_mixed"] = dict(
                lat(mt["sharded"]),
                page_migrations=mt["sharded"]["page_migrations"])
    dt = result.get("disagg_trace")
    if dt:
        sc["disagg_mixed"] = dict(
            lat(dt), tput_vs_chunked=dt["tput_vs_chunked"],
            handoff_overlap=dt["handoff_overlap"],
            warm_wave_affinity=dt["warm_wave_affinity"])
    tt = result.get("tiered_trace")
    if tt:
        sc["tiered_pressure"] = dict(
            lat(tt["tiered"]), resident_ratio=tt["resident_ratio"],
            decode_penalty=tt["decode_penalty"],
            copy_compute_overlap=tt["tiered"]["copy_compute_overlap"])
    pt = result.get("prefix_trace")
    if pt:
        for kind in ("fixed", "mixed"):
            if kind in pt:
                w = pt[kind]
                sc[f"prefix_{kind}"] = dict(
                    lat(w["skip_on"]),
                    skip_fraction=w["skip_on"]["skip_fraction"],
                    ttft_p50_reduction_x=w["ttft_p50_reduction_x"])
    ch = result.get("chaos_trace")
    if ch:
        sc["chaos_pressure"] = dict(
            lat(ch), pages_rebuilt=ch["pages_rebuilt"],
            pages_lost=ch["pages_lost"],
            drained_slots=ch["drained_slots"],
            re_prefills=ch["re_prefills"])
    sl = result.get("slo")
    if sl:
        sc["slo"] = {
            "goodput": sl["goodput"],
            "requests": sl["requests"],
            "met": sl["met"],
            "ttft_misses": sl["ttft_misses"],
            "itl_misses": sl["itl_misses"],
            "recorder_cost_enabled":
                sl["recorder"]["enabled_overhead_fraction"],
            "recorder_cost_disabled":
                sl["recorder"]["disabled_overhead_fraction"],
            "exec_residual": sl["recorder"]["exec_residual"],
        }
    return sc


def run(verbose=True, out_path=None, smoke=False, kv_shards=0,
        tiering=False, host_pages=0, prefix_heavy=False, seed=0,
        trace_path=None, disagg=False, slo=False, slo_report=None,
        chaos=False, bench_out=None):
    import jax

    import repro.configs as configs
    from repro.models import transformer as T
    from repro.serving.engine import make_engine

    cfg = configs.get_reduced(ARCH)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    result = {"arch": ARCH, "page_size": PAGE_SIZE, "seed": seed}

    # -- dense vs paged on the short trace ----------------------------
    short = _short_requests(cfg, 4 if smoke else 16,
                            max_new=4 if smoke else MAX_NEW,
                            seed=seed)
    kw_short = dict(max_len=DENSE_MAX_LEN, prefill_buckets=(32,))
    dense = make_engine(params, cfg, engine="dense",
                        slots=SLOTS_DENSE, **kw_short)
    _warmup(dense, cfg, (12,))
    dense_s, dense_tok = _serve(dense, short)

    paged_s_eng = make_engine(params, cfg, engine="paged",
                              slots=SLOTS_PAGED, page_size=PAGE_SIZE,
                              n_pages=DENSE_N_PAGES, **kw_short)
    _warmup(paged_s_eng, cfg, (12,))
    pshort_s, pshort_tok = _serve(paged_s_eng, short)
    ps_st = paged_s_eng.stats()

    result["short_trace"] = {
        "kv_token_rows": SLOTS_DENSE * DENSE_MAX_LEN,
        "n_requests": len(short),
        "dense": {"slots": SLOTS_DENSE, "tok_s": dense_tok / dense_s,
                  "wall_s": dense_s, "peak_active": SLOTS_DENSE},
        "paged": _eng_stats(ps_st, SLOTS_PAGED, pshort_tok, pshort_s),
    }

    # -- whole-prompt vs chunked on the mixed trace -------------------
    mixed = _mixed_requests(cfg, n_short=4 if smoke else N_SHORT,
                            n_long=1 if smoke else N_LONG,
                            max_new=4 if smoke else MAX_NEW,
                            seed=seed)
    kw_mixed = dict(max_len=MIXED_MAX_LEN, prefill_buckets=(32,),
                    slots=SLOTS_PAGED, page_size=PAGE_SIZE,
                    n_pages=MIXED_N_PAGES)
    # cover every bucket a preempted request's re-admission can land
    # in (32/64/96/128), not just the fresh-prompt buckets — otherwise
    # a preemption drops an XLA compile inside the timed region
    warm_lens = (97, 90, 33, 12)

    paged = make_engine(params, cfg, engine="paged", **kw_mixed)
    _warmup(paged, cfg, warm_lens)
    paged_s, paged_tok = _serve(paged, mixed)
    pst = paged.stats()

    chunked = make_engine(params, cfg, engine="chunked",
                          chunk_size=CHUNK, step_tokens=STEP_TOKENS,
                          **kw_mixed)
    _warmup(chunked, cfg, warm_lens)
    chunked_s, chunked_tok = _serve(chunked, mixed)
    cst = chunked.stats()

    result["mixed_trace"] = {
        "pages": MIXED_N_PAGES, "chunk_size": CHUNK,
        "step_tokens": STEP_TOKENS,
        "n_long": 1 if smoke else N_LONG,
        "n_short": 4 if smoke else N_SHORT,
        "paged": _eng_stats(pst, SLOTS_PAGED, paged_tok, paged_s),
        "chunked": _eng_stats(cst, SLOTS_PAGED, chunked_tok,
                              chunked_s),
    }

    # -- sharded pool on the mixed trace (DESIGN.md §4c) --------------
    if kv_shards > 1:
        baseline = {c.rid: c.tokens for c in chunked.completions}
        sh = _serve_sharded(params, cfg, kw_mixed, warm_lens, mixed,
                            kv_shards, baseline)
        result["mixed_trace"]["sharded"] = sh
        if verbose:
            occ = ", ".join(f"{o:.2f}" for o in sh["shard_occupancy"])
            print(f"# serve_bench sharded {sh['tok_s']:8.1f} tok/s "
                  f"(mixed, {kv_shards} shards, {sh['backing']}) "
                  f"occ=[{occ}] migrations={sh['page_migrations']} "
                  "token-identical to single-locality")
        emit("serve_sharded_tok_s", sh["tok_s"], "tok_per_s")
        emit("serve_sharded_page_migrations", sh["page_migrations"],
             f"kv_shards_{kv_shards}")

    # -- disaggregated prefill/decode on the mixed trace (§4f) --------
    if disagg:
        baseline = {c.rid: c.tokens for c in chunked.completions}
        deng = make_engine(params, cfg, engine="chunked",
                           chunk_size=CHUNK, step_tokens=STEP_TOKENS,
                           kv_shards=DISAGG_SHARDS, disagg=True,
                           **kw_mixed)
        _warmup(deng, cfg, warm_lens)
        disagg_s, disagg_tok = _serve(deng, mixed)
        dst = deng.stats()
        got = {c.rid: c.tokens for c in deng.completions}
        assert got == baseline, (
            "disaggregated outputs diverge from the single-locality "
            "chunked engine — parcels and handoffs must not change a "
            "token")
        # every request that reached decode crossed one handoff
        assert dst["handoffs"] > 0 and dst["handoff_bytes"] > 0
        assert 0.0 <= dst["handoff_overlap"] <= 1.0
        owner, total = _disagg_affinity_wave(deng, cfg, seed)
        affinity = owner / max(total, 1)
        assert affinity >= DISAGG_AFFINITY_FLOOR, (
            f"warm wave sent only {affinity:.0%} of its prefill "
            f"parcels to the prefix-owner locality "
            f"({owner}/{total}, floor {DISAGG_AFFINITY_FLOOR:.0%})")
        tput_ratio = (disagg_tok / disagg_s) \
            / (chunked_tok / chunked_s)
        if not smoke:
            assert tput_ratio >= DISAGG_TPUT_FLOOR, (
                f"disagg throughput is {tput_ratio:.2f}x the "
                f"single-locality chunked engine "
                f"(floor {DISAGG_TPUT_FLOOR})")
        result["disagg_trace"] = dict(
            _eng_stats(dst, deng.slots, disagg_tok, disagg_s),
            kv_shards=DISAGG_SHARDS,
            prefill_workers=dst["prefill_workers"],
            decode_workers=dst["decode_workers"],
            tput_vs_chunked=tput_ratio,
            prefill_parcels=dst["prefill_parcels"],
            parcels_sent=dst["parcels_sent"],
            parcels_local=dst["parcels_local"],
            dispatch_sizes=dst["dispatch_sizes"],
            handoffs=dst["handoffs"],
            handoff_bytes=dst["handoff_bytes"],
            handoff_overlap=dst["handoff_overlap"],
            warm_wave_affinity=affinity,
            warm_wave_parcels=total)
        if verbose:
            print(f"# serve_bench disagg  "
                  f"{disagg_tok / disagg_s:8.1f} tok/s (mixed, "
                  f"{dst['prefill_workers']}P/"
                  f"{dst['decode_workers']}D, "
                  f"{tput_ratio:.2f}x chunked) "
                  f"handoffs={dst['handoffs']} "
                  f"({dst['handoff_bytes']}B, "
                  f"overlap={dst['handoff_overlap']:.2f}) "
                  f"affinity={affinity:.0%} "
                  "token-identical to single-locality")
        emit("serve_disagg_tok_s", disagg_tok / disagg_s, "tok_per_s")
        emit("serve_disagg_handoff_bytes", dst["handoff_bytes"],
             "bytes")
        emit("serve_disagg_handoff_overlap", dst["handoff_overlap"],
             "fraction")
        emit("serve_disagg_affinity", affinity, "of_warm_parcels")
        emit("serve_disagg_parcels", dst["prefill_parcels"],
             f"sent_{dst['parcels_sent']}_local_"
             f"{dst['parcels_local']}")

    # -- two-tier percolation on the pressure trace (§4d) -------------
    if tiering:
        hp = host_pages or TIER_HOST_PAGES
        press = _pressure_requests(cfg, n=5 if smoke else N_PRESSURE,
                                   max_new=8 if smoke else TIER_MAX_NEW,
                                   seed=seed)
        kw_tier = dict(max_len=MIXED_MAX_LEN, prefill_buckets=(32,),
                       slots=SLOTS_TIERED, page_size=PAGE_SIZE,
                       chunk_size=CHUNK,
                       step_tokens=SLOTS_TIERED + 2 * CHUNK)
        warm_tier = (97, 90, 33, 12)

        def _press_run(**kw):
            eng = make_engine(params, cfg, engine="chunked",
                              **kw_tier, **kw)
            _warmup(eng, cfg, warm_tier)
            dt, tok = _serve(eng, press)
            return eng, eng.stats(), dt, tok

        def _decode_tok_s(eng):
            """Decode throughput from the step counters: tokens the
            decode batch produced per second of decode-batch time.
            Unlike wall tok/s it excludes transfer stalls, which on a
            real accelerator overlap compute — this is the number the
            <= 15% tiering-penalty budget is about."""
            tok = sum(c.get("decode_tokens", c["active"])
                      for c in eng.counters)
            ms = sum(c["decode_ms"] for c in eng.counters)
            return tok / (ms / 1e3) if ms else 0.0

        # token ground truth: an ample pool that never preempts
        ample_pages = SLOTS_TIERED * MIXED_MAX_LEN // PAGE_SIZE
        ample_eng, _, _, _ = _press_run(n_pages=ample_pages)
        truth = {c.rid: c.tokens for c in ample_eng.completions}

        # same tiny device budget, tiering off vs on
        base_eng, bst, base_s, base_tok = _press_run(
            n_pages=TIER_DEVICE_PAGES)
        tier_eng, tst, tier_s, tier_tok = _press_run(
            n_pages=TIER_DEVICE_PAGES, tiering=True, host_pages=hp)
        got = {c.rid: c.tokens for c in tier_eng.completions}
        assert got == truth, (
            "tiered outputs diverge from the ample-pool reference — "
            "restore is supposed to be byte-exact")
        resident_x = tst["peak_resident"] / max(bst["peak_resident"], 1)
        if not smoke:
            assert resident_x >= 2.0, (
                f"tiering holds only {resident_x:.2f}x the resident "
                f"requests ({tst['peak_resident']} vs "
                f"{bst['peak_resident']}) at {TIER_DEVICE_PAGES} "
                "device pages")
        result["tiered_trace"] = {
            "device_pages": TIER_DEVICE_PAGES, "host_pages": hp,
            "n_requests": len(press),
            "untiered": dict(
                _eng_stats(bst, SLOTS_TIERED, base_tok, base_s),
                peak_resident=bst["peak_resident"],
                mean_resident=bst["mean_resident"]),
            "tiered": dict(
                _eng_stats(tst, SLOTS_TIERED, tier_tok, tier_s),
                peak_resident=tst["peak_resident"],
                mean_resident=tst["mean_resident"],
                offloads=tst["offloads"], restores=tst["restores"],
                offload_bytes=tst["offload_bytes"],
                promote_bytes=tst["promote_bytes"],
                prefetch_hits=tst["prefetch_hits"],
                demand_promotes=tst["demand_promotes"],
                copy_compute_overlap=tst["copy_compute_overlap"],
                evictions=tst["evictions"]),
            "resident_ratio": resident_x,
            "decode_tok_s_untiered": _decode_tok_s(base_eng),
            "decode_tok_s_tiered": _decode_tok_s(tier_eng),
            "decode_penalty": 1.0 - _decode_tok_s(tier_eng)
            / max(_decode_tok_s(base_eng), 1e-9),
        }
        if verbose:
            t = result["tiered_trace"]
            print(f"# serve_bench tiered  {tier_tok / tier_s:8.1f} tok/s "
                  f"(pressure, {TIER_DEVICE_PAGES}+{hp} pages) "
                  f"resident={tst['peak_resident']} "
                  f"({resident_x:.1f}x untiered) "
                  f"offload={tst['offload_bytes']}B "
                  f"promote={tst['promote_bytes']}B "
                  f"overlap={tst['copy_compute_overlap']:.2f} "
                  f"penalty={t['decode_penalty'] * 100:+.1f}% "
                  "token-identical to ample pool")
        emit("serve_tiered_tok_s", tier_tok / tier_s, "tok_per_s")
        emit("serve_untiered_tok_s", base_tok / base_s, "tok_per_s")
        emit("serve_tiered_decode_tok_s", _decode_tok_s(tier_eng),
             "tok_per_s")
        emit("serve_untiered_decode_tok_s", _decode_tok_s(base_eng),
             "tok_per_s")
        emit("serve_tiered_peak_resident", tst["peak_resident"],
             f"untiered_{bst['peak_resident']}")
        emit("serve_tiered_offload_bytes", tst["offload_bytes"],
             "bytes")
        emit("serve_tiered_promote_bytes", tst["promote_bytes"],
             "bytes")
        emit("serve_tiered_overlap", tst["copy_compute_overlap"],
             "fraction")

    # -- prefix-heavy shared-system-prompt trace (DESIGN.md §4e) ------
    if prefix_heavy:
        n_wave = 4 if smoke else PREFIX_N
        n_reps = 1 if smoke else PREFIX_REPEATS
        wave_new = 4 if smoke else PREFIX_MAX_NEW
        result["prefix_trace"] = {
            "pages": PREFIX_PAGES, "host_pages": PREFIX_HOST_PAGES,
            "sys_tokens": PREFIX_SYS,
        }
        # (wave kind, suffix spec, skip-fraction floor, TTFT floor):
        # the fixed wave is the regression baseline the padded keys
        # could already share; the mixed wave is what they could NOT
        for kind, floor_skip, floor_x in (("fixed", 0.8, 3.5),
                                          ("mixed", 0.7, 3.0)):
            seed_req, wave = _prefix_traces(
                cfg, n=n_wave, repeats=n_reps, max_new=wave_new,
                seed=seed, mixed=(kind == "mixed"))
            off, off_toks = _prefix_run(params, cfg, seed_req, wave,
                                        False)
            on, on_toks = _prefix_run(params, cfg, seed_req, wave,
                                      True)
            assert on_toks == off_toks, (
                f"compute-skip outputs diverge from the skip-off "
                f"reference on the {kind} wave — the skipped prefill "
                "is supposed to be exact")
            ttft_x = off["ttft_p50_ms"] / max(on["ttft_p50_ms"], 1e-9)
            if not smoke:
                assert on["skip_fraction"] >= floor_skip, (
                    f"{kind} wave skipped only "
                    f"{on['skip_fraction']:.0%} of its prefill tokens "
                    f"(floor {floor_skip:.0%})")
                assert ttft_x >= floor_x, (
                    f"compute skip cut {kind}-wave p50 TTFT only "
                    f"{ttft_x:.1f}x ({off['ttft_p50_ms']:.1f}ms -> "
                    f"{on['ttft_p50_ms']:.1f}ms, floor {floor_x:.0f}x)")
                assert on["prefix_skips"] >= n_reps, (
                    "the exact-repeat requests did not admit straight "
                    "to decode")
                assert on["prefix_partial_hits"] >= n_wave - n_reps, (
                    f"{kind}-wave partial covers were not admitted "
                    "through the radix longest-prefix match")
            result["prefix_trace"][kind] = {
                "user_tokens": (list(PREFIX_USER_MIX)
                                if kind == "mixed" else PREFIX_USER),
                "n_requests": len(wave),
                "skip_off": off, "skip_on": on,
                "ttft_p50_reduction_x": ttft_x,
            }
            if verbose:
                print(f"# serve_bench prefix  {on['tok_s']:8.1f} "
                      f"tok/s (warm shared-prefix {kind} wave, "
                      f"{PREFIX_PAGES} pages) "
                      f"ttft_p50={on['ttft_p50_ms']:.1f}ms "
                      f"vs {off['ttft_p50_ms']:.1f}ms skip-off "
                      f"({ttft_x:.1f}x) "
                      f"skipped={on['skip_fraction']:.0%} "
                      f"full_skips={on['prefix_skips']} "
                      f"partial_hits={on['prefix_partial_hits']} "
                      "token-identical to skip-off")
            tag = "" if kind == "fixed" else "_mixed"
            emit(f"serve_prefix{tag}_warm_tok_s", on["tok_s"],
                 "tok_per_s")
            emit(f"serve_prefix{tag}_ttft_p50_on",
                 on["ttft_p50_ms"] * 1e3, "us")
            emit(f"serve_prefix{tag}_ttft_p50_off",
                 off["ttft_p50_ms"] * 1e3, "us")
            emit(f"serve_prefix{tag}_ttft_reduction", ttft_x, "x_p50")
            emit(f"serve_prefix{tag}_skip_fraction",
                 on["skip_fraction"], "fraction")
            emit(f"serve_prefix{tag}_full_skips", on["prefix_skips"],
                 "requests")
            emit(f"serve_prefix{tag}_partial_hits",
                 on["prefix_partial_hits"], "requests")

    # -- causal trace + overhead attribution (DESIGN.md §10) ----------
    if trace_path:
        result["traced"] = _traced_run(params, cfg, trace_path, smoke,
                                       seed, verbose, disagg=disagg)

    # -- request-level SLO/goodput observability (DESIGN.md §10) ------
    if slo or slo_report:
        result["slo"] = _slo_run(params, cfg, smoke, seed, verbose,
                                 report_path=slo_report)

    # -- locality-loss chaos drill (DESIGN.md §4g) --------------------
    if chaos:
        result["chaos_trace"] = _chaos_run(params, cfg, smoke, seed,
                                           verbose)
    if verbose:
        print(f"# serve_bench dense   {dense_tok / dense_s:8.1f} tok/s "
              f"(short trace, peak_active={SLOTS_DENSE})")
        print(f"# serve_bench paged   {pshort_tok / pshort_s:8.1f} tok/s "
              f"(short trace, peak_active={ps_st['peak_active']})")
        print(f"# serve_bench paged   {paged_tok / paged_s:8.1f} tok/s "
              f"(mixed) ttft_p50={pst['ttft_p50_ms']:.1f}ms "
              f"itl_p50={pst['itl_p50_ms']:.2f}ms "
              f"preempt={pst['preemptions']}")
        print(f"# serve_bench chunked {chunked_tok / chunked_s:8.1f} tok/s "
              f"(mixed) ttft_p50={cst['ttft_p50_ms']:.1f}ms "
              f"itl_p50={cst['itl_p50_ms']:.2f}ms "
              f"preempt={cst['preemptions']}")
        print("# json " + json.dumps(result))
    # serve_dense/paged_tok_s stay the SAME short trace as PR 1 (the
    # equal-KV-bytes pair); the mixed-trace engines get their own names
    emit("serve_dense_tok_s", dense_tok / dense_s, "tok_per_s")
    emit("serve_paged_tok_s", pshort_tok / pshort_s, "tok_per_s")
    emit("serve_paged_mixed_tok_s", paged_tok / paged_s, "tok_per_s")
    emit("serve_chunked_tok_s", chunked_tok / chunked_s, "tok_per_s")
    emit("serve_paged_peak_active", ps_st["peak_active"],
         f"dense_slots_{SLOTS_DENSE}_equal_kv_bytes")
    emit("serve_paged_ttft_p50", pst["ttft_p50_ms"] * 1e3, "us")
    emit("serve_chunked_ttft_p50", cst["ttft_p50_ms"] * 1e3, "us")
    emit("serve_paged_itl_p50", pst["itl_p50_ms"] * 1e3, "us")
    emit("serve_chunked_itl_p50", cst["itl_p50_ms"] * 1e3, "us")
    if out_path:
        with open(out_path, "w") as f:
            json.dump(result, f, indent=2)
    if bench_out:
        from benchmarks.common import write_bench
        doc = write_bench(
            bench_out, BENCH_ID, _bench_scenarios(result),
            floors=BENCH_FLOORS,
            meta={"arch": ARCH, "seed": seed, "smoke": bool(smoke),
                  "page_size": PAGE_SIZE})
        if verbose:
            print(f"# serve_bench bench trajectory: "
                  f"{len(doc['scenarios'])} scenarios -> {bench_out}")
    return result


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny traces (CI): exercises all three engines"
                         " without asserting the latency split")
    ap.add_argument("--kv-shards", type=int, default=0,
                    help="also serve the mixed trace from a pool "
                         "sharded over N AGAS localities (with a "
                         "forced migration) and assert token parity "
                         "with the single-locality engine")
    ap.add_argument("--tiering", action="store_true",
                    help="also serve the pressure trace untiered vs "
                         "two-tier (DESIGN.md §4d): write-back "
                         "offload, restore-not-reprefill, percolation "
                         "overlap; asserts token parity with an "
                         "ample-pool reference")
    ap.add_argument("--host-pages", type=int, default=0,
                    help="host-tier pages for --tiering "
                         f"(0 = {TIER_HOST_PAGES})")
    ap.add_argument("--prefix-heavy", action="store_true",
                    help="also serve the warm shared-system-prompt "
                         "waves with compute skip off vs on (DESIGN.md "
                         "§4e) at the same page budget: the fixed-"
                         "suffix wave asserts >= 3.5x p50 TTFT "
                         "reduction and >= 80% prefill tokens skipped "
                         "outside --smoke, the mixed-suffix-length "
                         "wave >= 3x and >= 70%, plus token parity "
                         "always")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="run the full stack (chunked + 2 KV shards + "
                         "tiering + forced migration) with the causal "
                         "tracer attached; writes a perfetto-viewable "
                         "Chrome trace to PATH and an overhead report "
                         "to PATH's .report.json sibling; asserts "
                         "span nesting, request->slot->page causal "
                         "links, and the tracer cost budgets")
    ap.add_argument("--disagg", action="store_true",
                    help="also serve the mixed trace on the "
                         "disaggregated prefill/decode engine "
                         "(DESIGN.md §4f): parcel-dispatched prefill "
                         "chunks over a 2-shard pool + percolation KV "
                         "handoffs; asserts token parity, >= 90% "
                         "prefix-owner dispatch affinity on a warm "
                         "wave, and reports handoff bytes/overlap. "
                         "With --trace, the traced run uses the "
                         "disagg engine too")
    ap.add_argument("--slo", action="store_true",
                    help="also run the SLO/goodput observability "
                         "drive (DESIGN.md §10): a deadline-carrying "
                         "pressure trace on the disagg 2-shard tiered "
                         "stack with the flight recorder on; asserts "
                         "deterministic goodput, blame/attribution "
                         "reconciliation (<= 5% residual), recorder "
                         "cost budgets (<= 5% on, <= 1% off), and the "
                         "Prometheus/JSONL exporter round-trips")
    ap.add_argument("--slo-report", default=None, metavar="PATH",
                    help="write the --slo goodput report (registry "
                         "aggregates, per-request verdicts + phase "
                         "decompositions, recorder overhead) to PATH "
                         "as JSON; implies --slo")
    ap.add_argument("--chaos", action="store_true",
                    help="also run the locality-loss chaos drill "
                         "(DESIGN.md §4g): the pressure trace on the "
                         "disagg 2-shard tiered stack with KV shard 1 "
                         f"killed at step {CHAOS_AT}; asserts every "
                         "future resolves token-identically to the "
                         "failure-free run (per rid, not sampled), "
                         "reports pages rebuilt from the host tier vs "
                         "lost, slots drained, and re-prefills, then "
                         "re-joins the shard and asserts a second "
                         "wave is identical too")
    ap.add_argument("--bench-out", default=None, metavar="PATH",
                    help=f"write the schema'd bench trajectory "
                         f"(BENCH_{BENCH_ID}.json: per-scenario "
                         "latency percentiles, throughput, goodput, "
                         "skip/handoff rates, floors) to PATH; diff "
                         "against the previous BENCH_*.json with "
                         "tools/bench_compare.py")
    ap.add_argument("--seed", type=int, default=0,
                    help="trace-generation seed: every trace "
                         "(short/mixed/pressure/prefix) derives from "
                         "it, so runs are reproducible across "
                         "machines")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(out_path=args.out, smoke=args.smoke, kv_shards=args.kv_shards,
        tiering=args.tiering, host_pages=args.host_pages,
        prefix_heavy=args.prefix_heavy, seed=args.seed,
        trace_path=args.trace, disagg=args.disagg, slo=args.slo,
        slo_report=args.slo_report, chaos=args.chaos,
        bench_out=args.bench_out)
