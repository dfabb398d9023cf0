"""Continuous-batching serving engines: chunked, paged, and dense.

The ParalleX reading of serving (DESIGN.md §4): each request is a
first-class object whose completion is an LCO — `submit` returns a
`core.lco.Future` that is set exactly once when the request finishes.
Arriving requests are parcels; decode is a dataflow chain per slot,
and the engine packs ready slots into batched decode steps (the
work-queue at token granularity).

Three engines share that skeleton:

* `ChunkedPagedServingEngine` (the default `ServingEngine`) — prefill
  is no longer one-shot per request: a prompt is split into
  page-size-aligned CHUNKS, each an independently schedulable task,
  and every `step()` spends a token budget on a mix of pending prefill
  chunks and the decode batch (decode-priority; chunks fill the
  remainder, FCFS by admission order — DESIGN.md §4b).  Time-to-first-
  token for short requests stops waiting behind long prompts, and the
  decode batch never idles for a whole-prompt admission — the serving
  rendering of the paper's Fig 3 granularity trade-off.

* `PagedServingEngine` — the whole-prompt baseline over the same AGAS
  page pool (serving/kvcache.py, DESIGN.md §4a; sharded across
  localities per §4c when `kv_shards > 1`): each admission runs
  one bucketed prefill for the entire prompt before any decode
  resumes.  Admission is gated on free *pages*, not free slots; when
  the pool runs dry the youngest request is preempted back to the
  queue (its pages freed, its progress carried so re-admission resumes
  seamlessly).  Every slot keeps its own position clock — there is no
  shared `len/cursor/abs`.  Per-step counters (queue depth, page
  occupancy, TTFT / inter-token latencies) expose the runtime's
  overheads in the spirit of the paper's Fig 9.

* `DenseServingEngine` — the static-ownership baseline: a bulk
  `(slots, max_len)` cache with one shared position clock spliced via
  `jnp.maximum`.  Kept as the CSP-style comparison point for parity
  tests and benchmarks/serve_bench.py; its memory scales with
  worst-case length whether or not tokens exist.

Design points that matter at scale and are implemented here:
* fixed-shape decode batch (slot pool) -> one compiled decode step;
* whole-prompt prefill runs at bucketed lengths (pad-to-bucket) and
  chunked prefill at one fixed chunk width, so compilation count stays
  bounded either way;
* slots free on EOS/length and refill from the queue (continuous
  batching);
* per-slot sampling state (greedy or temperature), keyed by the
  request id and its own generated-token count.
"""

from __future__ import annotations

import itertools
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lco import Future
from repro.core.parcels import ParcelPort
from repro.core.percolation import CopyParcel, PercolationQueue
from repro.ft.failures import FailurePlan
from repro.ft.supervisor import RecoveryBudget
from repro.models import transformer as T
from repro.models.config import ArchConfig
from repro.obs.metrics import MetricsRegistry
from repro.obs.slo import FlightRecorder, NULL_RECORDER, classify, \
    record_verdict
from repro.obs.trace import NULL_TRACER
from repro.serving.kvcache import (PagedKVCache, PageExhausted,
                                   PAGED_FAMILIES, page_keys)
from repro.serving.sampling import sample_rows, sample_step, \
    sample_tokens, sampler_inputs
# Request/Completion moved to serving/types.py (the worker split);
# re-exported here because tests/benchmarks import them from engine
from repro.serving.types import Completion, Request, _mean, _pct  # noqa: F401
from repro.serving.workers import (DecodeWorker, HandoffDecodeWorker,
                                   ParcelPrefillWorker, PrefillWorker,
                                   PREFILL_ACTIONS, StepScheduler)

#: The serving step's span tree, by profiler annotation name
#: (obs/trace.py).  `engine.fetch` nests wherever a blocking
#: device-to-host read happens:
#:
#:   engine.step
#:     engine.admit  engine.prefetch
#:     engine.prefill_chunk            (per chunk)
#:       engine.chunk_inputs           begin_chunk, host arrays, uploads
#:       engine.chunk_dispatch         the jitted chunk call
#:       engine.first_token            _finish_prefill's token read
#:     engine.prepare_writes
#:     engine.decode_batch
#:       engine.decode_inputs          token, sampler and kvc.batch_inputs()
#:       engine.decode_dispatch        the jitted decode call
#:       engine.sample                 the step's token read, kvc.advance
#:       engine.finish                 _finish / release of done slots
#:     engine.fetch                    every blocking device-to-host read
STEP_SPANS = (
    "engine.step", "engine.admit", "engine.prefetch",
    "engine.prefill_chunk", "engine.chunk_inputs",
    "engine.chunk_dispatch", "engine.first_token",
    "engine.prepare_writes", "engine.decode_batch",
    "engine.decode_inputs", "engine.decode_dispatch", "engine.sample",
    "engine.finish", "engine.fetch")

#: Expert-load keys of a counter row (moe family), summed over the
#: step's programs: the (token, expert) pairs computed, the (layer,
#: expert) pairs that computed any token, the busiest expert's pairs
#: summed over layers, and the decode program's part of the second.
#: Registry counters `moe.routed`, `moe.experts_hit`, ... alike.
MOE_KEYS = ("moe_routed", "moe_experts_hit", "moe_load_max",
            "moe_decode_experts_hit")


class _EngineBase:
    """Queue intake, bucketed prefill, sampling, and the run loop."""

    def __init__(self, params: Any, cfg: ArchConfig, *, slots: int,
                 max_len: int, prefill_buckets=(64, 128, 256),
                 tracer=None, flight_recorder=False):
        self.params = params
        self.cfg = cfg
        self.slots = slots
        self.max_len = max_len
        self.buckets = tuple(sorted(prefill_buckets))
        self.trace = tracer if tracer is not None else NULL_TRACER
        self.metrics = MetricsRegistry()
        # per-request lifecycle timelines (obs/slo.py); disabled is a
        # constant-time no-op singleton, mirroring NULL_TRACER
        self.recorder = FlightRecorder() if flight_recorder \
            else NULL_RECORDER
        self.slo_verdicts: Dict[int, dict] = {}   # rid -> classify()
        # queue items: {"req", "gen" (tokens carried over a
        # preemption), "preempts"}
        self.queue: List[dict] = []
        self.active: Dict[int, dict] = {}      # slot -> request state
        self.free_slots = list(range(slots))
        self.completions: List[Completion] = []
        self._futures: Dict[int, Future] = {}
        self._prefills: Dict[int, Any] = {}
        # this step's blocking device-to-host reads (`_fetch`) and the
        # host clock at its start, for the counter row's host times
        self._syncs = 0
        self._fetch_s = 0.0
        self._step_t0 = time.perf_counter()
        # moe: the programs return their (L, E) expert load beside the
        # tokens they sample.  A load that no read takes in its own
        # step is held, (counter row, device array, from the decode
        # program), until the next read takes it along; `_step_load`
        # sums this step's (`MOE_KEYS`, where `_moe_counts`)
        self._moe_counts = False
        self._held: List[tuple] = []
        self._step_load: Dict[str, int] = {}

    # -- observability ------------------------------------------------
    def set_tracer(self, tracer) -> None:
        """Rebind the tracer on the engine AND every subsystem it owns
        (pool, tiered transfer engine) — the hook serve_bench uses to
        attach tracing to an already-warmed engine."""
        tracer = tracer if tracer is not None else NULL_TRACER
        self.trace = tracer
        kvc = getattr(self, "kvc", None)
        if kvc is not None:
            kvc.trace = tracer
            kvc.pool.trace = tracer
            xfer = getattr(kvc.pool, "xfer", None)
            if xfer is not None:
                xfer.trace = tracer
                xfer.queue.trace = tracer

    def reset_metrics(self) -> None:
        """Zero the metrics registry (a serve_bench warmup boundary:
        callers that clear `completions`/`counters` clear this too so
        `stats()` stays consistent with the per-step telemetry).  The
        flight recorder and SLO verdicts reset with it — they are the
        same telemetry epoch."""
        self.metrics.reset()
        self.recorder.clear()
        self.slo_verdicts.clear()

    def set_recorder(self, recorder) -> None:
        """Swap the flight recorder on a warmed engine (serve_bench's
        recorder-cost A/B: same engine, recorder on vs off)."""
        self.recorder = recorder if recorder is not None \
            else NULL_RECORDER

    def _record_step_metrics(self, c: dict) -> None:
        """Fold one per-step counter dict into the registry."""
        m = self.metrics
        m.counter("engine.steps").inc()
        m.gauge("engine.peak_active").set_max(c["active"])
        resident = c.get("resident", c["active"])
        m.gauge("engine.peak_resident").set_max(resident)
        m.histogram("engine.resident").record(resident)
        if "page_occupancy" in c:
            m.gauge("engine.peak_page_occupancy").set_max(
                c["page_occupancy"])
        m.histogram("engine.decode_ms").record(c["decode_ms"])

    # -- request intake (a parcel arriving at the engine locality) ----
    def submit(self, req: Request) -> Future:
        """Enqueue; returns the completion LCO (set exactly once)."""
        fut = Future()
        self._futures[req.rid] = fut
        t_submit = time.perf_counter()
        self.queue.append({"req": req, "gen": [], "preempts": 0,
                           "t_submit": t_submit,
                           "ttft_s": None, "tok_t": []})
        self.trace.instant("engine", "submit", rid=req.rid,
                           prompt_len=len(req.prompt))
        if self.recorder.enabled:
            self.recorder.event(req.rid, "submit", t=t_submit,
                                prompt_len=len(req.prompt))
        return fut

    def _slot_bind(self, rid: int, slot: int) -> None:
        """Admission boundary: trace instant + flight-recorder bind
        event, one helper so every admit path records both."""
        self.trace.instant("engine", "slot_bind", rid=rid, slot=slot)
        if self.recorder.enabled:
            self.recorder.event(rid, "bind", slot=slot)

    @staticmethod
    def _queue_prompt(item: dict) -> np.ndarray:
        """Prompt + any tokens generated before a preemption."""
        req = item["req"]
        if item["gen"]:
            return np.concatenate(
                [np.asarray(req.prompt, np.int32),
                 np.asarray(item["gen"], np.int32)])
        return np.asarray(req.prompt, np.int32)

    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        # beyond the ladder: multiples of the largest bucket, so the
        # compile count stays bounded
        big = self.buckets[-1]
        return -(-n // big) * big

    @staticmethod
    def _pad_to(tokens: np.ndarray, length: int) -> np.ndarray:
        padded = np.zeros(length, np.int32)
        padded[length - len(tokens):] = tokens           # left-pad
        return padded

    def _padded_prompt(self, tokens: np.ndarray) -> np.ndarray:
        return self._pad_to(tokens, self._bucket(len(tokens)))

    def _prefill_fn(self, bucket: int):
        """One compiled prefill per bucket.  The real sequence may end
        before the padded buffer does (right-padded resumes); the last
        index is a traced operand, so it never forces a recompile."""
        if bucket not in self._prefills:
            cfg = self.cfg
            full_kv = self._FULL_KV

            def fn(params, tokens, last_index):
                batch = {"tokens": tokens}
                hidden, cache = T.prefill(params, batch, cfg,
                                          full_kv=full_kv,
                                          last_index=last_index)
                return T.logits_fn(params, hidden), cache
            self._prefills[bucket] = jax.jit(fn)
        return self._prefills[bucket]

    def _fetch(self, x) -> np.ndarray:
        """Every blocking device-to-host read of the engines: one
        `engine.fetch` span, counted and timed for the step's
        `host_syncs` and `fetch_ms`."""
        with self.trace.span("engine", "fetch", kind="copy"):
            t = time.perf_counter()
            out = jax.device_get(x) if isinstance(x, list) \
                else np.asarray(x)
            self._fetch_s += time.perf_counter() - t
        self._syncs += 1
        return out

    def _read(self, tokens, load=None, decode: bool = False
              ) -> np.ndarray:
        """A program's sampled tokens, in one `_fetch` together with its
        expert load (moe; the decode program's where `decode`) and
        every held one; the loads go to the counters."""
        if load is not None:
            self._held.append((len(self.counters), load, decode))
        if not self._held:
            return self._fetch(tokens)
        held, self._held = self._held, []
        out, *loads = self._fetch([tokens] + [a for _, a, _ in held])
        for (row, _, dec), a in zip(held, loads):
            self._note_load(row, a, dec)
        return out

    def _note_load(self, row: int, load: np.ndarray,
                   decode: bool = False) -> None:
        """Add one program's (L, E) expert load to counter row `row`
        (this step's, while that row is not yet written) and the
        registry."""
        hit = int((load > 0).sum())
        vals = (int(load.sum()), hit, int(load.max(axis=1).sum()),
                hit if decode else 0)
        row_d = self.counters[row] if row < len(self.counters) \
            else self._step_load
        for key, v in zip(MOE_KEYS, vals):
            row_d[key] = row_d.get(key, 0) + v
            self.metrics.counter("moe." + key[4:]).inc(v)

    def _step_telemetry(self) -> dict:
        """A counter row's host-time keys: the step's fetches, the host
        time blocked in them, and the step's host wall time so far."""
        return {"host_syncs": self._syncs,
                "fetch_ms": self._fetch_s * 1e3,
                "step_ms": (time.perf_counter() - self._step_t0) * 1e3,
                **self._step_load}

    def _sample(self, logits: jnp.ndarray, req: Request,
                n_gen: int) -> int:
        """Sample a prefill's (1, V) logits with the programs' device
        sampler (serving/sampling.py), keyed by (rid, generated-token
        count): one dispatch, one read."""
        s = sampler_inputs([(0, req, n_gen)], 1)
        tok = sample_rows(logits, s["temperature"], s["key_data"])
        return int(self._fetch(tok)[0])

    def _reject(self, item: dict, err: Exception) -> None:
        """Fail one request without killing the engine: its completion
        LCO carries the error; everything else keeps flowing."""
        fut = self._futures.pop(item["req"].rid, None)
        if fut is not None:
            fut.set_error(err)

    def _finish_queued(self, item: dict) -> None:
        """Finish a queued (preempted) request without re-admitting it,
        delivering the generation it carries.  Used when re-admission
        hits the length cap: an un-preempted request in that state is
        truncate-finished with its tokens delivered, and a preempted
        one must be too — its generated tokens are real work, never to
        be discarded through an error LCO."""
        self._drop_item_kv(item)
        now = time.perf_counter()
        self._finish({"req": item["req"], "tokens": list(item["gen"]),
                      "prefill_s": 0.0, "t0": now,
                      "preempts": item.get("preempts", 0),
                      **self._latency_state(item, now)})

    def _drop_item_kv(self, item: dict) -> None:
        """Release any KV a queue item still owns (offloaded pages of
        a written-back preemption).  No-op for engines without a
        tiered pool."""

    def _fail_pending(self, err: Exception) -> None:
        """Fail every request still queued or active (engine exiting
        with work pending): each completion LCO carries the error, and
        pages/slots are reclaimed so the engine stays usable."""
        for slot in list(self.active):
            self.active.pop(slot)
            kvc = getattr(self, "kvc", None)
            if kvc is not None:
                kvc.release(slot)
            self.free_slots.append(slot)
        for item in self.queue:
            self._drop_item_kv(item)
        self.queue.clear()
        for rid in list(self._futures):
            fut = self._futures.pop(rid)
            if not fut.done():
                fut.set_error(err)

    def _finish(self, st: dict) -> None:
        tok_t = st.get("tok_t", [])
        now = time.perf_counter()
        comp = Completion(st["req"].rid, st["tokens"], st["prefill_s"],
                          now - st["t0"],
                          st.get("preempts", 0),
                          ttft_s=st.get("ttft_s") or 0.0,
                          itl_s=[b - a for a, b in zip(tok_t, tok_t[1:])])
        self.completions.append(comp)
        # latency metrics stream into bounded histograms at completion
        # time — stats() reads these, never a per-completion list
        m = self.metrics
        m.histogram("engine.prefill_ms").record(comp.prefill_s * 1e3)
        if comp.ttft_s > 0.0:
            m.histogram("engine.ttft_ms").record(comp.ttft_s * 1e3)
        itl_hist = m.histogram("engine.itl_ms")
        for d in comp.itl_s:
            itl_hist.record(d * 1e3)
        self.trace.instant("engine", "finish", rid=comp.rid,
                           n_tokens=len(comp.tokens))
        if self.recorder.enabled:
            self.recorder.event(comp.rid, "finish", t=now,
                                n_tokens=len(comp.tokens))
        req = st["req"]
        if req.ttft_deadline_ms is not None or \
                req.itl_deadline_ms is not None:
            v = classify(req, comp,
                         timeline=self.recorder.timeline(comp.rid))
            record_verdict(m, v)
            self.slo_verdicts[comp.rid] = v
        fut = self._futures.pop(comp.rid, None)
        if fut is not None:
            fut.set(comp)

    @staticmethod
    def _latency_state(item: dict, now: float) -> dict:
        """TTFT / inter-token bookkeeping threaded from a queue item
        into a slot state (and back, across preemptions)."""
        return {"t_submit": item.get("t_submit", now),
                "ttft_s": item.get("ttft_s"),
                "tok_t": list(item.get("tok_t", []))}

    def _first_token(self, st: dict, now: float) -> None:
        if st["ttft_s"] is None:
            st["ttft_s"] = now - st["t_submit"]
            if self.recorder.enabled:
                self.recorder.event(st["req"].rid, "first_token",
                                    t=now)
        st["tok_t"].append(now)

    @staticmethod
    def _stopped(req: Request, tokens: List[int]) -> bool:
        """EOS or length cap reached — checked after EVERY sampled
        token, including the one prefill produces (a max_new_tokens=1
        request must not enter the decode batch at all)."""
        if req.eos_id is not None and tokens and \
                tokens[-1] == req.eos_id:
            return True
        return len(tokens) >= req.max_new_tokens

    def step(self) -> int:
        """One scheduling step.  The root span of the per-step trace
        tree (STEP_SPANS): overhead attribution decomposes its
        wall-clock into the child spans' kinds (obs/attribution.py)."""
        self._syncs, self._fetch_s = 0, 0.0
        self._step_t0 = time.perf_counter()
        self._step_load = dict.fromkeys(MOE_KEYS, 0) \
            if self._moe_counts else {}
        with self.trace.span("engine", "step") as sp:
            n = self._step()
            sp.args["ran"] = n
        return n

    def _step(self) -> int:
        raise NotImplementedError

    def _admit(self) -> None:
        raise NotImplementedError

    def run_to_completion(self, max_steps: int = 10_000,
                          on_step=None) -> None:
        """Drive the engine until idle.

        Never exits with submitted futures unset: exhausting
        `max_steps`, or a permanently head-of-line-blocked queue
        (nothing active to free pages, nothing admissible), fails the
        remaining futures instead of returning silently — a caller
        blocked on a completion LCO must either get its value or its
        error, never hang forever.
        """
        blocked_len = -1
        for _ in range(max_steps):
            if not self.active and not self.queue:
                return
            n = self.step()              # step() admits first
            if on_step is not None:      # periodic metrics reporting
                on_step(self)
            if n == 0 and not self.active and self.queue:
                # nothing ran and nothing is active: only a queue-head
                # rejection (queue shrinks) can change future steps —
                # an unchanged queue length means a permanent block
                if len(self.queue) == blocked_len:
                    self._fail_pending(RuntimeError(
                        f"head-of-line blocked: {len(self.queue)} "
                        "queued request(s) cannot be admitted and "
                        "nothing is active to free pages"))
                    return
                blocked_len = len(self.queue)
            else:
                blocked_len = -1
        if self.active or self.queue:
            self._fail_pending(RuntimeError(
                f"run_to_completion exhausted max_steps={max_steps} "
                f"with {len(self.active)} active and "
                f"{len(self.queue)} queued request(s)"))


class DenseServingEngine(_EngineBase):
    """Static bulk KV ownership: (slots, max_len), one shared clock."""

    _FULL_KV = False

    def __init__(self, params: Any, cfg: ArchConfig, *, slots: int = 4,
                 max_len: int = 512, prefill_buckets=(64, 128, 256),
                 tracer=None, flight_recorder=False):
        super().__init__(params, cfg, slots=slots, max_len=max_len,
                         prefill_buckets=prefill_buckets, tracer=tracer,
                         flight_recorder=flight_recorder)
        # one shared batched cache across slots
        self.cache = T.init_cache(cfg, slots, max_len)
        self._decode = jax.jit(
            lambda p, c, b: sample_step(T.decode_step, p, c, b, cfg))

    def _admit(self) -> None:
        while self.queue and self.free_slots:
            item = self.queue.pop(0)
            req = item["req"]
            toks = self._padded_prompt(self._queue_prompt(item))
            bucket = len(toks)
            if bucket > self.max_len:
                self._reject(item, ValueError(
                    f"request {req.rid}: padded prompt {bucket} "
                    f"exceeds max_len {self.max_len}"))
                continue
            slot = self.free_slots.pop(0)
            self._slot_bind(req.rid, slot)
            t0 = time.perf_counter()
            with self.trace.span("engine", "prefill", kind="compute",
                                 rid=req.rid, bucket=bucket):
                logits, pcache = self._prefill_fn(bucket)(
                    self.params, jnp.asarray(toks[None]),
                    jnp.int32(bucket - 1))
            if self.recorder.enabled:
                self.recorder.event(req.rid, "prefill", bucket=bucket,
                                    dur=time.perf_counter() - t0)
            # splice this request's prefill cache into the slot pool
            self._splice_cache(slot, pcache, bucket)
            first = self._sample(logits, req, len(item["gen"]))
            now = time.perf_counter()
            self.active[slot] = {
                "req": req, "tokens": item["gen"] + [int(first)],
                "prefill_s": now - t0,
                "t0": now,
                "pos": bucket,
                "preempts": item["preempts"],
                **self._latency_state(item, now),
            }
            self._first_token(self.active[slot], now)
            if self._stopped(req, self.active[slot]["tokens"]):
                self._finish(self.active.pop(slot))
                self.free_slots.append(slot)

    def _splice_cache(self, slot: int, pcache: dict, plen: int) -> None:
        def splice(pool, part):
            if pool.ndim == 0 or part is None:
                return pool
            # find the batch axis: pool (…, slots, …) vs part (…,1,…)
            for ax in range(pool.ndim):
                if part.shape[ax] == 1 and pool.shape[ax] == self.slots:
                    break
            else:
                return pool
            # seq axes differ (plen vs max_len): pad part
            pads = []
            for d in range(pool.ndim):
                if d == ax:
                    pads.append((0, 0))
                else:
                    pads.append((0, pool.shape[d] - part.shape[d]))
            part = jnp.pad(part, pads)
            idx = [slice(None)] * pool.ndim
            idx[ax] = slice(slot, slot + 1)
            return pool.at[tuple(idx)].set(part)

        for k in self.cache:
            if k in ("len", "cursor", "abs"):
                continue
            self.cache[k] = splice(self.cache[k], pcache.get(k))
        # shared counters: the pool cache uses one clock; keep max
        self.cache["len"] = jnp.maximum(self.cache["len"],
                                        pcache["len"])
        self.cache["cursor"] = jnp.maximum(self.cache["cursor"],
                                           pcache["cursor"])
        self.cache["abs"] = jnp.maximum(self.cache["abs"],
                                        pcache["abs"])

    # -- the decode work-queue ----------------------------------------
    def _step(self) -> int:
        """One batched decode step over all active slots."""
        with self.trace.span("engine", "admit", kind="sched"):
            self._admit()
        if not self.active:
            return 0
        tokens = np.zeros((self.slots, 1), np.int32)
        for slot, st in self.active.items():
            tokens[slot, 0] = st["tokens"][-1]
        batch = {"tokens": jnp.asarray(tokens), **sampler_inputs(
            ((slot, st["req"], len(st["tokens"]))
             for slot, st in self.active.items()), self.slots)}
        if self.cfg.family == "vlm":
            batch["patch_embeds"] = jnp.zeros(
                (self.slots, self.cfg.n_frontend_tokens,
                 32 if self.cfg.d_model < 1024 else 1280),
                jnp.dtype(self.cfg.dtype))
        with self.trace.span("engine", "decode_batch", kind="compute",
                             n=len(self.active)):
            next_tokens, self.cache = self._decode(self.params,
                                                   self.cache, batch)
        done = []
        now = time.perf_counter()
        toks = self._fetch(next_tokens)
        for slot, st in self.active.items():
            req = st["req"]
            st["tokens"].append(int(toks[slot]))
            st["tok_t"].append(now)
            if self._stopped(req, st["tokens"]):
                done.append(slot)
        for slot in done:
            self._finish(self.active.pop(slot))
            self.free_slots.append(slot)
        return len(self.active) + len(done)


class PagedServingEngine(_EngineBase):
    """KV memory as AGAS pages: demand allocation, prefix sharing,
    page-gated admission, and preemption under pressure.

    ``kv_shards > 1`` shards the page pool across AGAS localities
    (DESIGN.md §4c): least-loaded allocation, per-shard occupancy in
    `stats()`, and imbalance-triggered page migration between steps
    (`rebalance_tolerance` pages of drift; pass a value < 1 to disable,
    None for the automatic default).  ``mesh`` (with a "kv" axis of
    size kv_shards) device-backs the shards; without it the localities
    are simulated on one device with bit-identical results.

    ``tiering=True`` adds the host DRAM tier (DESIGN.md §4d,
    serving/tiering.py; ``host_pages`` sizes it, default 4x the device
    pool): a preempted request's pages are written back to host and
    restored on re-admission instead of re-prefilled, cold prefix
    pages spill to host instead of dropping, and the step scheduler
    stages the next admission's host->device copies while the current
    batch computes.  Greedy outputs are token-identical with tiering
    on or off.

    ``prefix_cache_compute=True`` turns the prefix cache's memory
    savings into COMPUTE savings (DESIGN.md §4e): every prefill
    checkpoints the post-norm hidden state at each page's last
    position into the prefix index, and a later prompt fully covered
    by cached pages admits straight to decode — its first token is
    sampled from the cached checkpoint (`T.resume_prefill`), zero
    transformer passes.  This whole-prompt engine skips full covers
    only; the chunked engine also resumes partially covered prompts
    at the cover's end.  Greedy outputs are token-identical with the
    flag on or off.
    """

    _FULL_KV = True

    def __init__(self, params: Any, cfg: ArchConfig, *, slots: int = 4,
                 max_len: int = 512, prefill_buckets=(64, 128, 256),
                 page_size: int = 16, n_pages: Optional[int] = None,
                 kv_shards: int = 1, mesh=None,
                 rebalance_tolerance: Optional[int] = None,
                 tiering: bool = False, host_pages: int = 0,
                 prefix_cache_compute: bool = False,
                 pin_threshold: int = 4, tracer=None,
                 flight_recorder=False,
                 failure_plan: Optional[FailurePlan] = None):
        super().__init__(params, cfg, slots=slots, max_len=max_len,
                         prefill_buckets=prefill_buckets, tracer=tracer,
                         flight_recorder=flight_recorder)
        if n_pages is None:
            # default: the dense engine's worst-case footprint — callers
            # shrink it to oversubscribe (kvcache preempts under
            # pressure), or grow slots beyond what dense could afford —
            # rounded up to fill every KV shard evenly
            n_pages = slots * (-(-max_len // page_size))
            n_pages = -(-n_pages // kv_shards) * kv_shards
        if tiering and host_pages <= 0:
            host_pages = 4 * n_pages       # host DRAM ~several x HBM
        self._tiering = bool(tiering) and host_pages > 0
        self.kvc = PagedKVCache(cfg, slots, max_len, n_pages, page_size,
                                n_shards=kv_shards, mesh=mesh,
                                host_pages=host_pages
                                if self._tiering else 0,
                                pin_threshold=pin_threshold,
                                tracer=self.trace)
        if rebalance_tolerance is None:
            rebalance_tolerance = max(
                2, self.kvc.pool.pages_per_shard // 4)
        self._rebalance_tol = int(rebalance_tolerance)
        # donate the page pool: on accelerators the step updates KV
        # pages in place instead of holding input + output copies
        # moe: the program returns ((tokens, expert load), pages)
        self._moe_counts = cfg.family == "moe"
        step = T.decode_step_paged_load if self._moe_counts \
            else T.decode_step_paged
        self._decode = jax.jit(
            lambda p, pages, b: sample_step(step, p, pages, b, cfg),
            donate_argnums=(1,))
        self._seq = itertools.count()          # admission order
        self.preemptions = 0
        self.offloads = 0       # preemptions that wrote KV back to host
        self.restores = 0       # re-admissions that skipped prefill
        # locality-loss recovery (DESIGN.md §4g): the plan fires at the
        # top of a step; drained requests re-admit with futures pending
        self.failure_plan = failure_plan
        self._killed: set = set()     # (step, shard) pairs already fired
        self.recovery_budget = RecoveryBudget()
        self.re_prefills = 0     # requests that lost KV and re-prefilled
        self.drained_slots = 0   # active slots drained by a kill
        # per-step telemetry, one row per step that ran a batch: queue
        # and slot counts, pages, and host times — `decode_ms` is host
        # time from the decode batch's start to the row, sampling
        # included, not device time; `host_syncs` counts the step's
        # blocking device-to-host reads, `fetch_ms` the host time
        # blocked in them, `step_ms` the step's host wall time
        self.counters: List[dict] = []
        # prefix-cache compute skip (DESIGN.md §4e)
        self._prefix_skip = bool(prefix_cache_compute)
        self.prefix_skips = 0            # fully-covered admissions
        self.prefix_partial_hits = 0     # partially-covered admissions
        self.prefill_tokens_skipped = 0  # prompt tokens never recomputed
        self._resume_logits = jax.jit(
            lambda p, h: T.resume_prefill(p, h))

    def _prefill_fn(self, bucket: int):
        """One compiled prefill per bucket, like the base engine's, but
        also returning the post-norm hidden at every page boundary plus
        the true last position — the activation checkpoints the prefix
        index stores for compute skip (DESIGN.md §4e).  The extra
        outputs are one tiny gather; the host copy that stores them is
        gated on `prefix_cache_compute` (the pool is per-engine, so a
        skip-off engine could never read them back)."""
        if bucket not in self._prefills:
            cfg = self.cfg
            ps = self.kvc.pool.page_size

            def fn(params, tokens, last_index):
                batch = {"tokens": tokens}
                hidden, cache = T.prefill(params, batch, cfg,
                                          full_kv=True, all_hidden=True)
                last = jax.lax.dynamic_index_in_dim(
                    hidden, last_index, axis=1, keepdims=False)
                return (T.logits_fn(params, last), cache,
                        hidden[:, ps - 1::ps], last)
            self._prefills[bucket] = jax.jit(fn)
        return self._prefills[bucket]

    # -- prefix-cache compute skip (DESIGN.md §4e) --------------------
    def _admit_skip(self, item: dict, layout: np.ndarray, real: int,
                    cov) -> bool:
        """Admit the queue head's fully-covered prompt straight to
        decode: attach the cached pages by refcount and sample the
        first token from the stored activation checkpoint — zero
        prefill compute, TTFT of one resume step.  False leaves the
        item at the queue head (pages or a promotion row not available
        yet — head-of-line blocking, like any page-gated admission)."""
        kvc = self.kvc
        need = sum(kvc.pool.page_cost(k) for k in cov.keys) + 1
        if need + self._upcoming_allocs() > kvc.pool.free_pages:
            return False
        self.queue.pop(0)
        slot = self.free_slots.pop(0)
        self._slot_bind(item["req"].rid, slot)
        t0 = time.perf_counter()
        try:
            kvc.attach_covered(slot, layout, cov.keys)
        except PageExhausted:
            # a covered page spilled and its promotion lost the race
            # for a device row; everything was rolled back — retry
            self.free_slots.append(slot)
            self.queue.insert(0, item)
            return False
        req = item["req"]
        tr = time.perf_counter() if self.recorder.enabled else 0.0
        with self.trace.span("engine", "resume", kind="compute",
                             rid=req.rid, slot=slot):
            logits = self._resume_logits(self.params,
                                         jnp.asarray(cov.hidden)[None])
        if self.recorder.enabled:
            self.recorder.event(req.rid, "resume",
                                dur=time.perf_counter() - tr)
        first = self._sample(logits, req, len(item["gen"]))
        now = time.perf_counter()
        self.prefix_skips += 1
        self.prefill_tokens_skipped += real
        self.active[slot] = {
            "req": req, "tokens": item["gen"] + [int(first)],
            "phase": "decode",       # no prefill phase at all (§4e)
            "n_gen0": len(item["gen"]),
            "prefill_s": now - t0,
            "t0": now,
            "seq": next(self._seq),
            "preempts": item["preempts"],
            "admit_step": len(self.counters),
            **self._latency_state(item, now),
        }
        self._first_token(self.active[slot], now)
        if self._stopped(req, self.active[slot]["tokens"]):
            self._finish(self.active.pop(slot))
            kvc.release(slot)
            self.free_slots.append(slot)
        return True

    # -- page-gated admission -----------------------------------------
    def _admission_layout(self, item: dict) -> Optional[tuple]:
        """Rebuild the queue head's token layout and screen out
        requests that can never run.

        Layouts are position-NORMALIZED: real tokens sit at positions
        0..len-1 with no pad, whether the request is fresh or
        re-admitted after a preemption (re-admission is simply prompt
        + generated tokens — identical positions, so it re-prefills to
        identical pages).  Padding exists only in the prefill COMPUTE
        buffer (right-pad to the bucket ladder, junk masked by the
        traced last index), never in the cache layout — which is what
        lets two prompts of different total lengths share prefix page
        keys (DESIGN.md §4e).  Returns (layout, real, need) where
        `need` counts fresh prefill pages plus one decode page of
        headroom, or None if the item was rejected (and popped)."""
        req = item["req"]
        layout = self._queue_prompt(item)
        real = len(layout)
        if real > self.max_len:
            self.queue.pop(0)
            if item["gen"]:
                # re-admission at the length cap: finish with the
                # partial generation (exactly what an un-preempted
                # request in this state gets via truncation) — never
                # error the LCO and discard generated tokens
                self._finish_queued(item)
            else:
                self._reject(item, ValueError(
                    f"request {req.rid}: prompt {real} "
                    f"exceeds max_len {self.max_len}"))
            return None
        need = self.kvc.pages_needed(layout) + 1
        if need > self.kvc.pool.capacity:
            self.queue.pop(0)
            if item["gen"]:
                self._finish_queued(item)
            else:
                self._reject(item, RuntimeError(
                    f"request {req.rid} needs {need} pages but the "
                    f"pool holds {self.kvc.pool.capacity}"))
            return None
        return layout, real, need

    def _upcoming_allocs(self) -> int:
        """Pages the CURRENT step's committed work will still take
        (decode writes at a page boundary or COW) — the admission
        watermark, so an admission can never be preempted away in the
        very same step."""
        return sum(1 for s in self.active if self.kvc.needs_alloc(s))

    def _admit(self) -> None:
        while self.queue and self.free_slots:
            item = self.queue[0]
            req = item["req"]
            if item.get("snap") is not None:
                if self._try_restore(item):
                    continue
                break                          # head-of-line blocking
            adm = self._admission_layout(item)
            if adm is None:
                continue
            layout, real, need = adm
            if self._prefix_skip:
                cov = self.kvc.covered_prefix(layout)
                if cov.full:
                    if self._admit_skip(item, layout, real, cov):
                        continue
                    break                      # head-of-line blocking
            # admit on PAGES, not slots: prefill pages (prefix-shared
            # ones are free), one decode page of headroom, plus a
            # watermark for active slots whose next write takes a page
            # (boundary alloc or COW) — otherwise an admission can be
            # preempted away in the very same step
            upcoming = self._upcoming_allocs()
            if need + upcoming > self.kvc.pool.free_pages:
                break                          # head-of-line blocking
            self.queue.pop(0)
            slot = self.free_slots.pop(0)
            self._slot_bind(req.rid, slot)
            t0 = time.perf_counter()
            # all prefills run at the bucket ladder: pad RIGHT (junk
            # tokens after the real end never enter the cache and,
            # under causality, cannot influence earlier positions), so
            # the compile count stays bucket-bounded while the CACHE
            # layout stays pad-free
            bucket = self._bucket(real)
            toks = np.zeros(bucket, np.int32)
            toks[:real] = layout
            tr = time.perf_counter() if self.recorder.enabled else 0.0
            with self.trace.span("engine", "prefill", kind="compute",
                                 rid=req.rid, bucket=bucket):
                logits, pcache, bh, hlast = self._prefill_fn(bucket)(
                    self.params, jnp.asarray(toks[None]),
                    jnp.int32(real - 1))
            if self.recorder.enabled:
                self.recorder.event(req.rid, "prefill", bucket=bucket,
                                    dur=time.perf_counter() - tr)
            self.kvc.attach(slot, layout,
                            pcache["k"][:, 0, :real],
                            pcache["v"][:, 0, :real])
            if self._prefix_skip:
                self.kvc.store_hidden_prefill(slot, real,
                                              self._fetch(bh[0]),
                                              self._fetch(hlast[0]))
            first = self._sample(logits, req, len(item["gen"]))
            now = time.perf_counter()
            self.active[slot] = {
                "req": req, "tokens": item["gen"] + [int(first)],
                "prefill_s": now - t0,
                "t0": now,
                "seq": next(self._seq),
                "preempts": item["preempts"],
                "admit_step": len(self.counters),
                **self._latency_state(item, now),
            }
            self._first_token(self.active[slot], now)
            if self._stopped(req, self.active[slot]["tokens"]):
                self._finish(self.active.pop(slot))
                self.kvc.release(slot)
                self.free_slots.append(slot)

    # -- inter-shard page migration (DESIGN.md §4c) -------------------
    def _maybe_rebalance(self) -> None:
        """Between steps: migrate pages when per-shard occupancy has
        drifted past the tolerance (block tables are refreshed, so the
        next gather resolves the moved rows — outputs are unchanged,
        which the migration-parity tests assert)."""
        if self.kvc.pool.n_shards > 1 and self._rebalance_tol >= 1:
            with self.trace.span("engine", "rebalance", kind="sched"):
                self.kvc.maybe_rebalance(self._rebalance_tol)

    def force_migrate(self) -> int:
        """Operational drill (and test hook): rotate every movable
        page to the next shard between steps.  Returns pages moved.
        Greedy outputs must be token-identical before and after — the
        AGAS promise that a page's global name survives the move."""
        moves = self.kvc.pool.plan_rotation()
        return self.kvc.migrate(moves) if moves else 0

    # -- locality failure and elastic membership (DESIGN.md §4g) ------
    def _check_failure_plan(self) -> None:
        """Poll the failure plan at the top of the step: a scheduled
        locality death fires here, through the same recovery path an
        operator drill (`kill_locality`) takes.  Idempotent per
        (step, shard) pair, so an engine that polls twice in one step
        (the disagg override) fires each kill exactly once."""
        if self.failure_plan is None:
            return
        shard = self.failure_plan.shard_to_kill(len(self.counters),
                                                self._killed)
        if shard is not None:
            self.kill_locality(shard)

    def kill_locality(self, locality: int) -> dict:
        """Lose one KV shard with requests in flight and keep every
        one of them alive (DESIGN.md §4g).

        The pool sweep retires the locality, rebuilds every page a
        host-tier copy covers, and returns the rest as LOST.  The
        drain pass here then walks every holder of a lost page —
        active slots, staged handoff snapshots, offloaded queue
        items — and re-admits the affected requests at the queue
        FRONT with their generated tokens retained: their completion
        futures stay pending and resolve with token-identical output
        after re-prefill (position-normalized layouts make the replay
        exact).  Spends one restart from the recovery budget — a
        fleet that keeps losing shards crashes loudly instead of
        thrashing forever."""
        self.recovery_budget.spend(f"locality {locality} loss")
        kvc = self.kvc
        lost = kvc.pool.kill_locality(locality)
        handoff_queue = getattr(self, "handoff_queue", None)
        drained: List[int] = []
        for slot in sorted(self.active):
            st = self.active[slot]
            snap = st.get("snap")
            if snap is not None:          # staged handoff (§4f)
                if not any(a.gid in lost for a in snap.addrs):
                    continue
                st.pop("snap")
                st.pop("next_phase", None)
                st.pop("handoff_step", None)
                if handoff_queue is not None:
                    handoff_queue.pop(("handoff", st["req"].rid))
                kvc.drop_snapshot(snap, lost)
                # detach already emptied the slot's table; nothing to
                # drain beyond the snapshot's refcounts
            else:
                if not any(a.gid in lost
                           for a in kvc._state[slot].addrs):
                    continue
                kvc.drain_slot(slot, lost)
            drained.append(slot)
        items = []
        for slot in sorted(drained,
                           key=lambda s: self.active[s]["seq"]):
            st = self.active.pop(slot)
            self.free_slots.append(slot)
            if self.recorder.enabled:
                self.recorder.event(st["req"].rid, "drain", slot=slot,
                                    locality=locality)
            items.append({"req": st["req"], "gen": list(st["tokens"]),
                          "preempts": st.get("preempts", 0),
                          "snap": None,
                          "prefill_s": st.get("prefill_s", 0.0),
                          "t_submit": st["t_submit"],
                          "ttft_s": st.get("ttft_s"),
                          "tok_t": st.get("tok_t", [])})
        self.queue[:0] = items            # FRONT, admission order kept
        # offloaded queue items whose snapshot lost a device-resident
        # shared page: drop the snapshot (and any staged restore) so
        # re-admission takes the re-prefill path instead of restoring
        # through a dangling name
        broken_snaps = 0
        xfer = getattr(kvc.pool, "xfer", None)
        for item in self.queue:
            snap = item.get("snap")
            if snap is None or \
                    not any(a.gid in lost for a in snap.addrs):
                continue
            if xfer is not None:
                xfer.drop(("restore", item["req"].rid))
            kvc.drop_snapshot(snap, lost)
            item["snap"] = None
            item.pop("resume", None)
            broken_snaps += 1
        # rebuilt pages moved shards: one directory walk re-resolves
        # every surviving slot's block table
        kvc.refresh_tables()
        self.drained_slots += len(items)
        self.re_prefills += len(items) + broken_snaps
        self.trace.instant("engine", "kill_locality",
                           locality=locality, lost=len(lost),
                           drained=len(items),
                           broken_snaps=broken_snaps)
        return {"locality": locality, "lost": len(lost),
                "drained": len(items), "broken_snaps": broken_snaps}

    def retire_locality(self, locality: int) -> int:
        """Planned elastic retire: evacuate every resident page to the
        surviving active shards (one migration — global names
        unchanged, so requests never notice) and remove the locality
        from placement.  Returns pages moved; raises `PageExhausted`
        (locality left active, nothing committed) when the survivors
        cannot hold its residents."""
        pool = self.kvc.pool
        if not pool.agas.is_active(locality):
            return 0
        pool.agas.deactivate(locality)
        try:
            moves = pool.plan_evacuation(locality)
        except PageExhausted:
            pool.agas.activate(locality)
            raise
        moved = self.kvc.migrate(moves) if moves else 0
        self.trace.instant("engine", "retire_locality",
                           locality=locality, moved=moved)
        return moved

    def join_locality(self, locality: int) -> int:
        """Elastic join (or re-join after a kill/retire): re-admit the
        locality to placement and rebalance movable pages toward it.
        Returns pages moved."""
        pool = self.kvc.pool
        pool.agas.activate(locality)
        moves = pool.plan_rebalance(1)
        moved = self.kvc.migrate(moves) if moves else 0
        self.trace.instant("engine", "join_locality",
                           locality=locality, moved=moved)
        return moved

    # -- percolation: offload / restore / prefetch (DESIGN.md §4d) ----
    def _try_restore(self, item: dict) -> bool:
        """Re-admit an offloaded request by promoting its written-back
        pages — KV restored byte-for-byte, no re-prefill.  False means
        the device tier cannot hold it yet (head-of-line blocking,
        exactly like a page-gated fresh admission)."""
        snap = item["snap"]
        req = item["req"]
        need = self.kvc.restore_pages_needed(snap) + 1
        if need + self._upcoming_allocs() > self.kvc.pool.free_pages:
            return False
        self.queue.pop(0)
        slot = self.free_slots.pop(0)
        self._slot_bind(req.rid, slot)
        tr = time.perf_counter() if self.recorder.enabled else 0.0
        try:
            with self.trace.span("engine", "restore", kind="sched",
                                 rid=req.rid, slot=slot):
                self.kvc.restore_slot(slot, snap,
                                      staged_key=("restore", req.rid))
        except PageExhausted:
            # the free-page estimate raced a pinned page; the snapshot
            # is still consistent — put everything back and wait.  The
            # failed attempt still burned TTFT-window time (and left a
            # restore span), so the flight timeline keeps it too
            if self.recorder.enabled:
                self.recorder.event(req.rid, "restore", ran=False,
                                    dur=time.perf_counter() - tr)
            self.free_slots.append(slot)
            self.queue.insert(0, item)
            return False
        self.restores += 1
        now = time.perf_counter()
        if self.recorder.enabled:
            self.recorder.event(req.rid, "restore", t=now,
                                dur=now - tr)
        st = {
            "req": req, "tokens": list(item["gen"]),
            "phase": "decode",      # overridden for mid-prefill below
            "prefill_s": item.get("prefill_s", 0.0),
            "t0": now,
            "seq": next(self._seq),
            "preempts": item["preempts"],
            "admit_step": len(self.counters),
            **self._latency_state(item, now),
        }
        resume = item.get("resume")
        if resume is not None:          # offloaded mid-prefill: keep
            st.update(phase="prefill",  # chunking where it stopped
                      layout=resume["layout"], real=resume["real"],
                      pos=resume["pos"], n_gen0=len(item["gen"]))
        self.active[slot] = st
        return True

    def _drop_item_kv(self, item: dict) -> None:
        snap = item.get("snap")
        if snap is not None:
            pool = self.kvc.pool
            pool.xfer.drop(("restore", item["req"].rid))
            self.kvc.drop_snapshot(snap)
            item["snap"] = None

    def _prefetch_percolation(self) -> None:
        """Stage the next admissions' host->device copies NOW, so they
        run while this step's batch computes (the §4d overlap model:
        `jax.device_put` dispatches asynchronously; the double buffer
        caps how far ahead the prefetcher works)."""
        if not self._tiering:
            return
        for item in self.queue[:2]:
            snap = item.get("snap")
            if snap is not None and \
                    self.kvc.restore_pages_needed(snap):
                self.kvc.stage_restore(("restore", item["req"].rid),
                                       snap)

    def force_demote(self) -> int:
        """Forced-eviction drill (and test hook): demote every
        evictable cold device page to host between steps.  Everything
        still decoding must be token-identical afterwards — evictable
        pages are refcount-0 by construction (refcount pinning)."""
        pool = self.kvc.pool
        if not getattr(pool, "tiered", False):
            return 0
        moved = pool.demote_all_cold()
        return moved

    # -- preemption under page pressure -------------------------------
    def _preempt(self, slot: int) -> None:
        """Evict a request: requeue it with its progress.  With
        tiering on, its pages
        are written back to the host tier (`KVSnapshot` in the queue
        item) so re-admission restores the KV instead of re-running
        prefill; otherwise — or when the host tier is full — they are
        freed and re-admission reconstructs the identical context
        layout by re-prefilling."""
        st = self.active.pop(slot)
        snap = self.kvc.offload_slot(slot) if self._tiering else None
        if snap is None:
            self.kvc.release(slot)
        else:
            self.offloads += 1
        self.free_slots.append(slot)
        self.preemptions += 1
        self.trace.instant("engine", "preempt", rid=st["req"].rid,
                           slot=slot, offloaded=snap is not None)
        if self.recorder.enabled:
            self.recorder.event(st["req"].rid, "preempt", slot=slot,
                                offloaded=snap is not None)
        item = {"req": st["req"], "gen": st["tokens"],
                "preempts": st["preempts"] + 1,
                "snap": snap,
                "prefill_s": st.get("prefill_s", 0.0),
                "t_submit": st["t_submit"],
                "ttft_s": st.get("ttft_s"),
                "tok_t": st.get("tok_t", [])}
        if snap is not None and st.get("phase") == "prefill":
            item["resume"] = {"layout": st["layout"],
                              "real": st["real"], "pos": st["pos"]}
        if snap is None:
            # pages forfeited: re-prefill is the costly path, so the
            # victim goes back to the queue FRONT and reclaims its
            # context at the first opportunity
            self.queue.insert(0, item)
        else:
            # KV written back: preemption is cheap now, so the victim
            # yields to fresh admissions (their first token is the
            # latency that matters; this one's restore is one staged
            # copy away whenever capacity returns) — the percolation
            # dividend: many more requests stay concurrently resident
            # than the device tier alone could hold
            self.queue.append(item)

    def _decode_slots(self) -> List[int]:
        """Slots currently in the decode phase (every active slot for
        the whole-prompt engine; the chunked engine overlays a prefill
        phase whose slots ride the decode batch as masked passengers)."""
        return [s for s in self.active
                if self.active[s].get("phase", "decode") == "decode"]

    def _prepare_writes(self, slots: Optional[List[int]] = None) -> None:
        """Reserve every decoding slot's write page, preempting the
        youngest request (LIFO — the oldest keeps its pages, so the
        system always drains) until the pool fits.  A lone request the
        pool cannot hold is failed via its LCO, not the engine."""
        while True:
            try:
                todo = [s for s in slots if s in self.active] \
                    if slots is not None else self._decode_slots()
                for slot in sorted(todo,
                                   key=lambda s: self.active[s]["seq"]):
                    self.kvc.prepare_decode(slot)
                return
            except PageExhausted:
                if len(self.active) <= 1:
                    slot, st = next(iter(self.active.items()))
                    self.active.pop(slot)
                    self.kvc.release(slot)
                    self.free_slots.append(slot)
                    self._reject({"req": st["req"]}, RuntimeError(
                        "page pool too small for request "
                        f"{st['req'].rid}: {self.kvc.pool.capacity} "
                        f"pages of {self.kvc.pool.page_size}"))
                    return
                victim = max(self.active,
                             key=lambda s: self.active[s]["seq"])
                self._preempt(victim)

    # -- the decode work-queue ----------------------------------------
    def _decode_batch(self, slots: List[int]) -> List[int]:
        """One compiled decode step for `slots`: assemble the batch,
        run the decode program, which samples every slot's next token
        on the device, read the tokens back in one fetch, and
        finish/release requests that hit EOS or their length cap.
        Returns the finished slots.  Shared by the whole-prompt and
        chunked engines, so sampling and completion bookkeeping can
        never diverge between them."""
        span = self.trace.span
        with span("engine", "decode_batch", kind="compute",
                  n=len(slots)) as sp:
            with span("engine", "decode_inputs", kind="copy"):
                tokens = np.zeros((self.slots, 1), np.int32)
                for slot in slots:
                    tokens[slot, 0] = self.active[slot]["tokens"][-1]
                batch = {"tokens": jnp.asarray(tokens),
                         **sampler_inputs(
                             ((slot, self.active[slot]["req"],
                               len(self.active[slot]["tokens"]))
                              for slot in slots), self.slots),
                         **self.kvc.batch_inputs()}
            with span("engine", "decode_dispatch", kind="compute"):
                out, pages = self._decode(
                    self.params, self.kvc.pool.pages, batch)
                self.kvc.pool.pages = pages
                next_tokens, load = out if self._moe_counts else (out, None)
            done: List[int] = []
            now = time.perf_counter()
            with span("engine", "sample", kind="compute"):
                toks = self._read(next_tokens, load, decode=True)
                for slot in slots:
                    st = self.active[slot]
                    self.kvc.advance(slot)
                    req = st["req"]
                    st["tokens"].append(int(toks[slot]))
                    st["tok_t"].append(now)
                    if self._stopped(req, st["tokens"]):
                        done.append(slot)
            with span("engine", "finish", kind="sched"):
                for slot in done:
                    self._finish(self.active.pop(slot))
                    self.kvc.release(slot)
                    self.free_slots.append(slot)
            sp.args["finished"] = len(done)
        return done

    def _offloaded_queued(self) -> int:
        """Queued requests whose KV is resident in the host tier."""
        return sum(1 for it in self.queue
                   if it.get("snap") is not None)

    def _step(self) -> int:
        """One batched decode step over all active slots."""
        self._check_failure_plan()         # scheduled locality loss
        self._maybe_rebalance()            # between-steps migration
        with self.trace.span("engine", "admit", kind="sched"):
            self._admit()
        # stage the next admissions' host->device copies: they run
        # under this step's compute (percolation, DESIGN.md §4d)
        with self.trace.span("engine", "prefetch", kind="parcel"):
            self._prefetch_percolation()
        # truncate requests whose next token has no cache room left
        # (bucket + generated reached max_len) instead of overflowing
        for slot in [s for s in self.active
                     if self.kvc.lengths[s] >= self.max_len]:
            self._finish(self.active.pop(slot))
            self.kvc.release(slot)
            self.free_slots.append(slot)
        if not self.active:
            return 0
        with self.trace.span("engine", "prepare_writes", kind="pages"):
            self._prepare_writes()
        if not self.active:                    # lone request rejected
            return 0
        t0 = time.perf_counter()
        done = self._decode_batch(list(self.active))
        pool = self.kvc.pool
        self.counters.append({
            "t": time.perf_counter(),
            "queue_depth": len(self.queue),
            "active": len(self.active) + len(done),
            # concurrently RESIDENT requests: decoding slots plus
            # offloaded requests whose KV survives in the host tier —
            # the capacity the tiered pool grows beyond HBM
            "resident": len(self.active) + len(done)
            + self._offloaded_queued(),
            "pages_used": pool.used_pages,
            "page_occupancy": pool.occupancy(),
            "preemptions": self.preemptions,
            "decode_ms": (time.perf_counter() - t0) * 1e3,
            **self._step_telemetry(),
        })
        self._record_step_metrics(self.counters[-1])
        return len(self.active) + len(done)

    def stats(self) -> dict:
        """Aggregate telemetry assembled from the metrics registry
        (the Fig 9 overhead view).  Step aggregates stream into the
        registry at step time and latency percentiles come from
        bounded streaming histograms recorded at completion time — no
        per-completion list is ever scanned here, so memory stays
        O(buckets) over arbitrarily long runs.  Safe to call at any
        point in the engine's life: before the first completion every
        aggregate degrades to 0.0.  Keys are the legacy names the
        serve_bench JSON and the dashboards read; the namespaced view
        is `engine.metrics.snapshot()`."""
        m = self.metrics
        pool = self.kvc.pool
        # mirror the pool's namespaced counters into the registry so
        # one snapshot() covers every subsystem the engine owns
        for name, v in pool.metrics().items():
            if isinstance(v, (int, float)):
                m.gauge(name).set(v)
        m.counter("engine.preemptions").value = self.preemptions
        m.counter("engine.re_prefills").value = self.re_prefills
        m.counter("engine.drained_slots").value = self.drained_slots
        m.counter("engine.prefix_skips").value = self.prefix_skips
        m.counter("engine.prefix_partial_hits").value = \
            self.prefix_partial_hits
        m.counter("engine.prefill_tokens_skipped").value = \
            self.prefill_tokens_skipped
        ttft = m.histogram("engine.ttft_ms")
        itl = m.histogram("engine.itl_ms")
        out = {
            "steps": int(m.counter("engine.steps").value),
            "peak_active": int(m.gauge("engine.peak_active").value),
            "peak_resident": int(m.gauge("engine.peak_resident").value),
            "mean_resident": m.histogram("engine.resident").mean,
            "peak_page_occupancy": float(
                m.gauge("engine.peak_page_occupancy").value),
            "mean_decode_ms": m.histogram("engine.decode_ms").mean,
            "preemptions": self.preemptions,
            "page_allocs": pool.allocs,
            "page_shares": pool.shares,
            "cow_copies": pool.cow_copies,
            # sharded-pool telemetry (length-1 lists on a single
            # locality, so dashboards need no special case)
            "kv_shards": pool.n_shards,
            "shard_pages_used": pool.shard_used(),
            "shard_occupancy": pool.shard_occupancy(),
            "page_migrations": pool.page_migrations,
            "mean_prefill_ms": m.histogram("engine.prefill_ms").mean,
            # latency split the chunked scheduler is judged on:
            # time-to-first-token vs steady-state inter-token gaps
            "mean_ttft_ms": ttft.mean,
            "ttft_p50_ms": ttft.quantile(50.0),
            "ttft_p95_ms": ttft.quantile(95.0),
            "ttft_p99_ms": ttft.quantile(99.0),
            "mean_itl_ms": itl.mean,
            "itl_p50_ms": itl.quantile(50.0),
            "itl_p95_ms": itl.quantile(95.0),
            "itl_p99_ms": itl.quantile(99.0),
            # prefix-cache compute skip (DESIGN.md §4e): covered
            # admissions (full skips vs partial radix hits) and the
            # prompt tokens never recomputed
            "prefix_cache_compute": self._prefix_skip,
            "prefix_skips": self.prefix_skips,
            "prefix_partial_hits": self.prefix_partial_hits,
            "prefill_tokens_skipped": self.prefill_tokens_skipped,
        }
        # locality-loss recovery (DESIGN.md §4g): what a kill swept,
        # what a host-tier copy rebuilt, what had to re-prefill
        out["recovery"] = {
            "localities_killed": pool.localities_killed,
            "pages_rebuilt": pool.pages_rebuilt,
            "pages_lost": pool.pages_lost,
            "re_prefills": self.re_prefills,
            "drained_slots": self.drained_slots,
            "recovery_restarts": self.recovery_budget.restarts,
        }
        # two-tier percolation telemetry (DESIGN.md §4d): offload /
        # promote traffic, prefetch overlap, write-back effectiveness
        out["tiering"] = bool(getattr(pool, "tiered", False))
        if out["tiering"]:
            out["offloads"] = self.offloads
            out["restores"] = self.restores
            out.update(pool.tier_stats())
        # SLO/goodput (obs/slo.py): only when any request carried a
        # deadline — the registry counters exist iff classify() ran
        tracked = m.get("slo.requests")
        if tracked is not None and tracked.value:
            from repro.obs.slo import BLAME_PHASES
            snap = m.snapshot()
            out["slo"] = {
                "requests": int(tracked.value),
                "met": int(snap.get("slo.met", 0)),
                "goodput": float(snap.get("slo.goodput", 0.0)),
                "ttft_misses": int(snap.get("slo.ttft_misses", 0)),
                "itl_misses": int(snap.get("slo.itl_misses", 0)),
                "blame": {p: int(snap.get(f"slo.blame.{p}", 0))
                          for p in BLAME_PHASES + ("unattributed",)},
            }
        return out


class ChunkedPagedServingEngine(PagedServingEngine):
    """Chunked prefill under a token-budget step scheduler.

    The serving grain is a page-size-aligned CHUNK of a prompt
    (DESIGN.md §4b): every `step()` spends at most `step_tokens`
    tokens — one per decoding slot first (decode priority), pending
    prefill chunks filling the remainder in admission order.  A long
    admission therefore never stalls the decode batch for its whole
    prefill, and a short prompt's first token stops waiting behind a
    long prompt's.  Admission is gated on the FIRST chunk's pages
    (plus headroom), not the whole prompt: later chunks allocate as
    they run, and page exhaustion mid-prefill preempts LIFO exactly
    like exhaustion mid-decode (the preempted request re-enters the
    queue and re-prefills from scratch on re-admission — deterministic,
    since an identical pad-free layout reproduces identical pages).

    With ``prefix_cache_compute=True`` (DESIGN.md §4e) admission first
    measures the prompt's covered prefix: fully-covered prompts skip
    prefill entirely (first token off the cached activation
    checkpoint), and partially-covered ones attach the cached pages
    by refcount and start chunking at the cover's end — the step
    budget is charged only for uncovered tokens, so a warm
    shared-system-prompt wave prefills at a fraction of its cold cost
    (`serve_bench --prefix-heavy` measures the TTFT dividend).
    """

    def __init__(self, params: Any, cfg: ArchConfig, *, slots: int = 4,
                 max_len: int = 512, prefill_buckets=(64, 128, 256),
                 page_size: int = 16, n_pages: Optional[int] = None,
                 chunk_size: Optional[int] = None,
                 step_tokens: Optional[int] = None,
                 kv_shards: int = 1, mesh=None,
                 rebalance_tolerance: Optional[int] = None,
                 tiering: bool = False, host_pages: int = 0,
                 prefix_cache_compute: bool = False,
                 pin_threshold: int = 4, tracer=None,
                 flight_recorder=False,
                 failure_plan: Optional[FailurePlan] = None):
        super().__init__(params, cfg, slots=slots, max_len=max_len,
                         prefill_buckets=prefill_buckets,
                         page_size=page_size, n_pages=n_pages,
                         kv_shards=kv_shards, mesh=mesh,
                         rebalance_tolerance=rebalance_tolerance,
                         tiering=tiering, host_pages=host_pages,
                         prefix_cache_compute=prefix_cache_compute,
                         pin_threshold=pin_threshold,
                         tracer=tracer,
                         flight_recorder=flight_recorder,
                         failure_plan=failure_plan)
        if chunk_size is None:
            chunk_size = 2 * page_size
        if chunk_size <= 0 or chunk_size % page_size:
            raise ValueError(
                f"chunk_size {chunk_size} must be a positive multiple "
                f"of page_size {page_size}")
        self.chunk_size = int(chunk_size)
        # every decoding slot gets its token, and at least one full
        # chunk always fits in the remainder-free case
        self.step_tokens = int(step_tokens or (slots + chunk_size))
        if self.step_tokens < self.chunk_size:
            raise ValueError(
                f"step_tokens {self.step_tokens} must cover at least "
                f"one chunk of {self.chunk_size}")
        # ONE compiled chunk step (fixed chunk width; the true last
        # position and start offset are traced operands).  It samples
        # the token after the true last position on the device (a
        # prompt's first token, read on its final chunk) and returns
        # the post-norm hidden there and at every page boundary — the
        # activation checkpoints the prefix index stores for compute
        # skip (§4e)
        ps = page_size

        def chunk_fn(p, pages, toks, tables, start, rows, last,
                     temperature, key_data):
            x, pages, load = T.prefill_chunk_load(p, pages, {
                "tokens": toks, "block_tables": tables, "start": start,
                "chunk_rows": rows, "last_index": last}, cfg,
                all_hidden=True)
            out = jax.lax.dynamic_index_in_dim(x, last, axis=1,
                                               keepdims=False)
            tok = sample_tokens(T.logits_fn(p, out), temperature,
                                key_data)
            return (tok, load), out, x[:, ps - 1::ps], pages
        self._chunk_step = jax.jit(chunk_fn, donate_argnums=(1,))
        # the role composition (DESIGN.md §4f): a role-agnostic token-
        # budget scheduler drives a prefill role and a decode role.
        # This engine is the single-locality composition — both roles
        # run where the engine runs; the disaggregated engine swaps in
        # parcel-dispatched workers without touching the scheduler.
        self._sched = StepScheduler(self.step_tokens, self.chunk_size,
                                    page_size)
        self._prefill_role = PrefillWorker()
        self._decode_role = DecodeWorker()

    # -- admission: gated on the first chunk, not the whole prompt ----
    def _upcoming_allocs(self) -> int:
        """The watermark counts EVERY allocation already committed for
        this step: decode writes at a page boundary/COW, AND the pages
        each mid-prefill slot's next chunk will take — prefill chunks
        run right after admission, so ignoring them (a decode-only
        count) would let an admission be preempted away in the very
        same step."""
        upcoming = sum(1 for s in self._decode_slots()
                       if self.kvc.needs_alloc(s))
        for s, st in self.active.items():
            if st.get("phase") == "prefill":
                nxt = min(st["pos"] + self.chunk_size, st["real"])
                upcoming += self.kvc.pages_needed_chunk(
                    st["layout"], st["pos"], nxt)
        return upcoming

    def _admit(self) -> None:
        while self.queue and self.free_slots:
            item = self.queue[0]
            req = item["req"]
            if item.get("snap") is not None:
                if self._try_restore(item):
                    continue
                break                          # head-of-line blocking
            adm = self._admission_layout(item)
            if adm is None:
                continue
            layout, real, _ = adm
            # compute skip (§4e): a fully-covered prompt admits
            # straight to decode off its cached checkpoint; a partial
            # cover starts chunking at the cover's end, charging only
            # uncovered tokens against the step budget
            start = 0
            cov = None
            if self._prefix_skip:
                cov = self.kvc.covered_prefix(layout)
                if cov.full:
                    if self._admit_skip(item, layout, real, cov):
                        continue
                    break                      # head-of-line blocking
                start = cov.covered
            # gate on the first UNCOVERED chunk plus one page of
            # headroom (and the watermark), plus any device rows the
            # covered pages' promotions will take; later chunks
            # allocate as they are scheduled and preempt under pressure
            first_end = min(start + self.chunk_size, real)
            upcoming = self._upcoming_allocs()
            need = self.kvc.pages_needed_chunk(layout, start,
                                               first_end) + 1
            if cov is not None:
                need += sum(self.kvc.pool.page_cost(k)
                            for k in cov.keys)
            if need + upcoming > self.kvc.pool.free_pages:
                break                          # head-of-line blocking
            self.queue.pop(0)
            slot = self.free_slots.pop(0)
            self._slot_bind(req.rid, slot)
            if start:
                try:
                    self.kvc.attach_covered(slot, layout, cov.keys)
                except PageExhausted:
                    # a covered page's promotion lost its device row;
                    # rolled back — retry from the queue head later
                    self.free_slots.append(slot)
                    self.queue.insert(0, item)
                    break
                self.prefix_partial_hits += 1
                self.prefill_tokens_skipped += start
            now = time.perf_counter()
            self.active[slot] = {
                "req": req, "tokens": list(item["gen"]),
                "phase": "prefill",
                "layout": layout, "real": real, "pos": start,
                "prefill_s": 0.0,
                "t0": now,                      # reset at first token
                "seq": next(self._seq),
                "preempts": item["preempts"],
                "n_gen0": len(item["gen"]),
                "admit_step": len(self.counters),
                **self._latency_state(item, now),
            }

    def _prefetch_percolation(self) -> None:
        """Chunked engines also percolate spilled PREFIX pages ahead
        of the chunk that will share them: stage this chunk's and the
        next chunk's host-resident prefix hits, so by the time
        `begin_chunk` resolves them the copy has been running under
        decode compute."""
        super()._prefetch_percolation()
        if not self._tiering:
            return
        for s, st in self.active.items():
            if st.get("phase") == "prefill":
                end = min(st["pos"] + 2 * self.chunk_size, st["real"])
                self.kvc.prefetch_chunk(s, st["layout"], st["pos"],
                                        end)

    def _chunk_locality(self, slot: int, st: dict) -> Optional[int]:
        """Placement hint for a chunk's fresh page allocations — None
        keeps the pool's default policy.  The disaggregated engine
        returns the chunk's parcel-dispatch locality, so a prefill
        worker's chunks allocate their pages where the worker runs
        (DESIGN.md §4f)."""
        return None

    # -- one prefill chunk as a schedulable task ----------------------
    def _run_chunk(self, slot: int, take: int) -> bool:
        """Acquire pages for and run one chunk of `slot`'s prompt.
        Returns False if the slot was preempted (or rejected) by page
        exhaustion instead of advanced."""
        rec = self.recorder.enabled
        st = self.active[slot]
        rid = st["req"].rid
        start = st["pos"]
        tr = time.perf_counter() if rec else 0.0
        with self.trace.span("engine", "prefill_chunk", kind="compute",
                             rid=rid, slot=slot, start=start, take=take,
                             loc=self._chunk_locality(slot, st)) as sp:
            ok = self._run_chunk_impl(slot, take)
            sp.args["ran"] = ok
        if rec:
            self.recorder.event(rid, "prefill_chunk", start=start,
                                take=take, ran=ok,
                                dur=time.perf_counter() - tr)
        return ok

    def _run_chunk_impl(self, slot: int, take: int) -> bool:
        st = self.active[slot]
        start = st["pos"]
        end = start + take
        with self.trace.span("engine", "chunk_inputs", kind="copy"):
            while True:
                try:
                    rows, _ = self.kvc.begin_chunk(
                        slot, st["layout"], start, end,
                        locality=self._chunk_locality(slot, st))
                    break
                except PageExhausted:
                    if len(self.active) <= 1:
                        self.active.pop(slot)
                        self.kvc.release(slot)
                        self.free_slots.append(slot)
                        self._reject({"req": st["req"]}, RuntimeError(
                            "page pool too small for request "
                            f"{st['req'].rid}: "
                            f"{self.kvc.pool.capacity} pages of "
                            f"{self.kvc.pool.page_size}"))
                        return False
                    victim = max(self.active,
                                 key=lambda s: self.active[s]["seq"])
                    self._preempt(victim)
                    if victim == slot:
                        return False
            ps = self.kvc.pool.page_size
            t0 = time.perf_counter()
            toks = np.zeros(self.chunk_size, np.int32)
            toks[:take] = st["layout"][start:end]
            rows_arr = np.full(self.chunk_size // ps,
                               self.kvc.pool.null_row, np.int32)
            rows_arr[:len(rows)] = rows
            # only a prompt's final chunk samples: earlier ones run at
            # temperature 0 and their token is never read
            final = [(0, st["req"], st["n_gen0"])] \
                if end == st["real"] else []
            s = sampler_inputs(final, 1)
            # a host copy: the table mutates in place while its
            # transfer may still read it (PagedKVCache.batch_inputs)
            inputs = (jnp.asarray(toks[None]),
                      jnp.asarray(self.kvc.tables[slot][None].copy()),
                      jnp.asarray([start], jnp.int32),
                      jnp.asarray(rows_arr[None]),
                      jnp.int32(take - 1),
                      s["temperature"], s["key_data"])
        with self.trace.span("engine", "chunk_dispatch", kind="compute"):
            (tok, load), hlast, bh, pages = self._chunk_step(
                self.params, self.kvc.pool.pages, *inputs)
            self.kvc.pool.pages = pages
        if self._prefix_skip:
            # checkpoint the chunk's page-boundary activations into
            # the prefix index (one small host copy) — later identical
            # prefixes resume from them instead of recomputing (§4e)
            self.kvc.store_hidden_chunk(slot, start, end,
                                        self._fetch(bh[0]),
                                        self._fetch(hlast[0]))
        st["pos"] = end
        st["prefill_s"] += time.perf_counter() - t0
        if load is not None:           # read with the next tokens read
            self._held.append((len(self.counters), load, False))
        if end == st["real"]:
            with self.trace.span("engine", "first_token",
                                 kind="compute"):
                self._finish_prefill(slot, st, tok)
        return True

    def _finish_prefill(self, slot: int, st: dict, tok) -> None:
        """Final chunk landed: the prompt is resident — read the first
        token the chunk program sampled and hand the slot to the decode
        batch.  The disaggregated engine overrides this seam to stage
        the prefill->decode KV handoff instead of flipping the phase in
        place (DESIGN.md §4f)."""
        now = time.perf_counter()
        st["phase"] = "decode"
        st["t0"] = now
        st["tokens"].append(int(self._read(tok)[0]))
        self._first_token(st, now)
        if self._stopped(st["req"], st["tokens"]):
            self._finish(self.active.pop(slot))
            self.kvc.release(slot)
            self.free_slots.append(slot)

    # -- the token-budget step ----------------------------------------
    def _step(self) -> int:
        """One budgeted step: every decoding slot gets its token, and
        pending prefill chunks (FCFS by admission order) fill whatever
        budget remains.  A prompt whose final chunk lands this step
        samples its first token now but starts decoding next step, so
        the step never exceeds its token budget."""
        self._check_failure_plan()         # scheduled locality loss
        self._maybe_rebalance()            # between-steps migration
        with self.trace.span("engine", "admit", kind="sched"):
            self._admit()
        with self.trace.span("engine", "prefetch", kind="parcel"):
            self._prefetch_percolation()
        # truncate decoding requests whose next token has no cache room
        for slot in [s for s in self._decode_slots()
                     if self.kvc.lengths[s] >= self.max_len]:
            self._finish(self.active.pop(slot))
            self.kvc.release(slot)
            self.free_slots.append(slot)
        if not self.active:
            return 0
        # the token-budget loop and the decode batch are the role-
        # agnostic scheduler's job (serving/workers.py): decode
        # reservation first, FCFS prefill chunks in the remainder —
        # this engine plugs in the single-locality roles, the
        # disaggregated engine the parcel-dispatched ones
        done, decoding, n_chunks, prefill_tok, t0 = \
            self._sched.run_step(self, self._prefill_role,
                                 self._decode_role)
        pool = self.kvc.pool
        self.counters.append({
            "t": time.perf_counter(),
            "queue_depth": len(self.queue),
            "active": len(self.active) + len(done),
            "resident": len(self.active) + len(done)
            + self._offloaded_queued(),
            "pages_used": pool.used_pages,
            "page_occupancy": pool.occupancy(),
            "preemptions": self.preemptions,
            "decode_ms": (time.perf_counter() - t0) * 1e3,
            "prefill_chunks": n_chunks,
            "prefill_chunk_tokens": prefill_tok,
            "decode_tokens": len(decoding),
            "budget_tokens": self.step_tokens,
            **self._step_telemetry(),
        })
        self._record_step_metrics(self.counters[-1])
        return len(self.active) + len(done)


class DisaggChunkedServingEngine(ChunkedPagedServingEngine):
    """Disaggregated prefill/decode over the chunked scheduler
    (DESIGN.md §4f).

    The step scheduler and its budget policy are inherited untouched;
    only the ROLES change.  Prefill chunks become `PrefillParcel`s
    dispatched through a `ParcelPort` to a prefill-worker locality —
    the locality owning the prompt's radix-matched prefix pages when
    the prompt is warm (move the work to the data: the shared pages
    never cross localities), least-loaded among the prefill workers
    when cold.  A finished prefill does not flip its slot to decode in
    place: its KV detaches into a snapshot, a `CopyParcel` is staged
    on the handoff percolation queue while the step's decode batch
    runs (the §4d double buffer), and the decode role commits the
    restore at the top of the next step — so the handoff copy
    overlaps decode compute instead of serializing before it.

    Because detach/restore round-trips the slot byte-identically
    (block table, position clock, chunk hash chain) and the scheduler
    is shared, this engine stays greedy token-identical to
    `ChunkedPagedServingEngine` — the differential fuzzer and
    serve_bench assert it.
    """

    def __init__(self, params: Any, cfg: ArchConfig, *,
                 prefill_workers: Optional[int] = None,
                 decode_workers: int = 1, **kwargs):
        super().__init__(params, cfg, **kwargs)
        n_loc = self.kvc.pool.n_shards
        self.prefill_workers = max(
            1, min(int(prefill_workers or n_loc), n_loc))
        self.decode_workers = max(1, min(int(decode_workers), n_loc))
        self._port = ParcelPort(self.kvc.pool.agas, PREFILL_ACTIONS)
        self._prefill_role = ParcelPrefillWorker(self.prefill_workers)
        self._decode_role = HandoffDecodeWorker()
        #: staged prefill->decode KV handoffs in flight (§4d machinery
        #: reused at the §4f role boundary; push/pop only — the
        #: demote/promote traffic counters belong to tiering)
        self.handoff_queue = PercolationQueue()
        self.handoffs = 0
        self.handoff_bytes = 0
        self.handoff_overlapped = 0
        self._last_chunk_ok = False

    # -- dispatch policy ----------------------------------------------
    def _dispatch_target(self, slot: int, st: dict):
        """(anchor, destination locality, warm) for a chunk parcel.

        A slot whose pages already live somewhere follows them (the
        anchor is its last page — sticky, so one prompt's chunks never
        scatter).  An unattached prompt's first chunk walks the radix
        prefix index stat-free (`lookup_prefix`, NOT `match` — match
        stamps hit stats and auto-pins, which would diverge from the
        single-locality engine) and dispatches to the deepest hit's
        owner; no hit places it on the least-loaded prefill worker.
        """
        pool = self.kvc.pool
        agas = pool.agas
        addrs = self.kvc._state[slot].addrs
        if addrs:
            anchor = addrs[-1]
            dst = agas.locality_of(anchor)
            if dst >= self.prefill_workers:   # host-tier resident
                dst = st.get("ploc", 0)
            if not agas.is_active(dst):       # cached target died (§4g)
                dst = self._cold_dispatch()
            st.setdefault("pwarm", True)      # attached covered pages
            st["ploc"] = dst
            st["panchor"] = anchor
            return anchor, dst, st["pwarm"]
        if "ploc" in st and agas.is_active(st["ploc"]):
            return st.get("panchor"), st["ploc"], st["pwarm"]
        anchor = None
        for key in page_keys(st["layout"], pool.page_size):
            hit = pool.lookup_prefix(key)
            if hit is None:
                break
            anchor = hit
        warm = anchor is not None \
            and agas.locality_of(anchor) < self.prefill_workers \
            and agas.is_active(agas.locality_of(anchor))
        if warm:
            dst = agas.locality_of(anchor)
        else:
            dst = self._cold_dispatch()
        st["ploc"] = dst
        st["panchor"] = anchor if warm else None
        st["pwarm"] = warm
        return st["panchor"], dst, warm

    def _cold_dispatch(self) -> int:
        """Least-loaded ACTIVE prefill worker, lowest locality on
        ties; when every worker shard is retired (§4g), any surviving
        active shard — a dead locality must never be a dispatch
        target, or its parcels' page allocations would raise."""
        agas = self.kvc.pool.agas
        cands = [l for l in range(self.prefill_workers)
                 if agas.is_active(l)]
        if not cands:
            cands = [l for l in range(self.kvc.pool.n_shards)
                     if agas.is_active(l)]
        return max(cands, key=lambda l: (agas.free_count(l), -l))

    def _home_locality(self, slot: int) -> int:
        """The decode locality a slot's handoff lands on (round-robin
        over the decode workers) — parcels dispatched elsewhere count
        as inter-locality sends."""
        return slot % self.decode_workers

    def _chunk_locality(self, slot: int, st: dict) -> Optional[int]:
        return st.get("ploc")

    # -- the prefill->decode handoff ----------------------------------
    def _finish_prefill(self, slot: int, st: dict, tok) -> None:
        """Final chunk landed at the prefill worker: read the first
        token its chunk program sampled THERE (the logits die with the
        chunk), then stage the KV handoff to the decode role instead of
        flipping the phase in place."""
        now = time.perf_counter()
        st["t0"] = now
        st["tokens"].append(int(self._read(tok)[0]))
        self._first_token(st, now)
        if self._stopped(st["req"], st["tokens"]):
            self._finish(self.active.pop(slot))
            self.kvc.release(slot)
            self.free_slots.append(slot)
            return
        self._stage_handoff(slot, st, next_phase="decode")

    def _stage_handoff(self, slot: int, st: dict,
                       next_phase: str) -> None:
        """Detach the slot's KV into a snapshot and stage its copy
        parcel.  The pages keep this slot's refcounts (they can never
        be evicted while staged), so the commit's restore is
        guaranteed to find them device-resident — the copy itself
        runs under whatever decode batch this step schedules."""
        pool = self.kvc.pool
        rid = st["req"].rid
        tr = time.perf_counter() if self.recorder.enabled else 0.0
        with self.trace.span("percolation", "handoff_stage",
                             kind="copy", rid=rid, slot=slot,
                             loc=self._home_locality(slot)):
            snap = self.kvc.detach_slot(slot)
            if snap is None:                  # empty slot: nothing to move
                st["phase"] = next_phase
                return
            st["snap"] = snap
            st["next_phase"] = next_phase
            st["phase"] = "handoff"
            st["handoff_step"] = len(self.counters)
            nbytes = len(snap.addrs) * pool.page_bytes() \
                + pool.hidden_nbytes(snap.addrs)
            self.handoff_queue.push(CopyParcel(
                ("handoff", rid), tuple(a.gid for a in snap.addrs),
                "handoff", nbytes))
        if self.recorder.enabled:
            self.recorder.event(rid, "handoff_stage", slot=slot,
                                nbytes=nbytes,
                                dur=time.perf_counter() - tr)

    def _commit_handoff(self, slot: int) -> None:
        """Land a staged handoff: restore the snapshot into the slot
        (on one locality, a table rebuild — the pages never moved; a
        multi-host port would commit its staged copy here) and hand
        the slot to its next phase."""
        st = self.active[slot]
        snap = st.pop("snap")
        parcel = self.handoff_queue.pop(("handoff", st["req"].rid))
        staged = st.pop("handoff_step", len(self.counters))
        # overlapped iff the staging step ran a decode batch under the
        # staged copy before this commit (the §4d double buffer)
        overlapped = len(self.counters) > staged \
            and self.counters[staged].get("decode_tokens", 0) > 0
        tr = time.perf_counter() if self.recorder.enabled else 0.0
        with self.trace.span("percolation", "handoff_commit",
                             kind="copy", rid=st["req"].rid, slot=slot,
                             gids=[a.gid for a in snap.addrs],
                             loc=self._home_locality(slot)):
            self.kvc.restore_slot(slot, snap)
        if self.recorder.enabled:
            self.recorder.event(st["req"].rid, "handoff_commit",
                                slot=slot, overlapped=overlapped,
                                dur=time.perf_counter() - tr)
        st["phase"] = st.pop("next_phase")
        self.handoffs += 1
        if parcel is not None:
            self.handoff_bytes += parcel.nbytes
        self.handoff_overlapped += int(overlapped)

    def force_handoff(self) -> int:
        """Drill: stage a MID-PREFILL handoff for every prefilling
        slot with resident pages (next phase: resume chunking where it
        left off).  Chunk boundaries are page-aligned, so the restored
        chain/position always satisfy `begin_chunk`'s resume contract.
        Returns the number of handoffs staged."""
        n = 0
        for slot, st in list(self.active.items()):
            if st.get("phase") == "prefill" \
                    and self.kvc._state[slot].addrs:
                self._stage_handoff(slot, st, next_phase="prefill")
                n += st.get("phase") == "handoff"
        return n

    # -- lifecycle seams the handoff phase must survive ---------------
    def _step(self) -> int:
        # the failure plan fires BEFORE staged handoffs commit: a
        # locality death takes in-flight handoff snapshots with it,
        # which is exactly the seam the chaos drill must exercise
        # (the second poll inside super()._step is idempotent)
        self._check_failure_plan()
        # commit staged handoffs FIRST: a prefill that finished in
        # step N decodes in step N+1, the same cadence the single-
        # locality engine has — with the copy already run under step
        # N's decode batch
        self._decode_role.commit_handoffs(self)
        return super()._step()

    def kill_locality(self, locality: int) -> dict:
        out = super().kill_locality(locality)
        # surviving prefill slots re-resolve their dispatch next
        # chunk: a cached target locality or anchor page may have
        # died with the shard
        for st in self.active.values():
            if st.get("phase") == "prefill":
                for k in ("ploc", "panchor", "pwarm"):
                    st.pop(k, None)
        return out

    def _preempt(self, slot: int) -> None:
        st = self.active.get(slot)
        if st is not None and st.get("phase") == "handoff":
            # land the handoff before evicting: the snapshot holds
            # page refcounts the offload/release path must see on the
            # slot, not dangling from the queue
            self._commit_handoff(slot)
        super()._preempt(slot)

    def _fail_pending(self, err: Exception) -> None:
        for slot, st in list(self.active.items()):
            if st.get("phase") == "handoff":
                snap = st.pop("snap", None)
                if snap is not None:
                    self.kvc.drop_snapshot(snap)
                self.handoff_queue.pop(("handoff", st["req"].rid))
                st["phase"] = st.pop("next_phase", "decode")
        super()._fail_pending(err)

    def stats(self) -> dict:
        out = super().stats()
        role = self._prefill_role
        total = role.parcels
        out.update({
            "disagg": True,
            "prefill_workers": self.prefill_workers,
            "decode_workers": self.decode_workers,
            # dispatch affinity: fraction of prefill parcels that ran
            # at the locality owning their prompt's prefix pages
            "prefill_parcels": total,
            "prefill_parcels_owner": role.owner_parcels,
            "prefill_parcels_cold": role.cold_parcels,
            "prefill_parcel_affinity":
                role.owner_parcels / total if total else 0.0,
            "prefill_parcels_inter_locality": role.inter_locality,
            "parcels_sent": self._port.sent,
            "parcels_local": self._port.local_applied,
            "dispatch_sizes": sorted(role.dispatch_sizes),
            # prefill->decode KV handoffs and their §4d overlap
            "handoffs": self.handoffs,
            "handoff_bytes": self.handoff_bytes,
            "handoff_overlap":
                self.handoff_overlapped / self.handoffs
                if self.handoffs else 0.0,
        })
        m = self.metrics
        m.counter("engine.prefill_parcels").value = total
        m.counter("engine.handoffs").value = self.handoffs
        m.counter("engine.handoff_bytes").value = self.handoff_bytes
        return out


#: The serving engine: chunked prefill over AGAS pages.
ServingEngine = ChunkedPagedServingEngine


def make_engine(params: Any, cfg: ArchConfig, *,
                engine: str = "chunked", disagg: bool = False,
                **kwargs) -> _EngineBase:
    """Engine factory.  `engine` selects the scheduler for
    attention-cache families: "chunked" (default — chunked prefill
    under a token budget), "paged" (whole-prompt prefill over AGAS
    pages), or "dense" (static slot-pool baseline).  ``disagg=True``
    upgrades the chunked engine to the disaggregated prefill/decode
    composition (DESIGN.md §4f; `prefill_workers`/`decode_workers`
    kwargs pick the role counts).  Families whose recurrent state has
    no paged layout (ssm/hybrid/vlm) always fall back to the dense
    engine."""
    if engine not in ("chunked", "paged", "dense"):
        raise ValueError(f"unknown engine {engine!r}")
    if disagg and engine != "chunked":
        raise ValueError(
            "disaggregated prefill/decode requires the chunked engine")
    if cfg.family in PAGED_FAMILIES and engine != "dense":
        if engine == "chunked":
            if disagg:
                return DisaggChunkedServingEngine(params, cfg, **kwargs)
            kwargs.pop("prefill_workers", None)
            kwargs.pop("decode_workers", None)
            return ChunkedPagedServingEngine(params, cfg, **kwargs)
        kwargs.pop("chunk_size", None)
        kwargs.pop("step_tokens", None)
        return PagedServingEngine(params, cfg, **kwargs)
    for k in ("page_size", "n_pages", "chunk_size", "step_tokens",
              "kv_shards", "mesh", "rebalance_tolerance", "tiering",
              "host_pages", "prefix_cache_compute", "pin_threshold",
              "prefill_workers", "decode_workers", "failure_plan"):
        kwargs.pop(k, None)
    return DenseServingEngine(params, cfg, **kwargs)
