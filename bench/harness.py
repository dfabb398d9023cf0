"""The harness around the serving engine: set-up, warm-up, the open- or
closed-loop window, and the comparison with the plain reference.

The window drives `make_engine(..., engine="chunked")` through
`submit` and `step` only.  Before each step it submits every request
whose due time has passed; each request is timed from when it was due.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from bench import spec, stats

WARM_RID = 1 << 40            # warm-up request ids, apart from traffic's
ANNOTATIONS = ("engine.step", "submit", "wait_arrival")


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.n += 1


def null_annotate(name):
    return contextlib.nullcontext()


def build_engine(arch, params, cell: dict, *, recorder: bool,
                 n_pages: Optional[int] = None):
    from repro.serving.engine import make_engine
    from repro.serving.kvcache import pages_that_fit
    ps = cell["page_size"]
    if n_pages is None:
        st = jax.devices()[0].memory_stats()
        free = st["bytes_limit"] - st["bytes_in_use"]
        n_pages = pages_that_fit(arch, ps, free - cell["reserve_bytes"])
    return make_engine(params, arch, engine="chunked", slots=cell["slots"],
                       max_len=cell["max_len"], page_size=ps,
                       n_pages=n_pages, chunk_size=cell["chunk_size"],
                       step_tokens=cell["step_tokens"],
                       flight_recorder=recorder)


def warm_up(eng, cell: dict, vocab: int) -> None:
    """One wave that fills every slot: compiles the chunk and decode
    programs and the sampler's eager ops at every slot index.  Nothing
    else is compiled; the window uses no other shape."""
    from repro.serving.engine import Request
    rng = np.random.default_rng(0)
    for i in range(cell["slots"]):
        eng.submit(Request(WARM_RID + i, rng.integers(
            0, vocab, cell["page_size"]).astype(np.int32),
            max_new_tokens=3))
    eng.run_to_completion()
    eng.completions.clear()
    eng.counters.clear()
    eng.reset_metrics()


def _slots(eng) -> Dict[int, tuple]:
    """(rid, phase, prefill position, resident tokens) of each slot."""
    return {s: (st["req"].rid, st.get("phase", "decode"), st.get("pos", 0),
                int(eng.kvc.lengths[s])) for s, st in eng.active.items()}


def _step_work(before: Dict[int, tuple], eng, t0: float, t1: float) -> dict:
    """What one step computed: the decoding slots' contexts (the new
    token included) and each prefill chunk's (start, take)."""
    c = eng.counters[-1] if eng.counters else {}
    dec = [ln + 1 for (_, ph, _, ln) in before.values() if ph == "decode"]
    dec = dec[:c.get("decode_tokens", len(dec))]
    chunks = []
    for s, st in eng.active.items():
        rid, ph, pos, _ = before.get(s, (None, "prefill", 0, 0))
        if rid != st["req"].rid:        # admitted during this step
            ph, pos = "prefill", 0
        if ph != "prefill":
            continue
        new = st["pos"] if st.get("phase") == "prefill" else st["real"]
        if new > pos:
            chunks.append((pos, new - pos))
    return {"t0": t0, "t1": t1, "decode_ctx": dec, "chunks": chunks}


def drive(eng, gen, *, ramp_s: float, window_s: float,
          clock: Callable[[], float] = time.perf_counter,
          sleep: Callable[[float], None] = time.sleep,
          annotate=null_annotate, tracer=None, trace_s: float = 0.0,
          log_steps: bool = False, compiles=None) -> dict:
    """Serve `gen`'s traffic for ramp_s + window_s seconds of host clock.

    The ramp is served and not counted, so the window starts in a steady
    state.  Returns the raw records; nothing is judged here."""
    from repro.serving.engine import Request
    pool = eng.kvc.pool
    recs: Dict[int, stats.RequestRecord] = {}
    prompts: Dict[int, np.ndarray] = {}
    comps: Dict[int, Any] = {}
    pending: Dict[int, Any] = {}
    steps: List[dict] = []
    t0 = clock()
    w0, w1 = t0 + ramp_s, t0 + ramp_s + window_s
    trace_at = w1 - trace_s
    at_w0 = None
    trace_span = None
    while True:
        now = clock()
        if at_w0 is None and now >= w0:
            at_w0 = {"allocs": pool.allocs, "shares": pool.shares,
                     "steps": len(eng.counters),
                     "compiles": compiles.n if compiles else 0}
        if tracer is not None and trace_span is None and now >= trace_at:
            tracer.start()
            trace_span = annotate("trace_window")
            trace_span.__enter__()
            now = clock()
        if now >= w1:
            break
        arrivals = gen.due(now - t0)
        if arrivals:
            with annotate("submit"):
                for a in arrivals:
                    t = clock()
                    pending[a.rid] = eng.submit(
                        Request(a.rid, a.prompt, max_new_tokens=a.max_new))
                    recs[a.rid] = stats.RequestRecord(
                        a.rid, t0 + a.due, t, len(a.prompt), a.max_new)
                    prompts[a.rid] = a.prompt
        if eng.active or eng.queue:
            before = _slots(eng) if log_steps else None
            ts = clock()
            with annotate("engine.step"):
                eng.step()
            t = clock()
            if log_steps:
                steps.append(_step_work(before, eng, ts, t))
            for rid in [r for r, f in pending.items() if f.done()]:
                f = pending.pop(rid)
                try:
                    comps[rid] = f.get()
                    recs[rid].finished = True
                    gen.finished(rid, comps[rid].tokens, t - t0)
                except Exception:          # the request's LCO failed
                    recs[rid].failed = True
                    gen.finished(rid, [], t - t0)
        else:
            nxt = gen.next_due()
            wake = w1 if nxt is None else min(t0 + nxt, w1)
            for edge in (w0, trace_at):
                if now < edge < wake:
                    wake = edge
            with annotate("wait_arrival"):
                sleep(max(0.0, wake - clock()))
    end_compiles = compiles.n if compiles else 0
    if trace_span is not None:
        trace_span.__exit__(None, None, None)
        tracer.stop()
    # token times: finished requests from their completion, the rest
    # from the engine's in-flight state (active slots, queued items)
    for rid, c in comps.items():
        r = recs[rid]
        first = r.submitted + c.ttft_s
        r.tok_t = [first] + list(first + np.cumsum(c.itl_s))
    for st in list(eng.active.values()) + list(eng.queue):
        rid = st["req"].rid
        if rid in recs and not recs[rid].finished:
            recs[rid].tok_t = list(st.get("tok_t", []))
    if eng.recorder.enabled:
        for rid, r in recs.items():
            binds = [e.t for e in eng.recorder.timeline(rid)
                     if e.name == "bind"]
            r.bind = binds[0] if binds else None
    at_w0 = at_w0 or {"allocs": pool.allocs, "shares": pool.shares,
                      "steps": len(eng.counters), "compiles": end_compiles}
    return {"t0": t0, "w0": w0, "w1": w1, "records": list(recs.values()),
            "prompts": prompts, "completions": comps, "steps": steps,
            "counters": eng.counters[at_w0["steps"]:],
            "pool": {"allocs": pool.allocs - at_w0["allocs"],
                     "shares": pool.shares - at_w0["shares"],
                     "capacity": pool.capacity},
            "compiles_in_window": end_compiles - at_w0["compiles"],
            "preemptions": eng.preemptions,
            "trace_interval": (trace_at, clock()) if tracer else None}


def sample(run: dict, check: dict, seed: int) -> List[int]:
    """Requests to compare with the reference, drawn from the seed among
    those finished: the longest, up to two that were preempted and
    re-admitted, then others until both floors are met."""
    comps = run["completions"]
    if not comps:
        return []
    size = {rid: len(run["prompts"][rid]) + len(c.tokens)
            for rid, c in comps.items()}
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    order = [int(r) for r in rng.permutation(sorted(comps))]
    pick = [max(comps, key=lambda r: (size[r], r))]
    pick += [r for r in order if comps[r].preemptions and r not in pick][:2]
    served = sum(len(comps[r].tokens) for r in pick)
    for r in order:
        if len(pick) >= check["max_requests"] or (
                len(pick) >= check["min_requests"]
                and served >= check["min_served_tokens"]):
            break
        if r not in pick:
            pick.append(r)
            served += len(comps[r].tokens)
    return pick


def token_gaps(lg: np.ndarray, toks: np.ndarray) -> np.ndarray:
    """By how much each token's reference logit lies below the
    reference's best at its position (rows of `lg`)."""
    return lg.max(axis=1) - lg[np.arange(len(toks)), toks]


def passes(share: float, check: dict) -> bool:
    """The verdict on the share of served tokens off the reference's
    best by more than the cell's `off_tol`: at most the cell's limit."""
    return bool(np.isfinite(share)) and \
        share <= float(check["off_share_limit"])


OFF_TOLS = (0.05, 0.1, 0.2, 0.5, 1.0)


def off_counts(gaps: np.ndarray) -> Dict[str, int]:
    """How many gaps exceed each of OFF_TOLS."""
    return {str(t): int((gaps > t).sum()) for t in OFF_TOLS}


def compare(params, cfg: dict, run: dict, rids: List[int], tol: float,
            control: bool = False) -> dict:
    """Gaps by which served tokens' logits lie below the reference's
    best, over every served token of `rids` (the reference is fed each
    prompt and the tokens served before): the share above `tol` and the
    widest.  With `control`, the fp8 reference takes the program's place
    at the same positions and the tokens it puts first are read alike."""
    ref = spec.load_module("reference", cfg["reference"])
    gaps, ctl_gaps, per = [], [], []
    for rid in rids:
        prompt = run["prompts"][rid]
        toks = np.asarray(run["completions"][rid].tokens, np.int32)
        seq = np.concatenate([prompt, toks[:-1]])
        rows = np.arange(len(prompt) - 1, len(seq))
        x = ref.hidden(params, cfg, seq)
        lg = np.asarray(ref.logits_at(params, cfg, x, rows))
        del x
        g = token_gaps(lg, toks)
        gaps.append(g)
        row = {"rid": int(rid), "prompt": len(prompt), "out": len(toks),
               "gap": float(g.max()), "at": int(g.argmax()),
               "off": int((g > tol).sum()),
               "preempted": int(run["completions"][rid].preemptions)}
        if control:
            xc = ref.hidden(params, cfg, seq, quant="fp8")
            lc = np.asarray(ref.logits_at(params, cfg, xc, rows,
                                          quant="fp8"))
            del xc
            gc = token_gaps(lg, lc.argmax(axis=1))
            ctl_gaps.append(gc)
            row["control_gap"] = float(gc.max())
            row["control_off"] = int((gc > tol).sum())
            row["control_logit_maxdiff"] = float(np.abs(lc - lg).max())
        per.append(row)
    out = {"requests": len(rids), "per_request": per}
    for key, gs in (("", gaps), ("control_", ctl_gaps)):
        if key and not control:
            continue
        g = np.concatenate(gs) if gs else np.zeros(0)
        out[key + "tokens"] = len(g)
        out[key + "off_share"] = float((g > tol).mean()) if len(g) else \
            float("nan")
        out[key + "logit_gap"] = float(g.max()) if len(g) else 0.0
        out[key + "off"] = off_counts(g)
    return out
