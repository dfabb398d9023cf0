"""Open loop of multi-turn sessions behind a few shared system prompts.

Session starts are Poisson at "session_rate_per_s" (load).  Each session
picks one of "system_prompts" prompts of "system_len" tokens with Zipf
popularity ("zipf_s"), and runs "turns" turns.  A turn's prompt is the
system prompt, the whole history (earlier prompts and the answers the
server gave) and a new user message ("user" lengths); it asks for an
"answer" of drawn length.  The next turn is due an exponential think
time ("think_s_mean") after the answer arrives.  A session ends early
rather than pass max_len.  Counts, lengths, gaps and think times are
stratified per block (_draw.blocked), so every seed offers the same
work in another order.
"""

from __future__ import annotations

import numpy as np

from bench.traffic._draw import Arrival, blocked, lengths, tokens


class Traffic:
    closed = False

    def __init__(self, mix: dict, load: dict, *, vocab: int, seed: int,
                 horizon_s: float, max_len: int):
        rate = float(load["session_rate_per_s"])
        block = int(mix.get("block", 16))
        rng = np.random.default_rng(seed)
        n = (int(np.ceil(rate * horizon_s)) // block + 2) * block
        gaps = blocked({"dist": "exponential", "mean": 1.0 / rate}, n,
                       block, rng)
        self._starts = np.cumsum(gaps)
        k = int(mix["system_prompts"])
        self._system = [tokens(rng, mix["system_len"], vocab)
                        for _ in range(k)]
        w = 1.0 / np.arange(1, k + 1) ** float(mix["zipf_s"])
        # stratified Zipf: each block of sessions holds the systems in
        # their popularity shares, permuted
        cum = np.cumsum(w / w.sum())
        per = np.searchsorted(cum, (np.arange(block) + 0.5) / block)
        self._sys_of = np.concatenate(
            [per[rng.permutation(block)] for _ in range(-(-n // block))])
        turns = lengths(mix["turns"], n, block, rng)
        m = int(mix["turns"]["max"])
        user = lengths(mix["user"], n * m, block, rng).reshape(n, m)
        ans = lengths(mix["answer"], n * m, block, rng).reshape(n, m)
        think = blocked({"dist": "exponential",
                         "mean": float(mix["think_s_mean"])}, n * m,
                        block, rng).reshape(n, m)
        self._sessions = [{"turns": int(turns[i]), "user": user[i],
                           "answer": ans[i], "think": think[i],
                           "history": self._system[self._sys_of[i]],
                           "turn": 0} for i in range(n)]
        self._rng = rng
        self._vocab = vocab
        self._max_len = max_len
        self._next_start = 0
        self._followups = []                 # (due, session index)
        self._rid_session = {}
        self._rid = 0

    def _turn(self, s: int, due: float):
        sess = self._sessions[s]
        t = sess["turn"]
        if t >= sess["turns"]:
            return None
        msg = tokens(self._rng, sess["user"][t], self._vocab)
        prompt = np.concatenate([sess["history"], msg])
        n_out = int(sess["answer"][t])
        if len(prompt) + n_out > self._max_len:
            return None                      # ends rather than overflow
        sess["history"] = prompt
        sess["turn"] = t + 1
        a = Arrival(self._rid, prompt, n_out, due)
        self._rid_session[self._rid] = s
        self._rid += 1
        return a

    def due(self, now: float) -> list:
        out = []
        while self._next_start < len(self._starts) and \
                self._starts[self._next_start] <= now:
            a = self._turn(self._next_start,
                           float(self._starts[self._next_start]))
            self._next_start += 1
            if a is not None:
                out.append(a)
        ready = sorted(f for f in self._followups if f[0] <= now)
        self._followups = [f for f in self._followups if f[0] > now]
        for due, s in ready:
            a = self._turn(s, due)
            if a is not None:
                out.append(a)
        return out

    def next_due(self):
        cands = [f[0] for f in self._followups]
        if self._next_start < len(self._starts):
            cands.append(float(self._starts[self._next_start]))
        return min(cands) if cands else None

    def finished(self, rid: int, tokens_out, now: float) -> None:
        s = self._rid_session.pop(rid, None)
        if s is None:
            return
        sess = self._sessions[s]
        sess["history"] = np.concatenate(
            [sess["history"], np.asarray(tokens_out, np.int32)])
        t = sess["turn"]
        if t < sess["turns"]:
            self._followups.append((now + float(sess["think"][t - 1]), s))
