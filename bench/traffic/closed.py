"""Closed loop: a fixed number of clients, each sending its next
document as soon as the answer to the previous one arrives.

Mix parameters: "document", "question", "output" length specs, "block".
Load: "clients".  A prompt is a document followed by a question.  The
documents come in blocks of stratified sizes (_draw.blocked), so every
seed sends the same sizes in each block, in another order.
"""

from __future__ import annotations

import numpy as np

from bench.traffic._draw import Arrival, lengths, tokens


class Traffic:
    closed = True

    def __init__(self, mix: dict, load: dict, *, vocab: int, seed: int,
                 horizon_s: float, max_len: int):
        self.clients = int(load["clients"])
        block = int(mix.get("block", 16))
        rng = np.random.default_rng(seed)
        n = int(mix.get("documents", 64))
        doc = lengths(mix["document"], n, block, rng)
        q = lengths(mix["question"], n, block, rng)
        out = lengths(mix["output"], n, block, rng)
        self._docs = []
        for i in range(n):
            plen = min(int(doc[i] + q[i]), max_len - 1)
            self._docs.append((plen, int(min(out[i], max_len - plen))))
        self._rng = rng
        self._vocab = vocab
        self._next = 0
        self._ready = [0.0] * self.clients      # due times not yet sent

    def _arrival(self, due: float) -> Arrival:
        plen, olen = self._docs[self._next % len(self._docs)]
        a = Arrival(self._next, tokens(self._rng, plen, self._vocab),
                    olen, due)
        self._next += 1
        return a

    def due(self, now: float) -> list:
        ready = [t for t in self._ready if t <= now]
        self._ready = [t for t in self._ready if t > now]
        return [self._arrival(t) for t in sorted(ready)]

    def next_due(self):
        return min(self._ready) if self._ready else None

    def finished(self, rid: int, tokens_out, now: float) -> None:
        self._ready.append(now)
