"""Open loop: independent requests at a fixed mean rate.

Mix parameters (traffic/<mix>.json): "prompt", "output" length specs
(_draw.quantiles), "block".  Load (cells/<cell>.json "load"):
"rate_per_s".  Arrival gaps are exponential with mean 1/rate, drawn
stratified per block like the lengths, so every seed offers the same
gaps in another order: a stratified Poisson process.  Each block of n
gaps holds the exponential's quantiles (j + 0.5) / n, so no gap exceeds
ln(2n) mean gaps (4.16 at n = 32) and bursts longer than a block are
smoothed away.  Independent gaps would change the number of requests a
40 s window sees by some 13% from seed to seed (test_bench_traffic).
"""

from __future__ import annotations

import numpy as np

from bench.traffic._draw import Arrival, blocked, lengths, tokens


class Traffic:
    closed = False

    def __init__(self, mix: dict, load: dict, *, vocab: int, seed: int,
                 horizon_s: float, max_len: int):
        rate = float(load["rate_per_s"])
        block = int(mix.get("block", 32))
        rng = np.random.default_rng(seed)
        n = (int(np.ceil(rate * horizon_s)) // block + 2) * block
        gaps = blocked({"dist": "exponential", "mean": 1.0 / rate}, n,
                       block, rng)
        due = np.cumsum(gaps)
        plen = lengths(mix["prompt"], n, block, rng)
        olen = lengths(mix["output"], n, block, rng)
        olen = np.minimum(olen, max_len - plen)
        self._pending = [Arrival(i, tokens(rng, plen[i], vocab), olen[i],
                                 due[i]) for i in range(n)]
        self._i = 0

    def due(self, now: float) -> list:
        out = []
        while self._i < len(self._pending) and \
                self._pending[self._i].due <= now:
            out.append(self._pending[self._i])
            self._i += 1
        return out

    def next_due(self):
        if self._i < len(self._pending):
            return self._pending[self._i].due
        return None

    def finished(self, rid: int, tokens_out, now: float) -> None:
        """Open loop: a finish schedules nothing."""
