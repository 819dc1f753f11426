"""Open loop: independent users send on a schedule, whatever the server
does.  Poisson arrivals at ``rate_per_s``, lognormal prompt and output
lengths (``bench/lengths.py``: every seed offers the same sizes and gaps
in one spread-out order, cycle after cycle of ``n_distinct`` requests)."""
from __future__ import annotations

from bench.lengths import (Spec, exponential_gaps, lognormal_quantiles,
                           spread_order)


class OpenLoop:
    def __init__(self, mix: dict, seed: int, n_slots: int):
        self.n = int(mix["n_distinct"])
        self.prompts = lognormal_quantiles(mix["prompt"], self.n)
        self.outputs = lognormal_quantiles(mix["output"], self.n)
        self.gaps = exponential_gaps(float(mix["rate_per_s"]), self.n)
        self._queue: list[Spec] = []
        self._cycle = 0
        self._t = 0.0
        self._next_index = 0

    def _extend(self) -> None:
        p, o, g = (a[spread_order(self.n, s, self._cycle)]
                   for s, a in enumerate((self.prompts, self.outputs,
                                          self.gaps)))
        for i in range(self.n):
            self._t += float(g[i])
            self._queue.append(Spec(self._next_index, self._t, int(p[i]),
                                    int(o[i])))
            self._next_index += 1
        self._cycle += 1

    def next_due(self) -> float:
        if not self._queue:
            self._extend()
        return self._queue[0].due

    def pop_due(self, t: float) -> list[Spec]:
        out = []
        while self.next_due() <= t:
            out.append(self._queue.pop(0))
        return out

    def on_finish(self, spec: Spec, t: float) -> None:
        pass


def make(mix: dict, seed: int, n_slots: int) -> OpenLoop:
    return OpenLoop(mix, seed, n_slots)
