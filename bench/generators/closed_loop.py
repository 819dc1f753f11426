"""Closed loop: ``callers`` callers, each waiting for its answer before it
sends again, with no think time, so the engine holds one request per
caller all through.  Caller ``i`` sends its first request at
``i * warmup_s / callers`` seconds, so the callers are out of phase by the
time the window opens; after that each sends when its last request
finishes (``on_finish``).  Prompt and output lengths are lognormal
quantiles in the spread-out order that every seed shares
(``bench/lengths.py``), taken in the order requests are sent."""
from __future__ import annotations

import math

from bench.lengths import Spec, lognormal_quantiles, spread_order


class ClosedLoop:
    def __init__(self, mix: dict, seed: int, n_slots: int):
        self.n = int(mix["n_distinct"])
        self.prompts = lognormal_quantiles(mix["prompt"], self.n)
        self.outputs = lognormal_quantiles(mix["output"], self.n)
        callers = int(mix["callers"])
        # per caller: when it sends next, or None while it waits
        self._due = [i * float(mix["warmup_s"]) / callers
                     for i in range(callers)]
        self._caller: dict[int, int] = {}       # request index -> caller
        self._sizes: list[tuple[int, int]] = []
        self._cycle = 0
        self._next_index = 0

    def _size(self) -> tuple[int, int]:
        if not self._sizes:
            p, o = (a[spread_order(self.n, s, self._cycle)]
                    for s, a in enumerate((self.prompts, self.outputs)))
            self._sizes = [(int(a), int(b)) for a, b in zip(p, o)]
            self._cycle += 1
        return self._sizes.pop(0)

    def next_due(self) -> float:
        return min((d for d in self._due if d is not None), default=math.inf)

    def pop_due(self, t: float) -> list[Spec]:
        out = []
        for caller, due in sorted(enumerate(self._due),
                                  key=lambda cd: (cd[1] is None, cd[1])):
            if due is None or due > t:
                break
            prompt, answer = self._size()
            out.append(Spec(self._next_index, due, prompt, answer))
            self._caller[self._next_index] = caller
            self._due[caller] = None
            self._next_index += 1
        return out

    def on_finish(self, spec: Spec, t: float) -> None:
        self._due[self._caller.pop(spec.index)] = t


def make(mix: dict, seed: int, n_slots: int) -> ClosedLoop:
    return ClosedLoop(mix, seed, n_slots)
