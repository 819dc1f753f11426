"""End-to-end metrics of one window, on the host clock.

Copied arithmetic, so that no change to the program moves the yardstick:
percentiles are numpy's linear ones (as ``runtime/metrics.percentiles``),
and every quantity is taken over the whole window:

  tok_per_s     generated tokens emitted inside the window / its seconds
  ttft_p95_ms   over every request due inside the window, from the time it
                was due (open loop) or sent (closed loop) to its first
                token; one with no first token by the window's end counts
                at its censored value, the window's end minus its due time,
                and so does one the engine refused
  itl_p50_ms,   over every gap between consecutive tokens of one request
  itl_p95_ms    whose later token was emitted inside the window
"""
from __future__ import annotations

import numpy as np


def pct(values, p: float) -> float | None:
    if not len(values):
        return None
    return float(np.percentile(np.asarray(values, np.float64), p))


def in_window(t: float, w) -> bool:
    return w.start <= t < w.end


def due_in_window(w) -> list:
    return [r for r in w.records if in_window(r.due, w)]


def ttfts(w) -> list[float]:
    out = []
    for r in due_in_window(w):
        first = r.times[0] if r.times else None
        out.append((first if first is not None and first < w.end else w.end)
                   - r.due)
    return out


def gaps(w) -> list[float]:
    out = []
    for r in w.records:
        t = r.times
        for i in range(1, len(t)):
            if in_window(t[i], w):
                out.append(t[i] - t[i - 1])
    return out


def tokens_in_window(w) -> int:
    return sum(1 for r in w.records for t in r.times if in_window(t, w))


def _ms(v):
    return None if v is None else 1e3 * v


def end_to_end(w) -> dict:
    seconds = w.end - w.start
    g = gaps(w)
    t = ttfts(w)
    return {"tok_per_s": tokens_in_window(w) / seconds,
            "ttft_p50_ms": _ms(pct(t, 50)),
            "ttft_p95_ms": _ms(pct(t, 95)),
            "itl_p50_ms": _ms(pct(g, 50)),
            "itl_p95_ms": _ms(pct(g, 95))}


def load(w) -> dict:
    """What the window offered and how the engine kept up: requests due,
    refused and finished inside it, the samples behind each percentile,
    and the queue and batch at its start and end (a queue that grows
    across the window means the rate is above what the engine sustains)."""
    inside = [s for s in w.steps if in_window(s.t0, w)]
    due = due_in_window(w)
    return {"requests_due": len(due),
            "requests_refused": sum(r.rejected for r in due),
            "requests_finished": sum(1 for r in w.records
                                     if r.finished is not None
                                     and in_window(r.finished, w)),
            "ttft_samples": len(due), "itl_samples": len(gaps(w)),
            "steps": len(inside),
            "waiting_start": inside[0].waiting if inside else None,
            "waiting_end": inside[-1].waiting if inside else None,
            "running_mean": (sum(s.running for s in inside) / len(inside)
                             if inside else None)}


def generator_lateness(w) -> dict:
    """How late the generator submitted what was due inside the window
    (one thread drives the engine, so a request due during a step is sent
    when the step returns; TTFT counts from the due time regardless)."""
    late = [r.sent - r.due for r in due_in_window(w)]
    return {"late_p50_ms": 1e3 * (pct(late, 50) or 0.0),
            "late_max_ms": 1e3 * (max(late) if late else 0.0)}
