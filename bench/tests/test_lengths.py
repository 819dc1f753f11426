"""Every seed gets the same work: the same sizes and arrival gaps, in one
spread-out order; the seed draws only token ids."""
import numpy as np
import pytest

from bench import lengths, registry
from bench.generators import open_loop

CHAT = registry.traffic("chat")


def _schedule(seed, n):
    src = open_loop.make(CHAT, seed, 32)
    out = []
    while len(out) < n:
        out += src.pop_due(src.next_due())
    return [(s.due, s.prompt_len, s.out_len) for s in out[:n]]


def test_open_loop_schedule_is_the_same_for_every_seed():
    assert _schedule(1, 70) == _schedule(2**33 + 5, 70)


def test_each_cycle_holds_the_same_quantiles():
    n = CHAT["n_distinct"]
    sched = _schedule(3, 3 * n)
    q = sorted(lengths.lognormal_quantiles(CHAT["output"], n).tolist())
    for c in range(3):
        assert sorted(o for _, _, o in sched[c * n:(c + 1) * n]) == q
    gaps = np.diff([0.0] + [d for d, _, _ in sched[:n]])
    assert gaps.mean() == pytest.approx(1 / CHAT["rate_per_s"])


@pytest.mark.parametrize("stream", [0, 1, 2])
def test_consecutive_requests_spread_over_the_quantiles(stream):
    """Any 8 consecutive positions of a 32-cycle take ranks from every
    quarter of the distribution."""
    n = 32
    order = np.concatenate([lengths.spread_order(n, stream, c)
                            for c in range(3)])
    assert all(sorted(lengths.spread_order(n, stream, c)) == list(range(n))
               for c in range(3))
    for i in range(len(order) - 8):
        assert set(order[i:i + 8] * 4 // n) == {0, 1, 2, 3}


def test_prompt_tokens_come_from_the_seed():
    a = lengths.prompt_tokens(1, 0, 16, 1000)
    assert a == lengths.prompt_tokens(1, 0, 16, 1000)
    assert a != lengths.prompt_tokens(2, 0, 16, 1000)
    assert lengths.prompt_tokens(2**40 + 3, 5, 8, 1000) != a[:8]
