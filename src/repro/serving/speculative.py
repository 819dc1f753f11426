"""Draft-verify speculative decoding over the serving engine's pools.

The paper's 8:16(+outlier) compression crosses the Performance Threshold —
the compressed model matches its dense parent closely enough that token-
level agreement is high — which makes it a near-free draft model for the
dense target the engine already serves.  Each decode step becomes:

            draft                      verify (ONE fused step)
  ┌──────────────────────┐   ┌───────────────────────────────────────┐
  │ proposer suggests    │   │ target runs [last_token, d1 .. dk]    │
  │ d1 .. dk per request │ → │ through unified_step at S = k+1:      │
  │ (8:16 model, or      │   │ writes k+1 KV positions, attends in   │
  │  n-gram self-draft)  │   │ place, logits[j] checks draft d_{j+1} │
  └──────────────────────┘   └───────────────────────────────────────┘
                                   ↓ leave-one-in verification
                             accept a leading drafts, emit a+1 tokens
                             (a accepted + 1 correction/bonus), roll
                             the cursor back to pos + a + 1

Verification costs one fused step instead of k sequential decodes because
``attend_over_pool`` (and the q-chunk paged kernel) already attends S
queries per lane — the verify step IS the engine's existing chunk step
function with per-lane ``n_new = k+1``, so no new jitted functions are
introduced and the S shapes ride the same power-of-two ``_bucket`` ladder
as prefill chunks (compiled-variant growth stays logarithmic in k, not
linear — pinned by tests/test_speculative.py).

Rollback is free in both KV layouts.  The engine's invariant is that
``pool.pos`` counts positions actually WRITTEN and the last emitted token's
KV is only written when it is fed into the next step; a verify step feeds
k+1 tokens and accepts a, so the cursor advances to ``pos + a + 1`` and
the positions beyond it hold rejected-draft garbage that (slot) the cursor
length-mask hides until the next step overwrites it, or (paged) sits in
blocks still owned by the row — exactly the half-filled-block state
chunk-aware allocation already handles.  Nothing is copied or zeroed.
``PagedKVPool.fork`` (copy-on-write block sharing) is the enabler for
tree/forked drafts on top of this.

Two proposers:

  ``ModelDrafter``  a second model (the 8:16-compressed zoo member) with
      its own slot-layout KV arena, co-resident on the engine's mesh with
      the same out-dim tensor-parallel placement as the target.  It keeps
      a per-slot draft cursor ``dpos`` and catches up LAZILY: before
      drafting it absorbs ``seq[dpos:]`` in one bucketed chunk — which
      uniformly covers fresh requests (drafter prefills the prompt),
      post-preemption resumes, and prefix-cache-hit admissions (the
      drafter has no prefix cache; ``dpos`` resets to 0 whenever the
      target (re)allocates the slot) — then proposes k tokens greedily
      with k-1 batched S=1 decodes.  After verification the draft cursor
      rolls back to the accepted prefix, so a rejection costs the drafter
      nothing either.
  ``NGramProposer``  prompt-lookup self-drafting: match the last n tokens
      of the sequence against its own history and propose the
      continuation of the most recent earlier occurrence.  Zero compute,
      zero state; rows with no match simply verify 0 drafts (a plain
      decode).

Acceptance-aware k adaptation lives in the engine (it owns the Request):
a request that accepts everything grows its ``draft_k`` toward ``max_k``;
one that rejects more than half shrinks toward ``min_k``.  Per-row k
variation is just per-lane ``n_new`` — no shape change.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from . import families


def _bucket(n: int, lo: int = 1) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def verify_bucket(n_new_max: int, k0: int) -> int:
    """S bucket for the fused verify step's q_len axis.

    Rung 1 covers draft-free steps (every proposer came back empty, so
    the verify degenerates to a decode-shaped step); any drafted step
    lands on a power-of-two ladder anchored at the CONFIGURED operating
    point ``_bucket(k0 + 1)`` rather than densely at every power of two
    below it.  Proposers with variable draft length (n-gram matches run
    0..k tokens; adaptive per-request k walks [min_k, max_k]) therefore
    reuse ONE compiled verify shape across the whole [1, k0] range — pad
    positions are masked by per-lane ``n_new`` — and only excursions
    above k0 add rungs, at most log2(max_k/k0) of them.  The old
    ``_bucket(max_nd + 1)`` ladder retraced once per draft-length bucket
    the workload happened to hit (9 ``step`` retraces for adaptive n-gram
    drafting in the serving bench); this trades a few masked pad columns
    on short-draft steps for a variant count that is workload-independent.
    """
    if n_new_max <= 1:
        return 1
    b = _bucket(k0 + 1)
    while b < n_new_max:
        b *= 2
    return b


@dataclasses.dataclass(frozen=True)
class SpeculativeConfig:
    """Engine-level speculative decoding configuration (``draft=``).

    ``method="model"`` drafts with a second model (``params`` required —
    typically the 8:16+outlier compressed counterpart of the target;
    ``cfg`` defaults to the target's config and must share its vocab).
    ``method="ngram"`` self-drafts by prompt lookup (suffix length
    ``ngram``).  ``k`` is the initial per-request draft length; with
    ``adaptive`` on, each request's k walks within [min_k, max_k] by its
    own acceptance history.
    """
    k: int = 4
    method: str = "model"
    params: Any = None
    cfg: Any = None
    ngram: int = 2
    adaptive: bool = True
    min_k: int = 1
    max_k: int = 8

    def __post_init__(self):
        if self.method not in ("model", "ngram"):
            raise ValueError(
                f"draft method must be 'model' or 'ngram', not "
                f"{self.method!r}")
        if self.method == "model" and self.params is None:
            raise ValueError("draft method 'model' needs draft params")
        if not (1 <= self.min_k <= self.k <= self.max_k):
            raise ValueError(
                f"need 1 <= min_k <= k <= max_k, got min_k={self.min_k} "
                f"k={self.k} max_k={self.max_k}")
        if self.ngram < 1:
            raise ValueError("ngram suffix length must be >= 1")


class NGramProposer:
    """Prompt-lookup drafting: propose the continuation of the most recent
    earlier occurrence of the sequence's last-``n`` suffix."""

    def __init__(self, n: int = 2):
        self.n = n

    def propose(self, seq: list[int], k: int) -> list[int]:
        n = self.n
        if k <= 0 or len(seq) <= n:
            return []
        suffix = seq[-n:]
        for start in range(len(seq) - n - 1, -1, -1):
            if seq[start:start + n] == suffix:
                return list(seq[start + n:start + n + k])
        return []


class ModelDrafter:
    """A second model proposing tokens over its own slot-layout KV arena.

    Shares slot identity with the target engine (slot i of the draft arena
    belongs to whichever request holds target slot i) and the engine's
    placement — draft params are committed with the same out-dim
    tensor-parallel shardings as the target's, so both models are
    co-resident on one mesh.  Its calls, and its reads of the proposals,
    run inside the engine's ``serve.draft`` span (serving/observe.py).
    Proposals are greedy (argmax): for a deterministic proposer the
    leave-one-in verification in sampling.py preserves the target
    distribution regardless, and greedy maximizes acceptance for the
    low-temperature traffic speculation targets.
    """

    def __init__(self, cfg, params, placement, *, n_slots: int,
                 max_len: int, kv_dtype: str = "bf16"):
        psh = placement.param_shardings(params)
        params = params if psh is None else jax.device_put(params, psh)
        self.cfg = cfg
        self.adapter = families.TransformerAdapter(
            cfg, params, placement, psh, kv_layout="slot", n_slots=n_slots,
            max_len=max_len, block_size=16, n_blocks=None,
            prefix_caching=False, paged_attn_backend=None,
            kv_dtype=kv_dtype)
        self.max_len = max_len
        # dpos[slot]: draft-arena positions holding the slot's TRUE
        # sequence prefix (the draft cursor); _from[slot]: the sequence
        # length at the last catch-up, i.e. where this round's proposals
        # started writing — what rollback measures acceptance against
        self.dpos = np.zeros((n_slots,), np.int64)
        self._from = np.zeros((n_slots,), np.int64)

    def on_admit(self, slot: int) -> None:
        """Target (re)allocated this slot: whatever the draft arena holds
        there belongs to a previous occupant."""
        self.dpos[slot] = 0

    def propose(self, slots: list[int], seqs: list[list[int]],
                ks: list[int]) -> list[list[int]]:
        """Catch the draft KV up to each row's sequence and propose up to
        ``ks[i]`` greedy continuations.  One bucketed chunk absorbs
        ``seq[dpos:]`` for every row at once (per-lane cursors — rows at
        different depths share the call), whose last real logit is d1;
        then max(k)-1 batched S=1 decodes extend the drafts."""
        pool = self.adapter.pool
        # constant batch width (pad lanes hit the pool sentinel row): the
        # catch-up chunk compiles one variant per S bucket, not B x S
        B = _bucket(pool.n_slots)
        # the engine only speculates on decoding rows, which have emitted
        # at least one token since the last catch-up/rollback — so every
        # row has >= 1 token to absorb and a d1 logit to read
        needs = [len(seq) - int(self.dpos[s]) for s, seq in zip(slots, seqs)]
        ks = [min(k, self.max_len - len(seq))
              for k, seq in zip(ks, seqs)]           # never write past arena
        S = _bucket(max(needs))
        tokens = np.zeros((B, S), np.int32)
        cur = np.zeros((B,), np.int32)
        n_new = np.zeros((B,), np.int32)
        for i, (slot, seq, need) in enumerate(zip(slots, seqs, needs)):
            tokens[i, :need] = seq[len(seq) - need:]
            cur[i] = int(self.dpos[slot])
            n_new[i] = need
        lanes = pool.lane_rows(slots, B)
        logits = self.adapter.step_chunk(
            slots, jnp.asarray(lanes), jnp.asarray(cur), jnp.asarray(n_new),
            jnp.asarray(tokens))
        pool.advance_prefill(slots, [len(seq) for seq in seqs])
        for slot, seq in zip(slots, seqs):
            self.dpos[slot] = self._from[slot] = len(seq)
        first = np.asarray(jnp.argmax(
            logits[jnp.arange(len(slots)), jnp.asarray(needs) - 1], -1))
        drafts = [[int(first[i])] if ks[i] >= 1 else []
                  for i in range(len(slots))]

        feed = np.zeros((pool.n_slots,), np.int32)
        for i, slot in enumerate(slots):
            feed[slot] = first[i]
        for j in range(1, max(ks, default=0)):
            act = [s for i, s in enumerate(slots) if ks[i] > j]
            if not act:
                break
            logits = self.adapter.step_decode(jnp.asarray(feed[:, None]), act)
            mask = np.zeros((pool.n_slots,), bool)
            mask[act] = True
            pool.advance_decode(mask)
            self.dpos[act] += 1
            nxt = np.asarray(jnp.argmax(logits[:, 0, :], -1))
            for i, slot in enumerate(slots):
                if ks[i] > j:
                    drafts[i].append(int(nxt[slot]))
                    feed[slot] = nxt[slot]
        return drafts

    def rollback(self, slot: int, n_drafted: int, n_accepted: int) -> None:
        """Roll the draft cursor back to the verified prefix.  The last
        proposal round wrote drafts d1..d_{k-1} at sequence positions
        [_from, _from + k - 1); the first ``n_accepted`` of them are now
        true sequence tokens, the rest is garbage the cursor hides."""
        if n_drafted > 0:
            self.dpos[slot] = self._from[slot] + min(n_accepted,
                                                     n_drafted - 1)


class Speculator:
    """The engine's handle on speculation: one proposer + the config."""

    def __init__(self, spec: SpeculativeConfig, target_cfg, placement, *,
                 n_slots: int, max_len: int, kv_dtype: str = "bf16"):
        self.cfg = spec
        self.drafter = None
        self.ngram = None
        if spec.method == "model":
            dcfg = spec.cfg if spec.cfg is not None else target_cfg
            if dcfg.vocab != target_cfg.vocab:
                raise ValueError(
                    f"draft vocab {dcfg.vocab} != target vocab "
                    f"{target_cfg.vocab}: draft tokens must be target "
                    f"tokens")
            self.drafter = ModelDrafter(dcfg, spec.params, placement,
                                        n_slots=n_slots, max_len=max_len,
                                        kv_dtype=kv_dtype)
        else:
            self.ngram = NGramProposer(spec.ngram)

    def on_admit(self, slot: int) -> None:
        if self.drafter is not None:
            self.drafter.on_admit(slot)

    def propose(self, slots: list[int], seqs: list[list[int]],
                ks: list[int]) -> list[list[int]]:
        if self.drafter is not None:
            return self.drafter.propose(slots, seqs, ks)
        return [self.ngram.propose(seq, k) for seq, k in zip(seqs, ks)]

    def rollback(self, slot: int, n_drafted: int, n_accepted: int) -> None:
        if self.drafter is not None:
            self.drafter.rollback(slot, n_drafted, n_accepted)
