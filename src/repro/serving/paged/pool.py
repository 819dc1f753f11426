"""PagedKVPool: the engine-facing facade over the paged-KV machinery.

Satisfies the same cache-pool protocol as ``SlotKVPool``
(``serving/cache_pool.py``): ``n_free`` concurrency units, a
``max_request_tokens`` admission bound, ``k``/``v``/``pos`` device state
the jitted steps consume, and the ``adopt``/``advance_*``/``release``
lifecycle hooks.  The difference is what backs a request: a *row* here is
only scheduling state (a decode-batch lane plus a block table); the KV
bytes live in ``block_size``-token blocks allocated on demand from one
shared arena (``block_pool.py``), found via the per-row table
(``block_table.py``), and shared across requests with identical prefixes
(``prefix_cache.py``).

All KV writes happen INSIDE the jitted step functions: the pool exposes a
``PagedPoolView`` (arena + per-lane block tables + cursors) and
``models/transformer.unified_step`` scatters each chunk/decode token
through the table and attends in place over the blocks
(``paged_attention.py``) — prefill chunks never gather their
already-written prefix, so per-step HBM traffic is independent of the
cursor.

Admission decouples concurrency from reservation: a row costs nothing
until tokens are actually written, so ``n_rows`` can far exceed what
per-row ``max_len`` reservation would allow in the same HBM.  Allocation
is chunk-aware: ``admit(alloc_tokens=...)`` maps only the first prefill
chunk (plus any matched cached prefix) onto blocks, and
``ensure_capacity`` appends blocks as the engine's prefill cursor
advances — so a half-prefilled long prompt holds only the blocks it has
actually filled.  The flip side is that the arena can run dry mid-decode
or mid-prefill; ``prepare_decode``/``ensure_capacity`` raise
``OutOfBlocks`` and the engine preempts a running request back to the
queue instead of failing.

One block is reserved as the *trash block*: inactive decode-batch rows,
prefill bucket padding, and any position past a lane's ``n_new`` route
their writes there, so the fused steps can write unconditionally for
every lane without corrupting blocks that were recycled to another
request.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..cache_pool import (CachePoolError, CapacityError, DoubleFree,
                          write_arenas)
from .block_pool import BlockPool, OutOfBlocks
from .block_table import BlockTable, blocks_needed
from .prefix_cache import PrefixCache


@dataclasses.dataclass(frozen=True)
class PagedPoolView:
    """What ``transformer.attend_over_pool`` sees of a paged pool: the
    block arena plus per-lane block tables and cursors — NOT a gathered
    copy of context.  Constructed inside the engine's traced step
    functions; ``trash`` is the host-known trash-block id (static).

    ``k``/``v`` are the whole [L, n_blocks, block_size, KV, hd] arenas,
    carried through the per-layer loop; each layer writes and reads its
    own index.  ``block_tables`` [B, nb] maps each lane's sequence position p
    to physical block ``bt[b, p // block_size]`` (padding lanes carry
    all-trash tables).  ``cursor``/``n_new`` as in ``SlotPoolView``.

    ``k_scale``/``v_scale`` ([L, n_blocks, block_size, KV] f32, or None
    for bf16 arenas) are an int8 arena's per-position dequant scales; they
    are addressed through the SAME block tables as the values, so prefix
    sharing, copy-on-write and fork carry them implicitly.
    """
    k: Any
    v: Any
    block_tables: Any
    cursor: Any
    n_new: Any
    trash: int = 0
    k_scale: Any | None = None
    v_scale: Any | None = None

    rows = None                           # duck-type marker: paged layout

    def _write_slots(self, bs, S):
        """(block, offset) scatter indices per (lane, i), flattened;
        padding routes to the trash block."""
        nb = self.block_tables.shape[1]
        p = self.cursor[:, None] + jnp.arange(S)[None]        # [B,S]
        bi = p // bs
        blk = jnp.take_along_axis(self.block_tables,
                                  jnp.clip(bi, 0, nb - 1), axis=1)
        valid = (jnp.arange(S)[None] < self.n_new[:, None]) & (bi < nb)
        blk = jnp.where(valid, blk, self.trash)
        return blk.reshape(-1), (p % bs).reshape(-1)

    def write_layer(self, layer, fresh_k, fresh_v):
        """Scatter fresh [B, S, KV, hd] KV into layer ``layer`` of the
        arenas through the block tables at each lane's cursor, in place
        under donation; returns the view holding the updated arenas.
        Every padding element — batch-pad lanes, positions past a lane's
        ``n_new``, or positions past the table width — routes to the
        trash block, so the compiled scatter depends only on (B, S).  An
        int8 arena quantizes on scatter (``cache_pool.write_arenas``):
        the bf16 projections never land in HBM as an arena copy."""
        blk, off = self._write_slots(self.k.shape[2], fresh_k.shape[1])
        return write_arenas(self, (layer, blk, off), fresh_k, fresh_v)


class PagedKVPool:
    def __init__(self, cfg, n_rows: int, max_len: int, *,
                 block_size: int = 16, n_blocks: int | None = None,
                 prefix_caching: bool = True, placement=None,
                 kv_dtype: str = "bf16"):
        self.block_size = block_size
        self.max_blocks_per_row = blocks_needed(max_len, block_size)
        if n_blocks is None:
            # same HBM as a SlotKVPool(n_rows, max_len) reservation
            n_blocks = n_rows * self.max_blocks_per_row
        self.blocks = BlockPool(cfg, n_blocks + 1, block_size,
                                placement=placement,
                                kv_dtype=kv_dtype)            # +1 trash
        self.kv_dtype = kv_dtype
        self._trash = self.blocks.alloc()                       # permanent
        self.n_blocks = n_blocks                                # usable
        self.n_rows = n_rows
        self.max_len = max_len
        self.prefix_cache = PrefixCache(self.blocks) if prefix_caching \
            else None
        self.tables: list[BlockTable | None] = [None] * n_rows
        self._bt_np = np.full((n_rows, self.max_blocks_per_row),
                              self._trash, np.int32)
        self._bt_jnp = jnp.asarray(self._bt_np)
        self._bt_dirty = False
        self._pos_np = np.zeros((n_rows,), np.int32)
        self._free_rows = list(range(n_rows - 1, -1, -1))
        self.n_preemptions = 0

    # ----------------------------------------------------- protocol attrs
    @property
    def k(self):
        return self.blocks.k

    @property
    def v(self):
        return self.blocks.v

    @property
    def k_scale(self):
        return self.blocks.k_scale

    @property
    def v_scale(self):
        return self.blocks.v_scale

    @property
    def pos(self):
        return jnp.asarray(self._pos_np)

    @property
    def n_slots(self) -> int:
        return self.n_rows

    @property
    def n_free(self) -> int:
        return len(self._free_rows)

    @property
    def max_request_tokens(self) -> int:
        """Longest request (prompt + generation) that can ever complete."""
        return min(self.max_len, self.n_blocks * self.block_size)

    @property
    def trash_block(self) -> int:
        return self._trash

    @property
    def block_tables(self):
        if self._bt_dirty:
            self._bt_jnp = jnp.asarray(self._bt_np)
            self._bt_dirty = False
        return self._bt_jnp

    def lane_tables(self, rows: list[int], n_rows_padded: int) -> np.ndarray:
        """Host per-lane block tables for a chunk group; padding lanes are
        all-trash so their writes and gathers stay harmless."""
        out = np.full((n_rows_padded, self.max_blocks_per_row),
                      self._trash, np.int32)
        for i, row in enumerate(rows):
            out[i] = self._bt_np[row]
        return out

    # -------------------------------------------------------- allocation
    @property
    def free_blocks(self) -> int:
        """Blocks obtainable right now: free-list plus evictable cache."""
        n = self.blocks.n_free
        if self.prefix_cache is not None:
            n += self.prefix_cache.n_evictable
        return n

    def can_admit(self, n_tokens: int, lookahead_blocks: int = 1) -> bool:
        """Block-aware admission: a free row, and enough obtainable blocks
        to hold the whole prompt plus a decode lookahead margin.  A prefix
        hit can only reduce the real need, so this is conservative.  The
        requirement is clamped to the arena size so a request whose prompt
        alone fills the arena (legal: submit() bounds prompt+generation by
        capacity) is not deferred forever by the margin."""
        if not self._free_rows:
            return False
        need = min(blocks_needed(n_tokens, self.block_size)
                   + lookahead_blocks, self.n_blocks)
        return need <= self.free_blocks

    def _alloc_block(self) -> int:
        while True:
            try:
                return self.blocks.alloc()
            except OutOfBlocks:
                if self.prefix_cache is None \
                        or not self.prefix_cache.evict_one():
                    raise

    def _cow(self, block: int) -> int:
        while True:
            try:
                return self.blocks.copy_on_write(block)
            except OutOfBlocks:
                if self.prefix_cache is None \
                        or not self.prefix_cache.evict_one():
                    raise

    # --------------------------------------------------------- admission
    def admit(self, tokens, alloc_tokens: int | None = None) -> tuple[int, int]:
        """Assign a row and map the (leading part of the) prompt onto blocks.

        Matches the longest cached prefix (sharing those blocks
        read-only), allocates fresh blocks for the rest, and returns
        ``(row, n_cached)`` — the prefill only needs to compute
        ``tokens[n_cached:]``.  At least the final prompt token is always
        recomputed so there are logits to sample the first generated
        token from; when that token's block was itself a cache hit, the
        block is first copied copy-on-write so the shared original stays
        immutable.  Raises ``OutOfBlocks`` (engine requeues the request)
        without leaking references.

        ``alloc_tokens`` bounds how many leading tokens get fresh blocks
        NOW (chunk-aware admission: the engine allocates per chunk via
        ``ensure_capacity`` as the prefill cursor advances).  ``None``
        allocates for the whole sequence up front; matched prefix blocks
        are always kept regardless of the bound.
        """
        if not self._free_rows:
            raise CapacityError("admit called with no free rows")
        n = len(tokens)
        if n > self.max_request_tokens:
            raise CapacityError(
                f"prompt of {n} tokens exceeds pool capacity "
                f"{self.max_request_tokens}")
        matched = self.prefix_cache.match(tokens) \
            if self.prefix_cache is not None else []
        bs = self.block_size
        # at least the final prompt token must be recomputed (its logits
        # seed the first generated token), and the cached count is kept on
        # a block boundary so suffix prefills see a handful of distinct
        # (bucket) shapes instead of one per prompt length
        n_cached = min(len(matched) * bs, (n - 1) // bs * bs) if matched \
            else 0
        target = n if alloc_tokens is None else min(n, max(alloc_tokens,
                                                           n_cached))
        table_blocks = list(matched)
        try:
            if matched and n_cached < len(matched) * bs:
                # the recomputed prompt tail lands inside the final matched
                # block -> take a private copy before writing (the shared
                # original may be serving other requests read-only)
                if self.blocks.ref[table_blocks[-1]] > 1:
                    table_blocks[-1] = self._cow(table_blocks[-1])
            for _ in range(blocks_needed(target, bs) - len(table_blocks)):
                table_blocks.append(self._alloc_block())
        except OutOfBlocks:
            for b in table_blocks:
                self.blocks.decref(b)
            raise
        row = self._free_rows.pop()
        self.tables[row] = BlockTable(bs, table_blocks, n_cached)
        self._bt_np[row, :] = self._trash
        self._bt_np[row, :len(table_blocks)] = table_blocks
        self._bt_dirty = True
        self._pos_np[row] = 0            # set for real by advance_prefill
        return row, n_cached

    def ensure_capacity(self, row: int, n_tokens: int) -> None:
        """Grow the row's table until it can hold ``n_tokens`` positions
        (chunk-aware allocation: called before each prefill chunk lands).
        No-op when the table already covers them.  Raises ``OutOfBlocks``
        mid-growth; already-appended blocks stay on the table (they are
        accounted to the row and used by the retried chunk)."""
        if n_tokens > self.max_request_tokens:
            raise CapacityError(
                f"{n_tokens} tokens exceed pool capacity "
                f"{self.max_request_tokens}")
        t = self.tables[row]
        if t is None:
            raise CachePoolError(f"ensure_capacity on free row {row}")
        while t.capacity < n_tokens:
            t.append_block(self._alloc_block())
            self._bt_np[row, t.n_blocks - 1] = t.blocks[-1]
            self._bt_dirty = True

    def prefix_match_length(self, tokens) -> int:
        """Side-effect-free probe: how many leading tokens of ``tokens``
        the prefix cache already covers (0 when caching is off).  See
        ``PrefixCache.match_length`` — no refcounts, no LRU touch, so
        fleet routers can probe every replica per request for free."""
        if self.prefix_cache is None:
            return 0
        return self.prefix_cache.match_length(tokens)

    # -------------------------------------------------------------- data
    def register_prefix(self, row: int, tokens) -> None:
        """Publish the row's full blocks covering ``tokens`` into the
        prefix cache.  ``tokens`` may be any fully-WRITTEN prefix of the
        row's sequence — the whole prompt after its final chunk, or the
        written history at preemption time (cursor resume: a re-admission
        then matches these blocks instead of recomputing them)."""
        if self.prefix_cache is not None:
            self.prefix_cache.insert(tokens, self.tables[row].blocks)

    def prepare_decode(self, rows: list[int],
                       n_tokens: list[int] | None = None) -> None:
        """Ensure every active row can write its next ``n_tokens[i]``
        positions (1 each when omitted — plain decode; a speculative
        verify step writes its k+1 candidate positions in one fused
        step).  Allocates blocks across each row's write range [pos,
        pos + n) (raises ``OutOfBlocks`` — the engine preempts and
        retries) and copies-on-write any shared block inside it: a
        prefix-cache or fork sharer must never see this row's fresh —
        possibly later rejected and rolled back — tokens."""
        bs = self.block_size
        ns = [1] * len(rows) if n_tokens is None else n_tokens
        for row, n in zip(rows, ns):
            pos = int(self._pos_np[row])
            n = max(n, 1)
            if pos + n > self.max_request_tokens:
                raise CapacityError(
                    f"decode write of {n} tokens at position {pos} exceeds "
                    f"pool capacity {self.max_request_tokens}")
            t = self.tables[row]
            for bi in range(pos // bs, (pos + n - 1) // bs + 1):
                if bi >= t.n_blocks:
                    t.append_block(self._alloc_block())
                    self._bt_np[row, bi] = t.blocks[bi]
                    self._bt_dirty = True
                elif self.blocks.ref[t.blocks[bi]] > 1:
                    fresh = self._cow(t.blocks[bi])
                    t.replace_block(bi, fresh)
                    self._bt_np[row, bi] = fresh
                    self._bt_dirty = True

    def decode_kv_steps(self, n_heads: int,
                        window: int | None) -> tuple[int, int]:
        """(walked, grid) KV steps of one layer's paged-attention kernel
        in the decode step about to run, from the positions mirror: every
        row attends at its position over its table (``kv_steps``, the
        kernel's own geometry)."""
        from .paged_attention import kv_steps
        _, _, bs, KV, hd = self.blocks.k.shape
        return kv_steps(self._pos_np, 1, n_heads, KV, hd, bs,
                        self.max_blocks_per_row,
                        self.blocks.k.dtype.itemsize, window)

    def fork(self, row: int) -> int:
        """Fork ``row`` copy-on-write into a fresh row: the new row's
        table shares every parent block read-only (incref only — no KV
        bytes move).  The first write either side makes inside a shared
        block goes through ``BlockPool.copy_on_write``
        (``prepare_decode``/``ensure_capacity``/``admit``), so the two
        sequences diverge block-by-block from the fork point — the
        substrate tree/forked draft speculation builds on.  Raises
        ``CapacityError`` when no free row is available (callers treat it
        like admission pressure, not a bug)."""
        t = self.tables[row]
        if t is None:
            raise CachePoolError(f"fork of free row {row}")
        if not self._free_rows:
            raise CapacityError("fork with no free row available")
        for b in t.blocks:
            self.blocks.incref(b)
        new = self._free_rows.pop()
        self.tables[new] = BlockTable(self.block_size, list(t.blocks),
                                      t.n_cached_tokens)
        self._bt_np[new, :] = self._trash
        self._bt_np[new, :t.n_blocks] = t.blocks
        self._bt_dirty = True
        self._pos_np[new] = self._pos_np[row]
        return new

    # --------------------------------------------------------- lifecycle
    def adopt(self, k, v, k_scale=None, v_scale=None) -> None:
        """Take ownership of a step's output arenas (donated in place)."""
        self.blocks.k = k
        self.blocks.v = v
        if k_scale is not None:
            self.blocks.k_scale = k_scale
            self.blocks.v_scale = v_scale

    def advance_prefill(self, rows: list[int], ends: list[int]) -> None:
        for row, end in zip(rows, ends):
            self._pos_np[row] = end

    def advance_decode(self, active_mask) -> None:
        """Positions advance on the host mirror for this step's decode
        rows only.  Rows mid-prefill keep their cursor, free rows keep a
        stale (harmless) value — the batch-wide decode write for every
        non-decoding row lands either in the trash block (free rows:
        their table IS the trash block; mid-prefill rows at an
        unallocated block boundary) or at a position the next chunk
        scatter overwrites before any query can attend to it."""
        active = np.asarray(active_mask)
        self._pos_np = np.where(active, self._pos_np + 1,
                                self._pos_np).astype(np.int32)

    def release(self, row: int) -> None:
        t = self.tables[row]
        if t is None:
            raise DoubleFree(f"release of free row {row}")
        for b in t.blocks:
            self.blocks.decref(b)        # cached blocks survive via cache ref
        self.tables[row] = None
        self._bt_np[row, :] = self._trash
        self._bt_dirty = True
        self._pos_np[row] = 0
        self._free_rows.append(row)

    def stats(self) -> dict:
        occ = self.blocks.occupancy()
        out = {"layout": "paged", "n_blocks": self.n_blocks,
               "block_size": self.block_size,
               "free_blocks": self.blocks.n_free,
               "occupancy": occ,
               "kv_dtype": self.kv_dtype,
               "arena_bytes": occ["arena_bytes"],
               "scale_bytes": occ["scale_bytes"],
               "n_preemptions": self.n_preemptions}
        if self.prefix_cache is not None:
            out["prefix_cache"] = self.prefix_cache.stats()
        return out

    def reset_stats(self) -> None:
        self.n_preemptions = 0
        if self.prefix_cache is not None:
            self.prefix_cache.reset_stats()
