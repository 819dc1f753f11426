"""KV cache pools: the slot layout, the protocol both layouts satisfy, and
the slot ``PoolView`` the unified attention primitive consumes.

``SlotKVPool`` is the original contiguous layout: one allocation at engine
start of k/v buffers [L, n_slots, max_len, KV, hd] plus a per-slot
filled-position vector [n_slots].  Requests are assigned a slot for their
lifetime.  All KV writes happen INSIDE the jitted step functions: the pool
hands the engine a ``SlotPoolView`` (arena + lane->slot rows + cursors)
and ``models/transformer.unified_step`` scatters each chunk/decode token's
fresh KV at the cursor and attends in place against the arena with the
cursor as a length mask — no gathered prefix copies, so per-step HBM
traffic is independent of how much prefix a request has already written.
Buffer shapes never change, so each step shape compiles exactly once — at
the cost of reserving ``max_len`` tokens of HBM per slot whether a request
uses them or not.  ``serving/paged/`` removes that reservation.

Freed slots are immediately reusable and rows mid-prefill may share a fused
decode step with decoding rows: every KV position a request's attention can
see ([0, pos)) is freshly written by its own prefill chunk or decode before
it becomes visible, and any position >= pos is overwritten (by the next
chunk's scatter, or by decode's write-before-attend) before any query reads
it — so neither zeroing on release nor masking the batch-wide decode write
is needed.

Invariant violations raise ``CachePoolError`` subclasses — real
exceptions, not ``assert``, so the checks survive ``python -O``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np


class CachePoolError(RuntimeError):
    """Cache-pool invariant violation (these indicate engine bugs, not
    workload conditions — workload pressure raises QueueFull/OutOfBlocks)."""


class DoubleFree(CachePoolError):
    """A slot/row/block was released twice."""


class CapacityError(CachePoolError):
    """A write or admission exceeded what the pool can physically hold."""


KV_DTYPES = ("bf16", "int8")


def quantize_kv(fresh):
    """Symmetric per-position per-KV-head int8 quantization of fresh KV
    [..., KV, hd]: absmax over the head dim -> int8 values + f32 scales
    [..., KV].  Scale granularity matches the scatter granularity — each
    written position carries its own scale, so incremental chunk/decode
    writes, copy-on-write and prefix sharing never have to re-quantize
    neighbours.  All-zero positions (padding, fresh arenas) get scale 1.0
    so dequantization is always well-defined."""
    f = fresh.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(f), axis=-1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(f / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def arena_nbytes(*arrays) -> int:
    """Total device bytes of the given arenas (None entries skipped)."""
    return sum(a.size * a.dtype.itemsize for a in arrays if a is not None)


def write_arenas(view, index: tuple, fresh_k, fresh_v):
    """``view`` with fresh [B, S, KV, hd] KV set at ``index`` of its
    whole arenas: a tuple of a layer and [B*S] indices into the arenas'
    next axes, one entry per (lane, token).  An int8 arena quantizes on
    scatter, its values and scales routed by the same indices — which is
    what keeps scales glued to their positions through every write path.
    Indices past an axis drop their write."""
    B, S = fresh_k.shape[:2]

    def scat(arena, vals):
        return arena.at[index].set(
            vals.reshape(B * S, *vals.shape[2:]).astype(arena.dtype),
            mode="drop")
    if view.k_scale is None:
        return dataclasses.replace(view, k=scat(view.k, fresh_k),
                                   v=scat(view.v, fresh_v))
    qk, sk = quantize_kv(fresh_k)
    qv, sv = quantize_kv(fresh_v)
    return dataclasses.replace(view, k=scat(view.k, qk), v=scat(view.v, qv),
                               k_scale=scat(view.k_scale, sk),
                               v_scale=scat(view.v_scale, sv))


@runtime_checkable
class KVCachePool(Protocol):
    """What the engine requires of a KV layout.

    Attributes: ``k``/``v`` device arenas consumed (donated) by the jitted
    step functions, ``pos`` per-lane filled positions, ``n_slots``
    decode-batch width, ``n_free`` free concurrency units,
    ``max_request_tokens`` the longest admissible request.  The step
    lifecycle is: the engine builds a pool view (``chunk_view`` /
    ``decode_view``) whose arenas ride through ``transformer.unified_step``
    donated-in-place, then ``adopt``s the returned arenas and advances
    positions (``advance_prefill`` after a chunk, ``advance_decode`` after
    a fused decode).  Layout-specific admission paths stay on the concrete
    classes; the engine dispatches on ``kv_layout`` for those.
    """
    n_slots: int

    @property
    def n_free(self) -> int: ...

    @property
    def max_request_tokens(self) -> int: ...

    def release(self, slot: int) -> None: ...

    def adopt(self, k, v) -> None: ...

    def advance_prefill(self, rows: list[int], ends: list[int]) -> None: ...

    def advance_decode(self, active_mask) -> None: ...


@dataclasses.dataclass(frozen=True)
class SlotPoolView:
    """What ``transformer.attend_over_pool`` sees of a slot-layout pool:
    the arena itself plus lane addressing — NOT a gathered copy of
    context.  Constructed inside the engine's traced step functions, so
    every field is a tracer at use time.

    ``k``/``v`` are the whole [L, n_slots, max_len, KV, hd] arenas; the
    per-layer loop carries them and each layer writes and reads its own
    index (``write_layer``, ``lane_kv``), so no layer is sliced out.
    ``rows`` [B] maps each batch lane to its arena slot (values >=
    n_slots are padding lanes whose writes drop); ``rows=None`` means the
    batch IS the arena, lane i == slot i (the fused decode).  ``cursor``
    [B] counts tokens already written per lane; ``n_new`` [B] is how many
    of this step's S token positions are real for the lane (the rest are
    bucket padding: their writes are dropped and their queries' outputs
    discarded by the engine).

    ``k_scale``/``v_scale`` ([L, n_slots, max_len, KV] f32, or None for
    bf16 arenas) are the per-position dequantization scales of an int8
    arena; they ride the view through the jitted step exactly like the
    arenas (donated in, scattered in place, adopted out) and share the
    arenas' write indices.
    """
    k: Any
    v: Any
    rows: Any | None
    cursor: Any
    n_new: Any
    k_scale: Any | None = None
    v_scale: Any | None = None

    @property
    def block_tables(self):
        return None                       # duck-type marker: slot layout

    def lane_kv(self, k, v, layer):
        """Per-lane [B, max_len, ...] rows of layer ``layer`` of the
        arenas ``k``/``v`` (values or scales), for attention.  With
        ``rows=None`` the arena's slot dim is used directly; otherwise a
        B-row gather whose cost is independent of how much prefix the
        rows have written."""
        if self.rows is None:
            return k[layer], v[layer]
        return k[layer, self.rows], v[layer, self.rows]

    def write_layer(self, layer, fresh_k, fresh_v):
        """Scatter fresh [B, S, KV, hd] KV into layer ``layer`` of the
        arenas at each lane's cursor, in place under donation; returns
        the view holding the updated arenas.  Real (lane, i<n_new) pairs
        land at slot ``rows[b]``, position ``cursor[b] + i``; padding
        maps past the arena's slots and is dropped, so the compiled
        scatter depends only on (B, S)."""
        ns, ml = self.k.shape[1], self.k.shape[2]
        S = fresh_k.shape[1]
        rows = jnp.arange(ns) if self.rows is None else self.rows
        p = self.cursor[:, None] + jnp.arange(S)[None]        # [B,S]
        valid = (jnp.arange(S)[None] < self.n_new[:, None]) & (p < ml)
        r = jnp.where(valid, rows[:, None], ns)
        return write_arenas(self, (layer, r.reshape(-1), p.reshape(-1)),
                            fresh_k, fresh_v)


class SlotKVPool:
    def __init__(self, cfg, n_slots: int, max_len: int, placement=None,
                 kv_dtype: str = "bf16"):
        from .placement import ServingPlacement
        pl = placement or ServingPlacement()
        if kv_dtype not in KV_DTYPES:
            raise ValueError(f"kv_dtype must be one of {KV_DTYPES}, "
                             f"not {kv_dtype!r}")
        L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
        shape = (L, n_slots, max_len, KV, hd)
        arena_dtype = jnp.int8 if kv_dtype == "int8" else cfg.dtype
        # arenas are committed to the placement's KV-head-sharded layout at
        # birth; the jitted steps then update them shard-local in place
        self.k = pl.place_kv(jnp.zeros(shape, arena_dtype))
        self.v = pl.place_kv(jnp.zeros(shape, arena_dtype))
        if kv_dtype == "int8":
            sshape = (L, n_slots, max_len, KV)
            self.k_scale = pl.place_kv_scale(jnp.ones(sshape, jnp.float32))
            self.v_scale = pl.place_kv_scale(jnp.ones(sshape, jnp.float32))
        else:
            self.k_scale = self.v_scale = None
        self.kv_dtype = kv_dtype
        self.pos = pl.place_replicated(jnp.zeros((n_slots,), jnp.int32))
        self.n_slots = n_slots
        self.max_len = max_len
        self._free = list(range(n_slots - 1, -1, -1))   # pop() -> ascending

    # ---------------------------------------------------------------- slots
    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def max_request_tokens(self) -> int:
        return self.max_len

    def alloc(self) -> int | None:
        return self._free.pop() if self._free else None

    def release(self, slot: int) -> None:
        if slot in self._free:
            raise DoubleFree(f"release of free slot {slot}")
        self._free.append(slot)

    # kept for existing callers; same semantics as release
    free = release

    def stats(self) -> dict:
        """Occupancy snapshot, shape-compatible with PagedKVPool.stats()
        so benchmarks and the tracer's gauges read one surface.
        ``arena_bytes`` is the full HBM bill — int8 values AND their f32
        scales — so equal-budget comparisons are honest."""
        scale_bytes = arena_nbytes(self.k_scale, self.v_scale)
        return {"layout": "slot", "n_slots": self.n_slots,
                "n_free": self.n_free, "max_len": self.max_len,
                "kv_dtype": self.kv_dtype,
                "arena_bytes": arena_nbytes(self.k, self.v) + scale_bytes,
                "scale_bytes": scale_bytes}

    # ---------------------------------------------------------------- views
    def lane_rows(self, rows: list[int], n_rows_padded: int) -> np.ndarray:
        """Host lane->slot map for a chunk group; padding lanes point past
        the arena (their writes drop, their gathers clamp harmlessly)."""
        out = np.full((n_rows_padded,), self.n_slots, np.int32)
        out[:len(rows)] = rows
        return out

    def chunk_end_check(self, cursor: int, lengths: list[int]) -> None:
        if cursor + max(lengths) > self.max_len:
            raise CapacityError(
                f"prefill of {max(lengths)} tokens at offset {cursor} "
                f"exceeds slot capacity {self.max_len}")

    # ------------------------------------------------------------ lifecycle
    def adopt(self, k, v, k_scale=None, v_scale=None) -> None:
        """Take ownership of a step's output arenas (the jitted step
        donated the previous ones, so this is an in-place handoff).  An
        int8 pool's scale arenas ride the same handoff."""
        self.k = k
        self.v = v
        if k_scale is not None:
            self.k_scale = k_scale
            self.v_scale = v_scale

    def advance_prefill(self, rows: list[int], ends: list[int]) -> None:
        self.pos = self.pos.at[jnp.asarray(rows)].set(
            jnp.asarray(ends, jnp.int32))

    def advance_decode(self, active_mask) -> None:
        """Only rows in ``active_mask`` (this step's decode batch, minus
        retirements) advance their position; everyone else — free slots
        and rows mid-prefill — keeps its previous position, so a prefill
        cursor survives sharing the fused step with decoders.  (The
        batch-wide decode write did land a garbage token at each inactive
        row's position, but the next chunk scatter / next occupant's
        prefill overwrites it before any query can attend there — see the
        module docstring.)"""
        self.pos = jnp.where(jnp.asarray(active_mask), self.pos + 1,
                             self.pos)
