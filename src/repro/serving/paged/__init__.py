"""Paged KV-cache subsystem: block-granular KV memory for the serving
engine (``ServingEngine(..., kv_layout="paged")``).

  block_pool.py      ref-counted allocator over one [L, n_blocks,
                     block_size, KV, hd] arena, with copy-on-write
  block_table.py     per-request logical->physical page maps
  prefix_cache.py    hash-chained full-block prefix sharing (LRU evict)
  paged_attention.py in-place attention over block tables (decode AND
                     prefill chunks): jnp reference + Pallas
                     scalar-prefetch kernel over query tiles
                     (interpret off-TPU)
  pool.py            PagedKVPool — the cache-pool-protocol facade — and
                     PagedPoolView, what attend_over_pool sees of it
"""

from .block_pool import BlockPool, BlockPoolError, OutOfBlocks
from .block_table import BlockTable, blocks_needed
from .paged_attention import (paged_attention, paged_attention_pallas,
                              paged_attention_ref)
from .pool import PagedKVPool, PagedPoolView
from .prefix_cache import PrefixCache
