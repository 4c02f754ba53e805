"""Paged KV cache for the continuous-batching serving engine (DESIGN §10).

The seed decode path pads every request's KV cache to the max sequence
length — a 32-token request in a 32k-slot batch pays 1000× its footprint.
Here KV storage is a **page pool**: fixed-size pages of ``page_size``
token-rows per attention layer, a per-slot **page table** mapping each
slot's logical page index to a physical page, and a host-side free-list
allocator.  Heterogeneous sequence lengths then cost what they use
(rounded up to one page), and admission/eviction is O(pages) pointer
surgery — no cache reshapes, no recompilation.

Layout contract (mirrors the packed-bus alignment idioms of DESIGN §5,
via :func:`repro.kernels.ops.padded_size`):

* pools are head-major: a page holds ``page_size`` token-rows of one kv
  head in a ``(page_size, hd)`` tile, so the paged kernels fetch one
  head of one page as a Mosaic-tileable block; ``page_size`` is a
  multiple of the 8-row sublane;
* physical page 0 is the **null page**: the allocator never hands it out,
  free slots' page-table rows are all-zero, and idle slots' decode writes
  land there — so a write by a dead slot can never corrupt a live one,
  and the masked-tail property "never read an unallocated page" is
  testable by poisoning every unallocated page with NaN;
* ring mode (``window > 0``): a slot owns exactly ``window / page_size``
  pages and token position p lives at ring row ``p % window`` — the same
  ring layout the dense decode path and prefill's rolled cache use, so
  prefill caches scatter into pages without re-indexing.

The pools themselves are device arrays shaped like the model's stacked
cache tree — ``(n_blocks, K, num_pages, page_size, hd)`` per period
position — and flow through the jitted ``serve_step`` unchanged; only the
allocator below is host-side Python.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, block_period, layer_kinds

__all__ = ["PagedCacheConfig", "PageAllocator", "init_paged_pools",
           "paged_pool_shapes", "paged_pool_specs", "NULL_PAGE"]

NULL_PAGE = 0          # reserved physical page: write sink for idle slots
_SUBLANE = 8           # token-rows per page must tile the 8-row sublane


@dataclasses.dataclass(frozen=True)
class PagedCacheConfig:
    """Static geometry of the paged cache.

    ``max_context`` is the per-slot context ceiling (prompt + generated);
    ring mode caps it at ``window``.  ``num_pages`` counts *physical*
    pages including the reserved null page.
    """

    page_size: int
    num_pages: int
    max_slots: int
    max_context: int
    window: int = 0                 # 0 = linear; else ring of `window` rows

    def __post_init__(self):
        assert self.page_size > 0 and self.page_size % _SUBLANE == 0, \
            f"page_size must be a positive multiple of {_SUBLANE} rows " \
            f"(8×128-tileable pages), got {self.page_size}"
        if self.window:
            assert self.window % self.page_size == 0, \
                "ring mode needs window % page_size == 0 so a slot owns " \
                f"whole pages, got window={self.window} " \
                f"page_size={self.page_size}"
        assert self.num_pages > 1 + self.pages_per_slot, \
            ("page pool too small for even one slot "
             f"(num_pages={self.num_pages}, need "
             f"{1 + self.pages_per_slot}+)")

    @property
    def slot_context(self) -> int:
        """Rows of KV a slot can hold: the ring size in window mode, the
        context ceiling otherwise."""
        return self.window if self.window else self.max_context

    @property
    def pages_per_slot(self) -> int:
        """Width of one page-table row (logical pages per slot)."""
        return -(-self.slot_context // self.page_size)


def paged_pool_shapes(cfg: ModelConfig, pcfg: PagedCacheConfig):
    """ShapeDtypeStructs of the paged pool tree: one entry per period
    position, mirroring :func:`~repro.models.transformer.init_lm_cache`'s
    stacked block structure — pools scan over ``n_blocks`` exactly like
    dense caches do.  Attention-mixer positions get k/v page pools; the
    continuous engine is attention-family-only (SSM state is O(1)/slot
    and needs slot state, not pages — gated in the scheduler)."""
    period = block_period(cfg)
    kinds = layer_kinds(cfg)[:period]
    n_blocks = cfg.n_layers // period
    dt = jnp.dtype(cfg.dtype)
    shapes = []
    for mixer, _ in kinds:
        assert mixer == "attn", \
            "paged pools cover attention mixers only (SSM/hybrid decode " \
            "keeps O(1) per-slot state — see DESIGN §10 scope note)"
        leaf = jax.ShapeDtypeStruct(
            (n_blocks, cfg.n_kv_heads, pcfg.num_pages, pcfg.page_size,
             cfg.hd), dt)
        shapes.append({"k": leaf, "v": leaf})
    return tuple(shapes)


def init_paged_pools(cfg: ModelConfig, pcfg: PagedCacheConfig):
    """Zero-filled page pools (device arrays)."""
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        paged_pool_shapes(cfg, pcfg))


def paged_pool_specs(cfg: ModelConfig):
    """TP PartitionSpecs for the pools: kv heads over 'model', pages
    replicated-free of any collective — the paged kernel's page gather is
    slot-local, so the decode step is **ppermute-free** and composes with
    ``serve_param_specs`` (the head axis is the same 'model' axis the
    dense ``lm_cache_specs`` shard)."""
    from jax.sharding import PartitionSpec as P
    period = block_period(cfg)
    spec = {"k": P(None, "model", None, None, None),
            "v": P(None, "model", None, None, None)}
    return tuple(spec for _ in range(period))


class PageAllocator:
    """Host-side page-table bookkeeping: free-list page allocation and
    slot admit/release.  Pure numpy — the scheduler calls this between
    jitted decode steps and ships ``page_table``/``lengths`` to device
    once per step (two small int32 arrays, not the pools).

    Invariants (asserted):

    * physical page ``NULL_PAGE`` is never allocated;
    * a live slot's pages are disjoint from every other live slot's;
    * free slots' page-table rows are all-``NULL_PAGE`` and their length
      is 0 (their decode writes sink into the null page);
    * chunked-prefill slots (DESIGN §11): ``prefill_cursor`` counts prompt
      rows already written, ``lengths == prefill_cursor`` while
      ``prefilling`` and ``prefill_cursor <= prompt_len`` always — all
      pages are reserved at admission, so a mid-prefill slot can never
      OOM and its pages never move.
    """

    def __init__(self, pcfg: PagedCacheConfig):
        self.cfg = pcfg
        self.free_pages: List[int] = list(range(pcfg.num_pages - 1, 0, -1))
        self.free_slots: List[int] = list(range(pcfg.max_slots - 1, -1, -1))
        self.page_table = np.zeros((pcfg.max_slots, pcfg.pages_per_slot),
                                   np.int32)
        self.lengths = np.zeros((pcfg.max_slots,), np.int32)
        self.active = np.zeros((pcfg.max_slots,), bool)
        # chunked-prefill slot state (DESIGN §11)
        self.prompt_len = np.zeros((pcfg.max_slots,), np.int32)
        self.prefill_cursor = np.zeros((pcfg.max_slots,), np.int32)
        self.prefilling = np.zeros((pcfg.max_slots,), bool)

    # -- capacity queries ---------------------------------------------------

    def pages_needed(self, context_len: int) -> int:
        """Pages a slot with ``context_len`` total rows needs — the whole
        ring in window mode (the slot cycles through all of them)."""
        ctx = min(context_len, self.cfg.slot_context)
        if self.cfg.window:
            return self.cfg.pages_per_slot
        return -(-ctx // self.cfg.page_size)

    def can_admit(self, context_len: int) -> bool:
        return (bool(self.free_slots)
                and self.pages_needed(context_len) <= len(self.free_pages))

    @property
    def n_active(self) -> int:
        return int(self.active.sum())

    @property
    def pages_in_use(self) -> int:
        return (self.cfg.num_pages - 1) - len(self.free_pages)

    # -- admit / advance / release -----------------------------------------

    def admit(self, context_len: int, prompt_len: int, *,
              chunked: bool = False) -> int:
        """Reserve a slot + pages for a request whose total context will
        reach ``context_len`` rows (prompt + worst-case generation, capped
        by the ring in window mode).  All pages are reserved up front —
        no mid-decode allocation, so an admitted request can never OOM
        (and a ``chunked`` admission can never OOM *mid-prefill*).
        Returns the slot id.

        ``chunked=True`` admits for chunked prefill (DESIGN §11): the slot
        starts with ZERO written rows (``lengths = 0``) and a prefill
        cursor that :meth:`advance_prefill` walks to ``prompt_len`` one
        chunk at a time; ``chunked=False`` is the per-request-prefill
        path, where all ``prompt_len`` rows are scattered on admission."""
        assert context_len >= prompt_len > 0, (context_len, prompt_len)
        assert self.cfg.window or context_len <= self.cfg.max_context, \
            (context_len, self.cfg.max_context)
        assert self.can_admit(context_len), \
            f"admit() without can_admit(): {len(self.free_slots)} slots, " \
            f"{len(self.free_pages)} pages free"
        slot = self.free_slots.pop()
        n = self.pages_needed(context_len)
        pages = [self.free_pages.pop() for _ in range(n)]
        row = np.full((self.cfg.pages_per_slot,), NULL_PAGE, np.int32)
        row[:n] = pages
        self.page_table[slot] = row
        self.prompt_len[slot] = prompt_len
        self.prefill_cursor[slot] = 0 if chunked else prompt_len
        self.prefilling[slot] = chunked
        self.lengths[slot] = 0 if chunked else prompt_len
        self.active[slot] = True
        return slot

    def advance(self, slot: int, n: int = 1) -> None:
        """Account ``n`` decoded rows on ``slot`` (the device write already
        happened inside ``serve_step``; this keeps the host mirror and the
        next step's write position in sync).  ``lengths`` tracks the TRUE
        absolute length even in ring mode — the ring write row is
        ``length % window`` and RoPE needs the absolute position; the
        number of *valid* KV rows is ``min(length, window)``."""
        assert self.active[slot], slot
        assert not self.prefilling[slot], \
            f"decode advance on mid-prefill slot {slot}"
        self.lengths[slot] = int(self.lengths[slot]) + n
        assert self.cfg.window or self.lengths[slot] <= self.cfg.max_context, \
            (slot, int(self.lengths[slot]), self.cfg.max_context)

    def advance_prefill(self, slot: int, n: int) -> None:
        """Account ``n`` prompt rows written by a prefill chunk
        (DESIGN §11).  Keeps ``lengths == prefill_cursor`` so the decode
        dispatch's write position and RoPE base stay consistent with the
        pages actually filled; the slot leaves ``prefilling`` exactly when
        the cursor reaches the TRUE prompt length."""
        assert self.active[slot] and self.prefilling[slot], slot
        assert n >= 1, n
        cur = int(self.prefill_cursor[slot]) + n
        assert cur <= self.prompt_len[slot], \
            (slot, cur, int(self.prompt_len[slot]))
        self.prefill_cursor[slot] = cur
        self.lengths[slot] = cur
        if cur == self.prompt_len[slot]:
            self.prefilling[slot] = False

    def release(self, slot: int) -> None:
        """Evict: return the slot's pages to the free list and zero its
        page-table row (writes from the now-idle slot sink to the null
        page)."""
        assert self.active[slot], f"release of inactive slot {slot}"
        for p in self.page_table[slot]:
            if p != NULL_PAGE:
                self.free_pages.append(int(p))
        self.page_table[slot] = NULL_PAGE
        self.lengths[slot] = 0
        self.prompt_len[slot] = 0
        self.prefill_cursor[slot] = 0
        self.prefilling[slot] = False
        self.active[slot] = False
        self.free_slots.append(slot)

    # -- device views -------------------------------------------------------

    def device_tables(self) -> Tuple[jax.Array, jax.Array]:
        """(page_table, lengths) as device arrays for this decode step."""
        return jnp.asarray(self.page_table), jnp.asarray(self.lengths)

    def decode_tables(self) -> Tuple[jax.Array, jax.Array]:
        """(page_table, lengths) for the DECODE half of a mixed dispatch
        (DESIGN §11): mid-prefill slots' page-table rows are masked to the
        null page, so their (junk) decode write sinks harmlessly instead
        of corrupting a page the next prefill chunk will read — in ring
        mode the decode write row ``length % window`` aliases a LIVE ring
        row once the ring is full, so the mask is load-bearing, not just
        hygiene."""
        pt = self.page_table.copy()
        pt[self.prefilling] = NULL_PAGE
        return jnp.asarray(pt), jnp.asarray(self.lengths)
