"""Paged (block-table) KV cache + ragged decode attention.

Parity role: reference decode serving is a contiguous per-request KV
workspace (``inference_context.h`` KV-cache workspace management).  The
TPU-native upgrade is a *paged* cache — fixed-size pages shared across
sequences through per-sequence block tables (vLLM/ragged-paged-attention
style, cf. PAPERS.md) — which removes max-length over-allocation and lets
sequences of very different lengths batch together.

Layout:
  k_pages/v_pages: [num_pages, Hkv, page_size, D] — the physical pool
  (seq on sublanes, D on lanes — the layout Mosaic tiles natively)
  block_tables:    [B, max_pages_per_seq] int32 — page ids per sequence
  lengths:         [B] int32 — tokens currently stored per sequence

A serving dispatch holds every layer's pool stacked,
[L, num_pages, Hkv, page_size, D], and passes a layer index
(``write_paged``, ``paged_decode_attention(layer=)``).

Heads narrower than the chip's 128 lanes share a pool row
(``kv_lane_pack``): a pool whose minor dimension is under 128 is re-laid
by the TPU compiler on its way into and out of every kernel call, the
whole pool a dispatch.  So the pools of a model with ``D`` = 64 are
[L, P, Hkv / 2, page, 128], two adjacent key/value heads a row;
``write_paged`` and ``paged_decode_attention`` see it from the pool's
shape and pack the rows and spread the queries themselves (exact: a
query meets the other head's lanes with zeros), callers hand them heads
of ``D`` as ever.

A model with sliding-window layers holds TWO such stacks
(``WindowedKVCache``): the full-attention layers' pool under the growing
block tables above, and the window layers' pool under a RING a sequence,
``ring_pages(window, page_size)`` pages whatever the context: logical page
``p`` of a sequence lives in column ``p % ring`` of its ring table, so a
write past the ring's end lands on the oldest page, whose keys have left
every later query's window.  ``write_paged`` and
``paged_decode_attention`` take ``window`` and ``ring`` as static
arguments of the call; masks are by logical position.

Two compute paths behind one API: the fused ragged Pallas kernel
(``ops/pallas/ragged_paged_attention.py`` — the K/V index maps read the
block table so only each sequence's own pages are DMA'd, and one launch
serves a mixed prefill+decode batch) on TPU, and this module's jnp
gather + masked softmax as the oracle/fallback.
``resolve_attention_backend`` maps the ``serving.attention_backend``
config strings onto the pair.  Page allocation is host-side
(``PagedAllocator``) because it is control flow, not compute.
"""

import math
from collections import OrderedDict
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


class PagedKVCache(NamedTuple):
    k_pages: jnp.ndarray   # [P, Hkv, page, D]
    v_pages: jnp.ndarray


class WindowedKVCache(NamedTuple):
    """The pools of a model whose layers are of two kinds: ``full`` (the
    full-attention layers, [L_full, P, ...] under the growing tables) and
    ``ring`` (the sliding-window layers, [L_window, slots x ring + 1, ...]
    under each slot's ring table)."""
    full: PagedKVCache
    ring: PagedKVCache


LANES = 128     # the minor dimension the TPU tiles without padding


def kv_lane_pack(kv_heads: int, head_dim: int) -> int:
    """How many adjacent key/value heads share one row of the page pools:
    as many as fill the chip's 128 lanes, where the heads divide into
    that; 1 (no packing) for heads of 128 and wider, and for every model
    whose heads do not fit."""
    pack = LANES // head_dim if head_dim < LANES and LANES % head_dim == 0 \
        else 1
    return pack if kv_heads % pack == 0 else 1


def paged_pool_shape(layers, num_pages, kv_heads, page_size, head_dim):
    """The shape of one stacked pool (K or V) of ``layers`` layers."""
    pack = kv_lane_pack(kv_heads, head_dim)
    return (layers, num_pages, kv_heads // pack, page_size, head_dim * pack)


def _pack_rows(pool, rows):
    """Key or value rows [B, T, Hkv, D] as the pool holds them: ``pack``
    adjacent heads a row (a reshape)."""
    if pool.shape[-1] == rows.shape[-1]:
        return rows
    B, T = rows.shape[:2]
    return rows.reshape(B, T, pool.shape[-3], pool.shape[-1])


def _spread_queries(q, kv_heads, pack):
    """Queries [B, T, H, D] against packed rows: each in the lanes of its
    own key/value head, zeros in the others' -> [B, T, H, pack x D].  The
    ``pack x group`` query heads of a packed row stay adjacent."""
    B, T, H, D = q.shape
    group = H // kv_heads
    q = q.reshape(B, T, kv_heads // pack, pack, group, 1, D) \
        * jnp.eye(pack, dtype=q.dtype)[:, None, :, None]
    return q.reshape(B, T, H, pack * D)


def _gather_outputs(out, kv_heads, pack):
    """The inverse on the output side: each query head's own lanes."""
    B, T, H, wide = out.shape
    group = H // kv_heads
    out = out.reshape(B, T, kv_heads // pack, pack, group, pack, wide // pack)
    return jnp.einsum("btjsgsd->btjsgd", out).reshape(B, T, H, wide // pack)


def ring_pages(window: int, page_size: int) -> int:
    """Pages of a sequence's ring: the window's pages and one more, since
    a window that does not start on a page boundary touches that many."""
    return -(-window // page_size) + 1


# THE vocabulary of serving.attention_backend (docs/config-json.md), read
# by resolve_attention_backend alone
ATTENTION_BACKENDS = ("auto", "jnp", "pallas", "pallas-interpret")


def resolve_attention_backend(backend):
    """Map a ``serving.attention_backend`` string to (impl, interpret).

    ``impl`` is what ``use_pallas`` consumes (None = auto: Pallas on TPU,
    jnp elsewhere); ``interpret`` forces the Pallas kernel through the
    interpreter so CPU CI can run the exact kernel path bit-for-bit."""
    if backend is None or backend == "auto":
        return None, False
    if backend == "pallas-interpret":
        return "pallas", True
    if backend in ("jnp", "pallas"):
        return backend, False
    raise ValueError(f"unknown attention backend {backend!r}; "
                     f"expected one of {ATTENTION_BACKENDS}")


def resolve_paged_impl(impl, logit_softcap=None):
    """"pallas" or "jnp": the pair that writes and reads the page pools.

    One decision for both halves of a dispatch (``write_paged`` and
    ``paged_decode_attention``): the aliased write kernel and the ragged
    kernel on TPU or where ``impl`` says so; the jnp scatter and gather
    otherwise, and for softcapped models (the kernel takes no softcap)."""
    from deepspeed_tpu.ops.decode_attention import use_pallas
    return "pallas" if use_pallas(impl) and not logit_softcap else "jnp"


def _row_targets(block_tables, lengths, T, page_size, ring=None):
    """(page ids, in-page rows), both [B, T], of the T rows a sequence
    writes from ``lengths`` on (through a ring of ``ring`` columns: the
    logical page modulo it)."""
    pos = lengths[:, None] + jnp.arange(T)[None, :]          # [B, T]
    col = pos // page_size
    if ring:
        col = col % ring
    return (jnp.take_along_axis(block_tables, col, axis=1), pos % page_size)


def write_paged(cache: PagedKVCache, layer, block_tables, lengths, k_new,
                v_new, impl: Optional[str] = None,
                interpret: bool = False, ring=None) -> PagedKVCache:
    """Write rows [B, T, Hkv, D] from ``lengths`` on into layer ``layer``
    (may be traced) of the STACKED pools [L, P, Hkv, page, D], in place.

    What a serving dispatch calls once a layer, the pools riding its layer
    loop's carry.  ``impl`` as :func:`resolve_paged_impl` reads it: the
    aliased ``paged_kv_write`` kernel, or the jnp scatter on the stack.
    An XLA scatter next to the ragged kernel makes the compiler re-lay
    the whole pool between the two (docs/serving.md), so the write follows
    the read's backend.  ``ring`` (static): ``block_tables`` is a ring of
    that many columns and the rows wrap around it."""
    k_new, v_new = (_pack_rows(cache.k_pages, rows)
                    for rows in (k_new, v_new))
    if resolve_paged_impl(impl) == "pallas":
        from deepspeed_tpu.ops.pallas.ragged_paged_attention import \
            paged_kv_write
        return PagedKVCache(*paged_kv_write(
            cache.k_pages, cache.v_pages, layer, block_tables, lengths,
            k_new, v_new, interpret=interpret, ring=ring))
    page_idx, offset = _row_targets(block_tables, lengths, k_new.shape[1],
                                    cache.k_pages.shape[3], ring)
    k = cache.k_pages.at[layer, page_idx, :, offset].set(
        k_new.astype(cache.k_pages.dtype))
    v = cache.v_pages.at[layer, page_idx, :, offset].set(
        v_new.astype(cache.v_pages.dtype))
    return PagedKVCache(k_pages=k, v_pages=v)


def paged_read_items(q_shape, cache: PagedKVCache, block_tables, lengths,
                     impl: Optional[str] = None, window=None, ring=None):
    """What every layer's read of one dispatch shares: the ragged kernel's
    item map (the (q tile, kv step) pairs that hold keys, from ``lengths``
    — the new tokens included), or None where the jnp pair serves.  The
    layer loop's caller builds it once and hands it to each layer's
    :func:`paged_decode_attention` as ``items``.  Layers of another
    ``window`` or ``ring`` read through another map; ``cache`` and
    ``block_tables`` may be shapes (``jax.ShapeDtypeStruct``)."""
    if resolve_paged_impl(impl) != "pallas":
        return None
    from deepspeed_tpu.ops.pallas.ragged_paged_attention import rect_item_map
    # against packed rows the queries are as wide as a row
    q_shape = tuple(q_shape[:3]) + (cache.k_pages.shape[-1],)
    return rect_item_map(q_shape, cache.k_pages, block_tables, lengths,
                         window=window, ring=ring)


def paged_decode_attention(q, cache: PagedKVCache, block_tables, lengths,
                           softmax_scale: Optional[float] = None,
                           impl: Optional[str] = None,
                           interpret: bool = False,
                           logit_softcap: Optional[float] = None,
                           layer=None, items=None, window=None, ring=None):
    """q: [B, T, H, D] — the last T tokens of each sequence (T=1 decode).
    With ``layer`` (may be traced) ``cache`` holds the stacked pools
    [L, P, Hkv, page, D] and that layer is read in place; ``items`` is
    :func:`paged_read_items` of the same arguments, where the caller built
    it for all its layers (the kernel builds its own otherwise).

    ``impl`` and ``interpret`` as :func:`resolve_attention_backend` gives
    them: None (auto: Pallas kernel on TPU, jnp elsewhere), "pallas", or
    "jnp".  The Pallas path is the fused ragged kernel
    (``ops/pallas/ragged_paged_attention.py``); the jnp path gathers each
    sequence's pages into its logical view and runs masked attention over
    the valid ragged prefix — it is the oracle the kernel is tested
    against.  ``logit_softcap`` is jnp-only and forces the fallback.

    ``window`` (static): query ``i`` attends keys ``j`` with ``i - window
    < j <= i``.  ``ring`` (static, with a window): ``block_tables`` is a
    ring of that many columns, logical page ``p`` in column ``p % ring``;
    the call may bring at most ``ring x page - window + 1`` rows (a row
    written ``ring x page`` positions after another takes its place, and
    the call's first query still attends ``window - 1`` keys back)."""
    pack = cache.k_pages.shape[-1] // q.shape[-1]
    if pack > 1:    # ``pack`` key/value heads a pool row (kv_lane_pack)
        kv_heads = cache.k_pages.shape[-3] * pack
        if softmax_scale is None:
            softmax_scale = 1.0 / math.sqrt(q.shape[-1])
        out = paged_decode_attention(
            _spread_queries(q, kv_heads, pack), cache, block_tables, lengths,
            softmax_scale=softmax_scale, impl=impl, interpret=interpret,
            logit_softcap=logit_softcap, layer=layer, items=items,
            window=window, ring=ring)
        return _gather_outputs(out, kv_heads, pack)
    if ring:
        page_size = cache.k_pages.shape[-2]
        assert window and ring == block_tables.shape[1] and \
            q.shape[1] + window - 1 <= ring * page_size, (
                f"{q.shape[1]} rows through a ring of {ring} pages of "
                f"{page_size} under a window of {window}")
    if resolve_paged_impl(impl, logit_softcap) == "pallas":
        from deepspeed_tpu.ops.pallas.ragged_paged_attention import \
            ragged_paged_attention_rect
        return ragged_paged_attention_rect(q, cache.k_pages, cache.v_pages,
                                           block_tables, lengths,
                                           softmax_scale=softmax_scale,
                                           interpret=interpret, layer=layer,
                                           items=items, window=window,
                                           ring=ring)
    B, T, H, D = q.shape
    Hkv, page_size = cache.k_pages.shape[-3:-1]
    max_pages = block_tables.shape[1]
    S = max_pages * page_size

    # one gather of the sequences' pages (out of the stack where there is
    # one): [B, max_pages, Hkv, page, D] → [B, Hkv, S, D]
    pages = block_tables if layer is None else (layer, block_tables)
    k = jnp.swapaxes(cache.k_pages[pages], 1, 2).reshape(B, Hkv, S, D)
    v = jnp.swapaxes(cache.v_pages[pages], 1, 2).reshape(B, Hkv, S, D)
    if Hkv != H:
        rep = H // Hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scale = softmax_scale if softmax_scale is not None else 1.0 / math.sqrt(D)
    logits = jnp.einsum("bqhd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if logit_softcap:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)
    kpos = jnp.arange(S)[None, None, :]                       # [1, 1, S]
    qpos = (lengths[:, None] - T + jnp.arange(T)[None, :])[..., None]
    if ring:
        # what the ring holds, by logical position: column c the newest
        # page congruent to it, and past the context's last row of that
        # page still the rows of a ring earlier
        last = (lengths[:, None, None] - 1) // page_size      # [B, 1, 1]
        kpos = ((last - (last - kpos // page_size) % ring) * page_size
                + kpos % page_size)
        kpos = jnp.where(kpos < lengths[:, None, None], kpos, kpos - S)
    mask = kpos <= qpos                                       # [B, T, S]
    if ring:
        mask = mask & (kpos >= 0)
    if window:
        mask = mask & (kpos > qpos - window)
    logits = jnp.where(mask[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bqhd", probs.astype(v.dtype), v)
    return out.astype(q.dtype)   # impl-independent output dtype


class PageAllocationError(RuntimeError):
    """Typed allocator failure (pool exhausted, per-sequence cap exceeded,
    or an injected ``page_alloc`` fault): callers turn it into a structured
    rejection / retry instead of an engine-killing assert."""


class PagedAllocator:
    """Host-side page bookkeeping (the control-flow half of vLLM's block
    manager): per-sequence page lists over a fixed pool, with free-list
    reuse.

    Pages are REFCOUNTED so the prefix cache
    (``inference/prefix_cache.py``) can attach one physical page to many
    sequences' block tables: ``allocate(..., shared=pages)`` bumps the
    shared pages' refcounts instead of taking fresh ones, and a page only
    returns to circulation when its last reference drops.  Pages the cache
    has registered (``mark_cached``) don't go back to the free list on
    release — they park in an LRU "reclaimable" tier, still holding their
    KV content for future hits, and are evicted back into the free list
    (oldest first, ``evict_hook`` notified so the cache can drop its index
    entries) only when an allocation outgrows the free list.  With no
    cache layered on top every refcount is 1 and the reclaimable tier
    stays empty — the original allocator semantics.

    The allocator knows the layer kinds: with ``ring_pages`` a sequence
    also holds one RING of that many pages of the window layers' pool
    (``seq_rings``), taken with its first pages and returned with its
    last, out of ``ring_slots`` rings (pages 1 .. of that pool; page 0 is
    its scratch page).  A ring is never shared, grown or shrunk."""

    def __init__(self, num_pages: int, page_size: int,
                 max_pages_per_seq: int, reserve_scratch: bool = False,
                 injector=None, ring_pages: int = 0, ring_slots: int = 0):
        """``reserve_scratch``: keep page 0 out of the pool — serving
        engines point INACTIVE batch slots' tables at page 0 so their
        dummy-token writes land in a sacrificial page.  ``injector``: a
        ``runtime.resilience.FaultInjector`` consulted at the ``page_alloc``
        site before any page leaves the free list (so an injected fault
        never half-allocates)."""
        self.num_pages = num_pages
        self.page_size = page_size
        self.max_pages_per_seq = max_pages_per_seq
        self.scratch_reserved = bool(reserve_scratch)
        self.free: List[int] = list(range(1 if reserve_scratch else 0,
                                          num_pages))
        self.seq_pages = {}
        self.injector = injector
        self.ref = {}                       # page -> live-sequence refcount
        self.cached = set()                 # pages the prefix cache indexed
        self.reclaimable = OrderedDict()    # ref==0 cached pages, LRU order
        self.evict_hook = None              # called with each evicted page
        self.pages_taken = 0                # fresh pages handed out (stats)
        self.reclaim_evictions = 0          # reclaimable pages surrendered
        self.ring_pages = int(ring_pages)
        self.ring_pool = self.ring_pages * int(ring_slots)
        self.ring_free: List[int] = list(range(1, 1 + self.ring_pool))
        self.seq_rings = {}

    def can_allocate(self, n_pages: int) -> bool:
        return self.available_page_count >= n_pages

    @property
    def free_page_count(self) -> int:
        return len(self.free)

    @property
    def available_page_count(self) -> int:
        """Pages an allocation can actually obtain: the free list plus the
        reclaimable tier (cached pages evictable on demand)."""
        return len(self.free) + len(self.reclaimable)

    @property
    def ring_pages_in_use(self) -> int:
        return self.ring_pages * len(self.seq_rings)

    def ring_available(self) -> bool:
        """A sequence admitted now would find its ring."""
        return len(self.ring_free) >= self.ring_pages

    # -- refcount plumbing ----------------------------------------------
    def _ref_page(self, page: int):
        self.ref[page] = self.ref.get(page, 0) + 1
        self.reclaimable.pop(page, None)

    def _release_page(self, page: int):
        n = self.ref.get(page, 1) - 1
        if n > 0:
            self.ref[page] = n
            return
        self.ref.pop(page, None)
        if page in self.cached:
            # most-recently-used end; evictions pop from the other side
            self.reclaimable[page] = None
            self.reclaimable.move_to_end(page)
        else:
            self.free.append(page)

    def _take_page(self) -> int:
        """One fresh page: free list first, then evict the LRU reclaimable
        page (its cache index entries die via ``evict_hook``)."""
        if self.free:
            page = self.free.pop()
        else:
            page = self.evict_reclaimable()
            if page is None:
                raise PageAllocationError("out of KV pages: free list and "
                                          "reclaimable tier both empty")
        self.ref[page] = 1
        self.pages_taken += 1
        return page

    def evict_reclaimable(self) -> Optional[int]:
        """Evict the least-recently-used reclaimable page back toward the
        caller (None when the tier is empty).  The page leaves the cached
        set and the hook lets the prefix cache unindex it."""
        if not self.reclaimable:
            return None
        page, _ = self.reclaimable.popitem(last=False)
        self.cached.discard(page)
        self.reclaim_evictions += 1
        if self.evict_hook is not None:
            self.evict_hook(page)
        return page

    def reclaim_to_free(self) -> Optional[int]:
        """Evict the LRU reclaimable page straight onto the free list (the
        prefix cache's capacity enforcement); None when none evictable."""
        page = self.evict_reclaimable()
        if page is not None:
            self.free.append(page)
        return page

    def mark_cached(self, page: int):
        """The prefix cache indexed this page: on last release it parks in
        the reclaimable tier instead of returning to the free list."""
        self.cached.add(page)

    def unmark_cached(self, page: int):
        """Drop cache status; if the page is parked reclaimable it returns
        to the free list immediately."""
        self.cached.discard(page)
        if page in self.reclaimable:
            del self.reclaimable[page]
            self.free.append(page)

    def _check_injector(self):
        if self.injector is not None:
            try:
                self.injector.check("page_alloc")
            except Exception as e:
                raise PageAllocationError(
                    f"injected page_alloc fault: {e}") from e

    def allocate(self, seq_id, n_tokens: int, shared=(),
                 protect=()) -> List[int]:
        """Pages for ``n_tokens``, reusing ``shared`` cached pages (in
        order) as the sequence's leading pages — their refcounts bump
        instead of fresh pages being taken.  ``protect`` pages are pinned
        for the duration of the call so the reclaim-tier eviction that
        feeds fresh pages can never surrender them (the serving engine
        pins a copy-on-write source page this way).  All feasibility
        checks and the injected-fault site run BEFORE any state mutates,
        so a ``PageAllocationError`` never leaks a refcount or
        half-attaches a page."""
        shared = list(shared)
        need = -(-n_tokens // self.page_size)
        if need > self.max_pages_per_seq:
            raise PageAllocationError(
                f"{n_tokens} tokens exceed max_pages_per_seq "
                f"({self.max_pages_per_seq})")
        if len(shared) > need:
            raise PageAllocationError(
                f"{len(shared)} shared pages exceed the {need}-page "
                f"reservation for {n_tokens} tokens")
        fresh_needed = need - len(shared)
        # shared/protected pages parked in the reclaimable tier are about
        # to be pinned — they can't feed this allocation's fresh pages
        pinned = set(shared) | set(protect)
        evictable = sum(1 for p in self.reclaimable if p not in pinned)
        if fresh_needed > len(self.free) + evictable:
            raise PageAllocationError(
                f"out of KV pages: need {fresh_needed}, free "
                f"{len(self.free)} (+{evictable} reclaimable)")
        if not self.ring_available():
            raise PageAllocationError(
                f"out of ring pages: need {self.ring_pages}, free "
                f"{len(self.ring_free)}")
        self._check_injector()
        for p in protect:
            self._ref_page(p)
        try:
            for p in shared:
                self._ref_page(p)
            pages = shared + [self._take_page() for _ in range(fresh_needed)]
        finally:
            for p in protect:
                self._release_page(p)
        self.seq_pages[seq_id] = pages
        if self.ring_pages:
            self.seq_rings[seq_id] = [self.ring_free.pop()
                                      for _ in range(self.ring_pages)]
        return pages

    def extend(self, seq_id, total_tokens: int) -> List[int]:
        """Ensure ``seq_id`` has pages for ``total_tokens``; allocates new
        pages as it crosses page boundaries."""
        pages = self.seq_pages[seq_id]
        need = -(-total_tokens // self.page_size)
        if need > self.max_pages_per_seq:
            raise PageAllocationError(
                f"{total_tokens} tokens exceed max_pages_per_seq "
                f"({self.max_pages_per_seq})")
        if len(pages) < need:
            if not self.can_allocate(need - len(pages)):
                raise PageAllocationError(
                    f"out of KV pages: need {need - len(pages)} more, "
                    f"free {len(self.free)}")
            self._check_injector()
            while len(pages) < need:
                pages.append(self._take_page())
        return pages

    def shrink(self, seq_id, total_tokens: int):
        """Release pages beyond what ``total_tokens`` needs (a bucketed
        prefill over-allocates to the padded length, then trims)."""
        pages = self.seq_pages[seq_id]
        need = max(1, -(-total_tokens // self.page_size))
        while len(pages) > need:
            self._release_page(pages.pop())

    def free_sequence(self, seq_id):
        for page in self.seq_pages.pop(seq_id, []):
            self._release_page(page)
        self.ring_free.extend(self.seq_rings.pop(seq_id, []))

    def audit(self) -> dict:
        """Refcount/accounting invariants; {} when clean.  Every page is
        exactly one of: free, reclaimable (cached, ref 0), or referenced
        (ref == number of sequences holding it); totals balance against
        the pool."""
        problems = {}
        held = {}
        for pages in self.seq_pages.values():
            for p in pages:
                held[p] = held.get(p, 0) + 1
        if held != self.ref:
            dangling = {p: n for p, n in self.ref.items()
                        if held.get(p) != n}
            unrefed = {p: n for p, n in held.items()
                       if self.ref.get(p) != n}
            problems["refcounts"] = {"dangling": dangling,
                                     "unreferenced_held": unrefed}
        overlap = (set(self.free) & set(self.reclaimable)) | \
                  (set(self.free) & set(self.ref)) | \
                  (set(self.reclaimable) & set(self.ref))
        if overlap:
            problems["tier_overlap"] = sorted(overlap)
        pool = self.num_pages - (1 if self.scratch_reserved else 0)
        total = len(self.free) + len(self.reclaimable) + len(self.ref)
        if total != pool:
            problems["page_accounting"] = {
                "free": len(self.free), "reclaimable": len(self.reclaimable),
                "referenced": len(self.ref), "pool": pool}
        if not self.cached >= set(self.reclaimable):
            problems["uncached_reclaimable"] = sorted(
                set(self.reclaimable) - self.cached)
        ring_held = [p for ring in self.seq_rings.values() for p in ring]
        if set(self.seq_rings) != (set(self.seq_pages) if self.ring_pages
                                   else set()) or \
                sorted(ring_held + self.ring_free) != \
                list(range(1, 1 + self.ring_pool)) or \
                any(len(r) != self.ring_pages
                    for r in self.seq_rings.values()):
            problems["ring_accounting"] = {
                "held": len(ring_held), "free": len(self.ring_free),
                "sequences": sorted(self.seq_rings, key=str)}
        return problems

    def block_table(self, seq_ids) -> np.ndarray:
        """[B, max_pages_per_seq] table (0-padded) for the given batch."""
        out = np.zeros((len(seq_ids), self.max_pages_per_seq), np.int32)
        for b, sid in enumerate(seq_ids):
            pages = self.seq_pages[sid]
            out[b, :len(pages)] = pages
        return out
