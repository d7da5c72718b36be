"""Paged KV-cache subsystem: page pool + block tables behind ``CacheBackend``.

The paper's decode workload streams the KV cache at OI~=1; every wasted byte
moves the roofline bound itself.  A dense per-slot cache of capacity S wastes
``(S - len) / S`` of its traffic-eligible bytes on padding.  This module
stores KV in fixed-size *pages* (a shared pool per layer) with per-slot
*block tables* mapping logical block -> physical page — the software analog
of TROOP mechanisms (D)/(E): pages are hardware-aligned layout granules
(``core.troop.sublane``), physically disjoint by construction, so the
decoupled streams of the paged decode kernel read conflict-free contiguous
regions regardless of how slots come and go.

Two backends implement one protocol:

  * ``DenseBackend``  — the original layout: per-slot dense caches,
    admission splices prefill rows with pad + dynamic_update_slice.
  * ``PagedBackend``  — page pool + host-side ``BlockAllocator``; admission
    scatters prefill KV into freshly allocated pages and frees them when the
    request finishes (no splicing, no padding traffic).

Pages are *refcounted*: the chunked-prefill engine shares common prompt
prefixes (system prompts, few-shot headers) across slots through a radix
``PrefixIndex`` over page-granular token runs — a matched prefix maps to
existing physical pages (incref, zero recompute, zero extra HBM), and a
prompt diverging *mid-page* copies the divergence page once (copy-on-write)
before overwriting its tail.  Shared pages are read-only by invariant: the
engine only ever writes rows at positions >= its prefill offset, which by
construction land in freshly allocated (or COW-copied) pages.

The engine (``serve.scheduler``) talks only to the protocol; the model
(``models.attention``) recognizes ``PagedKVCache`` leaves and routes decode
reads/writes through the block table it receives in the step batch.

Pools are lane-dense, ``(.., P, page, KV*hd)``: a page is whole (8|16, 128)
tiles at head sizes 64 and 128, so the compiled step keeps a donated pool
in the layout it arrives in.  A stacked layer group's pools ride whole in
the layer scan's carry and each layer scatters into, and gathers from, its
own index of the stack — a step moves O(tokens) pool bytes, with no
per-layer slice, write-back or whole-pool copy (``models.transformer.
_run_groups``).  Helpers here tell a stacked pool from a per-layer one by
``PagedKVCache.stacked``.

Kept import-light on purpose: no top-level ``repro.models`` import (models
import this module for the ``PagedKVCache`` leaf type).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, NamedTuple, Optional, Protocol

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.troop import sublane

NULL_PAGE = 0          # page 0 is never allocated: idle slots point here


class PagedKVCache(NamedTuple):
    """Paged KV leaf: page pools, indexed by a per-slot block table.

    ``k_pool``/``v_pool``: (P, page, KV*hd) — or (L, P, page, KV*hd) when
    the layer group is stacked for ``lax.scan``.  A page row holds a
    token's heads side by side (lane-dense): a page is a whole number of
    (8|16, 128) tiles at head sizes 64 and 128, so XLA keeps the pool in
    the layout the caller hands in and a one-row write updates it in place.
    Rows are viewed as (KV, hd) only on the small new rows and on the
    gathered view.  The block table is *not* part of the leaf: it is
    per-step input (``batch["block_tables"]``), while the pools are
    per-step state — one table addresses every layer's pool.

    The model step carries a stacked leaf whole through its layer scan and
    each layer scatters its rows into, and gathers its pages from, its
    ``layer`` index of the stack: no layer's pool is ever sliced out,
    copied, or written back (``models.transformer._run_groups``).

    ``kv_dtype="int8"`` pools carry *scale pages* alongside: per-(token,
    head) absmax scales, (P, page, KV), addressed by the SAME block table
    — the allocator/free list never knows they exist.
    """
    k_pool: jax.Array
    v_pool: jax.Array
    k_scale_pool: Optional[jax.Array] = None   # (.., P, page, KV) if int8
    v_scale_pool: Optional[jax.Array] = None

    @property
    def quantized(self) -> bool:
        return self.k_scale_pool is not None

    @property
    def stacked(self) -> bool:
        """Whether the pools carry a leading layer axis (a scanned group)."""
        return self.k_pool.ndim == 4

    @property
    def page_size(self) -> int:
        return self.k_pool.shape[-2]

    @property
    def num_pages(self) -> int:
        return self.k_pool.shape[-3]


class ChunkStage(NamedTuple):
    """bf16 staging rows for the one in-flight chunked-prefill slot.

    Only allocated when the page pools are quantized: chunk c of a prompt
    attends over the KV of chunks < c, and reading those rows back through
    int8 pages would make chunked prefill numerically diverge from the
    bucketed engine (which runs the whole prompt in bf16 and quantizes only
    at storage).  The stage keeps the *current request's own* prefill rows
    at full precision — `(1, S, KV, hd)`, one slot's worth — while the int8
    pages written alongside stay the decode-time source of truth.  bf16
    pools skip the stage entirely (pages already hold exact bf16 rows).
    """
    k: jax.Array       # (1, S, KV, hd) bf16
    v: jax.Array


@dataclass(frozen=True)
class PageSpec:
    """Static paging geometry for one engine."""
    page_size: int            # tokens per page (a troop layout granule)
    num_pages: int            # physical pages per layer pool (incl. null)
    blocks_per_slot: int      # logical blocks per slot (= ceil(S / page))
    kv_dtype: str = "bfloat16"  # page-pool storage ("int8" adds scale pages)

    def validate(self):
        g = sublane(self.kv_dtype)
        assert self.page_size % g == 0, \
            f"page_size {self.page_size} not a multiple of the " \
            f"{g}-row layout granule for {self.kv_dtype} (mechanism D)"
        assert self.num_pages > NULL_PAGE + 1
        return self

    @staticmethod
    def for_engine(slots: int, cache_len: int, page_size: int,
                   num_pages: Optional[int] = None,
                   dtype="bfloat16") -> "PageSpec":
        blocks = -(-cache_len // page_size)
        pages = num_pages if num_pages is not None else slots * blocks + 1
        return PageSpec(page_size, pages, blocks,
                        jnp.dtype(dtype).name).validate()


class BlockAllocator:
    """Host-side refcounted free list over physical pages [1, num_pages).

    ``alloc`` hands out pages at refcount 1; ``incref`` adds holders
    (another slot sharing the page, or the prefix index keeping it warm);
    ``free`` decrefs and returns a page to the free list only when its last
    holder lets go.  The original alloc/free discipline (every page held by
    exactly one slot) is the refcount-1 special case.

    With a ``tracer``, emits ``page_alloc`` / ``page_free`` instants on the
    allocator track — ``page_free`` counts pages *actually returned* to the
    free list (a decref of a shared page is not a free), so at any moment
    ``sum(page_alloc.pages) - sum(page_free.pages) == pages in use``.
    """

    def __init__(self, num_pages: int, tracer=None):
        self.num_pages = num_pages
        self.tracer = tracer
        self._free: List[int] = list(range(num_pages - 1, NULL_PAGE, -1))
        self._refs: Dict[int, int] = {}

    @property
    def num_free(self) -> int:
        return len(self._free)

    def ref(self, page: int) -> int:
        return self._refs.get(page, 0)

    def alloc(self, n: int) -> Optional[List[int]]:
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        if self.tracer is not None and n:
            self.tracer.instant("page_alloc", "allocator", pages=n)
            self.tracer.counter("pages_in_use",
                                self.num_pages - 1 - len(self._free))
        return pages

    def incref(self, pages: List[int]):
        for p in pages:
            assert self._refs.get(p, 0) > 0, f"incref of unheld page {p}"
            self._refs[p] += 1

    def free(self, pages: List[int]):
        returned = 0
        for p in pages:
            assert p != NULL_PAGE
            n = self._refs.get(p, 0)
            assert n > 0, f"double free of page {p}"
            if n == 1:
                del self._refs[p]
                self._free.append(p)
                returned += 1
            else:
                self._refs[p] = n - 1
        if self.tracer is not None and returned:
            self.tracer.instant("page_free", "allocator", pages=returned)
            self.tracer.counter("pages_in_use",
                                self.num_pages - 1 - len(self._free))


class _PrefixNode:
    """One cached page: ``tokens`` (page_size-tuple) -> physical ``page``."""

    __slots__ = ("tokens", "page", "children", "last_used")

    def __init__(self, tokens, page):
        self.tokens = tokens
        self.page = page
        self.children: Dict[tuple, "_PrefixNode"] = {}
        self.last_used = 0


class PrefixIndex:
    """Radix tree over page-granular token prefixes -> physical pages.

    Each node is one full page of prompt tokens; a path from the root spells
    a prompt prefix and yields the refcounted pages holding its KV.  The
    index itself holds one reference on every cached page (taken at
    ``insert``, dropped at eviction), so pages survive their originating
    request and are evicted LRU-leaf-first only under pool pressure.

    ``match`` returns the longest run of fully matched pages plus, when the
    next page agrees on a strict prefix of its tokens, a *partial* match
    ``(page, depth)`` — the copy-on-write divergence page.
    """

    def __init__(self, page_size: int, allocator: BlockAllocator):
        self.page_size = page_size
        self.allocator = allocator
        self.root = _PrefixNode((), NULL_PAGE)
        self._clock = 0
        self._nodes = 0
        self.hits = 0
        self.lookups = 0
        self.evictions = 0

    @property
    def num_pages(self) -> int:
        return self._nodes

    def _pages(self, prompt) -> List[tuple]:
        ps = self.page_size
        toks = [int(t) for t in prompt]
        return [tuple(toks[i:i + ps]) for i in range(0, len(toks) - ps + 1,
                                                     ps)]

    def match(self, prompt):
        """Longest cached prefix of ``prompt``: (pages, partial).

        ``pages``: physical pages of fully matched leading pages (NOT yet
        incref'd — the caller takes its references).  ``partial``: `(page,
        depth)` when the first unmatched page shares its leading ``depth``
        tokens with a cached page (0 < depth < page_size) — the COW
        candidate — else ``None``.  The match is capped so at least the
        prompt's final token is always left to compute (prefill must
        produce next-token logits).
        """
        self.lookups += 1
        self._clock += 1
        ps = self.page_size
        limit = len(prompt) - 1            # tokens allowed to come from cache
        node, pages, depth = self.root, [], 0
        for key in self._pages(prompt):
            if depth + ps > limit:
                break
            child = node.children.get(key)
            if child is None:
                break
            child.last_used = self._clock
            pages.append(child.page)
            node, depth = child, depth + ps
        partial = None
        rest = [int(t) for t in prompt[depth:limit]]
        if rest:
            best = 0
            for key, child in node.children.items():
                j = 0
                while j < len(rest) and j < ps and key[j] == rest[j]:
                    j += 1
                if j > best:
                    best, partial = j, (child.page, j)
                    child.last_used = self._clock
        if pages or partial:
            self.hits += 1
        return pages, partial

    def insert(self, prompt, pages: List[int]):
        """Register ``prompt``'s leading full pages (physical ids ``pages``,
        one per full page) — the index increfs each page it newly adopts;
        pages whose token run is already cached are left alone."""
        self._clock += 1
        node = self.root
        for key, page in zip(self._pages(prompt), pages):
            child = node.children.get(key)
            if child is None:
                child = _PrefixNode(key, page)
                node.children[key] = child
                self.allocator.incref([page])
                self._nodes += 1
            child.last_used = self._clock
            node = child

    def evict(self, need: int) -> int:
        """Drop LRU leaf pages (held only by the index, refcount 1) until
        ``need`` pages have been freed or nothing more is evictable.

        Each pass collects every evictable leaf in one tree walk and frees
        them oldest-first (O(nodes log nodes) per pass, not one full walk
        per page); freeing a leaf may expose its parent, so passes repeat
        until sated or a pass frees nothing."""
        freed = 0
        while freed < need:
            victims = []                  # (last_used, parent, key, node)
            stack = [self.root]
            while stack:
                node = stack.pop()
                for key, child in node.children.items():
                    if not child.children \
                            and self.allocator.ref(child.page) == 1:
                        victims.append((child.last_used, node, key, child))
                    stack.append(child)
            if not victims:
                break
            victims.sort(key=lambda v: v[0])
            for _, parent, key, child in victims[:need - freed]:
                del parent.children[key]
                self.allocator.free([child.page])
                self._nodes -= 1
                self.evictions += 1
                freed += 1
        return freed

    def clear(self):
        """Drop every index-held reference (leaves first, repeatedly)."""
        while self._nodes and self.evict(self._nodes):
            pass


# --------------------------------------------------------------------------
# Tree splicing helpers (shared by both backends)
# --------------------------------------------------------------------------
def _batch_dim(dst_shape, src_shape, slots):
    """Batch dim for a B=1 splice: where dst == slots and src == 1 (prefer
    dim 1: stacked layer caches are (layers, B, ...))."""
    for d in (1, 0):
        if len(dst_shape) > d and dst_shape[d] == slots \
                and src_shape[d] == 1:
            return d
    raise ValueError(f"cannot locate batch dim: {dst_shape} vs {src_shape}")


def splice_row(dst, src, row: int, slot: int, slots: int,
               axis: Optional[int] = None):
    """Insert row ``row`` of a batched prefill array into slot ``slot`` of a
    batch-cache array, padding trailing (sequence) dims up to dst size.

    ``axis`` is the leaf's slot axis (from ``slot_axes`` — exact, no shape
    guessing); without it, fall back to the B=1 heuristic (compat shim).
    """
    bi = _batch_dim(dst.shape, src.shape, slots) if axis is None else axis
    if bi < 0:
        return dst                 # slot-independent leaf (shared pool)
    src = jax.lax.index_in_dim(src, row, axis=bi, keepdims=True)
    src = src.astype(dst.dtype)
    pads = []
    for d in range(src.ndim):
        tgt = 1 if d == bi else dst.shape[d]
        pads.append((0, tgt - src.shape[d]))
    src = jnp.pad(src, pads)
    start = [0] * dst.ndim
    start[bi] = slot
    return jax.lax.dynamic_update_slice(dst, src, tuple(start))


def slot_axes(model, slots: int, cache_len: int, page_spec=None,
              chunk_stage: int = 0):
    """Per-leaf slot axis of the cache tree, derived structurally: diff the
    ``eval_shape`` of ``init_caches`` at two slot counts — the axis whose
    extent changes is the slot axis (-1: slot-independent, e.g. a shared
    page pool).  No allocation, no shape heuristics — a state leaf whose
    head/seq extent happens to equal ``slots`` cannot be misidentified."""
    a = jax.eval_shape(
        lambda: model.init_caches(slots, cache_len, page_spec=page_spec,
                                  chunk_stage=chunk_stage))
    b = jax.eval_shape(
        lambda: model.init_caches(slots + 1, cache_len, page_spec=page_spec,
                                  chunk_stage=chunk_stage))

    def axis(x, y):
        for d, (p, q) in enumerate(zip(x.shape, y.shape)):
            if p != q:
                return d
        return -1

    return jax.tree.map(axis, a, b)


def _is_paged(x) -> bool:
    return isinstance(x, PagedKVCache)


def _pool_scatter(pool, rows, pages: List[int], stacked: bool):
    """Write prefill KV rows into allocated pages of one pool leaf.

    pool: (P, page, W) or, ``stacked``, (L, P, page, W) — W is KV*hd for
    value pools and KV for scale pools; rows: (T, KV, hd|1) / (L, T, KV,
    hd|1) correspondingly — padded or truncated to exactly fill the pages.
    """
    t_axis = 1 if stacked else 0
    page = pool.shape[-2]
    need = len(pages) * page
    T = rows.shape[t_axis]
    if T < need:
        pads = [(0, 0)] * rows.ndim
        pads[t_axis] = (0, need - T)
        rows = jnp.pad(rows, pads)
    elif T > need:
        rows = jax.lax.slice_in_dim(rows, 0, need, axis=t_axis)
    buf = rows.reshape(rows.shape[:t_axis] + (len(pages), page, -1))
    buf = buf.astype(pool.dtype)
    idx = jnp.asarray(pages, jnp.int32)
    if stacked:
        return pool.at[:, idx].set(buf)
    return pool.at[idx].set(buf)


def copy_page(caches, src, dst):
    """Copy physical page ``src`` -> ``dst`` in every paged leaf (the COW
    copy at a mid-page prefix divergence).  ``src``/``dst`` are int32
    scalars so the jitted copy compiles once; scale pages of int8 pools ride
    along — a COW'd page keeps value and scale rows coherent by
    construction (they share the index)."""
    def one(leaf):
        if not _is_paged(leaf):
            return leaf

        def cp(pool):
            if pool is None:
                return None
            if leaf.stacked:
                return pool.at[:, dst].set(pool[:, src])
            return pool.at[dst].set(pool[src])

        return PagedKVCache(cp(leaf.k_pool), cp(leaf.v_pool),
                            cp(leaf.k_scale_pool), cp(leaf.v_scale_pool))
    return jax.tree.map(one, caches, is_leaf=_is_paged)


def kv_row_bytes(cfg, kv_dtype: str) -> int:
    """Bytes one token-row of KV occupies across all attention layers —
    the unit of the serve layer's streamed-bytes model (decode reads every
    cached row once per step).  ``int8`` rows carry the per-(token, head)
    bf16 absmax scales alongside (quantize_kv layout)."""
    n_attn = sum(1 for (m, _) in cfg.layer_kinds() if m == "attn")
    if kv_dtype == "int8":
        per_layer = cfg.num_kv_heads * cfg.head_dim * 1 * 2   # K + V bytes
        per_layer += cfg.num_kv_heads * 2 * 2                 # bf16 scales
    else:
        itemsize = jnp.dtype(kv_dtype).itemsize
        per_layer = cfg.num_kv_heads * cfg.head_dim * itemsize * 2
    return n_attn * per_layer


def resolve_kv_dtype(model) -> str:
    """The ONE resolver of the KV-storage dtype (DESIGN.md §10).

    A model's KV rows are stored as ``rt.kv_dtype()`` — ``kv_cache_dtype``
    when set, else ``cache_dtype`` — normalized to a dtype name, falling
    back to the model compute dtype when the model carries no
    ``RuntimeConfig``.  Resolved ONCE at engine construction and passed
    down; the per-call-site ``or``-fallbacks that used to re-derive it
    (and silently disagreed for ``cache_dtype="int8"`` under the paged
    backend) are gone.
    """
    rt = getattr(model, "rt", None)
    if rt is not None:
        kd = rt.kv_dtype()
        return "int8" if kd == "int8" else jnp.dtype(kd).name
    return jnp.dtype(model.cfg.dtype).name


# --------------------------------------------------------------------------
# Backends
# --------------------------------------------------------------------------
class CacheBackend(Protocol):
    """What the serving engine needs from a cache layout."""

    name: str

    def init_caches(self, model, slots: int, cache_len: int): ...

    def check_admissible(self, tokens: int):
        """Raise if a request needing ``tokens`` rows can NEVER be admitted
        (backpressure must not degenerate into a silent drop)."""
        ...

    def reserve(self, slot: int, tokens: int) -> bool:
        """Claim capacity for ``tokens`` total rows in ``slot``; False if
        the backing store is exhausted (engine defers admission)."""
        ...

    def admit(self, caches, prefill_caches, *, row: int, slot: int,
              prompt_len: int):
        """Move row ``row`` of a batched-prefill cache into ``slot``."""
        ...

    def release(self, slot: int):
        """Return ``slot``'s capacity to the pool (request finished)."""
        ...

    def batch_extras(self) -> Dict[str, Any]:
        """Extra decode-batch entries (e.g. the block table)."""
        ...

    def stats(self) -> Dict[str, Any]: ...


class DenseBackend:
    """The original layout: per-slot dense caches of capacity ``cache_len``."""

    name = "dense"

    def __init__(self):
        self.slots = 0
        self.tracer = None         # set by the engine (repro.obs.Tracer)

    def init_caches(self, model, slots: int, cache_len: int):
        self.slots = slots
        self.cache_len = cache_len
        self._axes = slot_axes(model, slots, cache_len)
        return model.init_caches(slots, cache_len)

    def check_admissible(self, tokens: int):
        pass

    def reserve(self, slot: int, tokens: int) -> bool:
        return True

    def admit(self, caches, prefill_caches, *, row: int, slot: int,
              prompt_len: int):
        return jax.tree.map(
            lambda dst, src, ax: splice_row(dst, src, row, slot, self.slots,
                                            axis=ax),
            caches, prefill_caches, self._axes)

    def release(self, slot: int):
        pass

    def batch_extras(self) -> Dict[str, Any]:
        return {}

    def stats(self) -> Dict[str, Any]:
        return {"backend": self.name, "cache_tokens": self.slots *
                getattr(self, "cache_len", 0)}


class PagedBackend:
    """Page pool + block tables; pages are troop layout granules.

    ``num_pages=None`` sizes the pool for full occupancy (capacity parity
    with dense); smaller values overcommit HBM — admission then *defers*
    when the pool is exhausted instead of OOMing, exactly like a production
    engine under memory pressure.

    ``kv_dtype="int8"`` stores pages quantized (per-(token, head) absmax
    scales in sibling scale pages — same block table, same allocator; the
    free list never changes).  Left ``None`` it follows the model's
    ``RuntimeConfig.kv_cache_dtype`` so a quantized engine is one flag;
    note the int8 layout granule is coarser (pages must be multiples of 32
    rows, not 16 — ``PageSpec.validate``).
    """

    name = "paged"

    def __init__(self, page_size: int = 16,
                 num_pages: Optional[int] = None,
                 kv_dtype: Optional[str] = None,
                 prefix_cache: bool = False,
                 chunk_stage: int = 0):
        """``chunk_stage``: the chunked engine's chunk SIZE in tokens (0 =
        no staging buffer) — it sizes the bf16 stage over int8 pools; the
        engine sets it from its own ``chunk_size``."""
        self.page_size = page_size
        self.num_pages = num_pages
        self.kv_dtype = kv_dtype
        self.prefix_cache = prefix_cache
        self.chunk_stage = chunk_stage
        self.tracer = None         # set by the engine (repro.obs.Tracer)
        self.spec: Optional[PageSpec] = None
        self.prefix_index: Optional[PrefixIndex] = None
        self._pending_cow: Dict[int, Any] = {}
        self._shared_tokens = 0
        self.cow_copies = 0
        # tensor-parallel layout, set by the engine: kv_shards > 1 means
        # the pools are head-sharded and each device holds 1/kv_shards of
        # every page; kv_shards == 1 under tp > 1 means replicated pools
        # (the GQA fallback when kv_heads < tp)
        self.tp = 1
        self.kv_shards = 1

    def init_caches(self, model, slots: int, cache_len: int):
        dtype = self.kv_dtype or resolve_kv_dtype(model)
        self.kv_dtype = dtype          # resolved once, readable ever after
        self.slots = slots
        self.cache_len = cache_len
        self.spec = PageSpec.for_engine(slots, cache_len, self.page_size,
                                        self.num_pages, dtype)
        self.allocator = BlockAllocator(self.spec.num_pages,
                                        tracer=self.tracer)
        self.block_tables = np.full(
            (slots, self.spec.blocks_per_slot), NULL_PAGE, np.int32)
        self._slot_pages: Dict[int, List[int]] = {}
        if self.prefix_cache:
            self.prefix_index = PrefixIndex(self.spec.page_size,
                                            self.allocator)
        self._axes = slot_axes(model, slots, cache_len, page_spec=self.spec,
                               chunk_stage=self.chunk_stage)
        self._row_bytes = kv_row_bytes(model.cfg, dtype)
        return model.init_caches(slots, cache_len, page_spec=self.spec,
                                 chunk_stage=self.chunk_stage)

    def _pages_needed(self, tokens: int) -> int:
        return -(-min(tokens, self.cache_len) // self.spec.page_size)

    def check_admissible(self, tokens: int):
        """Raised at submit time — before anything is popped or reserved —
        so an impossible request never strands queue entries or pages."""
        need = self._pages_needed(tokens)
        if need > self.spec.num_pages - 1:
            raise ValueError(
                f"request needs {need} pages but the pool holds "
                f"{self.spec.num_pages - 1}: it can never be admitted — "
                f"raise num_pages or lower prompt_len + max_new_tokens")

    def _alloc_evicting(self, n: int) -> Optional[List[int]]:
        """Allocate ``n`` pages, evicting cold prefix-index pages to make
        room (shared pages held by live slots are never evicted — eviction
        only touches pages whose sole holder is the index)."""
        pages = self.allocator.alloc(n)
        if pages is None and self.prefix_index is not None:
            freed = self.prefix_index.evict(n - self.allocator.num_free)
            if freed and self.tracer is not None:
                self.tracer.instant("evict", "allocator", pages=freed)
            pages = self.allocator.alloc(n)
        return pages

    def reserve(self, slot: int, tokens: int) -> bool:
        pages = self._alloc_evicting(self._pages_needed(tokens))
        if pages is None:
            return False
        self._slot_pages[slot] = pages
        self.block_tables[slot] = NULL_PAGE
        self.block_tables[slot, :len(pages)] = pages
        return True

    def extend(self, slot: int, tokens: int) -> int:
        """Grow ``slot``'s page run to cover ``tokens`` rows (the
        speculative lookahead window past the baseline reservation).
        Returns the rows actually covered — all-or-nothing per allocation,
        so under pool pressure the run stays as-is and the caller clamps
        its window to what's covered (never below the baseline, so
        speculation degrades to plain decode instead of deadlocking)."""
        have = self._slot_pages.setdefault(slot, [])
        need = self._pages_needed(tokens) - len(have)
        if need > 0:
            fresh = self._alloc_evicting(need)
            if fresh is not None:
                self.block_tables[slot, len(have):len(have) + len(fresh)] \
                    = fresh
                have.extend(fresh)
        return min(len(have) * self.spec.page_size, self.cache_len)

    def rollback(self, slot: int, tokens: int) -> int:
        """Rewind ``slot`` to ``tokens`` rows: free every page past
        ``ceil(tokens / page_size)`` and NULL its table entry — the
        rejected speculative suffix.  Trailing pages are private by
        construction (shared prefix pages sit at the FRONT of the run and
        a rollback target always covers the whole prompt), and int8 scale
        pages share the block table, so freeing the index frees both.
        Returns the number of pages freed."""
        pages = self._slot_pages.get(slot)
        if not pages:
            return 0
        keep = self._pages_needed(max(tokens, 1))
        tail = pages[keep:]
        if not tail:
            return 0
        del pages[keep:]
        self.block_tables[slot, keep:keep + len(tail)] = NULL_PAGE
        self.allocator.free(tail)
        return len(tail)

    def reserve_with_prefix(self, slot: int, tokens: int,
                            prompt) -> Optional[int]:
        """Reserve ``slot`` reusing cached prefix pages of ``prompt``.

        Returns the number of prompt tokens whose KV comes from the cache
        (the chunked engine starts prefilling at that offset), or ``None``
        when the pool is exhausted (admission defers).  A mid-page partial
        match registers a pending copy-on-write: the engine must apply it
        (``take_cow`` / ``cow_done``) before writing the slot's pages.
        """
        if self.prefix_index is None:
            return 0 if self.reserve(slot, tokens) else None
        page = self.spec.page_size
        shared, partial = self.prefix_index.match(prompt)
        # take the slot's references before any eviction can run: a page
        # referenced here is unevictable for the lifetime of the slot
        self.allocator.incref(shared)
        cow_src, cow_depth = partial if partial else (None, 0)
        if cow_src is not None:
            self.allocator.incref([cow_src])
        fresh_n = self._pages_needed(tokens) - len(shared)
        fresh = self._alloc_evicting(fresh_n)
        if fresh is None:                       # pool pressure: undo, defer
            self.allocator.free(shared)
            if cow_src is not None:
                self.allocator.free([cow_src])
            return None
        if cow_src is not None:
            # divergence mid-page: the first fresh page becomes a private
            # copy of the matched page; rows [0, depth) are reused, the
            # tail is overwritten by this request's own prefill
            self._pending_cow[slot] = (cow_src, fresh[0])
        pages = shared + fresh
        self._slot_pages[slot] = pages
        self.block_tables[slot] = NULL_PAGE
        self.block_tables[slot, :len(pages)] = pages
        offset = len(shared) * page + cow_depth
        self._shared_tokens += offset
        if offset and self.tracer is not None:
            self.tracer.instant("prefix_hit", "allocator", slot=slot,
                                shared_pages=len(shared), tokens=offset,
                                cow=cow_src is not None)
        return offset

    def take_cow(self, slot: int):
        """Pending (src_page, dst_page) copy for ``slot``, or ``None``."""
        return self._pending_cow.get(slot)

    def cow_done(self, slot: int):
        """The engine copied the divergence page: drop the source ref."""
        src, dst = self._pending_cow.pop(slot)
        self.allocator.free([src])
        self.cow_copies += 1
        if self.tracer is not None:
            self.tracer.instant("cow_copy", "allocator", slot=slot,
                                src_page=src, dst_page=dst)

    def register_prefix(self, slot: int, prompt):
        """Index ``slot``'s fully written prompt pages for future reuse
        (called by the engine once the prompt's KV is entirely on-pool)."""
        if self.prefix_index is None:
            return
        page = self.spec.page_size
        full = len(prompt) // page
        if full:
            self.prefix_index.insert(prompt, self._slot_pages[slot][:full])

    def admit(self, caches, prefill_caches, *, row: int, slot: int,
              prompt_len: int):
        pages = self._slot_pages[slot]
        page = self.spec.page_size
        n_prefill = -(-prompt_len // page)

        def one(dst, src):
            if _is_paged(dst):
                # src is the dense prefill KVCache for this sublayer;
                # its batch axis is 0 (unstacked) or 1 (stacked layers)
                st = dst.stacked
                b_axis = 1 if st else 0

                def rows(a):
                    return jax.lax.index_in_dim(a, row, axis=b_axis,
                                                keepdims=False)

                use = pages[:n_prefill]
                if not dst.quantized:
                    return PagedKVCache(
                        _pool_scatter(dst.k_pool, rows(src.k), use, st),
                        _pool_scatter(dst.v_pool, rows(src.v), use, st))
                # int8 pools: scatter quantized rows + their scale rows.
                # An int8 *prefill* cache (rt.kv_cache_dtype == "int8")
                # already carries per-token scales — reuse them verbatim so
                # paged and dense int8 engines are numerically identical;
                # a bf16 prefill cache is quantized here, at admit.
                if getattr(src, "quantized", False):
                    k8, ks = rows(src.k), rows(src.k_scale)
                    v8, vs = rows(src.v), rows(src.v_scale)
                else:
                    from repro.quant.tensor import quantize_kv
                    k8, ks = quantize_kv(rows(src.k))
                    v8, vs = quantize_kv(rows(src.v))
                return PagedKVCache(
                    _pool_scatter(dst.k_pool, k8, use, st),
                    _pool_scatter(dst.v_pool, v8, use, st),
                    _pool_scatter(dst.k_scale_pool, ks, use, st),
                    _pool_scatter(dst.v_scale_pool, vs, use, st))
            return dst

        # paged leaves first (is_leaf stops recursion there), then the
        # remaining dense leaves (mamba/rwkv state, MLA, cross-attn KV,
        # int8 scales) take the dense splice path along their slot axis.
        caches = jax.tree.map(one, caches, prefill_caches, is_leaf=_is_paged)

        def dense(dst, src, ax):
            if _is_paged(dst):
                return dst
            return splice_row(dst, src, row, slot, self.slots, axis=ax)

        return jax.tree.map(dense, caches, prefill_caches, self._axes,
                            is_leaf=_is_paged)

    def release(self, slot: int):
        if slot in self._pending_cow:           # released before the copy
            src, _ = self._pending_cow.pop(slot)
            self.allocator.free([src])
        pages = self._slot_pages.pop(slot, None)
        if pages:
            self.allocator.free(pages)
        self.block_tables[slot] = NULL_PAGE

    def batch_extras(self) -> Dict[str, Any]:
        return {"block_tables": jnp.asarray(self.block_tables)}

    def kv_page_bytes(self) -> Dict[str, int]:
        """Logical vs resident KV traffic accounting: ``logical`` counts
        every block-table entry (what per-slot decode streams), ``resident``
        counts each *physical* page once — shared prefix pages land in HBM
        a single time no matter how many slots map them, and the bytes
        model of the serve layer must not double-count them."""
        sp = self.spec
        if sp is None:
            return {"kv_pages_logical": 0, "kv_pages_resident": 0}
        live = self.block_tables[self.block_tables != NULL_PAGE]
        page_bytes = sp.page_size * self._row_bytes
        logical_b = int(live.size) * page_bytes
        resident_b = int(np.unique(live).size) * page_bytes
        # per-device resident bytes: a head-sharded pool splits every page
        # 1/kv_shards per device — the headline stays the single-copy
        # footprint, never tp × it; a replicated pool (GQA fallback) really
        # does hold a full copy per device.
        shards = self.kv_shards if self.kv_shards > 1 else 1
        if shards > 1:
            per_device = [resident_b // shards] * shards
        else:
            per_device = [resident_b] * max(self.tp, 1)
        return {"kv_pages_logical": int(live.size),
                "kv_pages_resident": int(np.unique(live).size),
                "kv_page_bytes_logical": logical_b,
                "kv_page_bytes_resident": resident_b,
                "kv_page_bytes_per_device": per_device,
                "kv_shards": shards}

    def stats(self) -> Dict[str, Any]:
        sp = self.spec
        out = {
            "backend": self.name,
            "page_size": sp.page_size if sp else self.page_size,
            "num_pages": sp.num_pages if sp else self.num_pages,
            "kv_dtype": sp.kv_dtype if sp else self.kv_dtype,
            "pages_free": self.allocator.num_free if sp else None,
            "pages_in_use": (sp.num_pages - 1 - self.allocator.num_free)
            if sp else None,
        }
        out.update(self.kv_page_bytes())
        if self.prefix_index is not None:
            out.update({
                "prefix_lookups": self.prefix_index.lookups,
                "prefix_hits": self.prefix_index.hits,
                "prefix_pages_cached": self.prefix_index.num_pages,
                "prefix_evictions": self.prefix_index.evictions,
                "prefix_shared_tokens": self._shared_tokens,
                "cow_copies": self.cow_copies,
            })
        return out


def make_backend(backend) -> CacheBackend:
    """'dense' | 'paged' | an instance -> a CacheBackend instance."""
    if backend is None:
        return DenseBackend()
    if isinstance(backend, str):
        if backend == "dense":
            return DenseBackend()
        if backend == "paged":
            return PagedBackend()
        raise ValueError(f"unknown cache backend {backend!r}")
    return backend


def bucket_length(n: int, min_bucket: int = 8,
                  cap: Optional[int] = None) -> int:
    """Power-of-2 prefill bucket for a prompt of length ``n`` — one XLA
    prefill compile per bucket, ever (the recompile-free admission path)."""
    b = max(min_bucket, 1 << max(0, math.ceil(math.log2(max(n, 1)))))
    if cap is not None:
        b = min(b, cap)
    return b
