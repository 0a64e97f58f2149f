"""Paged KV-cache management: page allocator + host-side cache surgery.

KV memory is a shared pool of fixed-size pages per attention layer (see
``MultiheadAttention.Config.kv_cache_layout = "paged"``); sequences hold
*page tables* instead of dense ``slots x max_len`` rows. This module owns
the host-side resource management:

  * :class:`BlockAllocator` — a refcounted free-list over physical page
    ids with double-free/double-decref/leak guards. One allocator serves
    every layer: each layer has its own pool of identical geometry, so a
    single page id names the same page in all of them. Refcounts are what
    make shared-prefix pages safe: every sequence mapping a shared page
    holds one reference, and the page returns to the free list only when
    the last holder lets go.
  * :class:`PrefixIndex` — a hash-addressed index of immutable, fully
    written KV pages keyed by the page-granular rolling hash of the token
    chain they cache. Admission consults it to map a new sequence's page
    table directly onto already-prefilled pages (skipping prefill);
    divergence mid-page is resolved by copy-on-write.
  * :class:`PagedCacheManager` — structure-aware surgery on the engine's
    (otherwise opaque) cache pytree: writing page-table rows, clearing
    recycled pages, copy-on-write page forks, extracting a sequence's
    pages + per-slot rows to host memory (eviction), and re-splicing them
    into freshly allocated pages (restore) — no re-prefill.

Leaf-name contract (how an opaque pytree becomes pageable): attention's
paged cache exposes ``k_pool``/``v_pool`` (page axis at ``ndim-4``),
``pos_pool`` (page axis at ``ndim-2``) and ``page_table`` (batch axis at
``ndim-2``); any leading axes (e.g. ``Repeat``'s stacked-layer axis) are
carried transparently. Everything else (dense KV rows, Mamba/RWKV
recurrent state) is handled purely through its batch axis — recurrent
mixers keep their O(1) state and bypass paging entirely.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["BlockAllocator", "PagedCacheManager", "PrefixIndex"]

# Page axis of a pool leaf, keyed by leaf name, expressed as trailing rank:
# k_pool/v_pool are (..., P, Hkv, page, D) -> page axis at ndim-4;
# pos_pool is (..., P, page) -> ndim-2; scale_pool (quantized KV: one fp32
# scale per token slot per k|v) is (..., P, page, 2) -> ndim-3. Because the
# scale rows share the physical-page axis, every page op below — COW
# forks, evict/restore, prefix sharing — moves them atomically with the
# KV payload by construction.
_POOL_PAGE_AXIS = {"k_pool": -4, "v_pool": -4, "pos_pool": -2,
                   "scale_pool": -3}
NULL_PAGE = 0  # reserved: unmapped table entries clamp here on reads


class BlockAllocator:
    """Refcounted free-list allocator over physical KV pages.

    Page 0 (the null page) is reserved and never handed out. ``alloc``
    returns ``None`` (rather than raising) when the pool cannot satisfy the
    request — the scheduler turns that into preemption, not failure.

    Shared-prefix pages are refcounted: ``alloc`` hands pages out at
    refcount 1, each additional sharer ``incref``s, and ``decref`` drops
    one reference, returning the page to the free list only when the last
    holder releases it. A freed page keeps its contents (the prefix index
    may still name it for future cache hits); ``revive`` pulls such a
    cached-free page back off the free list when a new sequence matches
    its content. Consumers must treat *reallocated* pages as garbage —
    the serving scheduler resets a page's positions at allocation time
    and drops any prefix-index entry naming it.
    """

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError(f"need >= 2 pages (1 usable), got {num_pages}")
        self._free: List[int] = list(range(num_pages - 1, 0, -1))  # pop() -> 1
        self._ref: Dict[int, int] = {}
        self.num_pages = num_pages

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_in_use(self) -> int:
        return len(self._ref)

    @property
    def capacity(self) -> int:
        """Allocatable pages (the null page is not)."""
        return self.num_pages - 1

    def refcount(self, page: int) -> int:
        """Live references on a page (0 = free or never allocated)."""
        return self._ref.get(page, 0)

    def alloc(self, n: int) -> Optional[List[int]]:
        """n fresh page ids at refcount 1, or None if fewer than n are
        free."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._ref[p] = 1
        return pages

    def incref(self, page: int):
        """Add a sharer to an in-use page."""
        if page not in self._ref:
            raise ValueError(f"incref of unallocated page {page}")
        self._ref[page] += 1

    def revive(self, page: int):
        """Reclaim a cached-free page — one whose last holder released it
        but whose contents a prefix-index hit wants back — from the free
        list, at refcount 1."""
        if page in self._ref:
            raise ValueError(f"revive of in-use page {page}; incref instead")
        try:
            self._free.remove(page)
        except ValueError:
            raise ValueError(f"revive of page {page} not on the free list")
        self._ref[page] = 1

    def decref(self, page: int) -> bool:
        """Drop one reference; returns True iff this freed the page. Raises
        on a page with no live references — the double-decref guard."""
        r = self._ref.get(page)
        if r is None:
            raise ValueError(f"decref of unallocated page {page}")
        if r > 1:
            self._ref[page] = r - 1
            return False
        del self._ref[page]
        self._free.append(page)
        return True

    def decref_all(self, pages: List[int]) -> List[int]:
        """decref each page; returns the subset actually freed."""
        return [p for p in pages if self.decref(p)]

    def free(self, pages: List[int]):
        """Hard-free exclusively owned pages. Raises on double-free, on a
        page this allocator never handed out, or on a *shared* page
        (refcount > 1) — freeing a page out from under its other sharers
        is exactly the bug the guard exists for; use ``decref``."""
        for p in pages:
            r = self._ref.get(p)
            if r is None:
                raise ValueError(f"free of unallocated page {p}")
            if r > 1:
                raise ValueError(
                    f"free of shared page {p} (refcount {r}); use decref")
        for p in pages:
            self.decref(p)


_ROOT_HASH = 0x9E3779B9  # chain hash of the empty prefix
_HASH_MOD = (1 << 61) - 1


def _chain_hash(parent: int, tokens: Tuple[int, ...]) -> int:
    """Page-granular rolling hash: fold one page of token ids into the
    parent chain's hash. Collisions are tolerable — every index hit is
    confirmed against the stored token ids before any page is shared."""
    h = parent
    for t in tokens:
        h = (h * 1000003 + 2654435761 * (int(t) + 1)) % _HASH_MOD
    return h


@dataclasses.dataclass
class _PrefixEntry:
    page: int  # physical page caching this chain's last page of tokens
    parent: int  # chain hash of the preceding pages (_ROOT_HASH at depth 0)
    tokens: Tuple[int, ...]  # this page's token ids — the exact-match guard


class PrefixIndex:
    """Hash-addressed index of immutable, fully written KV pages.

    Each entry maps the rolling chain hash of ``pages[0..i]`` of some
    sequence's prompt to the physical page caching page ``i``. A new
    prompt walks its own chain through the index: every hit is a page of
    prefill it can skip by mapping the existing page (shared, refcounted);
    the first miss may still be a *partial* match — a published page whose
    tokens share a proper prefix with the prompt's next page — which the
    scheduler resolves by copy-on-write.

    The index never owns pages. The scheduler increfs/revives matched
    pages through the allocator, and must call :meth:`forget_pages`
    whenever pages are (re)allocated fresh — reallocation invalidates
    whatever chain a page used to cache.
    """

    def __init__(self, page_size: int):
        self.page_size = int(page_size)
        self._by_hash: Dict[int, _PrefixEntry] = {}
        self._by_page: Dict[int, int] = {}  # physical page -> chain hash
        self._children: Dict[int, set] = {}  # parent hash -> chain hashes

    def __len__(self) -> int:
        return len(self._by_hash)

    def publish(self, parent: int, tokens: Tuple[int, ...], page: int) -> int:
        """Register ``page`` as caching ``tokens`` at the end of chain
        ``parent``; returns the extended chain hash. First publisher wins:
        an existing entry for the same chain is kept (its page is the one
        other sequences may already share)."""
        if len(tokens) != self.page_size:
            raise ValueError(f"publish of a non-full page ({len(tokens)} "
                             f"tokens, page_size {self.page_size})")
        h = _chain_hash(parent, tokens)
        if h not in self._by_hash:
            self._by_hash[h] = _PrefixEntry(page, parent,
                                            tuple(int(t) for t in tokens))
            self._by_page[page] = h
            self._children.setdefault(parent, set()).add(h)
        return h

    def match(self, prompt: np.ndarray) -> Tuple[List[int], int,
                                                 Optional[Tuple[int, int]]]:
        """Longest cached chain covering a proper prefix of ``prompt``.

        At most ``len(prompt) - 1`` tokens match — the final prompt token
        must always go through prefill so its next-token logits exist.
        Returns ``(full_pages, chain_hash, partial)`` where ``full_pages``
        are physical pages caching whole prompt pages, ``chain_hash`` is
        the chain after them (the publish cursor for the matching
        sequence), and ``partial`` is an optional ``(donor_page, j)``:
        a published page whose first ``j`` tokens extend the match, to be
        copy-on-write forked by the caller.
        """
        ps = self.page_size
        limit = len(prompt) - 1
        pages: List[int] = []
        h = _ROOT_HASH
        m = 0
        while (m + 1) * ps <= limit:
            toks = tuple(int(t) for t in prompt[m * ps:(m + 1) * ps])
            e = self._by_hash.get(_chain_hash(h, toks))
            if e is None or e.parent != h or e.tokens != toks:
                break
            pages.append(e.page)
            h = _chain_hash(h, toks)
            m += 1
        # Partial match: among published children of the matched chain,
        # the page sharing the longest proper token-prefix with the
        # prompt's next page.
        rest = [int(t) for t in prompt[m * ps:limit]]
        best: Optional[Tuple[int, int]] = None
        if rest:
            for ch in self._children.get(h, ()):
                e = self._by_hash.get(ch)
                if e is None:
                    continue
                j = 0
                for a, b in zip(e.tokens, rest):
                    if a != b:
                        break
                    j += 1
                if j >= 1 and (best is None or j > best[1]):
                    best = (e.page, j)
        return pages, h, best

    def forget_pages(self, pages: List[int]):
        """Drop any entries naming these physical pages (they were just
        reallocated — their cached content is about to be overwritten)."""
        for p in pages:
            h = self._by_page.pop(p, None)
            if h is None:
                continue
            e = self._by_hash.pop(h, None)
            if e is not None:
                kids = self._children.get(e.parent)
                if kids is not None:
                    kids.discard(h)
                    if not kids:
                        del self._children[e.parent]


@dataclasses.dataclass
class _LeafInfo:
    name: str  # last dict key on the leaf's path
    batch_axis: int  # -1 = shared leaf (no per-slot rows)
    page_axis: int  # -1 = not a pool leaf


class PagedCacheManager:
    """Host-side surgery on a (possibly paged) engine cache pytree.

    Built once from a template cache and the engine's per-leaf batch-axis
    map; every operation takes and returns a full cache pytree (leaves are
    device arrays; ops dispatch eagerly — these are rare control-plane
    events, not the decode hot path).
    """

    def __init__(self, template_cache: Any, batch_axes: Any):
        leaves, self._treedef = jax.tree_util.tree_flatten(template_cache)
        axes_leaves = jax.tree_util.tree_flatten(batch_axes)[0]
        paths = jax.tree_util.tree_flatten_with_path(template_cache)[0]
        self._info: List[_LeafInfo] = []
        self.page_size = self.num_pages = self.n_logical = None
        # Storage dtype of the KV pools (observability: surfaces quantized
        # caches in gateway metrics without any dtype branching here).
        self.pool_dtype: Optional[str] = None
        for (path, leaf), ax in zip(paths, axes_leaves):
            name = ""
            for entry in reversed(path):
                key = getattr(entry, "key", None)
                if isinstance(key, str):
                    name = key
                    break
            page_axis = -1
            if name in _POOL_PAGE_AXIS:
                page_axis = leaf.ndim + _POOL_PAGE_AXIS[name]
                if name == "pos_pool":
                    self.num_pages, self.page_size = leaf.shape[-2:]
                elif name == "k_pool":
                    self.pool_dtype = str(leaf.dtype)
            if name == "page_table":
                self.n_logical = leaf.shape[-1]
            self._info.append(_LeafInfo(name, int(ax), page_axis))

    @property
    def is_paged(self) -> bool:
        return self.num_pages is not None

    # ------------------------------------------------------------- helpers

    def _map(self, cache, fn):
        """fn(leaf, info) -> leaf over the flat cache."""
        leaves = jax.tree_util.tree_flatten(cache)[0]
        out = [fn(leaf, info) for leaf, info in zip(leaves, self._info)]
        return jax.tree_util.tree_unflatten(self._treedef, out)

    @staticmethod
    def _set_rows(leaf, axis, idx, vals):
        moved = jnp.moveaxis(leaf, axis, 0)
        return jnp.moveaxis(moved.at[idx].set(vals), 0, axis)

    # -------------------------------------------------------- page tables

    def write_table_row(self, cache, slot: int, row: np.ndarray):
        """Install a sequence's page table row (same ids in every layer)."""
        row = jnp.asarray(row, jnp.int32)

        def fn(leaf, info):
            if info.name != "page_table":
                return leaf
            return self._set_rows(leaf, leaf.ndim - 2, slot, row)

        return self._map(cache, fn)

    def clear_tables(self, cache):
        """Unmap every sequence (allocator-managed mode: init_states may
        have installed full-residency identity tables)."""
        def fn(leaf, info):
            if info.name != "page_table":
                return leaf
            return jnp.full_like(leaf, -1)

        return self._map(cache, fn)

    def reset_pages(self, cache, pages: List[int]):
        """Invalidate recycled pages' positions so a later partial fill
        can't expose a previous tenant's tokens to the mask."""
        if not pages:
            return cache
        idx = jnp.asarray(pages, jnp.int32)

        def fn(leaf, info):
            if info.name != "pos_pool":
                return leaf
            return self._set_rows(leaf, info.page_axis, idx, -1)

        return self._map(cache, fn)

    def set_index(self, cache, slot: int, value: int):
        """Set one slot's decode position counter (``index`` leaves) — a
        sequence admitted onto matched prefix pages starts mid-stream."""
        def fn(leaf, info):
            if info.name != "index":
                return leaf
            return self._set_rows(leaf, info.batch_axis, slot,
                                  jnp.asarray(value, leaf.dtype))

        return self._map(cache, fn)

    def copy_page(self, cache, src: int, dst: int, valid: int):
        """Copy-on-write fork: duplicate physical page ``src`` into ``dst``
        keeping only the first ``valid`` token slots' positions — the
        shared prefix. The rest are invalidated so the donor's later
        tokens can never leak into the borrower's attention mask."""
        def fn(leaf, info):
            if info.page_axis < 0:
                return leaf
            moved = jnp.moveaxis(leaf, info.page_axis, 0)
            row = moved[src]
            if info.name == "pos_pool":
                keep = jnp.arange(row.shape[-1]) < valid
                row = jnp.where(keep, row, -1)
            return jnp.moveaxis(moved.at[dst].set(row), 0, info.page_axis)

        return self._map(cache, fn)

    # ---------------------------------------------------- evict / restore

    def extract_slot(self, cache, slot: int) -> List[Optional[np.ndarray]]:
        """Host copy of one sequence's per-slot rows (recurrent state, dense
        KV rows, index — everything with a batch axis except the page
        table, which the allocator rebuilds on restore)."""
        leaves = jax.tree_util.tree_flatten(cache)[0]
        out = []
        for leaf, info in zip(leaves, self._info):
            if info.batch_axis < 0 or info.name == "page_table":
                out.append(None)
            else:
                out.append(np.asarray(jnp.take(leaf, slot,
                                               axis=info.batch_axis)))
        return out

    def splice_slot(self, cache, slot: int, rows: List[Optional[np.ndarray]]):
        def fn_pair():
            leaves = jax.tree_util.tree_flatten(cache)[0]
            out = []
            for leaf, info, row in zip(leaves, self._info, rows):
                if row is None:
                    out.append(leaf)
                else:
                    out.append(self._set_rows(leaf, info.batch_axis, slot,
                                              jnp.asarray(row)))
            return out

        return jax.tree_util.tree_unflatten(self._treedef, fn_pair())

    def extract_pages(self, cache, pages: List[int]) -> List[Optional[np.ndarray]]:
        """Host copy of the given physical pages from every pool leaf —
        the KV payload of an evicted sequence."""
        idx = jnp.asarray(pages, jnp.int32)
        leaves = jax.tree_util.tree_flatten(cache)[0]
        out = []
        for leaf, info in zip(leaves, self._info):
            if info.page_axis < 0:
                out.append(None)
            else:
                out.append(np.asarray(jnp.take(leaf, idx, axis=info.page_axis)))
        return out

    def insert_pages(self, cache, pages: List[int],
                     payload: List[Optional[np.ndarray]]):
        """Write evicted page contents into freshly allocated pages —
        restore is a re-splice, not a re-prefill."""
        idx = jnp.asarray(pages, jnp.int32)
        leaves = jax.tree_util.tree_flatten(cache)[0]
        out = []
        for leaf, info, content in zip(leaves, self._info, payload):
            if content is None:
                out.append(leaf)
            else:
                moved = jnp.moveaxis(leaf, info.page_axis, 0)
                vals = jnp.moveaxis(jnp.asarray(content), info.page_axis, 0)
                out.append(jnp.moveaxis(moved.at[idx].set(vals), 0,
                                        info.page_axis))
        return jax.tree_util.tree_unflatten(self._treedef, out)
