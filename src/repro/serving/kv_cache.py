"""KV-cache subsystem: Appendix-G memory accounting + the paged page-pool
cache behind the paged ``CacheBackend``s.

Two halves:

* **Accounting** (eqs. 37-39): ``kv_cache_bytes_fp`` / ``kv_cache_bytes_astra``
  / ``codebook_bytes`` — pure arithmetic used by the Appendix-G benchmark and
  the roofline tables.

* **Paged runtime cache**: ``PageAllocator`` (free-list over page ids) +
  ``PagedKVCache`` (per-group block tables, per-layer page pools).  Every
  attention layer's K/V pool is a ``(num_pages, page_size, ...)`` array; a
  request owns a list of pages recorded in its slot's block-table row, so
  engine memory scales with *allocated tokens* (page-granular) instead of
  ``slots * max_len``.  fp16/32 value pages ("paged") and uint8/16 VQ code
  pages ("paged_vq", the codes-only Appendix-G cache) share the same layout.

Layers are partitioned into **page groups** with their own allocator, id
space and block-table width:

* ``"global"`` — full-attention layers; ``max_len / page_size`` table
  entries per request.
* ``"window"`` — sliding-window (SWA) layers; capped at
  ``ceil(window / page_size)`` entries per request, used as a page-granular
  ring over the last ``window`` positions.  Windowed pools are therefore
  sized by the window, not ``max_len`` — the per-layer eq. 38/39 accounting
  below reflects that.

Page 0 of each group is a reserved scratch page: block-table rows of retired
or never-admitted slots point at it, so the fixed-shape decode step can keep
writing without corrupting live requests, and page-pool reads beyond a row's
allocation are masked by the attention validity mask.

**Cross-request prefix sharing** (``PrefixIndex`` + refcounted pages):
``PageAllocator`` counts references per page — ``alloc`` starts a page at
refcount 1, ``share`` adds a co-owner, and a page returns to the free list
only when its last owner releases it.  On top of that, ``PrefixIndex`` is a
radix tree over *page-sized token chunks*: each node is keyed by a rolling
hash of ``(parent_key, page_tokens)`` and pins one live page (the index is
an allocator owner like any slot).  Retiring requests insert their prompt's
full pages instead of freeing them; admission walks the incoming prompt down
the tree, points the slot's block-table rows at the shared pages
(``share``), copy-on-write forks a partial last page into a fresh page
(``copy_page``), and starts the chunked prefill at the first uncached token.
Under allocator pressure the index evicts least-recently-touched leaves
first; eviction only actually frees a page when no live request still
co-owns it.  Because a page id indexes *every* layer's pool in its group,
sharing is exact only when all attention layers see the same global causal
history — ``PagedKVCache.prefix_shareable`` gates the feature to all-global
attention stacks, and ``paged_vq`` nodes additionally carry host-side fp
snapshots of the prefill-view scratch so reuse stays bitwise identical to a
cold prefill.

**Preemption swap arena** (``SwapArena`` + ``snapshot_slot`` /
``restore_slot``): when the scheduler preempts a decoding request, the exact
bytes the victim owns — its block-table rows' pages per pool leaf, its
per-slot rows of every dense leaf, and (paged_vq) its per-page fp prefill
scratch — move to a host-side arena keyed by request uid.  Under
``paged_vq`` the swapped pages are *code* pages, so swap traffic is the
same ~16x cheaper than fp that Appendix G gets on the wire, applied to the
host memory hierarchy instead.  Re-admission re-grants pages and scatters
the saved payload into the new page ids (``restore_slot``, one fixed-shape
jit), so a restored decode is bitwise identical to one that was never
preempted.  The arena's ``_swapped`` dict is private to this module — the
``swap-arena-internals`` lint rule keeps every other module on the
``stash``/``peek``/``pop``/``holds``/``stats`` surface.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from repro.configs.base import ModelConfig

# leaf names marking a cache sub-dict as a shared page pool (no batch dim)
PAGED_LEAF_KEYS = frozenset(
    {"k_pages", "v_pages", "k_code_pages", "v_code_pages"})

# fp prefill-view scratch slabs carried by vq-coded layers during chunked
# prefill only (serving.cache_backend re-exports this as SCRATCH_KEYS)
PREFILL_SCRATCH_KEYS = frozenset({"k_fp", "v_fp"})


# ---------------------------------------------------------------------------
# Appendix-G accounting (eqs. 37-39)
# ---------------------------------------------------------------------------


def kv_cache_bytes_fp(cfg: ModelConfig, seq_len: int, batch: int = 1,
                      bytes_per_val: int = 2) -> int:
    """Original model KV-cache bytes: 2 * N * L * d_kv * b (eq. 38)."""
    layers = _attn_layers(cfg)
    return 2 * batch * seq_len * layers * cfg.d_kv * bytes_per_val


def kv_cache_bytes_astra(cfg: ModelConfig, seq_len: int, num_devices: int,
                         batch: int = 1, bytes_per_val: int = 2) -> int:
    """ASTRA per-device KV bytes (eq. 39): local FP + non-local VQ codes."""
    layers = _attn_layers(cfg)
    g = cfg.astra.groups
    bits = math.log2(cfg.astra.codebook_size)
    local = (seq_len / num_devices) * layers * cfg.d_kv * bytes_per_val
    remote = (num_devices - 1) * (seq_len / num_devices) * layers * g * bits / 8
    return int(2 * batch * (local + remote))


def kv_cache_bytes_codes(cfg: ModelConfig, seq_len: int, batch: int = 1) -> int:
    """Codes-only cache bytes (the eq.-39 remote term at (n-1)/n -> 1):
    every token stored as G * log2(K) bits for K and V."""
    layers = _attn_layers(cfg)
    bits = math.log2(cfg.astra.codebook_size)
    return int(2 * batch * seq_len * layers * cfg.astra.groups * bits / 8)


def kv_cache_bytes_sharded(cfg: ModelConfig, seq_len: int, num_devices: int,
                           batch: int = 1, bytes_per_val: int = 2) -> int:
    """Our runtime's sharded cache (beyond-paper): disjoint FP shards."""
    return kv_cache_bytes_fp(cfg, seq_len, batch, bytes_per_val) // num_devices


def codebook_bytes(cfg: ModelConfig, bytes_per_val: int = 2) -> int:
    """M_codebook = L * C * K * d * b (eq. 37); C=2 for quantize_mode='kv'."""
    c = 2 if cfg.astra.quantize_mode == "kv" else 1
    dim = cfg.d_kv if cfg.astra.quantize_mode == "kv" else cfg.d_model
    return _attn_layers(cfg) * c * cfg.astra.codebook_size * dim * bytes_per_val


def code_itemsize(codebook_size: int) -> int:
    """Storage bytes per VQ code (derived from the runtime's code dtype so
    accounting can never drift from what the pools materialize)."""
    from repro.core.vq import code_dtype

    return np.dtype(code_dtype(codebook_size)).itemsize


def _attn_layers(cfg: ModelConfig) -> int:
    """Number of attention layers, counted from the actual stage layout (the
    old closed-form undercounted/overcounted rg-pattern models whose layer
    count is not a multiple of 3)."""
    if cfg.arch_type == "ssm":
        return 0
    from repro.models.transformer import ATTN_KINDS, stages

    return sum(reps * sum(k in ATTN_KINDS for k in kinds)
               for kinds, reps in stages(cfg))


def memory_report(cfg: ModelConfig, seq_len: int, num_devices: int) -> Dict:
    fp = kv_cache_bytes_fp(cfg, seq_len)
    return {
        "kv_fp_bytes": fp,
        "kv_astra_bytes": kv_cache_bytes_astra(cfg, seq_len, num_devices),
        "kv_sharded_bytes": kv_cache_bytes_sharded(cfg, seq_len, num_devices),
        "codebook_bytes": codebook_bytes(cfg),
        "astra_fraction": kv_cache_bytes_astra(cfg, seq_len, num_devices) / fp
        if fp else 0.0,
    }


# ---------------------------------------------------------------------------
# Page groups: per-layer block-table widths
# ---------------------------------------------------------------------------


def _attn_kind_window(kind: str, cfg: ModelConfig) -> int:
    """Deferred alias of models.attention.kind_window — the single source
    of truth for which layer kinds are windowed (import deferred like the
    transformer imports above, to keep serving importable standalone)."""
    from repro.models.attention import kind_window

    return kind_window(kind, cfg)


def page_group_for(kind: str, cfg: ModelConfig) -> str:
    """Block-table group a layer kind reads/writes through."""
    return "window" if _attn_kind_window(kind, cfg) else "global"


def page_group_spans(cfg: ModelConfig, max_len: int,
                     page_size: int) -> Dict[str, int]:
    """Per-request block-table width (pages) for every page group this model
    needs.  Windowed layers are capped at ``ceil(window / page_size)`` — the
    table is a page-granular ring over the last ``span * page_size``
    positions, so a window never costs ``max_len`` worth of pages."""
    from repro.models.transformer import ATTN_KINDS, stages

    max_pages = -(-max_len // page_size)
    spans: Dict[str, int] = {}
    for kinds, _ in stages(cfg):
        for kind in kinds:
            if kind not in ATTN_KINDS:
                continue
            window = _attn_kind_window(kind, cfg)
            if window:
                spans["window"] = min(-(-window // page_size), max_pages)
            else:
                spans["global"] = max_pages
    return dict(sorted(spans.items()))


def dominant_group(spans: Dict[str, int]) -> str:
    """The group the engine-level ``num_pages`` knob applies to: the
    full-span one when present (windowed pools are bounded by construction,
    so admission pressure is only meaningful on the global pool)."""
    return "global" if "global" in spans else next(iter(spans))


# ---------------------------------------------------------------------------
# Page-granular accounting (what the paged runtime actually materializes)
# ---------------------------------------------------------------------------


def paged_pool_bytes(cfg: ModelConfig, *, max_len: int, page_size: int,
                     vq_codes: bool = False, slots: int = 1,
                     num_pages: Optional[int] = None,
                     dtype_bytes: int = 4, window_cap: bool = True) -> int:
    """Analytic byte size of the page pools a ``PagedKVCache`` materializes.

    Per-layer eq. 38 (or the codes-only eq.-39 remote term with
    ``vq_codes=True``) rounded up to page granularity, plus one scratch page
    per pool; windowed ("local") attention layers are sized by their page
    ring (``window_cap=True``, the runtime behaviour) instead of ``max_len``,
    and hold fp pages even under VQ codes, mirroring the dense "vq" mode
    which keeps them full-precision.  ``num_pages`` overrides the dominant
    group's pool size (the scheduler's admission-pressure knob).
    """
    from repro.models.transformer import ATTN_KINDS, stages

    spans = page_group_spans(cfg, max_len, page_size)
    if not window_cap:  # pre-cap accounting: every layer spans max_len
        spans = {name: -(-max_len // page_size) for name in spans}
    dom = dominant_group(spans) if spans else None
    total = 0
    for kinds, reps in stages(cfg):
        for kind in kinds:
            if kind not in ATTN_KINDS:
                continue
            group = page_group_for(kind, cfg)
            span = spans[group]
            pages = (int(num_pages) if num_pages and group == dom
                     else slots * span + 1)
            if vq_codes and not _attn_kind_window(kind, cfg):
                per = pages * page_size * cfg.astra.groups * code_itemsize(
                    cfg.astra.codebook_size)
            else:
                per = pages * page_size * cfg.d_kv * dtype_bytes
            total += 2 * reps * per  # K and V pools
    return total


def slab_cache_bytes(cfg: ModelConfig, *, max_len: int, slots: int = 1,
                     vq_codes: bool = False, dtype_bytes: int = 4) -> int:
    """Byte size of the contiguous slab caches ("fp"/"vq"): per-layer eq. 38
    with windowed layers holding only their ``min(window, max_len)`` ring."""
    from repro.models.transformer import ATTN_KINDS, stages

    total = 0
    for kinds, reps in stages(cfg):
        for kind in kinds:
            if kind not in ATTN_KINDS:
                continue
            window = _attn_kind_window(kind, cfg)
            s = min(window, max_len) if window else max_len
            if vq_codes and not window:
                per = s * cfg.astra.groups * code_itemsize(
                    cfg.astra.codebook_size)
            else:
                per = s * cfg.d_kv * dtype_bytes
            total += 2 * reps * slots * per
    return total


def is_paged_sub(sub: Dict[str, Any]) -> bool:
    """True if a per-layer cache dict is a shared page pool (no batch dim)."""
    return any(k in PAGED_LEAF_KEYS for k in sub)


def adopt_pools(fresh: List[Dict], live: List[Dict]) -> List[Dict]:
    """Replace the page-pool *leaves* of a cache tree with the live pools
    (prefill writes into the engine's pools in place of a per-request slab;
    non-pool leaves — batched dense state, and the fp prefill-view scratch
    a chunked vq prefill carries — keep their ``fresh`` state)."""
    out = []
    for f_stage, l_stage in zip(fresh, live):
        stage = {}
        for name, sub in f_stage.items():
            if is_paged_sub(sub):
                stage[name] = {k: (l_stage[name][k] if k in PAGED_LEAF_KEYS
                                   else v) for k, v in sub.items()}
            else:
                stage[name] = sub
        out.append(stage)
    return out


def strip_pool_leaves(caches: List[Dict]) -> List[Dict]:
    """Drop the shared page-pool leaves from a cache tree (host-side,
    structural).  The chunked scheduler adopts the live pools into the
    per-request prefill cache, so by merge time the pool arrays inside the
    fresh tree *are* the live tree's arrays — stripping them before the
    donated ``merge_slot`` call keeps XLA from seeing the same buffer as
    both a donated and a non-donated input."""
    return [{name: ({k: v for k, v in sub.items()
                     if k not in PAGED_LEAF_KEYS}
                    if is_paged_sub(sub) else sub)
             for name, sub in stage.items()} for stage in caches]


def merge_slot(live: List[Dict], fresh: List[Dict], slot) -> List[Dict]:
    """Merge a batch-1 prefill cache into row ``slot`` of the live batched
    cache, on device (jit-traced; ``slot`` may be a traced scalar).  Shared
    page-pool sub-dicts are adopted wholesale when ``fresh`` still carries
    them (the padded in-jit prefill path, where the fresh tree's pools hold
    the writes) and kept from ``live`` when the caller stripped them (the
    chunked path: prefill already wrote the live pools in place, and the
    stripped tree is what makes donating ``live`` sound — see
    ``strip_pool_leaves``).  Batched (R, B, ...) leaves get the (R, 1, ...)
    slice inserted at ``slot``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def one(batch_leaf, new_leaf):
        return lax.dynamic_update_slice_in_dim(
            batch_leaf, new_leaf.astype(batch_leaf.dtype),
            jnp.asarray(slot), axis=1)

    out = []
    with jax.named_scope("slot_merge"):
        for l_stage, f_stage in zip(live, fresh):
            sub = {}
            for name, l_sub in l_stage.items():
                f_sub = f_stage.get(name)
                if is_paged_sub(l_sub):
                    sub[name] = (f_sub if f_sub is not None
                                 and is_paged_sub(f_sub) else l_sub)
                else:
                    sub[name] = jax.tree.map(one, l_sub, f_sub)
            out.append(sub)
    return out


def copy_page(caches: List[Dict], src, dst) -> List[Dict]:
    """Device copy of pool page ``src`` into ``dst`` across every paged
    leaf of every layer — the copy-on-write fork for a partially shared
    page.  ``src``/``dst`` may be traced scalars, so the scheduler's jitted
    wrapper compiles once regardless of which pages fork.  Pool leaves are
    ``(reps, num_pages, page_size, ...)``; everything else rides through
    untouched."""
    out = []
    for stage in caches:
        sub_out = {}
        for name, sub in stage.items():
            if is_paged_sub(sub):
                sub_out[name] = {
                    k: (v.at[:, dst].set(v[:, src])
                        if k in PAGED_LEAF_KEYS else v)
                    for k, v in sub.items()}
            else:
                sub_out[name] = sub
        out.append(sub_out)
    return out


def pool_bytes(caches: Sequence[Dict]) -> int:
    """Measured bytes of the materialized page pools in a cache tree."""
    total = 0
    for stage in caches:
        for sub in stage.values():
            for name, leaf in sub.items():
                if name in PAGED_LEAF_KEYS:
                    total += int(np.prod(leaf.shape)) * leaf.dtype.itemsize
    return total


# ---------------------------------------------------------------------------
# Free-list allocator
# ---------------------------------------------------------------------------


class PageAllocator:
    """Free-list allocator over one page group's ids, with per-page
    refcounts.

    Pages ``[0, reserved)`` are never handed out — page 0 is the scratch
    page absorbing writes from retired/padded rows.  ``alloc`` doubles as
    append: allocating again for a live owner extends its page list.

    A freshly allocated page has refcount 1; ``share`` registers another
    owner on an already-live page (cross-request prefix reuse), and
    ``release``/``free`` drops one reference per page the owner held — a
    page returns to the free list only when its last reference goes.
    ``pages_in_use`` counts *distinct* live pages, so sharing makes the
    pool measurably cheaper, not just differently bookkept.
    """

    def __init__(self, num_pages: int, reserved: int = 1):
        if num_pages <= reserved:
            raise ValueError(
                f"num_pages={num_pages} must exceed reserved={reserved}")
        self.num_pages = int(num_pages)
        self.reserved = int(reserved)
        self._free: List[int] = list(range(num_pages - 1, reserved - 1, -1))
        self._owned: Dict[Any, List[int]] = {}
        self._refs: Dict[int, int] = {}

    @property
    def capacity(self) -> int:
        return self.num_pages - self.reserved

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        """Distinct live pages (a shared page counts once)."""
        return len(self._refs)

    def owned(self, owner) -> List[int]:
        return list(self._owned.get(owner, ()))

    def refcount(self, page: int) -> int:
        return self._refs.get(int(page), 0)

    def alloc(self, owner, n_pages: int) -> Optional[List[int]]:
        """Hand ``n_pages`` to ``owner`` (appending to any existing grant).
        Returns the new pages, or None (state unchanged) on pressure."""
        if n_pages < 0:
            raise ValueError("n_pages must be >= 0")
        if n_pages > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n_pages)]
        for p in pages:
            self._refs[p] = 1
        self._owned.setdefault(owner, []).extend(pages)
        return pages

    def share(self, owner, pages: Sequence[int]) -> None:
        """Register ``owner`` as a co-owner of live ``pages`` (prefix
        reuse): each page's refcount rises by one and the page joins the
        owner's grant list in the given order (block-table rows are written
        from that order, so callers share *before* any fresh ``alloc``)."""
        pages = [int(p) for p in pages]
        for p in pages:
            if p not in self._refs:
                raise ValueError(
                    f"page {p} is not live — only allocated pages can be "
                    f"shared")
        for p in pages:
            self._refs[p] += 1
            self._owned.setdefault(owner, []).append(p)

    def free(self, owner) -> List[int]:
        """Drop one reference per page ``owner`` held; pages whose refcount
        hits zero return to the free list.  Returns the owner's pages."""
        pages = self._owned.pop(owner, [])
        for p in pages:
            self._refs[p] -= 1
            if not self._refs[p]:
                del self._refs[p]
                self._free.append(p)
        return pages

    # the refcount-era verb; ``free`` kept as the historical name
    release = free

    def release_pages(self, owner, pages: Sequence[int]) -> List[int]:
        """Partial release (rollback): drop one reference for each of
        ``pages`` from ``owner``'s grant.  A page co-owned by someone else
        (a prefix-index node, another slot) only loses this owner's
        reference; a page whose *last* reference goes returns to the free
        list.  Returns the pages actually freed.  Raises if ``owner`` does
        not hold one of the pages — rolling back pages you never owned is
        a caller bug, not pressure."""
        held = self._owned.get(owner)
        freed: List[int] = []
        for p in pages:
            p = int(p)
            if held is None or p not in held:
                raise ValueError(
                    f"owner {owner!r} does not hold page {p}")
            held.remove(p)
            self._refs[p] -= 1
            if not self._refs[p]:
                del self._refs[p]
                self._free.append(p)
                freed.append(p)
        if held is not None and not held:
            self._owned.pop(owner, None)
        return freed

    def check_invariants(self) -> None:
        counts: Dict[int, int] = {}
        for owner, pages in self._owned.items():
            seen_here = set()
            for p in pages:
                assert self.reserved <= p < self.num_pages, p
                assert p not in seen_here, \
                    f"page {p} listed twice for owner {owner!r}"
                seen_here.add(p)
                counts[p] = counts.get(p, 0) + 1
        assert counts == self._refs, (
            f"refcounts drifted from owner lists: {self._refs} vs {counts}")
        free = set(self._free)
        assert len(free) == len(self._free), "free list holds duplicates"
        assert not (set(counts) & free), "live page also on the free list"
        assert self.num_free + self.pages_in_use == self.capacity


# ---------------------------------------------------------------------------
# Radix prefix index (cross-request prefix caching)
# ---------------------------------------------------------------------------

# root key of the radix tree; node keys are rolling hashes and never 0
_PREFIX_ROOT = 0


def _chunk_key(parent_key: int, tokens: tuple) -> int:
    """Rolling content hash of one page-sized token chunk: the node key is
    ``hash((parent_key, tokens))``, so a chunk's key commits to the entire
    token prefix before it.  Int/tuple-of-int hashing is unsalted in
    CPython, so keys are stable within a process; ``| 1`` keeps keys off
    the root sentinel.  Lookups still verify ``(parent, tokens)`` on the
    node, so a collision degrades to a cache miss, never to wrong pages."""
    return hash((parent_key, tokens)) | 1


class _PrefixNode:
    """One cached page: ``tokens`` (page_size ids) extending ``parent``,
    pinning live page id ``page``.  ``fp`` optionally carries host-side
    numpy snapshots of the fp prefill-view scratch for this page (keyed by
    ``(stage_idx, sub_name)``) — the paged_vq layout decodes from codes but
    *prefills* against exact fp views, so bitwise reuse parity needs the
    original values, not a dequantization."""

    __slots__ = ("key", "parent", "tokens", "page", "fp", "tick")

    def __init__(self, key, parent, tokens, page, fp=None):
        self.key = key
        self.parent = parent
        self.tokens = tokens
        self.page = int(page)
        self.fp = fp
        self.tick = 0


class PrefixIndex:
    """Radix tree over page-sized token chunks -> live page ids.

    Host-side only.  Each node holds one reference on its page (allocator
    owner ``("px", key)`` — see ``PagedKVCache.prefix_insert``), so index
    residency alone keeps a page alive after its request retires.  LRU is
    a monotone touch tick; eviction removes least-recently-touched
    *leaves* first, which keeps every cached chain contiguous from the
    root."""

    def __init__(self, page_size: int, need_fp: bool = False):
        self.page_size = int(page_size)
        self.need_fp = bool(need_fp)
        self.nodes: Dict[int, _PrefixNode] = {}
        self._children: Dict[int, set] = {}
        self._tick = 0
        self.hits = 0
        self.hit_tokens = 0
        self.insertions = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self.nodes)

    def _lookup(self, parent: int, tokens: tuple) -> Optional[_PrefixNode]:
        node = self.nodes.get(_chunk_key(parent, tokens))
        if node is None or node.parent != parent or node.tokens != tokens:
            return None
        if self.need_fp and node.fp is None:
            return None
        return node

    def match(self, prompt: Sequence[int]) -> List[_PrefixNode]:
        """Longest chain of full page-chunk matches from the root."""
        ps = self.page_size
        out: List[_PrefixNode] = []
        parent = _PREFIX_ROOT
        for i in range(len(prompt) // ps):
            node = self._lookup(parent, tuple(prompt[i * ps:(i + 1) * ps]))
            if node is None:
                break
            out.append(node)
            parent = node.key
        return out

    def best_partial(self, parent: int, rem: Sequence[int]):
        """Child of ``parent`` sharing the longest nonzero token prefix
        with ``rem`` — the copy-on-write fork candidate.  Returns
        ``(node, common_len)`` or None."""
        rem = tuple(rem)
        best, best_len = None, 0
        for key in self._children.get(parent, ()):
            node = self.nodes[key]
            if self.need_fp and node.fp is None:
                continue
            common = 0
            for a, b in zip(node.tokens, rem):
                if a != b:
                    break
                common += 1
            if common > best_len:
                best, best_len = node, common
        return (best, best_len) if best is not None else None

    def touch(self, nodes: Sequence[_PrefixNode]) -> None:
        for node in nodes:
            self._tick += 1
            node.tick = self._tick

    def add(self, parent: int, tokens: tuple, page: int,
            fp=None) -> _PrefixNode:
        key = _chunk_key(parent, tokens)
        node = _PrefixNode(key, parent, tokens, page, fp)
        self.nodes[key] = node
        self._children.setdefault(parent, set()).add(key)
        self.insertions += 1
        self.touch([node])
        return node

    def lru_leaf(self) -> Optional[_PrefixNode]:
        leaves = [n for n in self.nodes.values()
                  if not self._children.get(n.key)]
        return min(leaves, key=lambda n: n.tick) if leaves else None

    def remove(self, node: _PrefixNode) -> None:
        del self.nodes[node.key]
        self._children.pop(node.key, None)
        siblings = self._children.get(node.parent)
        if siblings is not None:
            siblings.discard(node.key)
            if not siblings:
                del self._children[node.parent]
        self.evictions += 1

    def stats(self) -> Dict[str, int]:
        return {"nodes": len(self.nodes), "hits": self.hits,
                "hit_tokens": self.hit_tokens,
                "insertions": self.insertions, "evictions": self.evictions}


def snapshot_prefill_scratch(caches: List[Dict], num_tokens: int,
                             page_size: int) -> Optional[List[Dict]]:
    """Host numpy copies of the fp prefill-view scratch, one dict per full
    prompt page (``{(stage_idx, sub_name): (k_page, v_page)}`` with pages
    shaped ``(reps, 1, page_size, heads, head_dim)``).

    The paged_vq layout persists only VQ codes; the exact fp values exist
    transiently in the prefill scratch slabs and are stripped before
    decode.  Prefix nodes keep these snapshots so a later hit can re-seed a
    fresh request's scratch with the *original* values — dequantizing codes
    instead would break bitwise parity with a cold prefill.  Returns None
    when the tree carries no scratch (the plain paged layout)."""
    n_full = int(num_tokens) // int(page_size)
    slabs = {}
    for si, stage in enumerate(caches):
        for name, sub in stage.items():
            if PREFILL_SCRATCH_KEYS & set(sub):
                slabs[(si, name)] = (np.asarray(sub["k_fp"]),
                                     np.asarray(sub["v_fp"]))
    if not slabs or not n_full:
        return None if not slabs else []
    pages: List[Dict] = []
    for i in range(n_full):
        a, b = i * page_size, (i + 1) * page_size
        pages.append({key: (k[:, :, a:b].copy(), v[:, :, a:b].copy())
                      for key, (k, v) in slabs.items()})
    return pages


def hydrate_prefill_scratch(caches: List[Dict], fp_pages: Sequence[Dict],
                            reuse: int, page_size: int) -> List[Dict]:
    """Write prefix-node fp snapshots into a fresh prefill cache's scratch
    slabs for positions ``[0, reuse)`` (host-side assembly, one device
    transfer per slab — no jit, so nothing re-specializes).  Positions at
    and beyond ``reuse`` stay zero; the tail chunks overwrite them before
    any attention view reads them (scatter precedes the gathered view in
    ``chunk_attend``, and the causal mask hides unwritten keys)."""
    import jax.numpy as jnp

    out: List[Dict] = []
    for si, stage in enumerate(caches):
        new_stage = {}
        for name, sub in stage.items():
            if PREFILL_SCRATCH_KEYS & set(sub):
                k = np.asarray(sub["k_fp"]).copy()
                v = np.asarray(sub["v_fp"]).copy()
                for i, page in enumerate(fp_pages):
                    a = i * page_size
                    m = min(page_size, int(reuse) - a)
                    if m <= 0 or page is None:
                        break
                    pk, pv = page[(si, name)]
                    k[:, :, a:a + m] = pk[:, :, :m]
                    v[:, :, a:a + m] = pv[:, :, :m]
                sub = dict(sub)
                sub["k_fp"] = jnp.asarray(k, sub["k_fp"].dtype)
                sub["v_fp"] = jnp.asarray(v, sub["v_fp"].dtype)
            new_stage[name] = sub
        out.append(new_stage)
    return out


# ---------------------------------------------------------------------------
# Preemption swap arena
# ---------------------------------------------------------------------------


def snapshot_slot(caches: List[Dict], slot: int, table_row_for):
    """Host numpy snapshot of everything ``slot`` holds in a cache tree:
    per pool sub, the pages its block-table row points at (span-shaped —
    ungranted tail entries gather the scratch page, junk that the restore
    scatter routes straight back to scratch, so payload shapes are fixed
    and the restore jit compiles once); per dense sub, the ``(R, 1, ...)``
    slot rows ``merge_slot`` would write.  ``table_row_for(kind)`` maps an
    attention-kind name to its group's block-table row (unused on slab
    trees, which have no pool subs).  Returns ``(pages, dense, nbytes)``."""
    import jax

    pages: List[Dict] = []
    dense: List[Dict] = []
    for stage in caches:
        p_stage: Dict[str, Dict] = {}
        d_stage: Dict[str, Any] = {}
        for name, sub in stage.items():
            if is_paged_sub(sub):
                ids = table_row_for(name)
                p_stage[name] = {k: np.asarray(v[:, ids])
                                 for k, v in sub.items()
                                 if k in PAGED_LEAF_KEYS}
            else:
                d_stage[name] = jax.tree.map(
                    lambda leaf: np.asarray(leaf[:, slot:slot + 1]), sub)
        pages.append(p_stage)
        dense.append(d_stage)
    nbytes = sum(leaf.nbytes
                 for leaf in jax.tree.leaves((pages, dense)))
    return pages, dense, nbytes


def restore_slot(live: List[Dict], pages: List[Dict], dests: List[Dict],
                 dense: List[Dict], slot) -> List[Dict]:
    """Device-side inverse of ``snapshot_slot`` (jit-traced; the
    scheduler's wrapper donates ``live``).  Pool payloads scatter into the
    slot's *new* block-table rows (``dests``) — the junk tail entries land
    on reserved scratch page 0, which no valid read ever sees — and dense
    rows merge back at ``slot`` exactly like ``merge_slot``.  All shapes
    are fixed (span-shaped payloads, ``(R, 1, ...)`` rows), so one compile
    covers every restore regardless of how many pages the victim held."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def one(batch_leaf, row):
        return lax.dynamic_update_slice_in_dim(
            batch_leaf, jnp.asarray(row).astype(batch_leaf.dtype),
            jnp.asarray(slot), axis=1)

    out = []
    for l_stage, p_stage, t_stage, d_stage in zip(live, pages, dests, dense):
        sub_out = {}
        for name, l_sub in l_stage.items():
            if is_paged_sub(l_sub):
                ids = t_stage[name]
                pay = p_stage[name]
                sub_out[name] = {
                    k: (v.at[:, ids].set(jnp.asarray(pay[k]).astype(v.dtype))
                        if k in PAGED_LEAF_KEYS else v)
                    for k, v in l_sub.items()}
            else:
                sub_out[name] = jax.tree.map(one, l_sub, d_stage[name])
        out.append(sub_out)
    return out


@dataclasses.dataclass
class SwapEntry:
    """One preempted request's host-resident cache state: the page payload
    and dense rows from ``snapshot_slot``, the token high-water to re-grant
    on restore, the decode cursor (``length``/``cur_token``), and — for
    ``paged_vq`` under the prefix cache — the per-page fp prefill scratch
    snapshots that keep a later ``prefix_insert`` bitwise-exact."""

    uid: int
    granted: int
    pages: List[Dict]
    dense: List[Dict]
    length: int = 0
    cur_token: int = 0
    fp_pages: Optional[List] = None
    nbytes: int = 0


class SwapArena:
    """Host-side arena for preempted requests' swapped cache state, keyed
    by request uid, with swap-traffic accounting (counts + bytes each way;
    ``paged_vq`` entries hold code pages, so they are ~16x smaller than
    their fp equivalents — Appendix G applied to the memory hierarchy).

    The backing ``_swapped`` dict is private to ``serving/kv_cache.py``
    (enforced by the ``swap-arena-internals`` lint rule); schedulers use
    ``stash``/``holds``/``peek``/``pop``/``stats``."""

    def __init__(self) -> None:
        self._swapped: Dict[int, SwapEntry] = {}
        self.swap_outs = 0
        self.swap_ins = 0
        self.bytes_out = 0
        self.bytes_in = 0

    def __len__(self) -> int:
        return len(self._swapped)

    def holds(self, uid) -> bool:
        return uid in self._swapped

    def stash(self, entry: SwapEntry) -> None:
        if entry.uid in self._swapped:
            raise ValueError(f"uid {entry.uid} is already swapped out")
        self._swapped[entry.uid] = entry
        self.swap_outs += 1
        self.bytes_out += entry.nbytes

    def peek(self, uid) -> SwapEntry:
        """The entry for ``uid`` without swapping it in (grant sizing)."""
        return self._swapped[uid]

    def pop(self, uid) -> SwapEntry:
        """Swap ``uid`` back in: remove and return its entry."""
        entry = self._swapped.pop(uid)
        self.swap_ins += 1
        self.bytes_in += entry.nbytes
        return entry

    @property
    def resident_bytes(self) -> int:
        return sum(e.nbytes for e in self._swapped.values())

    def stats(self) -> Dict[str, int]:
        return {"swap_outs": self.swap_outs, "swap_ins": self.swap_ins,
                "bytes_out": self.bytes_out, "bytes_in": self.bytes_in,
                "resident": len(self._swapped),
                "resident_bytes": self.resident_bytes}


# ---------------------------------------------------------------------------
# Paged KV cache
# ---------------------------------------------------------------------------


class _PageGroup:
    """One block-table group: its own id space, allocator(s) and table
    width.

    With ``shards > 1`` (sequence-sharded paged pools) the group splits
    into per-shard ``PageAllocator`` instances behind the same protocol:
    shard ``i`` owns the global page-id range ``[i*n, (i+1)*n)`` (``n =
    num_pages // shards``) and table entry ``j`` draws from shard
    ``j // (span // shards)`` — so the device-side shard_map can slice its
    own table columns and find only its own page ids there, and each shard
    reserves its own local scratch page (global id ``i*n``).  Admission
    stalls when *any* needed shard's allocator runs dry."""

    def __init__(self, name: str, slots: int, span: int, num_pages: int,
                 shards: int = 1):
        self.name = name
        self.span = int(span)
        self.shards = int(shards)
        if self.shards > 1 and self.span % self.shards:
            raise ValueError(
                f"page group {name!r}: table span {self.span} must divide "
                f"across {self.shards} sequence shards (use a max_len that "
                f"is a multiple of shards * page_size)")
        if int(num_pages) % self.shards:
            raise ValueError(
                f"page group {name!r}: num_pages={num_pages} must be a "
                f"multiple of the {self.shards} sequence shards")
        self.num_pages = int(num_pages)
        self.pages_per_shard = self.num_pages // self.shards
        self.allocators = [PageAllocator(self.pages_per_shard)
                           for _ in range(self.shards)]
        self.block_table = np.zeros((slots, self.span), np.int32)

    @property
    def allocator(self) -> PageAllocator:
        """Single-allocator view (shard 0) for unsharded callers — the
        prefix index goes through this, and sharded groups never enable
        prefix caching (``prefix_shareable`` is False under the mesh)."""
        return self.allocators[0]

    def _shard_of_entry(self, entry: int) -> int:
        return entry * self.shards // self.span

    def entries_granted(self, owner) -> int:
        """Table entries granted to ``owner`` (entries always grow as a
        prefix ``[0, have)``, so the per-shard owned counts sum to it)."""
        return sum(len(a.owned(owner)) for a in self.allocators)

    def _need_per_shard(self, owner, need: int) -> Dict[int, List[int]]:
        have = self.entries_granted(owner)
        per: Dict[int, List[int]] = {}
        for j in range(have, need):
            per.setdefault(self._shard_of_entry(j), []).append(j)
        return per

    def can_grow(self, owner, need: int) -> bool:
        return all(len(js) <= self.allocators[s].num_free
                   for s, js in self._need_per_shard(owner, need).items())

    def grow(self, owner, need: int) -> None:
        """Grant the table entries ``[have, need)`` from their owning
        shards' allocators, writing *global* page ids into the table.
        Callers pre-check ``can_grow``."""
        per = self._need_per_shard(owner, need)
        for s in sorted(per):
            js = per[s]
            pages = self.allocators[s].alloc(owner, len(js))
            assert pages is not None  # pre-checked by can_grow
            base = s * self.pages_per_shard
            for j, p in zip(js, pages):
                self.block_table[owner, j] = base + p

    def shrink(self, owner, keep: int) -> int:
        """Release the table entries past ``keep`` (rollback tail); a page
        co-owned by the prefix index or another slot only drops this
        owner's reference.  Returns the pages actually freed."""
        have = self.entries_granted(owner)
        freed = 0
        for j in range(max(int(keep), 0), have):
            s = self._shard_of_entry(j)
            local = (int(self.block_table[owner, j])
                     - s * self.pages_per_shard)
            freed += len(self.allocators[s].release_pages(owner, [local]))
            self.block_table[owner, j] = 0
        return freed

    def free_owner(self, owner) -> int:
        """Retire ``owner``: return all its pages, point its row at
        scratch."""
        n = 0
        for a in self.allocators:
            n += len(a.free(owner))
        self.block_table[owner, :] = 0
        return n

    def can_ever_fit_entries(self, need: int) -> bool:
        if self.shards > 1:
            # entries spread across shards; shard 0 carries the most
            need = min(need, self.span // self.shards)
        return need <= self.allocators[0].capacity

    @property
    def pages_in_use(self) -> int:
        return sum(a.pages_in_use for a in self.allocators)

    def check_invariants(self) -> None:
        for a in self.allocators:
            a.check_invariants()


class PagedKVCache:
    """Per-group block tables + page pools for the serving engines.

    Host side: one ``PageAllocator`` and ``(slots, span)`` int32 block table
    per page group (row = slot, entry = page id, 0 = scratch).  Device side:
    ``init_cache()`` builds the model cache tree whose attention leaves are
    ``(num_pages, page_size, ...)`` pools — fp K/V pages for "paged",
    uint8/16 code pages for "paged_vq" — which the engines thread through
    the jitted prefill/decode steps unchanged-shape.  Windowed layers read
    and write through the narrower "window" table as a page ring.
    """

    def __init__(self, cfg: ModelConfig, *, slots: int, max_len: int, ctx,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 dtype=None):
        import jax.numpy as jnp

        if not ctx.backend.paged:
            raise ValueError(
                f"ctx backend {ctx.backend.name!r} is not a paged backend")
        if page_size <= 0:
            raise ValueError("page_size must be positive")
        if max_len % page_size:
            raise ValueError(
                f"max_len={max_len} must be a multiple of page_size="
                f"{page_size} (the paged decode view spans max_len exactly)")
        self.cfg = cfg
        self.ctx = ctx
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.page_size = int(page_size)
        self.max_pages = max_len // page_size
        self.dtype = jnp.float32 if dtype is None else dtype
        self.spans = page_group_spans(cfg, max_len, page_size)
        if not self.spans:
            raise ValueError(f"{cfg.name}: no attention layers to page")
        self.dominant = dominant_group(self.spans)
        # under a sequence-sharded mesh the global pool splits into
        # per-shard allocators (shard-local pages, global ids); window
        # rings stay replicated and keep one allocator
        mesh = getattr(ctx, "mesh", None)
        self.seq_shards = (int(mesh.num_seq_shards)
                           if getattr(ctx, "seq_sharded", False)
                           and mesh is not None else 1)
        self.groups: Dict[str, _PageGroup] = {}
        for name, span in self.spans.items():
            shards = self.seq_shards if name == "global" else 1
            n = (int(num_pages) if num_pages and name == self.dominant
                 else self.slots * span + shards)
            self.groups[name] = _PageGroup(name, self.slots, span, n,
                                           shards=shards)
        # engine-facing compat: the dominant group's knobs
        self.num_pages = self.groups[self.dominant].num_pages
        # cross-request prefix index; None until enable_prefix_cache()
        self.prefix: Optional[PrefixIndex] = None
        # per-slot granted token high-water (what ``advance`` covered);
        # ``rollback`` retreats it and frees the tail pages it implies
        self._granted: Dict[Any, int] = {}
        # host-side swap arena for preempted requests (uid -> SwapEntry)
        self.arena = SwapArena()

    # -- host-side bookkeeping ----------------------------------------------
    @property
    def allocator(self) -> PageAllocator:
        return self.groups[self.dominant].allocator

    @property
    def block_tables(self) -> np.ndarray:
        return self.groups[self.dominant].block_table

    @property
    def num_pages_by_group(self) -> Dict[str, int]:
        return {name: g.num_pages for name, g in self.groups.items()}

    def pages_for(self, num_tokens: int) -> int:
        return -(-max(int(num_tokens), 1) // self.page_size)

    def group_pages_for(self, name: str, num_tokens: int) -> int:
        return min(self.pages_for(num_tokens), self.groups[name].span)

    def can_allocate(self, slot, num_tokens: int) -> bool:
        return all(g.can_grow(slot, self.group_pages_for(name, num_tokens))
                   for name, g in self.groups.items())

    def can_ever_fit(self, num_tokens: int) -> bool:
        return all(g.can_ever_fit_entries(
                       self.group_pages_for(name, num_tokens))
                   for name, g in self.groups.items())

    def advance(self, slot, num_tokens: int) -> bool:
        """Grow ``slot``'s grant in every group to cover ``num_tokens`` total
        tokens.  False (state unchanged) on allocator pressure."""
        if not self.can_allocate(slot, num_tokens):
            return False
        for name, g in self.groups.items():
            g.grow(slot, self.group_pages_for(name, num_tokens))
        self._granted[slot] = max(self._granted.get(slot, 0),
                                  int(num_tokens))
        return True

    # historical name (PR 2 API); ``advance`` is the CacheBackend verb
    allocate = advance

    def granted(self, slot) -> int:
        """Token high-water ``advance`` has covered for ``slot``."""
        return self._granted.get(slot, 0)

    def rollback(self, slot, n: int) -> int:
        """Retreat ``slot``'s token grant by ``n`` tokens and return the
        tail pages that implies (speculative-decode rejection, preemption).

        Full-span groups (no ring wrap: span == max_len/page_size) free the
        pages past the new grant and point their table entries back at
        scratch; true window rings keep every page — each ring page still
        holds live in-window positions regardless of where the length
        retreats to.  A tail page shared from the prefix index only drops
        this slot's reference (``PageAllocator.release_pages``) — a
        co-owned page never returns to the free list here.  Returns the
        number of pages actually freed."""
        n = int(n)
        if n < 0:
            raise ValueError("rollback n must be >= 0")
        if n == 0:
            return 0
        new_tokens = max(self._granted.get(slot, 0) - n, 0)
        self._granted[slot] = new_tokens
        freed = 0
        for name, g in self.groups.items():
            if g.span < self.max_pages:
                continue  # ring: every page may hold live window positions
            keep = self.group_pages_for(name, new_tokens) if new_tokens \
                else 0
            freed += g.shrink(slot, keep)
        return freed

    def free(self, slot) -> int:
        """Retire a request: return all its pages, point the rows at
        scratch."""
        n = 0
        for g in self.groups.values():
            n += g.free_owner(slot)
        self._granted.pop(slot, None)
        return n

    # -- preemption swap ----------------------------------------------------
    def swap_out(self, slot, caches) -> SwapEntry:
        """Host snapshot of everything ``slot`` owns, for preemption: the
        pages its block-table rows point at (``paged_vq``: code pages —
        ~16x cheaper than fp) plus its rows of every dense leaf.  Pure
        read — the caller then drops the slot's page references
        (``CacheBackend.release``; prefix-shared pages survive via their
        other owners' refcounts), requeues the request, and later restores
        with ``advance`` + ``swap_dests`` + ``restore_slot``."""
        pages, dense, nbytes = snapshot_slot(
            caches, slot,
            lambda kind: np.asarray(
                self.groups[page_group_for(kind, self.cfg)]
                .block_table[slot], np.int32))
        return SwapEntry(uid=-1, granted=self.granted(slot), pages=pages,
                         dense=dense, nbytes=nbytes)

    def swap_dests(self, slot, pages: List[Dict]) -> List[Dict]:
        """Destination block-table rows for ``restore_slot``, mirroring a
        swap payload's stage/kind structure — call after re-granting the
        slot so the rows hold the fresh page ids."""
        return [{kind: np.asarray(
                     self.groups[page_group_for(kind, self.cfg)]
                     .block_table[slot], np.int32)
                 for kind in p_stage} for p_stage in pages]

    @property
    def pages_in_use(self) -> int:
        return sum(g.pages_in_use for g in self.groups.values())

    def check_invariants(self) -> None:
        """Allocator bookkeeping balances in every page group (refcounts
        match owner lists, free list disjoint from live pages)."""
        for g in self.groups.values():
            g.check_invariants()

    # -- cross-request prefix caching ---------------------------------------
    @property
    def prefix_shareable(self) -> bool:
        """True when page sharing is content-addressable for this model: a
        page id indexes *every* layer's pool in its group, so two requests
        may share a page only if every attention layer's KV at those
        positions is a pure function of the token prefix — i.e. all-global
        causal attention, no windowed rings, no recurrent state folded
        across chunk boundaries."""
        from repro.models.transformer import ATTN_KINDS, stages

        if set(self.groups) != {"global"}:
            return False
        if any(g.shards > 1 for g in self.groups.values()):
            # per-shard allocators don't share pages across requests (a
            # shared chain would pin the same shard-local ids on every
            # shard); prefix caching stays a single-host feature
            return False
        return all(kind in ATTN_KINDS and not _attn_kind_window(kind, self.cfg)
                   for kinds, _ in stages(self.cfg) for kind in kinds)

    def enable_prefix_cache(self) -> None:
        if not self.prefix_shareable:
            raise ValueError(
                f"{self.cfg.name}: prefix caching needs an all-global-"
                f"attention stack (groups={sorted(self.groups)}) — windowed "
                f"rings and recurrent state are not content-addressable")
        self.prefix = PrefixIndex(self.page_size,
                                  need_fp=self.ctx.backend.vq_codes)

    def prefix_grant(self, slot, prompt: Sequence[int], tokens_needed: int):
        """Admission grant through the prefix index: attach the longest
        cached prefix to ``slot``'s block-table row via shared pages, then
        allocate the rest.  Returns ``(reuse_tokens, cow, fp_pages)`` —
        ``cow`` is a ``(src_page, dst_page)`` copy-on-write fork when the
        reuse boundary splits a cached page, ``fp_pages`` the matched
        nodes' fp snapshots (vq hydration) — or None on allocator pressure
        (only LRU evictions may have happened; the slot is untouched).

        Reuse is capped at ``len(prompt) - 1`` tokens: the final prompt
        token's chunk must run to produce ``last_logits``."""
        prompt = list(prompt)
        n = len(prompt)
        ps = self.page_size
        g = self.groups["global"]
        if self.prefix is None:
            return (0, None, None) if self.advance(slot, tokens_needed) \
                else None
        # longest full-page chain, capped so >= 1 prompt token remains
        nodes = self.prefix.match(prompt)[:max(n - 1, 0) // ps]
        parent = nodes[-1].key if nodes else _PREFIX_ROOT
        matched = len(nodes) * ps
        partial = self.prefix.best_partial(parent, prompt[matched:])
        extra = 0
        if partial is not None:
            extra = min(partial[1], (n - 1) - matched)
        cow_node = partial[0] if extra > 0 else None
        self.prefix.touch(nodes + ([cow_node] if cow_node else []))
        # pressure: fresh pages needed beyond the shared ones
        need_total = self.group_pages_for("global", tokens_needed)
        fresh_needed = need_total - len(nodes)
        while fresh_needed > g.allocator.num_free:
            if not self._prefix_evict_one():
                return None
        for i, node in enumerate(nodes):
            g.allocator.share(slot, [node.page])
            g.block_table[slot, i] = node.page
        cow = None
        if cow_node is not None:
            dst = g.allocator.alloc(slot, 1)
            assert dst is not None  # covered by the pressure loop
            g.block_table[slot, len(nodes)] = dst[0]
            cow = (cow_node.page, dst[0])
        ok = self.advance(slot, tokens_needed)
        assert ok, "pressure loop guaranteed the fresh pages"
        reuse = matched + extra
        if reuse:
            self.prefix.hits += 1
            self.prefix.hit_tokens += reuse
        fp_pages = None
        if self.prefix.need_fp:
            fp_pages = [node.fp for node in nodes]
            if cow_node is not None:
                fp_pages.append(cow_node.fp)
        return reuse, cow, fp_pages

    def prefix_insert(self, slot, prompt: Sequence[int],
                      fp_pages=None) -> int:
        """Insert ``slot``'s prompt-region *full* pages into the index (at
        retirement, before ``free(slot)`` drops the slot's references).
        Each new node takes its own reference on the page, so the page
        outlives the request.  Returns the number of nodes added."""
        if self.prefix is None:
            return 0
        ps = self.page_size
        g = self.groups["global"]
        prompt = list(prompt)
        inserted = 0
        parent = _PREFIX_ROOT
        for i in range(len(prompt) // ps):
            chunk = tuple(prompt[i * ps:(i + 1) * ps])
            node = self.prefix._lookup(parent, chunk)
            if node is not None:  # chain already cached: refresh, descend
                self.prefix.touch([node])
                parent = node.key
                continue
            if self.prefix.nodes.get(_chunk_key(parent, chunk)) is not None:
                break  # hash collision or fp-less twin: stop extending
            page = int(g.block_table[slot, i])
            if page < g.allocator.reserved:
                break  # defensive: never index the scratch page
            fp = fp_pages[i] if fp_pages and i < len(fp_pages) else None
            if self.prefix.need_fp and fp is None:
                break
            key = _chunk_key(parent, chunk)
            g.allocator.share(("px", key), [page])
            self.prefix.add(parent, chunk, page, fp)
            inserted += 1
            parent = key
        return inserted

    def _prefix_evict_one(self) -> bool:
        """Evict the least-recently-touched index leaf; its page returns to
        the free list only if no live request still co-owns it."""
        node = self.prefix.lru_leaf() if self.prefix else None
        if node is None:
            return False
        self.groups["global"].allocator.free(("px", node.key))
        self.prefix.remove(node)
        return True

    def tables(self) -> Dict[str, Any]:
        """Device copies of the block tables (fixed shapes: compile-once)."""
        import jax.numpy as jnp

        return {name: jnp.asarray(g.block_table)
                for name, g in self.groups.items()}

    # -- device-side pools --------------------------------------------------
    def init_cache(self, batch: Optional[int] = None,
                   prefill_scratch: bool = False):
        """Model cache tree: shared page pools for attention layers, batched
        dense state for ring/recurrent/ssm layers (``prefill_scratch`` adds
        the fp prefill-view slabs chunked vq prefill carries)."""
        from repro.models import transformer as tlm

        return self.ctx.backend.commit_caches(
            tlm.init_lm_cache(self.cfg, batch or self.slots, self.max_len,
                              self.ctx, self.dtype,
                              page_size=self.page_size,
                              num_pages=self.num_pages_by_group,
                              prefill_scratch=prefill_scratch), self.ctx)

    def pool_bytes(self, caches=None) -> int:
        """Measured page-pool bytes (materialized if ``caches`` given, else
        the analytic page-granular size)."""
        if caches is not None:
            return pool_bytes(caches)
        return paged_pool_bytes(
            self.cfg, max_len=self.max_len, page_size=self.page_size,
            vq_codes=self.ctx.backend.vq_codes, slots=self.slots,
            num_pages=self.num_pages,
            dtype_bytes=np.dtype(self.dtype).itemsize)


class SlabCache:
    """Host-side cache handle for the contiguous slab backends — the same
    duck-typed surface as ``PagedKVCache`` so the engines never branch on
    the cache layout (``advance``/``free`` are trivial: a slab row always
    holds ``max_len`` positions)."""

    pages_in_use = 0

    def __init__(self, cfg: ModelConfig, *, slots: int, max_len: int, ctx,
                 dtype=None):
        import jax.numpy as jnp

        self.cfg = cfg
        self.ctx = ctx
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.dtype = jnp.float32 if dtype is None else dtype
        # host-side swap arena for preempted requests (uid -> SwapEntry)
        self.arena = SwapArena()

    def advance(self, slot, num_tokens: int) -> bool:
        return int(num_tokens) <= self.max_len

    allocate = advance

    def can_ever_fit(self, num_tokens: int) -> bool:
        return int(num_tokens) <= self.max_len

    def free(self, slot) -> int:
        return 0

    def rollback(self, slot, n: int) -> int:
        """Slab rows always span ``max_len``: a length retreat frees
        nothing (device-side ring restoration is ``verify_rollback``'s
        job).  Kept for the ``CacheBackend.rollback`` contract."""
        if int(n) < 0:
            raise ValueError("rollback n must be >= 0")
        return 0

    def tables(self) -> None:
        return None

    # -- preemption swap ----------------------------------------------------
    def swap_out(self, slot, caches) -> SwapEntry:
        """Slab swap-out: no page pools — the per-slot rows of every dense
        leaf are the whole state, so slot preemption works on the
        contiguous fp/vq layouts too (at slab cost: a full ``max_len``
        row each way instead of page-granular payloads)."""
        pages, dense, nbytes = snapshot_slot(caches, slot, None)
        return SwapEntry(uid=-1, granted=self.max_len, pages=pages,
                         dense=dense, nbytes=nbytes)

    def swap_dests(self, slot, pages: List[Dict]) -> List[Dict]:
        """No pool leaves on a slab tree: one empty dict per stage."""
        return [{} for _ in pages]

    def init_cache(self, batch: Optional[int] = None,
                   prefill_scratch: bool = False):
        from repro.models import transformer as tlm

        return self.ctx.backend.commit_caches(
            tlm.init_lm_cache(self.cfg, batch or self.slots, self.max_len,
                              self.ctx, self.dtype,
                              prefill_scratch=prefill_scratch), self.ctx)

    def pool_bytes(self, caches=None) -> int:
        return 0
