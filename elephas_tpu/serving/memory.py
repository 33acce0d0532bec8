"""Paged serving memory: block allocator, radix prefix cache, paged KV.

The dense :class:`~elephas_tpu.serving.cache.SlotKVCache` pins
``slots × capacity`` KV rows in HBM whether or not anyone is using them;
concurrency is capped by the worst case. This module replaces that with a
vLLM-style paged layout:

* **Physical pool** ``{"k"/"v": [L, P, Hkv, page, Dh]}`` — ``P`` fixed-size
  pages per partition (local: one partition; mesh: ``dp·sp`` partitions,
  pool rows sharded over both axes). Page 0 of every partition is the
  **trash page**: its refcount is pinned to 1, unallocated block-table
  cells point at it, and dead/parked rows' garbage writes land there.
* **Block tables** ``[S, M]`` int32 — per-slot maps from logical page
  index to LOCAL physical page id. Attention reads through the table
  DIRECTLY: the fused paged kernels
  (:mod:`~elephas_tpu.ops.paged_attention`, wired through
  ``TransformerLM.decode_step_paged`` / ``decode_chunk_paged``) stream
  K/V pages out of the pool via block index maps dereferencing the
  table, and each layer scatters only the NEWLY PRODUCED rows into their
  owning pages — O(new tokens) traffic, no dense-layout round trip. On
  CPU the reference path gathers a transient per-slot view whose time
  axis equals the dense capacity and applies the exact dense attention
  math, so its reductions group identically to the dense path. That is
  the bit-identity contract, and it is why ``page`` must divide the
  per-shard cache length.
* **Refcounts + radix prefix cache** — full prompt pages are registered
  in a radix tree keyed on their token content at page granularity.
  A later request with the same prefix *adopts* the cached pages (pure
  incref — it skips prefill for them) and shares them copy-on-write:
  fork = incref, divergence lands in a fresh tail page. Sharing is sound
  bitwise because every local attention path reduces over the full
  capacity axis with masked positions contributing exactly zero, making
  a page's K/V content a pure function of the token prefix regardless of
  how prefill was chunked.
* **Multi-tenant adapters** — a per-slot adapter-id vector rides along
  with the table; models exposing ``adapter_context`` (see
  :class:`~elephas_tpu.models.lora.MultiTenantLM`) apply their per-slot
  low-rank deltas inside the very same compiled decode/insert kernels.

Host bookkeeping (refcounts, tables, radix tree) is pure Python; device
mutation goes through the compiled kernels below (or the sharded
programs from ``build_paged_serving_ops``), all of which DONATE the
pool. The device block table is resident too: dirty slot ROWS are
refreshed with a jitted one-row scatter, never a whole-table upload.
"""

from __future__ import annotations

import itertools
from functools import partial
from typing import Any, Dict, FrozenSet, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.transformer import (_adapter_ctx, select_slot_tokens,
                                  spec_verify_select)
from ..ops.flash_decode import aligned_cache_length
from .cache import SlotKVCache


class PagesExhausted(RuntimeError):
    """A partition's free list ran dry mid-allocation. The engine reacts
    by evicting clean prefix pages and, failing that, preempting the
    newest request; ``partition``/``shortfall`` say where and how much."""

    def __init__(self, partition: int, shortfall: int):
        super().__init__(
            f"partition {partition} out of KV pages (short {shortfall})")
        self.partition = int(partition)
        self.shortfall = int(shortfall)


class BlockAllocator:
    """Refcounted fixed-size page allocator, one free list per partition.

    Page id 0 of every partition is the trash page: refcount pinned to 1,
    never allocated, never freed. All other pages cycle alloc → incref*
    → decref* → free. :meth:`check` asserts the full invariant set and is
    cheap enough to run after every operation in the fuzz tests.
    """

    def __init__(self, n_partitions: int, pages_per_partition: int):
        if n_partitions < 1 or pages_per_partition < 2:
            raise ValueError(
                f"need >=1 partition and >=2 pages/partition (trash + 1), "
                f"got {n_partitions} x {pages_per_partition}")
        self.n_partitions = int(n_partitions)
        self.pages_per_partition = int(pages_per_partition)
        P = self.pages_per_partition
        self._refs: List[List[int]] = [[0] * P
                                       for _ in range(self.n_partitions)]
        self._free: List[List[int]] = [list(range(P - 1, 0, -1))
                                       for _ in range(self.n_partitions)]
        for part in range(self.n_partitions):
            self._refs[part][0] = 1     # trash page, pinned

    def alloc(self, partition: int) -> int:
        """Pop a free page (refcount 1) or raise :class:`PagesExhausted`."""
        free = self._free[partition]
        if not free:
            raise PagesExhausted(partition, 1)
        lid = free.pop()
        self._refs[partition][lid] = 1
        return lid

    def incref(self, partition: int, lid: int) -> None:
        if lid == 0 or self._refs[partition][lid] < 1:
            raise ValueError(
                f"incref of unallocated page {lid} in partition {partition}")
        self._refs[partition][lid] += 1

    def decref(self, partition: int, lid: int) -> None:
        if lid == 0 or self._refs[partition][lid] < 1:
            raise ValueError(
                f"decref of unallocated page {lid} in partition {partition}")
        self._refs[partition][lid] -= 1
        if self._refs[partition][lid] == 0:
            self._free[partition].append(lid)

    def free_count(self, partition: int) -> int:
        return len(self._free[partition])

    def refcount(self, partition: int, lid: int) -> int:
        return self._refs[partition][lid]

    def check(self) -> None:
        """Assert every allocator invariant (fuzz-test hook)."""
        for part in range(self.n_partitions):
            refs, free = self._refs[part], self._free[part]
            assert refs[0] == 1, f"trash refcount {refs[0]} != 1 (p{part})"
            assert all(r >= 0 for r in refs), f"negative refcount (p{part})"
            assert len(set(free)) == len(free), f"free-list dup (p{part})"
            assert 0 not in free, f"trash page on free list (p{part})"
            for lid in free:
                assert refs[lid] == 0, \
                    f"free page {lid} has refcount {refs[lid]} (p{part})"
            on_free = set(free)
            for lid in range(1, self.pages_per_partition):
                if refs[lid] == 0:
                    assert lid in on_free, \
                        f"leaked page {lid} (ref 0, not free) (p{part})"


class _PrefixNode:
    """One cached prefix page. ``key`` is the page's token tuple;
    ``parent`` is the children-dict that CONTAINS this node (unlink is
    ``del parent[key]``); the node holds ONE allocator reference on
    ``(partition, lid)`` for as long as it exists."""

    __slots__ = ("key", "parent", "children", "partition", "lid", "stamp",
                 "depth")

    def __init__(self, key, parent, partition, lid, stamp, depth):
        self.key = key
        self.parent = parent
        self.children: Dict[Tuple[int, ...], "_PrefixNode"] = {}
        self.partition = partition
        self.lid = lid
        self.stamp = stamp
        self.depth = depth


class RadixPrefixCache:
    """Radix tree over token prefixes at page granularity.

    One tree root per ``(data_rank, adapter_id)``: pages are physically
    resident on one data rank's partitions, and adapters change the K/V
    content (LoRA touches k/v projections), so sharing across either
    would be wrong. Within a rank, a node at depth ``d`` always lives in
    seq partition ``rank·sp + d // Ml`` — slot-independent, which is what
    lets any slot of that rank adopt it.
    """

    def __init__(self, page: int):
        self.page = int(page)
        self._roots: Dict[Tuple[int, int],
                          Dict[Tuple[int, ...], _PrefixNode]] = {}
        self._clock = itertools.count()
        self.n_nodes = 0

    def _keys(self, tokens, n_pages: int):
        toks = [int(t) for t in np.asarray(tokens).reshape(-1)]
        return [tuple(toks[m * self.page:(m + 1) * self.page])
                for m in range(n_pages)]

    def match(self, rank: int, aid: int, tokens, max_pages: int,
              touch: bool = True) -> List[_PrefixNode]:
        """Longest cached page-chain for ``tokens`` (at most ``max_pages``
        pages deep). ``touch`` bumps the LRU stamp of every matched node."""
        chain: List[_PrefixNode] = []
        children = self._roots.get((rank, aid))
        if children is None or max_pages <= 0:
            return chain
        for key in self._keys(tokens, max_pages):
            node = children.get(key)
            if node is None:
                break
            if touch:
                node.stamp = next(self._clock)
            chain.append(node)
            children = node.children
        return chain

    def register(self, rank: int, aid: int, tokens,
                 pages: List[Tuple[int, int]],
                 allocator: BlockAllocator) -> int:
        """Walk/extend the tree along ``tokens``'s first ``len(pages)``
        full pages. Missing nodes are created holding ``pages[m]`` (the
        cache increfs — it owns its reference independently of any slot);
        existing nodes keep THEIR page untouched (the registering slot
        simply holds a duplicate copy). Returns the number of new nodes."""
        children = self._roots.setdefault((rank, aid), {})
        created = 0
        for m, key in enumerate(self._keys(tokens, len(pages))):
            node = children.get(key)
            if node is None:
                part, lid = pages[m]
                allocator.incref(part, lid)
                node = _PrefixNode(key, children, part, lid,
                                   next(self._clock), m)
                children[key] = node
                created += 1
                self.n_nodes += 1
            else:
                node.stamp = next(self._clock)
            children = node.children
        return created

    def nodes(self) -> Iterator[_PrefixNode]:
        stack = [n for root in self._roots.values() for n in root.values()]
        while stack:
            node = stack.pop()
            yield node
            stack.extend(node.children.values())

    def evict(self, allocator: BlockAllocator, partition: int, n: int,
              protect: FrozenSet[_PrefixNode] = frozenset()) -> int:
        """Free up to ``n`` pages in ``partition`` by dropping LRU LEAF
        nodes whose page is held by the cache alone (refcount 1) and that
        are not in ``protect``. Returns how many pages were freed. O(tree)
        per freed page — the tree is small relative to a decode step."""
        freed = 0
        while freed < n:
            victim = None
            for node in self.nodes():
                if (node.partition == partition and not node.children
                        and node not in protect
                        and allocator.refcount(node.partition, node.lid) == 1):
                    if victim is None or node.stamp < victim.stamp:
                        victim = node
            if victim is None:
                break
            allocator.decref(victim.partition, victim.lid)
            del victim.parent[victim.key]
            self.n_nodes -= 1
            freed += 1
        return freed


@partial(jax.jit, static_argnames=("model", "page"), donate_argnums=(3,))
def _paged_insert_kernel(model, page, params, pool, table, slot, tokens,
                         t_last, pos0, aid):
    """Paged prefill-insert, fused: run ``decode_chunk_paged`` for slot
    ``slot`` DIRECTLY over the pool through its block-table row — each
    layer scatters only the chunk's own K/V rows into their owning pages
    (adopted prefix pages are attended through the table, never
    rewritten) and no dense view is materialized. Adapter deltas apply
    when the model is multi-tenant. Bucket-padding positions past the
    prompt write finite garbage into the owned tail page (or the trash
    page when unmapped), exactly the stale-dead rows the dense path
    leaves — decode overwrites them before anything attends. Keyed on
    (model, page, Tb); the pool is donated."""
    M = table.shape[1]
    trow = jax.lax.dynamic_slice(table, (slot, 0), (1, M))     # [1, M]
    with _adapter_ctx(model, jnp.reshape(aid, (1,))):
        logits, pool = model.decode_chunk_paged(params, tokens, pos0,
                                                pool, trow, page)
    last = jax.lax.dynamic_index_in_dim(logits[0], t_last, axis=0,
                                        keepdims=False)
    return last, pool


@partial(jax.jit, static_argnames=("model", "page"), donate_argnums=(3,))
def _paged_decode_kernel(model, page, params, pool, table, aids, tokens,
                         pos, temps, keys, live):
    """One batched decode step DIRECTLY over the paged pool: every layer
    of ``decode_step_paged`` scatters exactly one new K/V row per slot
    into its owning page (O(new tokens) traffic) and attends through the
    block table with the fused paged kernel — the old per-step
    gather-to-dense/scatter-back round trip is gone. Slots whose table
    cell at the write position is unmapped (freed rows, chunk-parked rows
    at a page boundary) write into the trash page; parked rows mid-page
    overwrite their own write-head garbage exactly like the dense path,
    repaired by the next chunk before it is read."""
    with _adapter_ctx(model, aids):
        logits, pool = model.decode_step_paged(params, tokens, pos, pool,
                                               table, page)
    emit = select_slot_tokens(logits, pos + 1, temps, keys)
    tokens = jnp.where(live, emit, tokens)
    pos = jnp.where(live, pos + 1, pos)
    return emit, tokens, pos, pool


@partial(jax.jit, static_argnames=("model", "page", "n_steps"),
         donate_argnums=(4,))
def _paged_fused_kernel(model, page, n_steps, params, pool, table, aids,
                        tokens, pos, temps, keys, live):
    """``n_steps`` paged decode steps in ONE program: scan the single-step
    paged body with the POOL ITSELF as carry — each step's layers write
    their one new K/V row per slot straight into the owning page, so the
    whole window moves O(S · n_steps) rows and never materializes a dense
    view. Non-live rows re-write their own write head (or trash) each
    step, which is idempotent garbage the position mask never shows.
    Token-identical to ``n_steps`` single-step launches."""
    def body(carry, _):
        tok, p, pk, pv = carry
        with _adapter_ctx(model, aids):
            logits, new = model.decode_step_paged(
                params, tok, p, {"k": pk, "v": pv}, table, page)
        emit = select_slot_tokens(logits, p + 1, temps, keys)
        tok = jnp.where(live, emit, tok)
        p = jnp.where(live, p + 1, p)
        return (tok, p, new["k"], new["v"]), emit

    (tokens, pos, pk, pv), emitted = jax.lax.scan(
        body, (tokens, pos, pool["k"], pool["v"]), None, length=n_steps)
    return emitted.T, tokens, pos, {"k": pk, "v": pv}


@partial(jax.jit, static_argnames=("model", "page"), donate_argnums=(3,))
def _paged_verify_kernel(model, page, params, pool, table, aids, drafts,
                         tokens, pos, temps, keys, live):
    """Speculative verify DIRECTLY over the paged pool, ONE program:
    score carry + ``W`` drafts as a ``decode_chunk_paged`` under each
    row's adapter and accept with the exact-match rule
    (:func:`~elephas_tpu.models.transformer.spec_verify_select`). The
    FULL chunk's K/V — rejected tail included — lands in the slot's own
    pages, mirroring the dense path's stale-dead rows. That is safe
    because pages covering decode-era positions are never registered in
    the prefix cache (``register_prefix`` publishes full PROMPT pages
    only, at insert time), so no other slot can observe the rejected
    bytes, and the staleness-repair invariant
    (:meth:`~elephas_tpu.models.transformer.TransformerLM.generate_speculative`)
    rewrites every position past the accepted run before anything attends
    it. An accepted position's page bytes are bitwise what a sequential
    decode would have written there (same pool, same inputs), which is
    what keeps paged ≡ dense under speculation."""
    chunk = jnp.concatenate([tokens[:, None], drafts], axis=1)   # [S, C]
    with _adapter_ctx(model, aids):
        logits, pool = model.decode_chunk_paged(params, chunk, pos, pool,
                                                table, page)
    sel, n_acc = spec_verify_select(logits, drafts, pos, temps, keys)
    corr = jnp.take_along_axis(sel, n_acc[:, None], axis=1)[:, 0]
    tokens = jnp.where(live, corr, tokens)
    pos = jnp.where(live, pos + n_acc + 1, pos)
    return sel, n_acc, tokens, pos, pool


@partial(jax.jit, donate_argnums=(0,))
def _scatter_table_row(table_dev, slot, row):
    """Refresh ONE slot's block-table row in the device-resident table
    (donated in place) — the steady-state alternative to re-uploading the
    whole ``[S, M]`` host table every launch."""
    return table_dev.at[slot].set(row)


@partial(jax.jit, donate_argnums=(0,))
def _scatter_aids_row(aids_dev, slot, aid):
    """Refresh one slot's adapter id in the device-resident vector."""
    return aids_dev.at[slot].set(aid)


class PagedKVCache:
    """Drop-in replacement for :class:`SlotKVCache` backed by the paged
    pool: same ``allocate/insert/advance/release/pos/remaining/cache``
    surface the engine drives, plus page bookkeeping (``_ensure_span`` /
    ``ensure_decode``), prefix adoption/registration, eviction, admission
    accounting, and engine-signature ``decode_fn``/``fused_fn`` wrappers
    that fetch the device table/adapter-id arrays themselves. The device
    copies are RESIDENT across steps: host bookkeeping marks individual
    slot ROWS dirty, and each launch refreshes just those rows with a
    jitted donate-in-place scatter — steady-state decode uploads nothing,
    admissions/releases upload ``O(M)`` ints, never the whole table.

    ``pages_per_partition`` defaults to the dense-equivalent pool
    (``n_slots_local × pages_per_slot + trash``), where paged-vs-dense
    identity holds with zero preemptions; shrink it to trade HBM for
    occasional preemption under pressure.
    """

    def __init__(self, model, params, n_slots: int,
                 max_len: Optional[int] = None, page_size: int = 16,
                 pages_per_partition: Optional[int] = None,
                 prefix_cache: bool = True, mesh=None):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        if model._ring_cache:
            raise NotImplementedError(
                "PagedKVCache needs a linear (horizon) cache; all-windowed "
                "models allocate rolling buffers (see "
                "TransformerLM.prefill_slot)")
        if getattr(model, "passes", 1) > 1:
            raise NotImplementedError(
                "PagedKVCache: a looped stack (passes > 1) keeps a cache "
                "layer a pass and layer, and the page pool holds one layer a "
                "weight layer: there is no looped page pool yet")
        self.model = model
        self.params = params
        self.n_slots = int(n_slots)
        self.max_len = int(model.max_len if max_len is None else max_len)
        self.page = int(page_size)
        self._ops = None
        if mesh is None:
            self.dp = self.sp = 1
            self.capacity = aligned_cache_length(self.max_len)
            self.Tl = self.capacity
        else:
            from ..models.sharded_generate import build_paged_serving_ops
            self._ops = build_paged_serving_ops(
                model, mesh, n_slots, max_len=self.max_len,
                page_size=self.page,
                pages_per_partition=pages_per_partition)
            self.dp, self.sp = self._ops.dp, self._ops.sp
            self.capacity = self._ops.capacity
            self.Tl = self._ops.Tl
            pages_per_partition = self._ops.pages_per_partition
        if self.Tl % self.page:
            raise ValueError(
                f"page_size {self.page} must divide the per-shard cache "
                f"length {self.Tl} (the dense-view bit-identity contract)")
        self.Ml = self.Tl // self.page          # logical pages per shard
        self.M = self.capacity // self.page     # logical pages per slot
        self.Sl = self.n_slots // self.dp       # slots per data rank
        self.n_partitions = self.dp * self.sp
        if pages_per_partition is None:
            pages_per_partition = self.Sl * self.Ml + 1
        self.pages_per_partition = int(pages_per_partition)
        self.allocator = BlockAllocator(self.n_partitions,
                                        self.pages_per_partition)
        self.prefix: Optional[RadixPrefixCache] = (
            RadixPrefixCache(self.page) if prefix_cache else None)

        if self._ops is not None:
            self.cache = self._ops.init_pool()
        else:
            L = model.n_layers
            Hkv = model.n_kv_heads
            Dh = model.d_model // model.n_heads
            shape = (L, self.pages_per_partition, Hkv, self.page, Dh)
            # DISTINCT buffers: XLA refuses donation of aliased inputs
            self.cache = {"k": jnp.zeros(shape, model.compute_dtype),
                          "v": jnp.zeros(shape, model.compute_dtype)}

        S, M = self.n_slots, self.M
        self.table = np.zeros((S, M), np.int32)
        self.aids = np.zeros(S, np.int32)
        self.owned: List[Dict[int, Tuple[int, int]]] = [{} for _ in range(S)]
        self.pos = np.zeros(S, np.int32)
        self._free: List[int] = list(range(S - 1, -1, -1))
        self._table_dev = None
        self._aids_dev = None
        self._table_rows_dirty: set = set()
        self._aids_rows_dirty: set = set()
        self.preemptions = 0
        self._prefix_hits = 0
        self._prefix_lookups = 0

    # -- slot accounting (SlotKVCache surface) ---------------------------
    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def active_slots(self) -> int:
        return self.n_slots - len(self._free)

    def allocate(self) -> int:
        if not self._free:
            raise RuntimeError("no free slot (caller must check free_slots)")
        return self._free.pop()

    def release(self, slot: int) -> None:
        if slot in self._free or not 0 <= slot < self.n_slots:
            raise ValueError(f"bad release of slot {slot}")
        for part, lid in self.owned[slot].values():
            self.allocator.decref(part, lid)
        self.owned[slot] = {}
        self.table[slot, :] = 0
        self.aids[slot] = 0
        self.pos[slot] = 0
        self._table_rows_dirty.add(slot)
        self._aids_rows_dirty.add(slot)
        self._free.append(slot)

    def advance(self, slot: int) -> None:
        self.pos[slot] += 1

    def remaining(self, slot: int) -> int:
        return self.max_len - int(self.pos[slot])

    # -- page bookkeeping ------------------------------------------------
    def _partition(self, slot: int, m: int) -> int:
        """Physical partition holding slot ``slot``'s logical page ``m``:
        data rank ``slot // Sl``, seq shard ``m // Ml``."""
        return (slot // self.Sl) * self.sp + (m // self.Ml)

    def set_adapter(self, slot: int, adapter_id: int) -> None:
        self.aids[slot] = int(adapter_id)
        self._aids_rows_dirty.add(slot)

    def _ensure_span(self, slot: int, lo: int, hi: int) -> None:
        """Allocate (idempotently) every page covering positions
        ``[lo, hi)`` of ``slot``. Raises :class:`PagesExhausted` mid-way
        on shortage — already-allocated pages stay owned, so the caller
        can evict/preempt and simply retry."""
        if hi <= lo:
            return
        for m in range(lo // self.page, (hi - 1) // self.page + 1):
            if m not in self.owned[slot]:
                part = self._partition(slot, m)
                lid = self.allocator.alloc(part)
                self.owned[slot][m] = (part, lid)
                self.table[slot, m] = lid
                self._table_rows_dirty.add(slot)

    def ensure_decode(self, slots, n_steps: int) -> None:
        """Allocate the pages the next ``n_steps`` decode writes of each
        active slot will land in (positions ``pos .. pos+n_steps-1``)."""
        for slot in slots:
            p = int(self.pos[slot])
            self._ensure_span(slot, p, p + n_steps)

    # -- prefix cache ----------------------------------------------------
    def adopt_prefix(self, slot: int, prompt) -> int:
        """Adopt the longest cached page-chain matching ``prompt`` for
        ``slot`` (pure increfs — cannot fail) and return how many PROMPT
        TOKENS are covered. Capped at ``(T0-1)//page`` pages so at least
        one real token remains to prefill (the first-token logits must
        come from a genuine forward)."""
        if self.prefix is None:
            return 0
        prompt = np.asarray(prompt).reshape(-1)
        cap = (len(prompt) - 1) // self.page
        rank = slot // self.Sl
        self._prefix_lookups += cap
        chain = self.prefix.match(rank, int(self.aids[slot]), prompt, cap)
        self._prefix_hits += len(chain)
        for m, node in enumerate(chain):
            assert node.partition == self._partition(slot, m)
            self.allocator.incref(node.partition, node.lid)
            self.owned[slot][m] = (node.partition, node.lid)
            self.table[slot, m] = node.lid
            self._table_rows_dirty.add(slot)
        return len(chain) * self.page

    def register_prefix(self, slot: int, prompt) -> int:
        """Publish ``slot``'s full prompt pages into the radix tree (page
        content is a pure function of the token prefix — see module doc).
        Called once prefill completes; partial tail pages and every page
        decode will write are excluded by construction."""
        if self.prefix is None:
            return 0
        prompt = np.asarray(prompt).reshape(-1)
        n = len(prompt) // self.page
        pages = [self.owned[slot][m] for m in range(n)]
        rank = slot // self.Sl
        return self.prefix.register(rank, int(self.aids[slot]), prompt,
                                    pages, self.allocator)

    def evict_pages(self, partition: int, n: int,
                    protect: FrozenSet = frozenset()) -> int:
        """Drop up to ``n`` clean (cache-only) prefix pages from
        ``partition``; returns how many were actually freed."""
        if self.prefix is None:
            return 0
        return self.prefix.evict(self.allocator, partition, n, protect)

    # -- weight rollover --------------------------------------------------
    def flush_prefixes(self) -> int:
        """Drop EVERY cached prefix page (the cache's own references only)
        and return how many were released. Cached pages hold K/V computed
        under the weights that prefilled them, so a weight swap must
        invalidate the whole tree — "page content is a pure function of
        the token prefix" only holds per weight version. Live slots keep
        their own refcounts on any pages they adopted, so in-flight
        requests are untouched; their pages return to the free pool at
        release."""
        if self.prefix is None:
            return 0
        flushed = 0
        for node in list(self.prefix.nodes()):
            self.allocator.decref(node.partition, node.lid)
            flushed += 1
        self.prefix._roots.clear()
        self.prefix.n_nodes = 0
        return flushed

    def set_params(self, params) -> None:
        """Swap the weights future PREFILL INSERTS run under (decode /
        verify launches take params from the engine) and flush the prefix
        cache — its pages were built under the old weights and adopting
        them after the swap would splice old-version K/V into new-version
        streams. Reassignment alone never retraces (same tree shapes) and
        params are never donated."""
        self.params = params
        self.flush_prefixes()

    # -- admission -------------------------------------------------------
    def fits(self, total_len: int) -> bool:
        """Could a request of ``total_len`` total positions (prompt +
        budget) EVER hold its pages alone? Checked at submit so a too-big
        request is rejected instead of looping through preemption."""
        n = -(-int(total_len) // self.page)
        for q in range(self.sp):
            need = max(0, min(n, (q + 1) * self.Ml) - q * self.Ml)
            if need > self.pages_per_partition - 1:
                return False
        return True

    def admission_check(self, prompt, adapter_id: int,
                        rank: int) -> Tuple[int, int]:
        """Free/needed page counts for admitting ``prompt`` on data rank
        ``rank`` — the pair the scheduler gates on (admit iff ``need <=
        free``). Counts the pages a fresh insert plus the FIRST decode
        write would allocate beyond the cached prefix, per seq partition,
        and tries to evict clean prefix pages where short; returns the
        binding partition's ``(free, need)``."""
        prompt = np.asarray(prompt).reshape(-1)
        T0 = len(prompt)
        cap = (T0 - 1) // self.page
        chain = (self.prefix.match(rank, int(adapter_id), prompt, cap,
                                   touch=False)
                 if self.prefix is not None else [])
        need_by_q: Dict[int, int] = {}
        for m in range(len(chain), T0 // self.page + 1):
            q = m // self.Ml
            need_by_q[q] = need_by_q.get(q, 0) + 1
        protect = frozenset(chain)
        binding = (0, 0)
        worst = None
        for q, need in need_by_q.items():
            part = rank * self.sp + q
            free = self.allocator.free_count(part)
            if free < need:
                self.evict_pages(part, need - free, protect)
                free = self.allocator.free_count(part)
            if worst is None or free - need < worst:
                worst = free - need
                binding = (free, need)
        return binding

    # -- device ops (SlotKVCache surface) --------------------------------
    padded_length = SlotKVCache.padded_length

    def insert_program(self, insert_fn=None):
        """The compiled program :meth:`insert` runs, for its name (it is
        called there, with the block table)."""
        return (_paged_insert_kernel if self._ops is None
                else self._ops.insert)

    def insert(self, slot: int, prompt: np.ndarray,
               insert_fn=None, pos0: int = 0) -> jnp.ndarray:
        """Prefill ``prompt`` ``[T0]`` into ``slot`` at positions
        ``pos0..pos0+T0-1`` through the block table; returns the last REAL
        position's logits ``[V]``. Validation, bucketing, and semantics
        match :meth:`SlotKVCache.insert` exactly; ``pos0 > 0`` serves both
        chunked-prefill continuations and prefix-adopted suffixes (the
        chunk attends adopted pages through the same gathered view).
        ``insert_fn`` is accepted for signature compatibility but unused —
        the paged kernels are dispatched internally."""
        del insert_fn
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        T0 = prompt.shape[0]
        pos0 = int(pos0)
        if not 1 <= T0 <= self.max_len:
            raise ValueError(f"prompt length {T0} not in [1, {self.max_len}]")
        if not 0 <= pos0 <= self.max_len - T0:
            raise ValueError(
                f"pos0 {pos0} + chunk {T0} exceeds max_len {self.max_len}")
        Tb = self.padded_length(T0, pos0)
        padded = np.zeros((1, Tb), np.int32)
        padded[0, :T0] = prompt
        self._ensure_span(slot, pos0, pos0 + T0)
        table, _ = self._device_tables()
        if self._ops is not None:
            last, self.cache = self._ops.insert(
                self.params, self.cache, table, jnp.asarray(padded),
                T0 - 1, slot, pos0, int(self.aids[slot]))
        else:
            last, self.cache = _paged_insert_kernel(
                self.model, self.page, self.params, self.cache, table,
                slot, jnp.asarray(padded), T0 - 1, pos0,
                jnp.int32(self.aids[slot]))
        self.pos[slot] = pos0 + T0
        return last

    def _device_tables(self):
        """Current device block table + adapter ids. Both stay RESIDENT on
        device: the first call uploads them whole, after which dirty slot
        rows (admission, release, page growth, adapter swap) are patched
        in place with a jitted one-row scatter — a steady-state decode
        step uploads nothing, and no launch ever re-uploads the full
        ``[S, M]`` host table again."""
        if self._table_dev is None:
            if self._ops is not None:
                self._table_dev = self._ops.upload_table(self.table)
            else:
                self._table_dev = jnp.asarray(self.table)
            self._table_rows_dirty.clear()
        elif self._table_rows_dirty:
            scatter = (self._ops.scatter_table_row
                       if self._ops is not None else _scatter_table_row)
            for s in sorted(self._table_rows_dirty):
                self._table_dev = scatter(self._table_dev, jnp.int32(s),
                                          jnp.asarray(self.table[s]))
            self._table_rows_dirty.clear()
        if self._aids_dev is None:
            if self._ops is not None:
                self._aids_dev = self._ops.upload_aids(self.aids)
            else:
                self._aids_dev = jnp.asarray(self.aids)
            self._aids_rows_dirty.clear()
        elif self._aids_rows_dirty:
            scatter = (self._ops.scatter_aids_row
                       if self._ops is not None else _scatter_aids_row)
            for s in sorted(self._aids_rows_dirty):
                self._aids_dev = scatter(self._aids_dev, jnp.int32(s),
                                         jnp.int32(self.aids[s]))
            self._aids_rows_dirty.clear()
        return self._table_dev, self._aids_dev

    def decode_fn(self, params, cache, tokens, pos, temps, keys, live):
        """Engine-signature single decode step (the engine calls this
        exactly like the dense ``_decode_kernel`` partial)."""
        table, aids = self._device_tables()
        if self._ops is not None:
            return self._ops.decode(params, cache, table, aids, tokens,
                                    pos, temps, keys, live)
        return _paged_decode_kernel(self.model, self.page, params, cache,
                                    table, aids, tokens, pos, temps, keys,
                                    live)

    def fused_fn(self, params, cache, tokens, pos, temps, keys, live,
                 n_steps: int):
        """Engine-signature fused multi-step decode."""
        table, aids = self._device_tables()
        if self._ops is not None:
            return self._ops.decode_fused(params, cache, table, aids,
                                          tokens, pos, temps, keys, live,
                                          n_steps)
        return _paged_fused_kernel(self.model, self.page, int(n_steps),
                                   params, cache, table, aids, tokens,
                                   pos, temps, keys, live)

    def verify_fn(self, params, cache, drafts, tokens, pos, temps, keys,
                  live):
        """Engine-signature speculative verify: one fused program scoring
        carry + drafts per slot, committing accepted runs through the
        block table with the rejected tail trash-masked (see
        :func:`_paged_verify_kernel`)."""
        table, aids = self._device_tables()
        if self._ops is not None:
            return self._ops.verify(params, cache, table, aids, drafts,
                                    tokens, pos, temps, keys, live)
        return _paged_verify_kernel(self.model, self.page, params, cache,
                                    table, aids, drafts, tokens, pos,
                                    temps, keys, live)

    # -- observability / integrity ---------------------------------------
    def memory_stats(self) -> Dict[str, Any]:
        """JSON-able snapshot section: page utilization, HBM footprint,
        prefix-hit ratio, preemption count."""
        total = self.n_partitions * (self.pages_per_partition - 1)
        free = sum(self.allocator.free_count(p)
                   for p in range(self.n_partitions))
        used = total - free
        k = self.cache["k"]
        bytes_ = 2 * int(np.prod(k.shape)) * k.dtype.itemsize
        L, _, Hkv, _, Dh = k.shape
        # one K+V time-row: the ONLY per-token copy the fused kernels pay
        row_bytes = 2 * L * Hkv * Dh * k.dtype.itemsize
        return {
            "page_size": self.page,
            "pages_per_partition": self.pages_per_partition,
            "n_partitions": self.n_partitions,
            "pages_total": total,
            "pages_used": used,
            "pages_free": free,
            "page_utilization": used / total if total else 0.0,
            "kv_hbm_bytes": bytes_,
            # gather/scatter traffic accounting (per slot): the fused
            # paged kernels scatter one new K/V row per produced token;
            # the retired gather-to-dense round trip moved the slot's
            # whole capacity through HBM each step and scattered it back
            "copy_bytes_per_token": row_bytes,
            "copy_bytes_per_step_gathered": row_bytes * (self.capacity + 1),
            "preemptions": self.preemptions,
            "prefix": {
                "nodes": self.prefix.n_nodes if self.prefix else 0,
                "hits_pages": self._prefix_hits,
                "lookups_pages": self._prefix_lookups,
                "hit_ratio": (self._prefix_hits / self._prefix_lookups
                              if self._prefix_lookups else 0.0),
            },
        }

    def check(self) -> None:
        """Assert full cross-structure integrity: allocator invariants,
        refcount == (#owning slots + cache hold) for every page, and
        table/ownership agreement. Fuzz-test hook."""
        self.allocator.check()
        expect: Dict[Tuple[int, int], int] = {}
        for d in self.owned:
            for key in d.values():
                expect[key] = expect.get(key, 0) + 1
        if self.prefix is not None:
            for node in self.prefix.nodes():
                key = (node.partition, node.lid)
                expect[key] = expect.get(key, 0) + 1
        for part in range(self.n_partitions):
            for lid in range(1, self.pages_per_partition):
                want = expect.get((part, lid), 0)
                got = self.allocator.refcount(part, lid)
                assert got == want, \
                    f"page (p{part}, {lid}): refcount {got} != {want} holders"
        for s in range(self.n_slots):
            for m in range(self.M):
                lid = int(self.table[s, m])
                if m in self.owned[s]:
                    part, own_lid = self.owned[s][m]
                    assert lid == own_lid and part == self._partition(s, m), \
                        f"table[{s},{m}]={lid} disagrees with ownership"
                else:
                    assert lid == 0, \
                        f"table[{s},{m}]={lid} but page not owned"
