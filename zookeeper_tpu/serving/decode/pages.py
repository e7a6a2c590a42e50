"""The KV layout: the shared device page pool, its host-side
allocator, and the radix prefix cache (docs/DESIGN.md §20).

A cache of contiguous rows a slot provisions every slot's WORST case —
``slots × capacity`` rows of KV HBM. Here KV rows live in per-layer
POOLS of fixed-size pages
(``[num_pages, head_shards, page_size, row_width]``: a token's heads
folded end to end on the last dimension, ``ops.fold_kv_rows``), any
slot's logical page
``p`` resolves through a ``[slots, max_pages] int32`` PAGE TABLE
carried as a runtime operand, and three host-side structures make the
pool a serving system rather than a bag of bytes:

- :class:`PagePool` — the allocator: a free-list + per-page refcounts
  over the pool indices, plus the authoritative page table. Admission
  allocates pages for a prompt, each decode/verify dispatch is
  preceded by an ``ensure_rows`` covering its writes, release unrefs —
  a page frees when its LAST reference (active slots + the prefix
  cache) drops. Capacity is pooled: the pool serves any mix of
  lengths summing to ``num_pages × page_size`` resident tokens,
  instead of ``slots`` independent worst cases.
- :class:`RadixPrefixCache` — a radix trie over prompt token prefixes
  at page-chunk granularity. A warm lookup returns the shared pages of
  the longest cached prefix; the requester REFERENCES them instead of
  recomputing prefill for those tokens (TTFT collapses for the
  shared-system-prompt traffic shape). Sharing is copy-on-write at the
  divergence point: the page containing the first divergent position
  is device-copied to a fresh page before the new occupant writes into
  it (full pages strictly before the divergence are never written —
  the validity invariant means writes only land at ``j >= length`` —
  so they share by reference forever). Refcount-0 nodes evict LRU
  under pool pressure.
- int8 quantization hooks — the pool tree optionally stores int8 rows
  plus page-shaped ``[num_pages, head_shards, page_size,
  heads_per_shard]`` float32 scale arrays (``ops.quantizers.quantize_kv_rows``), dequantized inside the
  attention read: double the resident tokens per HBM byte.

Validity composes with §15 unchanged: a slot's row ``j`` is meaningful
iff ``j < length``, wherever the page table put it. A freshly-allocated
page may hold a PREVIOUS tenant's rows — the poisoned-free-page
equality tests certify that garbage beyond ``length`` (now: garbage in
recycled pages) cannot perturb output, bit for bit. The prefix cache's
validity argument is determinism: prefill of the same token prefix
under the same weights writes the same bytes, so a cached page IS the
page a cold prefill would have produced — which is why a weight
hot-swap must invalidate the cache (exactly once), and why cached
pages never outlive a swap.

A second kind of per-sequence state rides in the same tree (docs/
DESIGN.md §27): a model with a recurrent mixer keeps, a layer, a FIXED
BLOCK A SLOT beside the rows a token: whatever leaves the model names
(``slot_leaves``, ``{name: (shape a slot, dtype)}``; for a state-space
mixer the recurrence's float32 state and the convolution's last input
rows), each ``[slots, *shape]``. Nothing here knows what they hold.
Those leaves are indexed by slot, not through the page table: no page is
allocated for them, a prefill overwrites the whole block at the slots it
admits, a decode step advances every slot's block in place, and nothing
of them can be shared, copied a page at a time or rolled back by
``lengths``, which is why the prefix cache, chunked prefill, speculation
and the page handoff are refused for such a model
(``DecodeEngine.bind``).

What a layer's entry of the tree holds follows the layer's kind
(docs/DESIGN.md §28): K/V pages only where the layer has attention
(``attention_layers``), the block a slot only where it has a recurrent
mixer (``slot_leaves``, a layer). A layer whose only mixer is linear
attention has the block and no rows; the page table, the allocator and
every page count are the attention layers' alone.

Everything here is HOST state. The device half (the pool tree itself)
is allocated by :func:`allocate_page_pool` and owned/donated by the
``DecodeEngine``.
"""

import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from zookeeper_tpu.serving.decode.prefix_key import walk_insert, walk_match

__all__ = [
    "PagePool",
    "RadixPrefixCache",
    "allocate_page_pool",
    "page_pool_bytes",
    "slot_state_bytes",
]

def allocate_page_pool(
    num_layers: int,
    num_pages: int,
    page_size: int,
    num_heads: int,
    head_dim: int,
    dtype: Any,
    quant: str = "none",
    head_shards: int = 1,
    window_layers: Sequence[bool] = (),
    window_pages: int = 0,
    slots: int = 0,
    slot_leaves: Sequence[Dict[str, Tuple[Tuple[int, ...], Any]]] = (),
    attention_layers: Sequence[bool] = (),
) -> Tuple[dict, ...]:
    """Zero-initialized page-pool pytree: a per-layer tuple of
    ``{"k", "v"}`` pools ``[num_pages, head_shards, page_size,
    row_width]`` — each token row holds a head shard's heads end to end,
    zero-padded to whole 128-lane registers (``ops.fold_kv_rows``;
    ``head_shards`` is the model-axis size the heads shard over, 1 on
    one device) — plus ``{"k_scale", "v_scale"}`` ``[num_pages,
    head_shards, page_size, heads_per_shard]`` float32 when
    ``quant="int8"`` (rows stored int8). The engine places it under the
    partitioner's page-pool sharding and donates it through every
    dispatch. ``num_heads`` is the
    heads a row holds: the key/value heads where they are grouped.

    Layer groups: the layers marked in ``window_layers`` (sliding-window
    layers, which keep only the last ``window`` tokens of a sequence)
    get pools of ``window_pages`` pages, indexed by their own table
    (:class:`PagePool`); every other layer gets ``num_pages``.

    ``slot_leaves`` (a layer, ``{name: (shape a slot, dtype)}``, the
    model's own names; empty: none anywhere): the layer also gets
    ``[slots, *shape]`` zeros under each name, the fixed block a slot of
    a recurrent mixer. ``attention_layers`` (a layer; empty: all): a
    layer without attention gets no ``k`` / ``v`` pages at all."""
    import jax.numpy as jnp

    from zookeeper_tpu.ops import kv_row_width

    window_layers = _window_layers(num_layers, window_layers, window_pages)
    if num_pages < 1 or page_size < 1:
        raise ValueError(
            f"page pool needs num_pages >= 1 and page_size >= 1, got "
            f"num_pages={num_pages}, page_size={page_size}."
        )
    if quant not in ("none", "int8"):
        raise ValueError(f"quant={quant!r}: expected 'none' or 'int8'.")
    width = kv_row_width(num_heads, head_dim, head_shards)
    row_dtype = jnp.int8 if quant == "int8" else dtype
    attends = _attends(num_layers, attention_layers)
    layers = []
    for i, windowed in enumerate(window_layers):
        layer = {}
        if attends[i]:
            pages = window_pages if windowed else num_pages
            shape = (pages, head_shards, page_size, width)
            layer["k"] = jnp.zeros(shape, row_dtype)
            layer["v"] = jnp.zeros(shape, row_dtype)
            if quant == "int8":
                # Scale 1.0 everywhere: a zeroed int8 page dequantizes to
                # exact zeros, matching the fp pool's initial state.
                scales = shape[:3] + (num_heads // head_shards,)
                layer["k_scale"] = jnp.ones(scales, jnp.float32)
                layer["v_scale"] = jnp.ones(scales, jnp.float32)
        leaves = slot_leaves[i] if slot_leaves else {}
        for name, (per_slot, leaf_dtype) in leaves.items():
            layer[name] = jnp.zeros((slots,) + tuple(per_slot), leaf_dtype)
        layers.append(layer)
    return tuple(layers)


def slot_state_bytes(
    slots: int,
    slot_leaves: Sequence[Dict[str, Tuple[Tuple[int, ...], Any]]],
) -> Dict[str, int]:
    """Bytes of each slot leaf over all slots and the layers that have
    it (``{name: bytes}``; empty for a model without them)."""
    out: Dict[str, int] = {}
    for leaves in slot_leaves:
        for name, (shape, dtype) in leaves.items():
            out[name] = out.get(name, 0) + (
                slots * int(np.prod(shape)) * np.dtype(dtype).itemsize
            )
    return out


def page_pool_bytes(
    num_layers: int,
    num_pages: int,
    page_size: int,
    num_heads: int,
    head_dim: int,
    itemsize: int,
    quant: str = "none",
    head_shards: int = 1,
    window_layers: Sequence[bool] = (),
    window_pages: int = 0,
    attention_layers: Sequence[bool] = (),
) -> int:
    """Total HBM the pool occupies (k + v rows at their padded width,
    all layers that have attention, plus the scale arrays when
    quantized) — the §20 capacity-planning number. Layer groups and
    ``attention_layers`` as in :func:`allocate_page_pool`."""
    from zookeeper_tpu.ops import kv_row_width

    attends = _attends(num_layers, attention_layers)
    kinds = _window_layers(num_layers, window_layers, window_pages)
    windowed = sum(w and a for w, a in zip(kinds, attends))
    pages = (sum(attends) - windowed) * num_pages + windowed * window_pages
    rows = 2 * pages * page_size
    width = head_shards * kv_row_width(num_heads, head_dim, head_shards)
    total = rows * width * (1 if quant == "int8" else itemsize)
    if quant == "int8":
        total += rows * num_heads * 4  # float32 scale per (row, head)
    return total


def _attends(
    num_layers: int, attention_layers: Sequence[bool]
) -> Tuple[bool, ...]:
    attention_layers = tuple(bool(a) for a in attention_layers)
    if not attention_layers:
        return (True,) * num_layers
    if len(attention_layers) != num_layers:
        raise ValueError(
            f"attention_layers has {len(attention_layers)} entries for "
            f"{num_layers} layers."
        )
    return attention_layers


def _window_layers(
    num_layers: int, window_layers: Sequence[bool], window_pages: int
) -> Tuple[bool, ...]:
    window_layers = tuple(bool(w) for w in window_layers)
    if not window_layers:
        return (False,) * num_layers
    if len(window_layers) != num_layers:
        raise ValueError(
            f"window_layers has {len(window_layers)} entries for "
            f"{num_layers} layers."
        )
    if any(window_layers) and window_pages < 1:
        raise ValueError("window layers need window_pages >= 1.")
    return window_layers


class _TrieNode:
    __slots__ = ("chunk", "page", "children", "parent", "last_used")

    def __init__(self, chunk: Tuple[int, ...], page: int, parent):
        self.chunk = chunk
        self.page = int(page)
        self.children: Dict[Tuple[int, ...], "_TrieNode"] = {}
        self.parent = parent
        self.last_used = 0


class RadixPrefixCache:
    """Radix trie over prompt token prefixes, page-chunk keyed.

    Internal nodes hold one FULL ``page_size`` token chunk each (the
    page covering those positions); a leaf may hold a PARTIAL tail
    chunk. Lookup walks exact full-chunk matches, then takes the
    longest common prefix against any child's chunk for the partial
    tail — a partial hit shares that child's page, which the caller
    must copy-on-write before its first write lands in it.

    The cache holds its OWN reference on every node's page (via the
    ``ref``/``unref`` callables, wired to the :class:`PagePool`
    refcounts), so cached pages survive their inserting slot's release;
    :meth:`evict_lru` drops least-recently-used childless nodes whose
    page the cache alone still references (``refcount == 1`` — the only
    evictions that actually free pool pages). :meth:`clear` is the
    hot-swap invalidation: cached pages hold K/V of the OLD weights and
    must never serve a warm hit under the new ones.
    """

    def __init__(self, page_size: int, ref, unref, evictable) -> None:
        if page_size < 1:
            raise ValueError(f"page_size={page_size} must be >= 1.")
        self.page_size = int(page_size)
        self._ref = ref
        self._unref = unref
        self._evictable = evictable  # page -> bool (refcount == 1)
        self._root = _TrieNode((), -1, None)
        self._clock = 0
        #: Token-level accounting behind ``zk_prefix_cache_hit_rate``.
        self.lookup_tokens = 0
        self.hit_tokens = 0
        self.lookups = 0
        self.hits = 0
        self.evicted_pages = 0
        self.invalidations = 0

    def _touch(self, node: _TrieNode) -> None:
        self._clock += 1
        node.last_used = self._clock

    @property
    def nodes(self) -> int:
        count = 0
        stack = [self._root]
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            count += len(n.children)
        return count

    @property
    def hit_rate(self) -> float:
        """Lifetime shared-token fraction (-1 before any lookup)."""
        if not self.lookup_tokens:
            return -1.0
        return self.hit_tokens / self.lookup_tokens

    def lookup(self, tokens: Sequence[int]) -> Tuple[int, List[int]]:
        """Longest cached prefix of ``tokens``: returns ``(t, pages)``
        where the first ``t`` tokens are covered by the ``ceil(t /
        page_size)`` cached ``pages`` (the last partial when ``t`` is
        off a page boundary — the caller's CoW case). The caller caps
        ``t`` (never the whole prompt — at least the final token is
        always recomputed so the first-emission logits exist) and takes
        its own references on the pages it adopts. The walk itself is
        the shared ``prefix_key.walk_match`` — the fleet router's
        per-replica :class:`~zookeeper_tpu.serving.decode.prefix_key.\
PrefixIndex` predicts THIS method's match length with the same code."""
        tokens = [int(x) for x in tokens]
        self.lookups += 1
        self.lookup_tokens += len(tokens)
        t, visited = walk_match(self._root, tokens, self.page_size)
        pages: List[int] = []
        for node in visited:
            pages.append(node.page)
            self._touch(node)
        if t:
            self.hits += 1
            self.hit_tokens += t
        return t, pages

    def insert(self, tokens: Sequence[int], pages: Sequence[int]) -> int:
        """Cache ``tokens``' pages (``pages[i]`` covers positions
        ``[i*page_size, (i+1)*page_size)``; the last may be partial).
        Existing nodes keep their ORIGINAL page — by determinism the
        bytes are identical, and swapping would orphan other sharers'
        view of the trie. Returns how many NEW nodes (= new cache page
        references) were created."""
        ps = self.page_size
        tokens = [int(x) for x in tokens]
        created = 0
        visited = walk_insert(
            self._root,
            tokens,
            ps,
            lambda chunk, i, parent: _TrieNode(chunk, pages[i], parent),
            # A partial tail is cached only when a page actually covers
            # those positions.
            tail=len(pages) > len(tokens) // ps,
        )
        for node, was_created in visited:
            if was_created:
                self._ref(node.page)
                created += 1
            self._touch(node)
        return created

    def evict_lru(self, want_pages: int) -> int:
        """Free pool pages by dropping LRU childless nodes whose page
        only the cache still references. Returns pages actually freed
        (may be < ``want_pages`` when everything left is shared with an
        active slot or is an interior node). One DFS collects the whole
        evictable-leaf layer and frees it in LRU order; the outer loop
        rescans only when evictions exposed NEW leaves (parents of
        fully-evicted subtrees) — so the cost is one walk per trie
        LAYER consumed, not one per page (this runs under the
        scheduler lock)."""
        freed = 0
        while freed < want_pages:
            leaves = []
            stack = [self._root]
            while stack:
                n = stack.pop()
                for child in n.children.values():
                    if child.children:
                        stack.append(child)
                    elif self._evictable(child.page):
                        leaves.append(child)
            if not leaves:
                return freed
            leaves.sort(key=lambda n: n.last_used)
            for victim in leaves:
                if freed >= want_pages:
                    return freed
                del victim.parent.children[victim.chunk]
                self._unref(victim.page)
                self.evicted_pages += 1
                freed += 1
        return freed

    def clear(self) -> int:
        """Drop every cached node + reference (the hot-swap
        invalidation). Returns nodes dropped."""
        dropped = 0
        stack = list(self._root.children.values())
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            self._unref(n.page)
            dropped += 1
        self._root = _TrieNode((), -1, None)
        # Counted unconditionally: "how many times was the cache
        # invalidated" is the hot-swap-discipline number the chaos
        # tests pin (exactly once per applied swap), not "how many
        # invalidations found nodes to drop".
        self.invalidations += 1
        return dropped


class _WindowGroup:
    """The allocator of a model's sliding-window layers: a table, a free
    list and a pool size of its own (see :class:`PagePool`). A sequence
    keeps only the logical pages its next ``window`` keys can lie in;
    ``first[slot]`` is the first of them, everything before it has been
    released (``-1`` in the table). Pages here are never shared: the
    prefix cache does not reach this group."""

    def __init__(self, num_pages, page_size, slots, max_pages_per_slot, window):
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.window = int(window)
        self.table = np.full((slots, max_pages_per_slot), -1, np.int32)
        self.first = np.zeros(slots, np.int32)
        self.counts = np.zeros(slots, np.int32)  # one past the last page
        self._free: List[int] = list(range(self.num_pages - 1, -1, -1))
        self.allocated_pages = 0
        self.released_behind = 0

    def first_needed(self, length: int) -> int:
        """The first logical page a query at position ``length`` can
        reach: keys ``length - window < p <= length``."""
        return max(int(length) - self.window + 1, 0) // self.page_size

    def missing(self, slot: int, length: int, pages: int) -> int:
        """Pages :meth:`cover` would have to allocate."""
        lo = max(self.first_needed(length), int(self.counts[slot]))
        return max(0, pages - lo)

    def cover(self, slot: int, length: int, pages: int) -> None:
        """Hold logical pages ``first_needed(length) .. pages - 1``
        (the caller checked :meth:`missing` against the free list)."""
        have = int(self.counts[slot])
        lo = max(self.first_needed(length), have)
        if not have:
            self.first[slot] = lo
        for page in range(lo, pages):
            self.table[slot, page] = self._free.pop()
        self.allocated_pages += max(0, pages - lo)
        self.counts[slot] = max(have, pages)

    def release_behind(self, slot: int, length: int) -> int:
        """Free the pages wholly behind the window of a query at
        ``length``; returns how many."""
        lo, hi = int(self.first[slot]), min(
            self.first_needed(length), int(self.counts[slot])
        )
        for page in range(lo, hi):
            self._free.append(int(self.table[slot, page]))
            self.table[slot, page] = -1
        if hi > lo:
            self.first[slot] = hi
            self.released_behind += hi - lo
        return max(0, hi - lo)

    def release_slot(self, slot: int) -> None:
        for page in range(int(self.first[slot]), int(self.counts[slot])):
            self._free.append(int(self.table[slot, page]))
        self.table[slot] = -1
        self.first[slot] = 0
        self.counts[slot] = 0

    def reset(self) -> None:
        self.table.fill(-1)
        self.first.fill(0)
        self.counts.fill(0)
        self._free = list(range(self.num_pages - 1, -1, -1))

    def leak_check(self) -> int:
        held = int(np.sum(self.table >= 0))
        return self.num_pages - len(self._free) - held


class PagePool:
    """Host-side page allocator + page table for one decode engine's
    shared device pool (see module docstring).

    Layer groups: a model whose layers are all of one kind has one group
    and this class is its allocator, table for table. A model with
    sliding-window layers beside full ones (``window`` and
    ``window_pages`` given) has a second group for them,
    :class:`_WindowGroup`: window layers need a sequence's last
    ``window`` tokens only, so that group's pools are smaller
    (``slots x (window + a page)`` instead of ``slots x capacity``), a
    prompt's admission allocates just its tail there, and
    :meth:`release_behind_window` hands back the pages a sequence has
    left behind as it grows. Admission, growth and release act on both
    groups or on neither. The dispatches then carry both tables,
    stacked ``[full, window]`` (:meth:`operand`).

    The DEVICE pool tree is owned by the engine; this object owns the
    indices: the free list, per-page refcounts, the authoritative
    ``[slots, max_pages]`` table the dispatches carry as a runtime
    operand, and (optionally) the radix prefix cache whose nodes hold
    their own page references. NOT thread-safe by itself — the
    scheduler calls every mutator under its own lock, the same
    discipline as its slot arrays.
    """

    def __init__(
        self,
        *,
        num_pages: int,
        page_size: int,
        slots: int,
        max_pages_per_slot: int,
        prefix_cache: bool = True,
        window: int = 0,
        window_pages: int = 0,
        slot_state: bool = False,
    ) -> None:
        if slot_state and prefix_cache:
            raise ValueError(
                "the prefix cache shares pages, and a model with "
                "recurrent state keeps a block a slot that no page "
                "holds: a shared prefix has no state to go with it; "
                "build the pool with prefix_cache=False."
            )
        #: The pool's tree also holds a fixed block a slot: its pages
        #: alone are not a sequence.
        self.slot_state = bool(slot_state)
        if window and prefix_cache:
            raise ValueError(
                "the prefix cache does not reach a window group's pages "
                "(a hit would need the full group's pages for the whole "
                "prefix and the window group's for its last `window` "
                "tokens); build the pool with prefix_cache=False."
            )
        if num_pages < max_pages_per_slot:
            raise ValueError(
                f"num_pages={num_pages} below max_pages_per_slot="
                f"{max_pages_per_slot}: one full-capacity sequence "
                "could never be served."
            )
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.slots = int(slots)
        self.max_pages_per_slot = int(max_pages_per_slot)
        #: The runtime page-table operand: -1 = unallocated (dispatches
        #: clip it; masked by ``lengths`` per the validity invariant).
        self.table = np.full(
            (self.slots, self.max_pages_per_slot), -1, np.int32
        )
        self.counts = np.zeros(self.slots, np.int32)
        self.refcount = np.zeros(self.num_pages, np.int32)
        self._free: List[int] = list(range(self.num_pages - 1, -1, -1))
        self.cow_pages = 0
        self.exhausted_events = 0
        self.allocated_pages = 0
        self.window_group: Optional[_WindowGroup] = (
            _WindowGroup(
                window_pages, page_size, slots, max_pages_per_slot, window
            )
            if window
            else None
        )
        self.prefix: Optional[RadixPrefixCache] = (
            RadixPrefixCache(
                self.page_size,
                ref=self._ref,
                unref=self._unref,
                evictable=lambda p: int(self.refcount[p]) == 1,
            )
            if prefix_cache
            else None
        )

    # -- refcounting -----------------------------------------------------

    def _ref(self, page: int) -> None:
        self.refcount[page] += 1

    def _unref(self, page: int) -> None:
        self.refcount[page] -= 1
        if self.refcount[page] < 0:
            raise AssertionError(f"page {page} refcount went negative.")
        if self.refcount[page] == 0:
            self._free.append(int(page))

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return self.num_pages - len(self._free)

    def pages_for(self, tokens: int) -> int:
        return max(0, math.ceil(int(tokens) / self.page_size))

    def _alloc(self, n: int) -> Optional[List[int]]:
        """Pop ``n`` fresh pages, evicting prefix-cache LRU nodes under
        pressure; None (nothing mutated beyond evictions) when the pool
        is genuinely exhausted."""
        if len(self._free) < n and self.prefix is not None:
            self.prefix.evict_lru(n - len(self._free))
        if len(self._free) < n:
            self.exhausted_events += 1
            return None
        out = [self._free.pop() for _ in range(n)]
        for p in out:
            self.refcount[p] += 1
        self.allocated_pages += n
        return out

    def _window_short(self, slot: int, length: int, pages: int) -> bool:
        """Whether the window group cannot cover ``pages`` for ``slot``
        (checked BEFORE the full group allocates: both or neither)."""
        group = self.window_group
        if group is None:
            return False
        if group.missing(slot, length, pages) <= len(group._free):
            return False
        self.exhausted_events += 1
        return True

    def operand(self, slots: Optional[Sequence[int]] = None, rows: int = 0):
        """The page-table operand of a dispatch: the whole table, or the
        rows of ``slots`` padded with all ``-1`` rows to ``rows``; one
        group: ``[n, max_pages]``, two: ``[2, n, max_pages]`` (full,
        window)."""
        tables = [self.table]
        if self.window_group is not None:
            tables.append(self.window_group.table)
        if slots is not None:
            picked = []
            for table in tables:
                out = np.full((rows, table.shape[1]), -1, np.int32)
                for i, s in enumerate(slots):
                    out[i] = table[int(s)]
                picked.append(out)
            tables = picked
        if len(tables) == 1:
            return np.ascontiguousarray(tables[0])
        return np.stack(tables)

    # -- slot lifecycle --------------------------------------------------

    def assign_prompt(self, slot: int, prompt) -> Optional[dict]:
        """Admission: build ``slot``'s page-table row for ``prompt``
        (1-D int tokens), sharing the longest cached prefix when the
        prefix cache is on. Returns a plan dict —

        - ``shared_tokens``: prompt tokens whose KV is already resident
          (prefill is skipped for them; the engine's warm-extend
          program computes only the suffix),
        - ``cow``: ``(src_page, dst_page)`` when the divergence point
          lands mid-page — the engine must device-copy ``src`` into
          ``dst`` BEFORE the suffix dispatch writes into it,

        or None when the pool cannot serve the prompt (caller sheds /
        requeues; nothing was allocated)."""
        if self.counts[slot]:
            raise AssertionError(
                f"slot {slot} still holds pages at admission; release "
                "first."
            )
        prompt = [int(x) for x in np.asarray(prompt).tolist()]
        length = len(prompt)
        shared_tokens = 0
        shared_pages: List[int] = []
        if self.prefix is not None:
            t, pages = self.prefix.lookup(prompt)
            # Never match the WHOLE prompt: the final token is always
            # recomputed so the warm dispatch produces the first
            # emission's logits (and the accounting stays honest).
            t = min(t, length - 1)
            shared_tokens = t
            shared_pages = pages[: self.pages_for(t)]
        n_full_shared = shared_tokens // self.page_size
        partial = shared_tokens % self.page_size != 0
        total_pages = self.pages_for(length)
        fresh_needed = total_pages - n_full_shared
        if self._window_short(slot, length, total_pages):
            return None
        fresh = self._alloc(fresh_needed)
        if fresh is None:
            return None
        if self.window_group is not None:
            self.window_group.cover(slot, length, total_pages)
        row = list(shared_pages[:n_full_shared]) + fresh
        for p in shared_pages[:n_full_shared]:
            self._ref(p)
        cow = None
        if partial:
            # Divergence mid-page: the suffix writes into this page at
            # offset shared_tokens % page_size, so the shared bytes are
            # copied to the first fresh page (device copy, engine-run).
            cow = (int(shared_pages[n_full_shared]), int(fresh[0]))
            self.cow_pages += 1
        self.table[slot, :len(row)] = row
        self.counts[slot] = len(row)
        return {"shared_tokens": shared_tokens, "cow": cow}

    def adopt_slot(self, slot: int, n_pages: int) -> Optional[List[int]]:
        """Disaggregated handoff, destination side (docs/DESIGN.md
        §22): allocate ``n_pages`` FRESH pages and install them as
        ``slot``'s table row — no prefix lookup, no sharing; the page
        CONTENTS arrive by transfer from another engine's pool.
        Returns the page list (the transfer's scatter targets), or
        None when the pool cannot serve it (nothing mutated beyond
        evictions — caller requeues or sheds). Unwind a failed
        transfer with :meth:`release_slot`."""
        if self.window_group is not None:
            raise NotImplementedError(
                "page handoff into a pool with a window group is not "
                "implemented (the transfer moves one group's pages)."
            )
        if self.slot_state:
            raise NotImplementedError(
                "page handoff into a pool with recurrent state a slot is "
                "not implemented (the transfer moves pages, and the "
                "slot's state block is in none of them)."
            )
        if self.counts[slot]:
            raise AssertionError(
                f"slot {slot} still holds pages at adoption; release "
                "first."
            )
        n_pages = int(n_pages)
        if n_pages < 1 or n_pages > self.max_pages_per_slot:
            raise ValueError(
                f"adopt_slot needs 1..{self.max_pages_per_slot} pages, "
                f"got {n_pages}."
            )
        fresh = self._alloc(n_pages)
        if fresh is None:
            return None
        self.table[slot, :n_pages] = fresh
        self.counts[slot] = n_pages
        return fresh

    def ensure_rows(self, slot: int, rows: int) -> bool:
        """Grow ``slot``'s row to cover ``rows`` total KV rows (the
        pre-dispatch guarantee: decode needs ``length + 1``, a verify
        window ``length + w``). False = pool exhausted after eviction;
        nothing was allocated."""
        needed = self.pages_for(rows)
        if needed > self.max_pages_per_slot:
            raise ValueError(
                f"slot {slot} needs {needed} pages for {rows} rows, "
                f"table holds {self.max_pages_per_slot}."
            )
        have = int(self.counts[slot])
        if needed <= have:
            return True
        # the row that grows is the last: a query at rows - 1
        if self._window_short(slot, rows - 1, needed):
            return False
        fresh = self._alloc(needed - have)
        if fresh is None:
            return False
        self.table[slot, have:needed] = fresh
        self.counts[slot] = needed
        if self.window_group is not None:
            self.window_group.cover(slot, rows - 1, needed)
        return True

    def release_behind_window(self, lengths: Sequence[int]) -> int:
        """Once an iteration: for every slot that holds pages, free the
        window group's pages that lie wholly behind ``length - window``
        (the next token, at position ``lengths[slot]``, cannot reach
        them, and nothing later will). Returns the pages freed; 0 for a
        model with one group."""
        group = self.window_group
        if group is None:
            return 0
        return sum(
            group.release_behind(slot, int(lengths[slot]))
            for slot in np.flatnonzero(group.counts)
        )

    def release_slot(self, slot: int) -> None:
        """Drop the slot's references (stream finished/failed). Pages
        the prefix cache also references stay resident for warm hits;
        everything else returns to the free list."""
        n = int(self.counts[slot])
        for i in range(n):
            self._unref(int(self.table[slot, i]))
        self.table[slot, :n] = -1
        self.counts[slot] = 0
        if self.window_group is not None:
            self.window_group.release_slot(slot)

    def insert_prefix(self, slot: int, prompt) -> int:
        """Cache the slot's prompt pages for future warm hits (called
        after the prefill/extend dispatch landed their contents)."""
        if self.prefix is None:
            return 0
        prompt = np.asarray(prompt)
        n = self.pages_for(int(prompt.shape[0]))
        return self.prefix.insert(
            prompt.tolist(), [int(p) for p in self.table[slot, :n]]
        )

    def invalidate_prefix(self) -> int:
        """Hot-swap invalidation: cached pages hold OLD-weight K/V."""
        if self.prefix is None:
            return 0
        return self.prefix.clear()

    def reset(self) -> None:
        """Return to the freshly-constructed allocation state (the
        engine's ``_reset_cache`` pairing, docs/DESIGN.md §20): table
        cleared, refcounts zeroed, every page free, the prefix trie
        dropped — the device pool it indexed was just reallocated
        zeroed, so every cached node points at bytes that no longer
        exist. Lifetime counters (CoW, evictions, hit accounting)
        survive; the trie's drop counts as an invalidation."""
        self.table.fill(-1)
        self.counts.fill(0)
        self.refcount.fill(0)
        self._free = list(range(self.num_pages - 1, -1, -1))
        if self.window_group is not None:
            self.window_group.reset()
        if self.prefix is not None:
            old = self.prefix
            fresh = RadixPrefixCache(
                self.page_size,
                ref=self._ref,
                unref=self._unref,
                evictable=lambda p: int(self.refcount[p]) == 1,
            )
            fresh.lookup_tokens = old.lookup_tokens
            fresh.hit_tokens = old.hit_tokens
            fresh.lookups = old.lookups
            fresh.hits = old.hits
            fresh.evicted_pages = old.evicted_pages
            fresh.invalidations = old.invalidations + 1
            self.prefix = fresh

    # -- accounting ------------------------------------------------------

    @property
    def prefix_hit_rate(self) -> float:
        if self.prefix is None:
            return -1.0
        return self.prefix.hit_rate

    def leak_check(self) -> int:
        """Pages absent from the free list that nothing references
        (must be 0 — the chaos tests pin it: a crash path that forgot
        a release would strand pages here forever)."""
        leaked = (
            self.num_pages
            - len(self._free)
            - int(np.sum(self.refcount > 0))
        )
        if self.window_group is not None:
            leaked += self.window_group.leak_check()
        return leaked

    def status(self) -> dict:
        """The ``/statusz`` ``kv_pool`` sub-section."""
        out = {
            "num_pages": self.num_pages,
            "page_size": self.page_size,
            "used_pages": self.used_pages,
            "free_pages": self.free_pages,
            "fill": round(self.used_pages / self.num_pages, 4),
            "cow_pages": self.cow_pages,
            "exhausted_events": self.exhausted_events,
            # Stranded pages (must be 0): exposed here so the chaos
            # certification can assert leak-freedom over /statusz on a
            # live worker process, not just in-process.
            "leaked": self.leak_check(),
        }
        group = self.window_group
        if group is not None:
            out.update(
                window_num_pages=group.num_pages,
                window_used_pages=group.num_pages - len(group._free),
                window_released_behind=group.released_behind,
            )
        if self.prefix is not None:
            out.update(
                prefix_nodes=self.prefix.nodes,
                prefix_lookups=self.prefix.lookups,
                prefix_hits=self.prefix.hits,
                prefix_hit_rate=round(self.prefix.hit_rate, 4),
                prefix_evicted_pages=self.prefix.evicted_pages,
                prefix_invalidations=self.prefix.invalidations,
            )
        return out
