"""PagedKVCache: a preallocated page pool with per-sequence page tables.

The TPU-native KV cache shape (Ragged Paged Attention, arxiv 2604.15464):
instead of one contiguous [B, L_max, H, D] buffer per sequence — whose
batch slots pin worst-case length forever — the cache is a single pool of
fixed-size pages per layer, ``[num_pages, page_size, H, D]``, and every
sequence owns an ordered list of page ids (its page table).  Appending a
token touches at most one page; freeing a finished sequence returns whole
pages to the free list, so memory utilization tracks the *actual* token
count across ragged sequence lengths instead of ``B * L_max``.

Two storage backends share the bookkeeping (page tables, free list,
reservation logic — always host-side):

- ``PagedKVCache`` — host numpy pools updated in place.  Every decode
  step must ship the WHOLE pool host->device for the attention call, so
  the per-token cost scales with the pool (`layer_pools` counts those
  bytes).
- ``DeviceKVPool`` — the pools are device-resident ``jax.Array``s (HBM
  on TPU), appended with jitted donated scatters (the batched form of
  ``dynamic_update_slice``: XLA updates the donated buffer in place).
  A decode step moves one token per sequence, not the pool — O(tokens)
  bytes instead of O(pool) (docs/GENERATION.md "Device-resident pools").

Both expose the same surface (``reserve`` / ``append`` /
``append_prefill`` / ``gather_block_tables`` / the batched
``write_decode_tokens`` / ``write_prefill_batch``), so the scheduler and
the token-identity oracle never see the difference.

Prefix caching (refcounted copy-on-write page sharing) also lives in the
shared bookkeeping: full pages of prompt token ids are CHAIN-KEYED into
a prefix index (``register_prefix``), admission looks up the longest
cached page run (``match_prefix``) and aliases those physical pages into
a new sequence's page table (``adopt_prefix``) so a thousand users of
one system prompt hold ONE physical copy and pay its prefill once.
Every page carries a refcount; ``free`` decrefs instead of releasing,
shared pages are read-only with copy-on-write on the first divergent
append (``reserve`` swaps in a private copy before any write can land),
and refcount-0 runs stay RESIDENT as an LRU cache evicted only under
pool pressure — docs/GENERATION.md "Prefix caching".
"""
import heapq
import math
import threading
import zlib

import numpy as np


def page_chain_hash(prev_hash, page_tokens):
    """CRC chain hash of one FULL page of token ids on top of its
    parent's chain hash — the fleet-level identity of a prefix run
    (serving/disagg/page_service.py).  Unlike the trie key (which
    stores literal tokens for equality-exactness), the chain hash is a
    compact summary safe to gossip across replicas: a collision can at
    worst route a request to a replica whose index then misses —
    adoption and admission both re-verify against literal tokens, so a
    colliding hash can never alias page CONTENT."""
    return zlib.crc32(np.asarray(page_tokens, np.int64).tobytes(),
                      int(prev_hash))


def compact_prefix_deltas(deltas):
    """Collapse a register/evict delta log to its NET op per chain —
    an add followed by a drop (and any longer churn) nets to the LAST
    op, which is all a consumer's index state can observe.  Shared by
    the cache's own delta log and the transport's heartbeat
    accumulator so neither grows O(churn) between drains on week-long
    uptimes."""
    last = {}
    for op, chain in deltas:
        last[chain] = op
    return [(op, chain) for chain, op in last.items()]


class OutOfPagesError(RuntimeError):
    """The page pool is exhausted: no free page for a required append.
    The scheduler catches this to preempt (or reject) a sequence rather
    than corrupting another sequence's pages."""


class KVQuantMismatchError(ValueError):
    """A page payload crossed a quantization boundary: an int8 pool was
    handed a float payload (or a payload without its scale arrays), or
    a float pool was handed int8 pages.  Typed and LOUD — a
    heterogeneous fleet (bf16 replica adopting an int8 replica's warm
    run, or vice versa) must fail the transfer, never install bytes the
    receiving pool would silently mis-decode.  Subclasses ValueError so
    the serving tier's adoption/migration fallbacks (which already
    catch ValueError and degrade to a cold path) stay graceful while
    direct cache callers get the specific type."""


class UnknownSequenceError(KeyError):
    """A cache operation named a seq_id the cache does not hold — never
    allocated, already freed, or double-freed.  Typed (and loud) so a
    scheduler bug fails the call instead of silently corrupting another
    sequence's pages; subclasses KeyError so legacy handlers still
    catch it."""

    def __init__(self, seq_id, live_count):
        super().__init__(seq_id)
        self.seq_id = seq_id
        self.live_count = live_count

    def __str__(self):
        return (f"unknown sequence {self.seq_id!r}: not allocated or "
                f"already freed ({self.live_count} live sequence(s))")


class TokenRows:
    """What a model tells `DeviceKVPool` a token's cache row is: ONE row
    of `width` numbers a layer in `dtype`, no head axis and no V pool.
    A layer's pool is ``[num_pages, page_size, lanes]``, as its kernel
    reads it, so no step relays the pool out; `layout` is what the
    `generation.kv_pool_layout` gauge says of such a pool.  The ONE
    description of a row: the pool's constructor, the step's shapes and
    the `kv_token_bytes` gauge read it here.

    `lanes` is the stored width: `width` up to a whole number of
    128-lane vregs, zeros past `width`.  A trailing dimension that is
    not lane-aligned makes XLA:TPU pick a pool layout with the PAGE
    axis minor-most, and every kernel call then copies the whole pool
    (compile-only for v5e, PR 28: 576 wide copied 424 MB a call, 640
    wide none).  The tiled layout would pad to the same bytes anyway."""

    layout = "rows"

    def __init__(self, width, dtype):
        self.width = int(width)
        self.dtype = np.dtype(dtype)

    @property
    def lanes(self):
        return -(-self.width // 128) * 128

    def token_bytes(self, num_layers):
        """Logical bytes a cached token costs over `num_layers`."""
        return self.width * self.dtype.itemsize * int(num_layers)


class LatentRows(TokenRows):
    """A latent-attention model's row: the compressed kv and the
    rotated shared key side by side, of which the first `value_width`
    numbers are also every head's value."""

    layout = "latent"

    def __init__(self, width, value_width, dtype):
        super().__init__(width, dtype)
        self.value_width = int(value_width)
        if not 0 < self.value_width <= self.width:
            raise ValueError(
                f"value_width {value_width} outside (0, width={width}]")


class HeadRows(TokenRows):
    """A grouped-query model's row: the keys of its `kv_heads` heads
    and then their values, ``[k_0 .. k_{n-1} | v_0 .. v_{n-1}]``,
    `head_dim` numbers each.  With heads of 128 every head's key and
    value is a whole 128-lane slice of the row, which is how
    `gqa_ragged_attention_kernel` splits a fetched block."""

    layout = "kv_rows"

    def __init__(self, kv_heads, head_dim, dtype):
        super().__init__(2 * int(kv_heads) * int(head_dim), dtype)
        self.kv_heads = int(kv_heads)
        self.head_dim = int(head_dim)


class SlotState:
    """What a `state` layer keeps, told to `DeviceKVPool` by a model
    whose `kv_layer_kinds()` names such layers: nothing a TOKEN, and for
    each decode SLOT a pair of arrays whatever the context's length: the
    `tail` (the last rows of a causal convolution's input, `tail_shape`
    in `tail_dtype`) and the recurrent `state` (`state_shape` in
    `state_dtype`).  The pool keeps both ``[slots + 1, ...]`` a state
    layer (the last row is where descriptors that belong to no slot
    point), zeroed when made; the STEP starts a slot from zero when a
    sequence's first token arrives, so the host never resets one."""

    def __init__(self, tail_shape, tail_dtype, state_shape, state_dtype):
        self.tail_shape = tuple(int(n) for n in tail_shape)
        self.tail_dtype = np.dtype(tail_dtype)
        self.state_shape = tuple(int(n) for n in state_shape)
        self.state_dtype = np.dtype(state_dtype)

    @property
    def bytes_a_slot(self):
        """Bytes one slot costs in one state layer."""
        return (int(np.prod(self.tail_shape)) * self.tail_dtype.itemsize
                + int(np.prod(self.state_shape)) * self.state_dtype.itemsize)


class WindowPageGroup:
    """The page table and the free list of the layers that keep only
    the last `window` tokens of a sequence (a query at position p sees
    keys p - window + 1 .. p), beside the cache's own, which serve the
    layers that keep everything.

    A sequence's table maps LOGICAL pages as the full group's does
    (position t at ``table[t // page_size]``), so both groups share
    positions, rows and descriptors; what differs is that a page whose
    last token no later query can see goes back to this group's free
    list while the sequence lives (`release_behind`), in work
    proportional to the pages released.  Entries under `first_live`
    are stale page ids: the window kernel's list starts at its horizon
    and never reads them.

    `reserve_tokens` is the most one reservation appends (the engine's
    prefill chunk): a sequence then never holds more than
    `sequence_cap` pages, which is what admission charges a long
    prompt here."""

    def __init__(self, num_pages, page_size, window, reserve_tokens):
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.window = int(window)
        self.reserve_tokens = max(int(reserve_tokens), 1)
        if self.num_pages < 1 or self.window < 1:
            raise ValueError("num_pages and window must be >= 1")
        self._free = list(range(self.num_pages - 1, -1, -1))
        self._tables = {}    # seq_id -> [page ids], logical order
        self._first = {}     # seq_id -> first live logical page
        # cumulative; `take_counters` drains what is new
        self.pages_reserved = 0
        self.pages_released = 0   # behind the window, sequence alive
        self._taken = (0, 0)
        self.peak_held = 0        # most pages one sequence ever held

    @staticmethod
    def pages_a_sequence(page_size, window, reserve_tokens):
        """The most pages one sequence holds: its window, one
        reservation ahead of it, and the partial pages at both ends."""
        return -(-(int(window) + int(reserve_tokens)) // int(page_size)) + 1

    @property
    def sequence_cap(self):
        return self.pages_a_sequence(self.page_size, self.window,
                                     self.reserve_tokens)

    @property
    def free_pages(self):
        return len(self._free)

    def pages_for(self, tokens):
        """What admission charges a context of `tokens` here."""
        return min(-(-int(tokens) // self.page_size), self.sequence_cap)

    def held(self, seq_id):
        return len(self._tables[seq_id]) - self._first[seq_id]

    def first_live(self, seq_id):
        return self._first[seq_id]

    def table(self, seq_id):
        return self._tables[seq_id]

    def allocate(self, seq_id):
        self._tables[seq_id] = []
        self._first[seq_id] = 0

    def free(self, seq_id):
        """Every live page of `seq_id` back to the free list (not
        counted as released: the sequence is gone)."""
        table = self._tables.pop(seq_id)
        first = self._first.pop(seq_id)
        self._free.extend(reversed(table[first:]))

    def pages_needed(self, seq_id, new_len):
        return max(-(-int(new_len) // self.page_size)
                   - len(self._tables[seq_id]), 0)

    def check(self, seq_id, new_len):
        need = self.pages_needed(seq_id, new_len)
        if need > len(self._free):
            raise OutOfPagesError(
                f"need {need} window-group pages for {seq_id!r}, only "
                f"{len(self._free)} of {self.num_pages} free")

    def grow(self, seq_id, new_len):
        """Pages for positions up to `new_len` (checked by `check`)."""
        table = self._tables[seq_id]
        need = self.pages_needed(seq_id, new_len)
        for _ in range(need):
            table.append(self._free.pop())
        self.pages_reserved += need
        self.peak_held = max(self.peak_held,
                             len(table) - self._first[seq_id])

    def release_behind(self, seq_id, length):
        """Give back the pages wholly behind the window of the NEXT
        query of a sequence `length` tokens long (position `length`
        sees keys from ``length - window + 1``).  Returns the count."""
        first = self._first[seq_id]
        upto = min(max(0, (int(length) - self.window + 1)
                       // self.page_size), len(self._tables[seq_id]))
        if upto <= first:
            return 0
        table = self._tables[seq_id]
        self._free.extend(table[first:upto])
        self._first[seq_id] = upto
        self.pages_released += upto - first
        return upto - first

    def truncate(self, seq_id, new_len):
        """Tail pages past `new_len` back to the free list.  A rewind
        whose next query would see a released page is refused."""
        first = self._first[seq_id]
        if first and int(new_len) - self.window + 1 < first * self.page_size:
            raise ValueError(
                f"truncate({seq_id!r}) to {new_len} tokens would read "
                f"pages released behind the {self.window}-token window "
                f"(the first kept page starts at "
                f"{first * self.page_size})")
        table = self._tables[seq_id]
        keep = max(-(-int(new_len) // self.page_size), first)
        dropped = table[keep:]
        del table[keep:]
        self._free.extend(reversed(dropped))
        return len(dropped)

    def take_counters(self):
        """(reserved, released) pages since the last take."""
        now = (self.pages_reserved, self.pages_released)
        out = (now[0] - self._taken[0], now[1] - self._taken[1])
        self._taken = now
        return out

    def gather_tables(self, seq_ids, max_pages):
        """``[B, max_pages]`` int32 of the sequences' logical tables;
        released and unused slots hold page 0 (never read: the list
        starts at the horizon; a valid DMA target all the same)."""
        pt = np.zeros((len(seq_ids), max_pages), np.int32)
        for i, sid in enumerate(seq_ids):
            table, first = self._tables[sid], self._first[sid]
            pt[i, first:len(table)] = table[first:]
        return pt


class UnsupportedCachePathError(NotImplementedError):
    """A cache operation this pool kind does not carry (a latent pool
    asked for a per-head K/V read, write or page payload): refused by
    name rather than served from the wrong layout."""


class _PrefixNode:
    """One full page of prompt tokens in the prefix index.

    Nodes form a trie over PAGES: a node is keyed by (parent node id,
    the page's token tuple), so two prompts share a chain exactly as
    far as their token streams agree page for page.  The key stores the
    literal tokens (not a hash of them), so a colliding hash can never
    alias two different prefixes — lookup is dict-hash fast but
    equality-exact.  `page` is the physical page holding the K/V for
    these tokens (valid for ANY sequence whose prefix matches: causal
    attention makes a position's K/V a function of the token prefix
    alone).  `last_use` orders LRU eviction; `children` counts cached
    child nodes so eviction can peel leaves first; `queued` marks a
    live entry in the evictable-leaf heap (at most one per node — the
    dedup that keeps the heap bounded by the trie size, not by the
    adopt/free churn of the warm steady state)."""

    __slots__ = ("page", "key", "parent", "ident", "children", "last_use",
                 "queued", "chain", "demand")

    def __init__(self, page, key, parent, ident, chain=0):
        self.page = page
        self.key = key
        self.parent = parent
        self.ident = ident
        self.children = 0
        self.last_use = 0
        self.queued = False
        # CRC chain hash of the token prefix this node completes — the
        # fleet-level identity register/evict deltas gossip
        self.chain = chain
        # cross-replica demand: fleet page-service export requests
        # observed for this node (note_fleet_demand) — folded into the
        # eviction key so a chain siblings keep adopting outlives a
        # locally-cold one
        self.demand = 0


class PagedKVCache:
    """Paged KV storage for `num_layers` attention layers.

    Layout per pool (one K pool and one V pool):
        ``[num_layers, num_pages, page_size, num_heads, head_dim]``

    Per sequence:
        ``page_table``: ordered page ids; position `t` of the sequence
        lives at ``page_table[t // page_size]``, row ``t % page_size``.
    """

    # storage layout of layer_pools() arrays; DeviceKVPool can store the
    # kernel layout instead (see its pool_layout)
    pool_layout = "token"

    # recency-clock ticks one unit of observed cross-replica demand is
    # worth in the eviction order (note_fleet_demand): a chain the
    # fleet adopted once outlives a local run untouched for this many
    # recency events.  Zero disables the fold (pure-LRU ablation).
    fleet_demand_boost = 256

    # a WindowPageGroup where some layers keep only a window of tokens
    # (DeviceKVPool(layer_kinds=...)); every other cache has ONE group,
    # this class's own tables and free list
    window_group = None
    # a SlotState where some layers keep a recurrent state a decode
    # slot and no pages (DeviceKVPool(state=...))
    slot_state = None

    def __init__(self, num_layers, num_heads, head_dim, num_pages=256,
                 page_size=16, dtype=np.float32):
        if num_pages < 1 or page_size < 1:
            raise ValueError("num_pages and page_size must be >= 1")
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.dtype = np.dtype(dtype)
        # int8 storage: pools carry a per-page per-head float32 abs-max
        # scale beside the bytes (quantized_kv.py owns the math; every
        # write path quantizes, every read path dequantizes in-kernel
        # or at gather).  Scales are state: they reset when a page
        # returns to the allocator, ride COW copies, and ship with
        # exports — "quantized" gates all of it.
        self.quantized = self.dtype == np.dtype(np.int8)
        self._scale_bytes = 0  # scale traffic (subset of _bytes_moved)
        # LIFO free list: a just-freed (cache-warm) page is reused first
        self._free = list(range(self.num_pages - 1, -1, -1))
        self._tables = {}    # seq_id -> [page ids]
        self._lens = {}      # seq_id -> token count
        self._bytes_moved = 0  # host<->device KV bytes (take_bytes_moved)
        # ---- prefix cache state (dormant until register_prefix) ----
        self._refs = {}       # page -> live sequence refcount (0 = page
        #                       resident only as a cached prefix run)
        self._nodes = {}      # (parent ident, token tuple) -> _PrefixNode
        self._page_node = {}  # page -> its _PrefixNode (indexed pages)
        self._next_node_id = 1   # 0 is the trie root
        self._clock = 0          # LRU recency counter
        self._cow_copies = 0         # drained by take_prefix_counters
        self._prefix_evictions = 0   # drained by take_prefix_counters
        # incrementally-maintained counts (every _refs transition runs
        # through _incref/_decref/_take_owned_page/_drop_node/flush),
        # so the per-step gauges and capacity checks stay O(1) instead
        # of scanning the refcount dict
        self._n_shared = 0   # pages with refcount > 1
        self._n_cached = 0   # refcount-0 registered residents
        # prefix register/evict delta log for the fleet-level page
        # service (None = disabled; a transport enables it and drains
        # take_prefix_deltas on stats/heartbeat — serving/disagg).
        # Its OWN tiny mutex: the drain runs on the router's submit
        # hot path, which must never wait behind an in-flight engine
        # step just to swap a list
        self._prefix_deltas = None
        self._delta_lock = threading.Lock()
        # delta-log growth bound: past _delta_compact_at entries the
        # log collapses to net ops (compact_prefix_deltas) — an
        # enabled-but-undrained log stays O(live chains), not O(churn)
        self._delta_compact_at = 4096
        self.prefix_delta_compactions = 0
        self._import_seq = 0   # temp seq ids for import_prefix_run
        # incrementally-maintained min-heap of evictable LEAF nodes,
        # entries (last_use_at_push, ident, node): pushed at the exact
        # refcount/trie transitions that make a node evictable (last
        # decref to 0; dropping a node's last child), validated lazily
        # at pop — so a pressured reserve pays O(log n) per evicted
        # page instead of re-seeding a heap with a full trie scan
        self._evict_heap = []
        self._init_pools()

    def _init_pools(self):
        shape = (self.num_layers, self.num_pages, self.page_size,
                 self.num_heads, self.head_dim)
        self.k_pool = np.zeros(shape, self.dtype)
        self.v_pool = np.zeros(shape, self.dtype)
        if self.quantized:
            sshape = (self.num_layers, self.num_pages, self.num_heads)
            self.k_scale = np.zeros(sshape, np.float32)
            self.v_scale = np.zeros(sshape, np.float32)

    def _reset_page_scale(self, page):
        """Zero a just-allocated page's scales: quantization grids are
        per-page state and a reused page must quantize exactly like a
        fresh one (a stale large scale would both coarsen the new
        sequence's grid and make its bytes depend on pool history —
        the determinism the int8-vs-int8 oracle pins)."""
        self.k_scale[:, page] = 0.0
        self.v_scale[:, page] = 0.0

    def layer_scales(self, layer):
        """One layer's ``(k_scale, v_scale)`` page-head scale arrays
        ``[P, H]`` for the attention dequant (None pair when the pool
        is not quantized)."""
        if not self.quantized:
            return None, None
        return self.k_scale[layer], self.v_scale[layer]

    def _count_scale_payload(self, n_pages, layers):
        """Scale bytes a quantized write (or transfer) moves alongside
        the int8 payload — scales are bytes in flight too, folded into
        _bytes_moved AND tracked separately for the
        generation.kv_scale_bytes counter."""
        if not self.quantized or not n_pages:
            return
        b = int(2 * layers * n_pages * self.num_heads * 4)
        self._bytes_moved += b
        self._scale_bytes += b

    def take_scale_bytes(self):
        """Scale bytes accumulated since the last take (already folded
        into take_bytes_moved's total)."""
        n, self._scale_bytes = self._scale_bytes, 0
        return n

    def _table(self, seq_id):
        """The page table of a LIVE sequence; typed failure otherwise."""
        try:
            return self._tables[seq_id]
        except KeyError:
            raise UnknownSequenceError(seq_id, len(self._tables)) from None

    # ------------------------- allocation ---------------------------
    def allocate(self, seq_id):
        """Register an empty sequence (no pages until tokens land)."""
        if seq_id in self._tables:
            raise ValueError(f"sequence {seq_id!r} already allocated")
        self._tables[seq_id] = []
        self._lens[seq_id] = 0
        if self.window_group is not None:
            self.window_group.allocate(seq_id)

    def free(self, seq_id):
        """Release `seq_id`'s hold on its pages — a DECREF per page, not
        an unconditional release: a page aliased by other sequences
        stays theirs, and a page registered in the prefix index stays
        RESIDENT at refcount 0 (an evictable cached run) instead of
        returning to the free list.  Exclusive unindexed pages return to
        the pool exactly as before.  A double free (or a free of a
        never-allocated id) raises UnknownSequenceError — an explicit
        error, never a silent second release of pages that may already
        belong to another sequence."""
        pages = self._table(seq_id)
        del self._tables[seq_id]
        del self._lens[seq_id]
        for page in reversed(pages):   # reversed: LIFO warm reuse
            self._decref(page)
        if self.window_group is not None:
            self.window_group.free(seq_id)

    def has(self, seq_id):
        return seq_id in self._tables

    def _take_page(self):
        if not self._free:
            raise OutOfPagesError(
                f"page pool exhausted ({self.num_pages} pages of "
                f"{self.page_size} tokens all in use)")
        return self._free.pop()

    def pages_needed(self, seq_id, new_tokens):
        """Pages an append of `new_tokens` to `seq_id` would allocate —
        including the copy-on-write page when the append's first token
        lands mid-page in a SHARED page (the private copy `reserve`
        swaps in costs one fresh page)."""
        table = self._table(seq_id)
        length = self._lens[seq_id]
        need = (math.ceil((length + new_tokens) / self.page_size)
                - len(table))
        if new_tokens > 0 and self._cow_page_index(seq_id) is not None:
            need += 1
        return need

    def reserve(self, seq_id, new_tokens=1):
        """Grow `seq_id`'s page table to hold `new_tokens` more tokens and
        advance its length; returns the first new position.  All-or-
        nothing: on OutOfPagesError nothing is allocated or advanced.
        Under pool pressure, refcount-0 cached prefix runs are EVICTED
        (LRU) before the error is raised — the cache gives pages back
        before any live sequence is preempted for them.  If the append
        starts mid-page in a shared page, that page is copy-on-write
        replaced with a private copy first, so the coming write can
        never touch storage another sequence (or the prefix index)
        still reads."""
        need = self.pages_needed(seq_id, new_tokens)
        wg = self.window_group
        if wg is not None:
            # both groups or neither: the window group's shortfall is
            # raised before anything is taken here
            wg.check(seq_id, self._lens[seq_id] + new_tokens)
        if need > len(self._free):
            self._evict_prefix(need - len(self._free))
        if need > len(self._free):
            raise OutOfPagesError(
                f"need {need} pages for {new_tokens} tokens of "
                f"{seq_id!r}, only {len(self._free)} free")
        table = self._tables[seq_id]
        if new_tokens > 0:
            self._cow_if_shared(seq_id)
        while len(table) < math.ceil(
                (self._lens[seq_id] + new_tokens) / self.page_size):
            table.append(self._take_owned_page())
        start = self._lens[seq_id]
        self._lens[seq_id] = start + new_tokens
        if wg is not None:
            wg.grow(seq_id, start + new_tokens)
        return start

    def release_window_pages(self):
        """Give back, for every live sequence, the window-group pages
        that no later query of it can see; returns the pages released.
        Host work over the live sequences and the pages released, never
        over a context.  0 from a cache with one group."""
        wg = self.window_group
        if wg is None:
            return 0
        return sum(wg.release_behind(seq_id, length)
                   for seq_id, length in self._lens.items())

    def truncate(self, seq_id, new_len):
        """REWIND `seq_id` to exactly `new_len` resident tokens — the
        speculative-decoding rejection primitive (engine._apply_spec:
        rejected draft tokens leave the cache through here), usable by
        any caller that over-reserved.  Whole tail pages past the new
        length return to the allocator (host bookkeeping only; the
        device side needs no dispatch — a dropped page's bytes are
        unreachable once no table maps it, and page reuse re-grounds
        them through the normal donation-chain writes).  Rows of the
        retained tail page past `new_len` become stale: they are
        masked out of every attention read (kv_len gates visibility)
        and fully overwritten when their position is next reserved, so
        they can never influence a value.

        Typed and loud, all-or-nothing:

        - UnknownSequenceError for a never-allocated or freed seq_id;
        - ValueError on GROWTH (``new_len > seq_len``) — growing goes
          through reserve, which owns capacity/COW/eviction;
        - ValueError when the rewind would touch an adopted/shared
          prefix run: a dropped page that other sequences or the
          prefix index still alias, or a clip landing MID-PAGE inside
          a shared page.  Rewinding into shared content would hand
          this sequence future writes over bytes other readers alias —
          the engine only ever rewinds spans it just privately
          reserved, so this firing means a caller bug.

        Quantized pools: released pages get their scale rows
        requantize-RESET immediately (the same zeroing page reuse
        performs, done eagerly so a freed page's grid state never
        outlives its content); the retained tail page keeps its grid —
        its scale is an abs-max over a superset of the live rows,
        which dequantizes them exactly as before the rewind.

        Returns the number of pages freed."""
        table = self._table(seq_id)
        new_len = int(new_len)
        cur = self._lens[seq_id]
        if new_len < 0 or new_len > cur:
            raise ValueError(
                f"truncate({seq_id!r}) to {new_len} tokens, but "
                f"{cur} are resident — truncate only rewinds (growth "
                f"goes through reserve)")
        if new_len == cur:
            return 0
        keep = math.ceil(new_len / self.page_size)
        dropped = table[keep:]
        if self.window_group is not None:
            # refuses, before anything moves, a rewind behind what the
            # window layers still hold
            self.window_group.truncate(seq_id, new_len)
        for page in dropped:
            if self._page_shared(page):
                raise ValueError(
                    f"truncate({seq_id!r}) to {new_len} would release "
                    f"shared page {page} (aliased or prefix-indexed) — "
                    f"rewinding into an adopted/shared prefix run is "
                    f"not supported")
        if new_len % self.page_size and self._page_shared(
                table[keep - 1]):
            raise ValueError(
                f"truncate({seq_id!r}) to {new_len} lands mid-page in "
                f"shared page {table[keep - 1]} — rewinding into an "
                f"adopted/shared prefix run is not supported")
        del table[keep:]
        self._lens[seq_id] = new_len
        for page in reversed(dropped):   # reversed: LIFO warm reuse
            self._decref(page)
            if self.quantized:
                self._reset_page_scale(page)
        return len(dropped)

    # ------------------------ prefix caching ------------------------
    def _tick(self):
        self._clock += 1
        return self._clock

    def _page_shared(self, page):
        """A page this sequence must NOT write through: aliased by more
        than one page table, or pinned read-only by the prefix index
        (future matches alias its content)."""
        return self._refs.get(page, 0) > 1 or page in self._page_node

    def _take_owned_page(self):
        page = self._take_page()
        self._refs[page] = 1
        if self.quantized:
            self._reset_page_scale(page)
        return page

    def _incref(self, page):
        """Pin one more alias on `page` (adoption): a cached resident
        leaves the evictable set, a second alias makes it shared."""
        old = self._refs.get(page, 0)
        if old == 0:
            self._n_cached -= 1
        self._refs[page] = old + 1
        if old == 1:
            self._n_shared += 1

    def _decref(self, page):
        n = self._refs.get(page, 1) - 1
        if n == 1:
            self._n_shared -= 1
        if n > 0:
            self._refs[page] = n
            return
        node = self._page_node.get(page)
        if node is not None:
            # last live reference gone but the run is cached: stay
            # resident at refcount 0, evictable under pool pressure
            self._refs[page] = 0
            self._n_cached += 1
            node.last_use = self._tick()
            if node.children == 0:
                # the node just became an evictable LEAF — queue it at
                # its current recency (interior refcount-0 nodes queue
                # later, when _drop_node peels their last child)
                self._push_evictable(node)
        else:
            self._refs.pop(page, None)
            self._free.append(page)

    def _cow_page_index(self, seq_id):
        """Index into `seq_id`'s table of the page a next append would
        write MID-PAGE while it is shared — the page `reserve` must
        copy-on-write — or None.  Only the tail page can qualify:
        appends always start at the current length, so a non-boundary
        start writes into exactly one existing page."""
        length = self._lens[seq_id]
        if length % self.page_size == 0:
            return None
        idx = length // self.page_size
        table = self._tables[seq_id]
        if idx >= len(table) or not self._page_shared(table[idx]):
            return None
        return idx

    def _cow_if_shared(self, seq_id):
        """Swap the shared tail page for a private copy before a write
        can land in it (caller pre-checked capacity via pages_needed).
        The copy is storage-level — host: one numpy slice copy; device:
        one donated in-trace page copy per pool list (see
        `_copy_kv_pages`) — and the old page is decref'd: other aliases
        and the prefix index keep reading the ORIGINAL bytes."""
        idx = self._cow_page_index(seq_id)
        if idx is None:
            return
        table = self._tables[seq_id]
        old = table[idx]
        new = self._take_owned_page()
        self._copy_page_storage(old, new)
        table[idx] = new
        self._decref(old)
        self._cow_copies += 1

    def _copy_page_storage(self, src, dst):
        """Copy one physical page's K/V across every layer (the COW
        copy).  Host backend: in-place numpy; DeviceKVPool overrides
        with a single donated dispatch.  Quantized pools copy the
        SCALE rows with the bytes — int8 content is meaningless apart
        from its grid, so a COW copy that dropped the scales would
        silently re-ground the private copy on a zero grid."""
        self.k_pool[:, dst] = self.k_pool[:, src]
        self.v_pool[:, dst] = self.v_pool[:, src]
        if self.quantized:
            self.k_scale[:, dst] = self.k_scale[:, src]
            self.v_scale[:, dst] = self.v_scale[:, src]

    def match_prefix(self, tokens):
        """Longest cached page run matching a strict prefix of `tokens`.

        Walks the trie one FULL page at a time (partial pages are never
        indexed) and returns ``(pages, matched_tokens)`` ready for
        `adopt_prefix`.  `matched_tokens` is clipped to
        ``len(tokens) - 1``: at least one token must remain for the
        suffix prefill, whose last-position logits ARE the first-token
        logits — a fully-aliased prompt would have nothing to sample
        from.  When the clip cuts into the final matched page, that
        page is still aliased (its rows up to the clip are valid) and
        the suffix prefill's first write triggers its copy-on-write.
        Touches each matched node's LRU recency."""
        n = len(tokens)
        ps = self.page_size
        pages = []
        parent_ident = 0
        i = 0
        while (i + 1) * ps <= n:
            key = (parent_ident,
                   tuple(int(t) for t in tokens[i * ps:(i + 1) * ps]))
            node = self._nodes.get(key)
            if node is None:
                break
            node.last_use = self._tick()
            pages.append(node.page)
            parent_ident = node.ident
            i += 1
        matched = min(len(pages) * ps, n - 1)
        if matched <= 0:
            return (), 0
        return tuple(pages[:math.ceil(matched / ps)]), matched

    def adopt_prefix(self, seq_id, pages, matched_tokens):
        """Alias a matched page run into a freshly allocated sequence:
        the pages join `seq_id`'s page table with their refcounts
        bumped — ZERO bytes move — and the sequence's length starts at
        `matched_tokens`, so prefill resumes at the first unmatched
        position.  Must run in the same scheduling step as the
        `match_prefix` that produced `pages` (an incref is what pins
        them against eviction)."""
        table = self._table(seq_id)
        if table or self._lens[seq_id]:
            raise ValueError(
                f"adopt_prefix on non-empty sequence {seq_id!r} "
                f"(len={self._lens[seq_id]})")
        if not (len(pages) - 1) * self.page_size < int(matched_tokens) \
                <= len(pages) * self.page_size:
            raise ValueError(
                f"matched_tokens={matched_tokens} does not land in the "
                f"last of {len(pages)} pages of {self.page_size}")
        for page in pages:
            self._incref(page)
        table.extend(int(p) for p in pages)
        self._lens[seq_id] = int(matched_tokens)

    # -------------------- page export / import ----------------------
    # The disaggregation hooks (serving/disagg): page BYTES move
    # point-to-point between replica pools — for the fleet page service
    # (a warm prefix run adopted by a replica that never prefilled it)
    # and for live migration (a mid-decode resident's pages shipped to
    # the sibling that resumes its stream).  Export/import speak ONE
    # canonical payload layout, [L, n, page_size, H, D] in the pool
    # dtype, whatever the storage layout or sharding — the importer
    # re-scatters into its own layout, so any two replicas can trade
    # pages (docs/GENERATION.md "Page export/import").

    def match_prefix_full(self, tokens):
        """Longest cached run of FULL pages matching a prefix of
        `tokens`, UNCLIPPED — the page-service export view.  Where
        match_prefix clips to ``len(tokens) - 1`` (an adopting sequence
        must keep one token to sample from), an exported run is
        re-REGISTERED on the importer, and the index only ever holds
        full pages — so the full run ships.  Touches recency like any
        other use.  Returns ``(pages, matched_tokens)``."""
        ps = self.page_size
        n = len(tokens)
        pages = []
        parent_ident = 0
        i = 0
        while (i + 1) * ps <= n:
            key = (parent_ident,
                   tuple(int(t) for t in tokens[i * ps:(i + 1) * ps]))
            node = self._nodes.get(key)
            if node is None:
                break
            node.last_use = self._tick()
            pages.append(node.page)
            parent_ident = node.ident
            i += 1
        return tuple(pages), len(pages) * ps

    def export_pages(self, pages):
        """Copy the given physical pages out of the pool as canonical
        ``[L, n, page_size, H, D]`` K/V arrays (pool dtype, bitwise the
        stored rows).  Counts the payload into bytes_moved — an export
        crosses the replica boundary by definition.  Quantized pools
        return a 4-tuple ``(k, v, k_scale, v_scale)`` with the
        ``[L, n, H]`` scale rows — int8 bytes never travel without
        their grid."""
        idx = np.asarray(pages, np.int64).reshape(-1)
        k = np.ascontiguousarray(self.k_pool[:, idx])
        v = np.ascontiguousarray(self.v_pool[:, idx])
        self._bytes_moved += k.nbytes + v.nbytes
        if not self.quantized:
            return k, v
        ks = np.ascontiguousarray(self.k_scale[:, idx])
        vs = np.ascontiguousarray(self.v_scale[:, idx])
        self._count_scale_payload(len(idx), self.num_layers)
        return k, v, ks, vs

    def _check_import_payload(self, k, v, k_scale, v_scale):
        want = (self.num_layers, k.shape[1], self.page_size,
                self.num_heads, self.head_dim)
        if k.shape != want or v.shape != want:
            raise ValueError(
                f"import payload shape {k.shape}/{v.shape} does not "
                f"match this pool's [L, n, page_size, H, D] = {want} — "
                f"pages only move between layout-compatible replicas")
        # the quantization boundary is typed and loud: int8 bytes into
        # a float pool (or float bytes into an int8 pool, or int8 bytes
        # arriving scale-less) would install content the receiver
        # mis-decodes — the heterogeneous-fleet corruption class
        payload_q = np.dtype(k.dtype) == np.dtype(np.int8)
        if payload_q != self.quantized:
            raise KVQuantMismatchError(
                f"page payload dtype {np.dtype(k.dtype)} does not match "
                f"this pool's kv_dtype {self.dtype}: quantized and "
                f"float replicas cannot trade pages")
        if self.quantized and (k_scale is None or v_scale is None):
            raise KVQuantMismatchError(
                "int8 page payload arrived without its scale arrays — "
                "refusing to install bytes with no grid")
        if self.quantized:
            swant = (self.num_layers, k.shape[1], self.num_heads)
            if np.shape(k_scale) != swant or np.shape(v_scale) != swant:
                raise KVQuantMismatchError(
                    f"scale payload shape {np.shape(k_scale)}/"
                    f"{np.shape(v_scale)} does not match [L, n, H] = "
                    f"{swant}")

    def import_pages(self, k, v, k_scale=None, v_scale=None):
        """Allocate fresh pages and install a canonical
        ``[L, n, page_size, H, D]`` K/V payload into them; returns the
        new page ids (each refcount 1, owned by the caller — hand them
        to adopt_imported or register-and-free them).  Evicts cached
        refcount-0 runs (LRU) under pool pressure before raising
        OutOfPagesError, exactly like reserve.  Quantized pools require
        the ``[L, n, H]`` scale payloads (KVQuantMismatchError
        otherwise — see _check_import_payload)."""
        k = np.asarray(k)
        v = np.asarray(v)
        n = int(k.shape[1]) if k.ndim >= 2 else 0
        if n == 0:
            return []
        self._check_import_payload(k, v, k_scale, v_scale)
        if n > len(self._free):
            self._evict_prefix(n - len(self._free))
        if n > len(self._free):
            raise OutOfPagesError(
                f"cannot import {n} pages: only {len(self._free)} free "
                f"even after evicting cached prefix runs")
        pages = [self._take_owned_page() for _ in range(n)]
        self._install_pages(pages, k, v, k_scale, v_scale)
        self._bytes_moved += k.nbytes + v.nbytes
        self._count_scale_payload(n, self.num_layers)
        return pages

    def _install_pages(self, pages, k, v, k_scale=None, v_scale=None):
        """Write a canonical import payload into freshly-owned pages
        (host backend: in-place numpy; DeviceKVPool overrides with one
        donated dispatch per pool list).  Installing OVERWRITES the
        pages' scales with the payload's — imported bytes keep the
        exporter's grid bitwise."""
        idx = np.asarray(pages, np.int64)
        self.k_pool[:, idx] = np.asarray(k, self.dtype)
        self.v_pool[:, idx] = np.asarray(v, self.dtype)
        if self.quantized:
            self.k_scale[:, idx] = np.asarray(k_scale, np.float32)
            self.v_scale[:, idx] = np.asarray(v_scale, np.float32)

    def adopt_imported(self, seq_id, pages, length):
        """Install freshly-imported pages as `seq_id`'s table with
        `length` tokens resident — the live-migration install: the
        sequence was just allocated empty, the pages just came from
        import_pages (refcount 1 each), and decode resumes at
        `length`."""
        table = self._table(seq_id)
        if table or self._lens[seq_id]:
            raise ValueError(
                f"adopt_imported on non-empty sequence {seq_id!r} "
                f"(len={self._lens[seq_id]})")
        length = int(length)
        if not (len(pages) - 1) * self.page_size < length \
                <= len(pages) * self.page_size:
            raise ValueError(
                f"length={length} does not land in the last of "
                f"{len(pages)} pages of {self.page_size}")
        table.extend(int(p) for p in pages)
        self._lens[seq_id] = length

    def import_prefix_run(self, tokens, k, v, k_scale=None, v_scale=None):
        """Adopt a sibling-exported prefix run into THIS pool and
        prefix index: install the page bytes (import_pages), register
        the chain under a throwaway sequence, and free it — registered
        pages stay RESIDENT at refcount 0 exactly like a locally
        prefilled run (read-only, COW-guarded, LRU-evictable), and
        pages whose chain this index already held are returned to the
        free list (first writer wins, duplicates cost nothing).
        `tokens` must cover every imported page (full pages of the
        prefix the run indexes).  Returns pages newly indexed.  Raises
        OutOfPagesError when the pool cannot hold the run even after
        eviction — the caller skips adoption, never fails a request
        over it."""
        k = np.asarray(k)
        v = np.asarray(v)
        n = int(k.shape[1]) if k.ndim >= 2 else 0
        if n == 0:
            return 0
        covered = n * self.page_size
        if len(tokens) < covered:
            raise ValueError(
                f"{len(tokens)} tokens cannot cover {n} imported pages "
                f"of {self.page_size}")
        pages = self.import_pages(k, v, k_scale, v_scale)
        sid = ("__prefix_import__", self._import_seq)
        self._import_seq += 1
        self.allocate(sid)
        self.adopt_imported(sid, pages, covered)
        added = self.register_prefix(sid, tokens[:covered])
        # decref: indexed pages stay cached residents, duplicate-chain
        # pages go straight back to the free list
        self.free(sid)
        return added

    def register_prefix(self, seq_id, tokens):
        """Index `seq_id`'s fully-written prompt pages for future
        matches.  Every FULL page of `tokens` (which must all be in the
        cache for `seq_id`) becomes a trie node mapping its chain key
        to the physical page; pages whose chain key is already indexed
        are skipped — the first writer wins, and a later identical
        prefill keeps its private pages (freed normally on decref).
        The engine calls this at prefill completion, when the pages are
        final: indexed pages are read-only from here on (writes would
        corrupt what future matches alias), enforced by the shared-page
        write guard.  Returns the number of NEW pages indexed."""
        table = self._table(seq_id)
        ps = self.page_size
        n_full = min(len(tokens), self._lens[seq_id]) // ps
        parent, parent_ident = None, 0
        added = 0
        chain = 0
        for i in range(n_full):
            page_tokens = tuple(int(t) for t in tokens[i * ps:(i + 1) * ps])
            key = (parent_ident, page_tokens)
            chain = page_chain_hash(chain, page_tokens)
            node = self._nodes.get(key)
            if node is None:
                page = table[i]
                if page in self._page_node:
                    # already indexed under another chain — impossible
                    # by construction (a page has one content history),
                    # but never double-index if it somehow happens
                    break
                node = _PrefixNode(page, key, parent, self._next_node_id,
                                   chain=chain)
                self._next_node_id += 1
                self._nodes[key] = node
                self._page_node[page] = node
                if parent is not None:
                    parent.children += 1
                added += 1
                self._log_prefix_delta("add", node)
            node.last_use = self._tick()
            parent, parent_ident = node, node.ident
        return added

    def _log_prefix_delta(self, op, node):
        """Record one register/evict transition for the fleet page
        service (no-op until a transport enables the log)."""
        if self._prefix_deltas is not None:
            with self._delta_lock:
                self._prefix_deltas.append((op, node.chain))
                if len(self._prefix_deltas) > self._delta_compact_at:
                    self._prefix_deltas = compact_prefix_deltas(
                        self._prefix_deltas)
                    self.prefix_delta_compactions += 1

    def enable_prefix_deltas(self):
        """Start recording register/evict deltas for take_prefix_deltas
        (idempotent).  The log only grows while someone drains it, so
        it stays disabled unless a fleet transport turns it on."""
        if self._prefix_deltas is None:
            self._prefix_deltas = []

    def take_prefix_deltas(self):
        """Drain ``[("add"|"drop", chain_hash), ...]`` accumulated since
        the last take — the register/evict bookkeeping a transport
        piggybacks on stats/heartbeat so the FleetPrefixIndex tracks
        which replica measurably holds which prefix run."""
        if not self._prefix_deltas:
            return []
        with self._delta_lock:
            out, self._prefix_deltas = self._prefix_deltas, []
        return out

    def note_fleet_demand(self, pages):
        """Fold observed cross-replica demand into eviction order: the
        fleet page service calls this on every export of a warm run
        (relay or p2p), bumping each exported node's demand count.
        Demanded chains sort later in the evictable-leaf heap
        (_evict_key), so a prefix siblings keep adopting outlives
        locally-cold runs — heap entries are corrected lazily at pop,
        exactly like a recency touch."""
        if not self.fleet_demand_boost:
            return
        for page in pages:
            node = self._page_node.get(page)
            if node is not None:
                node.demand += 1

    def _evict_key(self, node):
        """Eviction priority: LRU recency plus the fleet-demand fold —
        each observed adoption is worth fleet_demand_boost recency
        ticks, so cross-replica demand ages a chain without freezing
        it (a truly abandoned chain still drains out once the clock
        passes its boosted key)."""
        return node.last_use + node.demand * self.fleet_demand_boost

    def _push_evictable(self, node):
        """Queue an evictable leaf at its current eviction key.
        `queued` dedups: a node holds at most ONE live heap entry, so
        the warm steady state's adopt/free churn (decref-to-0 per
        request, the regime that never triggers eviction to drain the
        heap) cannot grow the heap past the trie size.  Entries are
        validated (and stale keys re-queued) lazily at pop, so a node
        that is touched, demanded, re-adopted, or evicted after the
        push costs one discarded heap entry, never a scan."""
        if node.queued:
            return
        node.queued = True
        heapq.heappush(self._evict_heap,
                       (self._evict_key(node), node.ident, node))

    def _evict_prefix(self, n_pages):
        """Evict up to `n_pages` refcount-0 cached pages to the free
        list, least-recently-used LEAF nodes first (a refcount-0 node's
        descendants are refcount-0 too — any sequence aliasing a child
        aliases the parent — so peeling leaves always makes progress).
        The evictable-leaf heap is maintained INCREMENTALLY at the
        refcount/trie transitions (_decref to 0, _drop_node peeling a
        parent), so a K-page eviction round is O(K log n) pops — never
        the O(nodes) trie rescan a large half-warm index used to pay on
        every pressured reserve.  Entries are validated at pop: nodes
        since re-adopted, grown a child, or dropped are discarded, and
        a node merely TOUCHED since its push (match_prefix recency) is
        re-queued at its current last_use so LRU order holds exactly.
        Returns pages actually freed."""
        if self._n_cached == 0:
            # nothing evictable (every indexed page is pinned by a live
            # sequence): this branch runs on every pressured reserve,
            # per decode token, under exactly the warm steady-state
            # load the cache targets
            return 0
        heap = self._evict_heap
        freed = 0
        while freed < n_pages and heap:
            key, _, node = heapq.heappop(heap)
            node.queued = False   # its one live entry just left the heap
            if self._nodes.get(node.key) is not node or node.children \
                    or self._refs.get(node.page, 1) != 0:
                continue  # stale entry: evicted, re-adopted, or grew
            if key != self._evict_key(node):
                # touched (or fleet-demanded) since queued: re-queue at
                # its true key so a recently-matched or fleet-hot run
                # outlives a colder sibling
                self._push_evictable(node)
                continue
            self._drop_node(node)
            freed += 1
        return freed

    def _drop_node(self, node):
        del self._nodes[node.key]
        del self._page_node[node.page]
        self._log_prefix_delta("drop", node)
        parent = node.parent
        if parent is not None:
            parent.children -= 1
            if parent.children == 0 \
                    and self._refs.get(parent.page, 1) == 0:
                # the parent just became an evictable leaf in turn
                self._push_evictable(parent)
        del self._refs[node.page]     # refcount 0 (eviction precondition)
        self._n_cached -= 1
        self._free.append(node.page)
        self._prefix_evictions += 1

    def flush_prefix_cache(self):
        """Drop the whole prefix index: refcount-0 pages return to the
        free list; pages still aliased by live sequences are merely
        unindexed (they free normally on their last decref).  Returns
        pages freed.  After draining every sequence, a flush restores
        the pool to all-free — the refcount-leak invariant the tests
        pin.  Flush-freed pages do NOT count into prefix_evictions:
        that counter means pressure-driven LRU eviction, and a
        recovery/operator flush spiking it would mimic pool-pressure
        thrash that never happened."""
        freed = 0
        for node in list(self._nodes.values()):
            self._log_prefix_delta("drop", node)
            if self._refs.get(node.page, 1) == 0:
                del self._refs[node.page]
                self._n_cached -= 1
                self._free.append(node.page)
                freed += 1
        self._nodes.clear()
        self._page_node.clear()
        self._evict_heap = []   # every queued node is gone with the trie
        return freed

    def take_prefix_counters(self):
        """(cow_copies, prefix_evictions) since the last take — the
        engine drains these into generation.* counters each step."""
        out = (self._cow_copies, self._prefix_evictions)
        self._cow_copies = 0
        self._prefix_evictions = 0
        return out

    @property
    def shared_pages(self):
        """Physical pages aliased by MORE than one page table — the
        bytes-deduplicated view N users of one system prompt produce.
        O(1): maintained at every refcount transition."""
        return self._n_shared

    @property
    def prefix_cached_pages(self):
        """Resident refcount-0 pages held only by the prefix index —
        reclaimable without touching any live sequence.  O(1):
        maintained at every refcount transition."""
        return self._n_cached

    @property
    def available_pages(self):
        """Free pages plus evictable cached pages — what admission and
        preemption decisions must compare against (a cached run is
        never a reason to preempt a live sequence)."""
        return len(self._free) + self.prefix_cached_pages

    def evictable_pages_in(self, pages):
        """How many of `pages` are refcount-0 cached residents RIGHT
        NOW — pages an adoption would pin, removing them from
        available_pages.  The admission gate subtracts this so a warm
        match can never double-count its own pages as both 'aliased
        for free' and 'evictable for the suffix'."""
        return sum(1 for p in pages if self._refs.get(p, 1) == 0)

    def _locate(self, seq_id, pos):
        """(page, row) of an already-reserved position, for a WRITE;
        typed errors, including the shared-page guard: every write path
        (eager scatters AND the host-side index computation feeding the
        fused in-trace scatters) funnels through here or _check_span, so
        a missed copy-on-write fails loudly instead of corrupting
        storage other sequences alias."""
        table = self._table(seq_id)
        if pos >= self._lens[seq_id]:
            raise IndexError(
                f"position {pos} not reserved for {seq_id!r} "
                f"(len={self._lens[seq_id]})")
        page = table[pos // self.page_size]
        if self._page_shared(page):
            raise RuntimeError(
                f"write at position {pos} of {seq_id!r} targets shared "
                f"page {page} — copy-on-write was missed")
        return page, pos % self.page_size

    def _count_write_payload(self, tokens, layers):
        """K+V bytes a write pulls across the host<->device boundary —
        the model computes K/V on device, so host-pool writes download
        the payload (and DeviceKVPool scatters count the same bound)."""
        self._bytes_moved += (2 * tokens * layers * self.num_heads *
                              self.head_dim * self.dtype.itemsize)

    # --------------------------- writes -----------------------------
    def write_token(self, seq_id, layer, pos, k, v):
        """Write one token's K/V for one layer at position `pos` (already
        reserved).  k, v: ``[num_heads, head_dim]``."""
        page, row = self._locate(seq_id, pos)
        if self.quantized:
            from .quantized_kv import host_quantized_write

            host_quantized_write(
                self.k_pool, self.v_pool, self.k_scale, self.v_scale,
                slice(layer, layer + 1), page, row,
                np.asarray(k, np.float32)[None, None],
                np.asarray(v, np.float32)[None, None])
            self._count_scale_payload(1, 1)
        else:
            self.k_pool[layer, page, row] = np.asarray(k, self.dtype)
            self.v_pool[layer, page, row] = np.asarray(v, self.dtype)
        self._count_write_payload(1, 1)

    def write_decode_tokens(self, seq_ids, positions, layer, k, v):
        """Write one decode step's new tokens for one layer: sequence i's
        token lands at its (already reserved) ``positions[i]``.  k, v:
        ``[B, num_heads, head_dim]`` (any array-like; the host backend
        copies to numpy)."""
        k = np.asarray(k)
        v = np.asarray(v)
        for i, sid in enumerate(seq_ids):
            self.write_token(sid, layer, int(positions[i]), k[i], v[i])

    def write_prefill_tokens(self, seq_id, start, layer, k, v):
        """Write one prefill CHUNK's K/V for ONE layer: positions
        ``[start, start + n)`` (already reserved — chunked prefill grows
        the reservation incrementally, one chunk at a time).  k, v:
        ``[n, num_heads, head_dim]``.  The per-layer sibling of
        ``write_decode_tokens``, used by the eager chunked-prefill
        attend callback (engine._prefill_chunk_eager)."""
        k = np.asarray(k)
        self._check_span_writable(seq_id, int(start), k.shape[0])
        self._write_span(seq_id, int(start), k[None], np.asarray(v)[None],
                         layers=slice(layer, layer + 1))

    def append(self, seq_id, k, v):
        """Append one token across every layer.  k, v:
        ``[num_layers, num_heads, head_dim]``.  Returns the position."""
        pos = self.reserve(seq_id, 1)
        page, row = self._locate(seq_id, pos)
        if self.quantized:
            from .quantized_kv import host_quantized_write

            host_quantized_write(
                self.k_pool, self.v_pool, self.k_scale, self.v_scale,
                slice(None), page, row,
                np.asarray(k, np.float32)[:, None],
                np.asarray(v, np.float32)[:, None])
            self._count_scale_payload(1, self.num_layers)
        else:
            self.k_pool[:, page, row] = np.asarray(k, self.dtype)
            self.v_pool[:, page, row] = np.asarray(v, self.dtype)
        self._count_write_payload(1, self.num_layers)
        return pos

    def append_prefill(self, seq_id, k, v):
        """Append a whole prompt's K/V across every layer.  k, v:
        ``[num_layers, T, num_heads, head_dim]``."""
        n = np.shape(k)[1]
        start = self.reserve(seq_id, n)
        self._check_span_writable(seq_id, start, n)
        self._write_span(seq_id, start, k, v)
        return start

    def _check_span(self, seq_id, start, n):
        """Typed validation that [start, start+n) is reserved (reads
        and writes alike — reads may legitimately span SHARED pages;
        writes go through _check_span_writable)."""
        self._table(seq_id)
        if int(start) + n > self._lens[seq_id]:
            raise IndexError(
                f"prefill span [{start}, {start + n}) not reserved "
                f"for {seq_id!r} (len={self._lens[seq_id]})")

    def _check_span_writable(self, seq_id, start, n):
        """Reserved AND writable: no page under the span may be shared
        (aliased or prefix-indexed) — reserve's copy-on-write must have
        privatized the tail page before any write lands (the fused
        dispatches run the same check pre-dispatch, so a donated
        in-trace scatter can never touch a shared page either)."""
        self._check_span(seq_id, start, n)
        if n <= 0:
            return
        table = self._tables[seq_id]
        for idx in range(int(start) // self.page_size,
                         (int(start) + n - 1) // self.page_size + 1):
            if idx < len(table) and self._page_shared(table[idx]):
                raise RuntimeError(
                    f"write span [{start}, {start + n}) of {seq_id!r} "
                    f"overlaps shared page {table[idx]} — copy-on-write "
                    f"was missed")

    def check_span_writable(self, seq_id, start, n):
        """Public pre-dispatch guard for in-trace writers (the jitted
        chunk and fused decode steps): the span must be reserved and
        privately owned."""
        self._check_span_writable(seq_id, int(start), int(n))

    def write_prefill_batch(self, seq_ids, starts, lengths, k, v):
        """Write a batch of (possibly length-padded) prefill K/V spans.
        Sequence i's real tokens ``[:lengths[i]]`` land at positions
        ``starts[i]:starts[i]+lengths[i]`` (already reserved); padded
        positions ``lengths[i]:`` are dropped, NEVER written — padding
        to a shape bucket must not touch pages the table doesn't own.
        k, v: ``[B, num_layers, T_padded, num_heads, head_dim]``."""
        k = np.asarray(k)
        v = np.asarray(v)
        for i, sid in enumerate(seq_ids):
            n = int(lengths[i])
            self._check_span_writable(sid, int(starts[i]), n)
            self._write_span(sid, int(starts[i]), k[i][:, :n], v[i][:, :n])

    def _write_span(self, seq_id, start, k, v, layers=slice(None)):
        """Page-by-page copy of one reserved span (k, v: [L, n, H, D],
        landing in pool rows `layers` — every layer by default; the
        chunked-prefill per-layer write passes a single-layer slice).
        Quantized pools route each page's slice through the shared
        quantized write transform (scale-max, page requant, row
        quantize — quantized_kv.host_quantized_write)."""
        quant = self.quantized
        if quant:
            from .quantized_kv import host_quantized_write

            k = np.asarray(k, np.float32)
            v = np.asarray(v, np.float32)
        else:
            k = np.asarray(k, self.dtype)
            v = np.asarray(v, self.dtype)
        table = self._table(seq_id)
        n = k.shape[1]
        t = 0
        pages_touched = 0
        while t < n:
            pos = start + t
            page = table[pos // self.page_size]
            row = pos % self.page_size
            take = min(self.page_size - row, n - t)
            if quant:
                host_quantized_write(
                    self.k_pool, self.v_pool, self.k_scale,
                    self.v_scale, layers, page, row,
                    k[:, t:t + take], v[:, t:t + take])
            else:
                self.k_pool[layers, page, row:row + take] = \
                    k[:, t:t + take]
                self.v_pool[layers, page, row:row + take] = \
                    v[:, t:t + take]
            t += take
            pages_touched += 1
        if quant:
            self._count_scale_payload(pages_touched, k.shape[0])
        self._count_write_payload(n, k.shape[0])

    # --------------------------- reads ------------------------------
    def layer_pools(self, layer):
        """One layer's ``(k, v)`` pools for the attention call, counted
        as host->device traffic: host-resident pools must ship the WHOLE
        pool to the device every step — the O(pool) cost DeviceKVPool
        exists to remove.  Quantized pools ship their scale arrays too
        (layer_scales) — counted here, since the attention call cannot
        decode the int8 bytes without them."""
        k = self.k_pool[layer]
        v = self.v_pool[layer]
        self._bytes_moved += k.nbytes + v.nbytes
        if self.quantized:
            self._count_scale_payload(self.num_pages, 1)
        return k, v

    def gather_prefix(self, seq_id, layer, length):
        """One layer's K/V for positions ``[0, length)`` of `seq_id`, in
        position order — the chunked-prefill prefix read.  Returns
        ``(k [length, H, D], v [length, H, D])``, EXACT copies of the
        stored rows (no padding: the view is sliced to the live token
        count, which is what keeps the chunked oracle bitwise).  Host
        pools count the gathered bytes as host->device traffic — the
        attention math runs on device, so the prefix view ships every
        chunk; DeviceKVPool overrides with a resident-array gather that
        never crosses the boundary."""
        self._check_span(seq_id, 0, int(length))
        table = self._table(seq_id)
        length = int(length)
        pages = np.asarray(table, np.int32)[
            :math.ceil(length / self.page_size)]
        k = self.k_pool[layer, pages].reshape(
            -1, self.num_heads, self.head_dim)[:length]
        v = self.v_pool[layer, pages].reshape(
            -1, self.num_heads, self.head_dim)[:length]
        self._bytes_moved += k.nbytes + v.nbytes
        if self.quantized:
            # the chunk reference takes dense rows: hand back the
            # DEQUANTIZED values — exactly what the in-kernel dequant
            # computes for the same bytes (same factor, quantized_kv)
            from .quantized_kv import dequantize_int8

            ks = np.repeat(self.k_scale[layer, pages], self.page_size,
                           axis=0)[:length][:, :, None]
            vs = np.repeat(self.v_scale[layer, pages], self.page_size,
                           axis=0)[:length][:, :, None]
            self._count_scale_payload(len(pages), 1)
            return dequantize_int8(k, ks), dequantize_int8(v, vs)
        return k, v

    def count_fused_append(self, tokens):
        """Account a fused-decode-step write of `tokens` new tokens across
        every layer.  The fused path scatters inside the jitted step — the
        payload never crosses the host<->device boundary at all — but the
        O(tokens) bound is counted anyway so ``generation.kv_bytes_moved``
        stays comparable across decode paths (it has always meant "bytes
        the write moves or would move", see _count_write_payload).
        Quantized pools count the per-token scale-row bound too (one
        page's scales per written row, mirroring the eager write
        paths) so kv_scale_bytes stays comparable across paths."""
        self._count_scale_payload(int(tokens), self.num_layers)
        self._count_write_payload(int(tokens), self.num_layers)

    def take_bytes_moved(self):
        """Host<->device KV bytes accumulated since the last take — the
        engine drains this once per decode step into
        ``generation.kv_bytes_moved``."""
        n, self._bytes_moved = self._bytes_moved, 0
        return n

    def seq_len(self, seq_id):
        self._table(seq_id)
        return self._lens[seq_id]

    def page_table(self, seq_id):
        return tuple(self._table(seq_id))

    def gather_block_tables(self, seq_ids, max_pages=None):
        """Batch the page tables for the decode kernel: returns
        ``(page_tables [B, max_pages] int32, seq_lens [B] int32)``.
        Unused slots are padded with page id 0 — always a valid DMA
        target; the kernel's length mask zeroes their contribution."""
        tables = [self._table(s) for s in seq_ids]
        if max_pages is None:
            max_pages = max((len(t) for t in tables), default=1) or 1
        pt = np.zeros((len(seq_ids), max_pages), np.int32)
        for i, t in enumerate(tables):
            if len(t) > max_pages:
                raise ValueError(
                    f"sequence {seq_ids[i]!r} spans {len(t)} pages > "
                    f"max_pages={max_pages}")
            pt[i, :len(t)] = t
        lens = np.asarray([self._lens[s] for s in seq_ids], np.int32)
        return pt, lens

    # --------------------------- stats ------------------------------
    @property
    def num_free_pages(self):
        return len(self._free)

    @property
    def pages_in_use(self):
        return self.num_pages - len(self._free)

    def utilization(self):
        """Fraction of the pool PINNED by live sequences.  Refcount-0
        cached prefix residents are excluded: they are instantly
        reclaimable (admission counts them available), so a warm but
        idle server reads ~0 here, not ~100 — the exported
        page_utilization_pct gauge must agree with the admission
        decisions, not contradict them.  `pages_in_use` stays the
        physical occupancy; stats() reports the resident-vs-pinned
        split."""
        return ((self.pages_in_use - self.prefix_cached_pages)
                / self.num_pages)

    def unique_tokens(self):
        """Token rows held across DISTINCT physical pages — the
        deduplicated occupancy.  Summing per-sequence lengths counts a
        shared page once per alias (N users of one system prompt would
        'hold' N copies that physically exist once); here each physical
        page contributes its deepest-written row count exactly once,
        and refcount-0 cached pages contribute their full page (they
        are always full prompt pages)."""
        rows = {}
        for seq_id, table in self._tables.items():
            length = self._lens[seq_id]
            for i, page in enumerate(table):
                used = min(self.page_size, length - i * self.page_size)
                if used > 0:
                    rows[page] = max(rows.get(page, 0), used)
        for page, refs in self._refs.items():
            if refs == 0:
                rows.setdefault(page, self.page_size)
        return int(sum(rows.values()))

    def token_utilization(self):
        """Fraction of allocated page *rows* actually holding tokens —
        the internal-fragmentation view (last page of each sequence is
        partially full).  Counts physically UNIQUE rows: with prefix
        sharing, the logical sum of sequence lengths can exceed the
        physical pool, but utilization never exceeds 1."""
        used = self.pages_in_use * self.page_size
        if not used:
            return 0.0
        return self.unique_tokens() / used

    def stats(self):
        return {
            "num_pages": self.num_pages,
            "page_size": self.page_size,
            "kv_dtype": str(self.dtype),
            "pages_in_use": self.pages_in_use,
            "pages_free": self.num_free_pages,
            "sequences": len(self._tables),
            # logical tokens (per-sequence sum: shared pages count once
            # per alias) vs the physically-unique row count
            "tokens": int(sum(self._lens.values())),
            "unique_tokens": self.unique_tokens(),
            "shared_pages": self.shared_pages,
            "prefix_cached_pages": self.prefix_cached_pages,
            "utilization_pct": round(100.0 * self.utilization(), 1),
            "token_utilization_pct":
                round(100.0 * self.token_utilization(), 1),
        }


# ----------------------- device-resident backend ------------------------


def _pin_sharding(pool, sharding):
    """Anchor a pool result to its NamedSharding (identity when the pool
    is unsharded).  Every write path routes its result through this, so
    GSPMD can never drift a pool off its head-axis layout mid-chain."""
    if sharding is None:
        return pool
    import jax

    return jax.lax.with_sharding_constraint(pool, sharding)


def scatter_pool_update(pool, pages, rows, x, layout, mesh=None,
                        tp_axis=None):
    """Scatter token payload `x` into `(pages[i], rows[i])` of one pool,
    layout-aware.  Out-of-range page ids (the padding sentinel
    ``num_pages``) are DROPPED — length-padded positions can never write
    past a sequence's page table.  Shared by the eager scatter dispatches
    below and the fused decode step's in-trace append (fused.py), so both
    write paths have identical semantics by construction.

    token layout:  pool [P, page_size, H, D], x [n, H, D]
    kernel layout: pool [H, P, page_size, D], x [n, H, D] (swapped in)

    A kernel-layout pool is written by row DMAs where Mosaic compiles
    them (`kernel_pool_scatter`; mesh / tp_axis name the head-sharded
    mesh for its shard_map), since XLA:TPU's scatter into that layout
    copies the whole pool there and back; the interpreter, and a pool
    those DMAs cannot address, take the XLA scatter: same result.
    """
    if layout == "kernel":
        import jax.numpy as jnp

        from ..ops.pallas.flash_attention import resolve_interpret
        from ..ops.pallas.paged_attention import (kernel_pool_scatter,
                                                  pool_scatter_in_place)

        if (pool_scatter_in_place(pool.shape, pool.dtype)
                and not resolve_interpret(None)):
            return kernel_pool_scatter(pool, pages, rows, x, mesh=mesh,
                                       tp_axis=tp_axis)
        return pool.at[:, pages, rows].set(jnp.swapaxes(x, 0, 1),
                                           mode="drop")
    return pool.at[pages, rows].set(x, mode="drop")


def _mesh_of(sharding):
    """``{mesh, tp_axis}`` of a kernel-layout pool's NamedSharding
    (`kv_pool_spec`: heads lead), nothing for an unsharded pool."""
    if sharding is None:
        return {}
    return {"mesh": sharding.mesh, "tp_axis": sharding.spec[0]}


def _scatter_kv(k_pool, v_pool, pages, rows, k, v, *, layout,
                sharding=None):
    """Scatter `k[i]` / `v[i]` into `(pages[i], rows[i])` of one layer's
    pools.  Donated: XLA performs the update in place, so an append
    moves the token payload, never the pool.  `sharding` pins the
    result for mesh-sharded pools (head-axis NamedSharding)."""
    on = _mesh_of(sharding)
    return (_pin_sharding(scatter_pool_update(k_pool, pages, rows, k,
                                              layout, **on), sharding),
            _pin_sharding(scatter_pool_update(v_pool, pages, rows, v,
                                              layout, **on), sharding))


def _scatter_kv_all_layers(k_pools, v_pools, pages, rows, k, v, *, layout,
                           sharding=None):
    """Every layer's scatter in ONE dispatch (the indices are identical
    across layers): k_pools/v_pools are length-L lists (all donated),
    k/v are ``[L, n, H, D]``.  Prefill latency stays flat in depth
    instead of paying L dispatches per chunk."""
    on = _mesh_of(sharding)
    return ([_pin_sharding(scatter_pool_update(kp, pages, rows, k[i],
                                               layout, **on), sharding)
             for i, kp in enumerate(k_pools)],
            [_pin_sharding(scatter_pool_update(vp, pages, rows, v[i],
                                               layout, **on), sharding)
             for i, vp in enumerate(v_pools)])


def _scatter_kv_quantized(k_pool, v_pool, k_scale, v_scale, pages, rows,
                          k, v, *, layout, sharding=None,
                          scale_sharding=None):
    """Quantized sibling of _scatter_kv: one layer's int8 pools + their
    [P, H] scale arrays through the shared three-step quantized write
    (quantized_kv.quantized_pool_write).  All four arrays are donated;
    shardings pinned like every other write path."""
    from .quantized_kv import quantized_pool_write

    kp, ks = quantized_pool_write(k_pool, k_scale, pages, rows, k, layout)
    vp, vs = quantized_pool_write(v_pool, v_scale, pages, rows, v, layout)
    return (_pin_sharding(kp, sharding), _pin_sharding(vp, sharding),
            _pin_sharding(ks, scale_sharding),
            _pin_sharding(vs, scale_sharding))


def _scatter_kv_all_layers_quantized(k_pools, v_pools, k_scales, v_scales,
                                     pages, rows, k, v, *, layout,
                                     sharding=None, scale_sharding=None):
    """Every layer's quantized scatter in ONE dispatch (k/v:
    [L, n, H, D]) — the quantized _scatter_kv_all_layers."""
    from .quantized_kv import quantized_pool_write

    k_out, v_out, ks_out, vs_out = [], [], [], []
    for i in range(len(k_pools)):
        kp, ks = quantized_pool_write(k_pools[i], k_scales[i], pages,
                                      rows, k[i], layout)
        vp, vs = quantized_pool_write(v_pools[i], v_scales[i], pages,
                                      rows, v[i], layout)
        k_out.append(_pin_sharding(kp, sharding))
        v_out.append(_pin_sharding(vp, sharding))
        ks_out.append(_pin_sharding(ks, scale_sharding))
        vs_out.append(_pin_sharding(vs, scale_sharding))
    return k_out, v_out, ks_out, vs_out


def _jitted_scatter_quantized(layout, sharding=None, scale_sharding=None):
    """Cached jitted donated quantized scatters per (layout, sharding)
    — the int8 sibling of _jitted_scatter."""
    import functools

    key = (layout, sharding, scale_sharding)
    if key not in _SCATTER_Q_JIT:
        import jax

        _SCATTER_Q_JIT[key] = (
            jax.jit(functools.partial(
                _scatter_kv_quantized, layout=layout, sharding=sharding,
                scale_sharding=scale_sharding),
                donate_argnums=(0, 1, 2, 3)),
            jax.jit(functools.partial(
                _scatter_kv_all_layers_quantized, layout=layout,
                sharding=sharding, scale_sharding=scale_sharding),
                donate_argnums=(0, 1, 2, 3)))
    return _SCATTER_Q_JIT[key]


_SCATTER_Q_JIT = {}


def _reset_scale_rows(k_scales, v_scales, pages, *, scale_sharding=None):
    """Zero the scale rows of freshly allocated pages across every
    layer in ONE donated dispatch (drop-mode: the padding sentinel
    num_pages never lands) — the device form of the page-reuse scale
    reset."""
    def z(s):
        out = s.at[pages].set(0.0, mode="drop")
        return _pin_sharding(out, scale_sharding)

    return [z(s) for s in k_scales], [z(s) for s in v_scales]


def _jitted_scale_reset(scale_sharding=None):
    import functools

    key = scale_sharding
    if key not in _SCALE_RESET_JIT:
        import jax

        _SCALE_RESET_JIT[key] = jax.jit(
            functools.partial(_reset_scale_rows,
                              scale_sharding=scale_sharding),
            donate_argnums=(0, 1))
    return _SCALE_RESET_JIT[key]


_SCALE_RESET_JIT = {}


def _copy_kv_pages(k_pools, v_pools, src, dst, *, layout, sharding=None):
    """Copy physical page `src` -> `dst` in every layer's pools — the
    copy-on-write body, ONE donated dispatch for all layers (the page
    axis is never the shard axis, so under a mesh the copy is fully
    local per device).  Same donation/sharding contract as the scatter
    dispatches above."""
    def copy(pool):
        if layout == "kernel":          # [H, P, page_size, D]
            out = pool.at[:, dst].set(pool[:, src])
        else:                           # [P, page_size, H, D]
            out = pool.at[dst].set(pool[src])
        return _pin_sharding(out, sharding)

    return [copy(p) for p in k_pools], [copy(p) for p in v_pools]


def _import_kv_pages(k_pools, v_pools, pages, k, v, *, layout,
                     sharding=None):
    """Install a canonical ``[L, n, page_size, H, D]`` import payload
    into physical pages `pages` of every layer's pools — the
    import_pages body, ONE donated dispatch for all layers.  Kernel-
    layout pools take the payload transposed to [H, n, ps, D]; under a
    mesh the per-shard scatter writes each device's head slice of the
    payload (kv_pool_spec shardings pinned), so an import round-trips
    a sharded pool without ever materializing it unsharded."""
    import jax.numpy as jnp

    def put(pool, payload):
        if layout == "kernel":          # pool [H, P, ps, D]
            out = pool.at[:, pages].set(       # payload [n, ps, H, D]
                jnp.transpose(payload, (2, 0, 1, 3)))
        else:                           # pool [P, ps, H, D]
            out = pool.at[pages].set(payload)
        return _pin_sharding(out, sharding)

    return ([put(kp, k[i]) for i, kp in enumerate(k_pools)],
            [put(vp, v[i]) for i, vp in enumerate(v_pools)])


def _copy_kv_pages_quantized(k_pools, v_pools, k_scales, v_scales, src,
                             dst, *, layout, sharding=None,
                             scale_sharding=None):
    """Quantized COW page copy: bytes AND scale rows move together in
    the one donated dispatch (int8 content is meaningless apart from
    its grid)."""
    k_out, v_out = _copy_kv_pages(k_pools, v_pools, src, dst,
                                  layout=layout, sharding=sharding)

    def cp(s):
        return _pin_sharding(s.at[dst].set(s[src]), scale_sharding)

    return k_out, v_out, [cp(s) for s in k_scales], \
        [cp(s) for s in v_scales]


def _jitted_page_copy_quantized(layout, sharding=None,
                                scale_sharding=None):
    import functools

    key = (layout, sharding, scale_sharding)
    if key not in _PAGE_COPY_Q_JIT:
        import jax

        _PAGE_COPY_Q_JIT[key] = jax.jit(
            functools.partial(_copy_kv_pages_quantized, layout=layout,
                              sharding=sharding,
                              scale_sharding=scale_sharding),
            donate_argnums=(0, 1, 2, 3))
    return _PAGE_COPY_Q_JIT[key]


_PAGE_COPY_Q_JIT = {}


def _import_kv_pages_quantized(k_pools, v_pools, k_scales, v_scales,
                               pages, k, v, ks, vs, *, layout,
                               sharding=None, scale_sharding=None):
    """Quantized page import: the int8 payload installs bitwise and the
    pages' scales are OVERWRITTEN with the exporter's [L, n, H] grid in
    the same donated dispatch."""
    k_out, v_out = _import_kv_pages(k_pools, v_pools, pages, k, v,
                                    layout=layout, sharding=sharding)

    def put(s, payload):
        return _pin_sharding(s.at[pages].set(payload), scale_sharding)

    return (k_out, v_out,
            [put(s, ks[i]) for i, s in enumerate(k_scales)],
            [put(s, vs[i]) for i, s in enumerate(v_scales)])


def _jitted_import_quantized(layout, sharding=None, scale_sharding=None):
    import functools

    key = (layout, sharding, scale_sharding)
    if key not in _IMPORT_Q_JIT:
        import jax

        _IMPORT_Q_JIT[key] = jax.jit(
            functools.partial(_import_kv_pages_quantized, layout=layout,
                              sharding=sharding,
                              scale_sharding=scale_sharding),
            donate_argnums=(0, 1, 2, 3))
    return _IMPORT_Q_JIT[key]


_IMPORT_Q_JIT = {}


def _jitted_import(layout, sharding=None):
    """Cached jitted donated page-import per (layout, sharding) — the
    disaggregation sibling of _jitted_scatter."""
    import functools

    key = (layout, sharding)
    if key not in _IMPORT_JIT:
        import jax

        _IMPORT_JIT[key] = jax.jit(
            functools.partial(_import_kv_pages, layout=layout,
                              sharding=sharding),
            donate_argnums=(0, 1))
    return _IMPORT_JIT[key]


_IMPORT_JIT = {}


def _jitted_page_copy(layout, sharding=None):
    """Cached jitted donated page-copy per (layout, sharding) — the COW
    sibling of _jitted_scatter."""
    import functools

    key = (layout, sharding)
    if key not in _PAGE_COPY_JIT:
        import jax

        _PAGE_COPY_JIT[key] = jax.jit(
            functools.partial(_copy_kv_pages, layout=layout,
                              sharding=sharding),
            donate_argnums=(0, 1))
    return _PAGE_COPY_JIT[key]


_PAGE_COPY_JIT = {}


def _jitted_latent_page_copy():
    """The copy-on-write body of a latent cache: page `src` -> `dst` in
    every layer's one pool, one donated dispatch."""
    if "latent" not in _PAGE_COPY_JIT:
        import jax

        _PAGE_COPY_JIT["latent"] = jax.jit(
            lambda pools, src, dst: [p.at[dst].set(p[src]) for p in pools],
            donate_argnums=(0,))
    return _PAGE_COPY_JIT["latent"]


def _checked_kinds(kinds, num_layers):
    if len(kinds) != int(num_layers) or set(kinds) - {
            "window", "full", "state"}:
        raise ValueError(
            f"layer kinds {kinds!r}: one of 'window' / 'full' / 'state' "
            f"for each of {num_layers} layers")
    return tuple(kinds)


class DeviceKVPool(PagedKVCache):
    """PagedKVCache whose pools live on the device (HBM on TPU).

    Bookkeeping (page tables, free list, reservation) is inherited
    unchanged and stays host-side; only the storage moves: per-layer
    ``jax.Array`` pools appended with jitted, buffer-donated scatters.
    ``layer_pools`` hands the live device arrays straight to the
    attention call — zero host->device re-upload, which is the whole
    point: a decode step's KV traffic is O(batch x layers x heads x
    head_dim), independent of the pool size.

    pool_layout picks the storage layout of each per-layer pool:

    - ``"token"`` (this class's default; the engine's for the jnp
      gather path): ``[num_pages, page_size, H, D]`` — the
      append-natural layout (one token's K is one contiguous row).
    - ``"kernel"`` (what the engine picks where the Pallas kernels read
      the pool: `GenerationConfig.pool_layout`): ``[H, num_pages,
      page_size, D]`` — the layout those kernels consume.  Scatters
      write INTO this layout (`scatter_pool_update`: row DMAs, in
      place), so no step transposes a pool — the O(pool) HBM traffic
      per layer per step the token layout forces on the kernel path,
      half its busy time at 335 MB a pool (PERF.md, PR 29).  The jnp
      reference gathers either layout bitwise-identically
      (decode_attention.py).  Page export/import and the prefix
      gather transpose the few pages they move; copy-on-write is a
      page-sized update in place.

    The arrays returned by ``layer_pools`` are invalidated by the next
    write (donation): read between writes, as the engine's step does.
    ``k_pool`` / ``v_pool`` are DEBUG host copies in the CANONICAL
    token layout regardless of pool_layout, not the hot path.

    mesh / tp_axis: tensor-parallel sharding — each per-layer pool is a
    single GSPMD ``jax.Array`` sharded over the HEAD axis of `mesh`'s
    `tp_axis` (NamedSharding via parallel.kv_pool_spec), so every device
    holds ``num_heads / tp_degree`` heads of every page: per-device KV
    memory is 1/tp_degree of the unsharded pool, and the head axis is
    exactly the axis the sharded fused decode step partitions attention
    over (docs/GENERATION.md "Sharded decode").  Bookkeeping stays
    host-global — page tables and the free list are replicated logic,
    only the storage is split.  ``reset_pools`` re-materializes with the
    SAME sharding, so poisoned-dispatch recovery never silently degrades
    to a single-device layout.
    """

    def __init__(self, num_layers, num_heads, head_dim, num_pages=256,
                 page_size=16, dtype=np.float32, pool_layout="token",
                 mesh=None, tp_axis=None, rows=None, window=None,
                 state=None):
        if pool_layout not in ("token", "kernel"):
            raise ValueError(
                f"pool_layout must be 'token' or 'kernel', got "
                f"{pool_layout!r}")
        self.pool_layout = pool_layout
        # a TokenRows: ONE pool a layer, [P, page_size, lanes], written
        # only inside the ragged step (see "row pools" below)
        self.rows = rows
        # window: (layer kinds, window tokens, window-group pages, the
        # most tokens one reservation appends) where some layers keep
        # only a window: those layers' pools hold the window group's
        # pages, the others' this cache's own `num_pages`
        # state: (layer kinds, a SlotState, decode slots) where some
        # layers keep a recurrent state a slot and no pages at all
        self.layer_kinds = None
        if rows is not None:
            if mesh is not None or pool_layout != "token":
                raise UnsupportedCachePathError(
                    "a row pool has no head axis to shard or to "
                    "transpose: mesh and pool_layout='kernel' do not "
                    "apply")
            dtype = rows.dtype
            self._count_write_payload = self._count_latent_payload
        if window is not None:
            if rows is None:
                raise UnsupportedCachePathError(
                    "window layers are carried by row pools alone")
            kinds, tokens, pages, reserve_tokens = window
            self.layer_kinds = _checked_kinds(kinds, num_layers)
            self.window_group = WindowPageGroup(
                pages, page_size, tokens, reserve_tokens)
        if state is not None:
            if rows is None:
                raise UnsupportedCachePathError(
                    "state layers are carried beside row pools alone")
            kinds, self.slot_state, slots = state
            self.layer_kinds = _checked_kinds(kinds, num_layers)
            self.state_slots = int(slots)
        self.mesh = mesh
        self.tp_axis = None
        self.tp_degree = 1
        self._sharding = None
        self._scale_sharding = None
        if mesh is not None:
            from ..parallel.sharding_annotations import (kv_pool_spec,
                                                         kv_scale_spec,
                                                         named_sharding)

            names = tuple(mesh.axis_names)
            self.tp_axis = tp_axis if tp_axis is not None else names[0]
            if self.tp_axis not in names:
                raise ValueError(
                    f"tp_axis {self.tp_axis!r} is not an axis of the "
                    f"mesh {names}")
            self.tp_degree = int(mesh.shape[self.tp_axis])
            if int(num_heads) % self.tp_degree:
                raise ValueError(
                    f"num_heads={num_heads} is not divisible by "
                    f"tp_degree={self.tp_degree} (axis {self.tp_axis!r} "
                    f"of the mesh): the head axis is the shard axis")
            self._sharding = named_sharding(
                mesh, *kv_pool_spec(pool_layout, self.tp_axis))
            self._scale_sharding = named_sharding(
                mesh, *kv_scale_spec(self.tp_axis))
        super().__init__(num_layers, num_heads, head_dim,
                         num_pages=num_pages, page_size=page_size,
                         dtype=dtype)

    @property
    def pool_sharding(self):
        """The pools' NamedSharding (None when unsharded) — what the
        fused step's prewarm ShapeDtypeStructs must carry."""
        return self._sharding

    @property
    def scale_sharding(self):
        """NamedSharding of the [P, H] scale arrays (heads sharded —
        kv_scale_spec); None when unsharded or not quantized."""
        return self._scale_sharding

    def _materialize_pools(self, shape):
        """Fresh zeroed per-layer pool storage in the pool's sharding —
        shared by construction and reset_pools so recovery re-creates
        the exact device layout it lost."""
        import jax

        jnp = self._jnp

        def zeros():
            z = jnp.zeros(shape, self.dtype)
            if self._sharding is not None:
                z = jax.device_put(z, self._sharding)
            return z

        if self.rows is not None:
            # a layer's page pool, or a state layer's state a slot ...
            self._k = [jnp.zeros(*self._row_pool_shape(layer))
                       for layer in range(self.num_layers)]
            # ... and the state layers' tails beside them
            self._v = [jnp.zeros((self.state_slots + 1,)
                                 + self.slot_state.tail_shape,
                                 self.slot_state.tail_dtype)
                       for _ in range(self.state_layers)]
            return
        self._k = [zeros() for _ in range(self.num_layers)]
        self._v = [zeros() for _ in range(self.num_layers)]
        if self.quantized:
            def zscale():
                z = jnp.zeros((self.num_pages, self.num_heads),
                              jnp.float32)
                if self._scale_sharding is not None:
                    z = jax.device_put(z, self._scale_sharding)
                return z

            self._ks = [zscale() for _ in range(self.num_layers)]
            self._vs = [zscale() for _ in range(self.num_layers)]
            # pages allocated since the last device write: their scale
            # rows must zero before the next quantized write reads them
            # (one batched donated dispatch, not one per allocation)
            self._pending_scale_reset = []

    def _init_pools(self):
        import jax.numpy as jnp

        self._jnp = jnp
        self._groups = (1 if self.rows is not None
                        else 4 if self.quantized else 2)
        if self.rows is not None:
            if self.quantized:
                raise UnsupportedCachePathError(
                    "int8 latent pools are not carried")
            self._materialize_pools(None)
            return
        if self.pool_layout == "kernel":
            shape = (self.num_heads, self.num_pages, self.page_size,
                     self.head_dim)
        else:
            shape = (self.num_pages, self.page_size,
                     self.num_heads, self.head_dim)
        self._materialize_pools(shape)
        if self.quantized:
            self._scatter, self._scatter_all = _jitted_scatter_quantized(
                self.pool_layout, self._sharding, self._scale_sharding)
        else:
            self._scatter, self._scatter_all = _jitted_scatter(
                self.pool_layout, self._sharding)

    # ---------------------- quantized-scale state --------------------
    def _reset_page_scale(self, page):
        """Defer the zeroing: allocations happen page-at-a-time in
        reserve(), and a dispatch per page would swamp the decode loop.
        The pending rows are flushed in ONE donated scatter before the
        next read or write of the scale state."""
        self._pending_scale_reset.append(int(page))

    def _flush_scale_resets(self):
        if not self.quantized or not self._pending_scale_reset:
            return
        pages = self._pending_scale_reset
        self._pending_scale_reset = []
        # pad to a power-of-two bucket with the drop sentinel so the
        # jitted reset compiles O(log pool) signatures, not one per
        # allocation burst size
        m = 1
        while m < len(pages):
            m *= 2
        padded = np.full((m,), self.num_pages, np.int32)
        padded[:len(pages)] = pages
        fn = _jitted_scale_reset(self._scale_sharding)
        self._ks, self._vs = fn(self._ks, self._vs,
                                self._jnp.asarray(padded))

    def layer_scales(self, layer):
        if not self.quantized:
            return None, None
        self._flush_scale_resets()
        return self._ks[layer], self._vs[layer]

    # --------------------------- writes -----------------------------
    def _pages_touched(self, pages):
        """Distinct REAL pages in a scatter target list (sentinel
        excluded) — the scale-traffic unit of a quantized write."""
        arr = np.asarray(pages)
        return int(len(np.unique(arr[arr < self.num_pages])))

    def _scatter_layer(self, layer, pages, rows, k, v, real_tokens):
        self._refuse_latent("a per-layer K/V write")
        jnp = self._jnp
        kp, vp = self._k[layer], self._v[layer]
        pg = jnp.asarray(np.asarray(pages), jnp.int32)
        rw = jnp.asarray(np.asarray(rows), jnp.int32)
        if self.quantized:
            self._flush_scale_resets()
            k = jnp.asarray(k).astype(jnp.float32)
            v = jnp.asarray(v).astype(jnp.float32)
            (self._k[layer], self._v[layer], self._ks[layer],
             self._vs[layer]) = self._scatter(
                kp, vp, self._ks[layer], self._vs[layer], pg, rw, k, v)
            self._count_scale_payload(self._pages_touched(pages), 1)
        else:
            k = jnp.asarray(k).astype(self.dtype)
            v = jnp.asarray(v).astype(self.dtype)
            self._k[layer], self._v[layer] = self._scatter(
                kp, vp, pg, rw, k, v)
        self._count_write_payload(real_tokens, 1)

    def write_token(self, seq_id, layer, pos, k, v):
        page, row = self._locate(seq_id, pos)
        self._scatter_layer(layer, [page], [row],
                            self._jnp.asarray(k)[None],
                            self._jnp.asarray(v)[None], 1)

    def write_decode_tokens(self, seq_ids, positions, layer, k, v):
        pages, rows = [], []
        for i, sid in enumerate(seq_ids):
            page, row = self._locate(sid, int(positions[i]))
            pages.append(page)
            rows.append(row)
        self._scatter_layer(layer, pages, rows, k, v, len(seq_ids))

    def _scatter_layers_once(self, pages, rows, k, v, real_tokens):
        """One donated dispatch covering every layer; k, v: [L, n, H, D]
        (indices are the same per layer, so there is no reason to pay
        num_layers dispatch latencies)."""
        self._refuse_latent("a K/V append")
        jnp = self._jnp
        pg = jnp.asarray(np.asarray(pages), jnp.int32)
        rw = jnp.asarray(np.asarray(rows), jnp.int32)
        if self.quantized:
            self._flush_scale_resets()
            self._k, self._v, self._ks, self._vs = self._scatter_all(
                self._k, self._v, self._ks, self._vs, pg, rw,
                jnp.asarray(k).astype(jnp.float32),
                jnp.asarray(v).astype(jnp.float32))
            self._count_scale_payload(self._pages_touched(pages),
                                      self.num_layers)
        else:
            self._k, self._v = self._scatter_all(
                self._k, self._v, pg, rw,
                jnp.asarray(k).astype(self.dtype),
                jnp.asarray(v).astype(self.dtype))
        self._count_write_payload(real_tokens, self.num_layers)

    def append(self, seq_id, k, v):
        pos = self.reserve(seq_id, 1)
        page, row = self._locate(seq_id, pos)
        k = self._jnp.asarray(k)[:, None]   # [L, 1, H, D]
        v = self._jnp.asarray(v)[:, None]
        self._scatter_layers_once([page], [row], k, v, 1)
        return pos

    def _span_pages_rows(self, seq_id, start, n, pad_to=None):
        """(pages, rows) int32 for positions [start, start+n), padded to
        `pad_to` entries with the DROP sentinel (page id num_pages)."""
        table = self._table(seq_id)
        pad_to = n if pad_to is None else pad_to
        pages = np.full((pad_to,), self.num_pages, np.int32)
        rows = np.zeros((pad_to,), np.int32)
        pos = start + np.arange(n)
        pages[:n] = np.asarray(table, np.int32)[pos // self.page_size]
        rows[:n] = pos % self.page_size
        return pages, rows

    def append_prefill(self, seq_id, k, v):
        k = self._jnp.asarray(k)                # [L, T, H, D]
        v = self._jnp.asarray(v)
        n = k.shape[1]
        start = self.reserve(seq_id, n)
        self._check_span_writable(seq_id, start, n)
        pages, rows = self._span_pages_rows(seq_id, start, n)
        self._scatter_layers_once(pages, rows, k, v, n)
        return start

    def write_prefill_batch(self, seq_ids, starts, lengths, k, v):
        k = self._jnp.asarray(k)
        v = self._jnp.asarray(v)
        b, _, t_pad = k.shape[:3]
        all_pages = np.empty((b, t_pad), np.int32)
        all_rows = np.empty((b, t_pad), np.int32)
        for i, sid in enumerate(seq_ids):
            n = int(lengths[i])
            self._check_span_writable(sid, int(starts[i]), n)
            all_pages[i], all_rows[i] = self._span_pages_rows(
                sid, int(starts[i]), n, pad_to=t_pad)
        real = int(np.sum(np.asarray(lengths)))
        h, d = self.num_heads, self.head_dim
        # [B, L, Tp, H, D] -> [L, B*Tp, H, D]: one flattened scatter
        # covering the whole chunk across every layer
        lk = self._jnp.transpose(k, (1, 0, 2, 3, 4)).reshape(
            self.num_layers, b * t_pad, h, d)
        lv = self._jnp.transpose(v, (1, 0, 2, 3, 4)).reshape(
            self.num_layers, b * t_pad, h, d)
        self._scatter_layers_once(all_pages.reshape(-1),
                                  all_rows.reshape(-1), lk, lv, real)

    def write_prefill_tokens(self, seq_id, start, layer, k, v):
        """One chunk's span for one layer as a single donated scatter
        (the per-layer sibling of write_decode_tokens)."""
        k = self._jnp.asarray(k)
        v = self._jnp.asarray(v)
        n = k.shape[0]
        self._check_span_writable(seq_id, int(start), n)
        pages, rows = self._span_pages_rows(seq_id, int(start), n)
        self._scatter_layer(layer, pages, rows, k, v, n)

    def export_pages(self, pages):
        """Device export: gather ONLY the requested pages per layer
        (never the k_pool debug property's whole-pool stack) and hand
        back canonical host arrays.  Under a mesh the gather is the
        per-shard read GSPMD assembles — np.asarray on the sharded
        slice collects every device's head split into the canonical
        full-head payload."""
        self._refuse_latent("a K/V page export")
        jnp = self._jnp
        self._flush_scale_resets()
        idx = jnp.asarray(np.asarray(pages, np.int32).reshape(-1))
        ks, vs = [], []
        for layer in range(self.num_layers):
            kp, vp = self._k[layer], self._v[layer]
            if self.pool_layout == "kernel":   # [H, P, ps, D]
                k = jnp.transpose(kp[:, idx], (1, 2, 0, 3))
                v = jnp.transpose(vp[:, idx], (1, 2, 0, 3))
            else:                              # [P, ps, H, D]
                k, v = kp[idx], vp[idx]
            ks.append(np.asarray(k))
            vs.append(np.asarray(v))
        k = np.stack(ks)
        v = np.stack(vs)
        self._bytes_moved += k.nbytes + v.nbytes
        if not self.quantized:
            return k, v
        kss = np.stack([np.asarray(self._ks[layer][idx])
                        for layer in range(self.num_layers)])
        vss = np.stack([np.asarray(self._vs[layer][idx])
                        for layer in range(self.num_layers)])
        self._count_scale_payload(int(idx.shape[0]), self.num_layers)
        return k, v, kss, vss

    def _install_pages(self, pages, k, v, k_scale=None, v_scale=None):
        """Device import: one donated dispatch installs the canonical
        payload across every layer's pools, sharding pinned (a
        mesh-sharded pool comes back in its NamedSharding — the same
        contract as every other write path).  Quantized pools install
        the exporter's scale rows in the same dispatch."""
        self._refuse_latent("a K/V page import")
        jnp = self._jnp
        pg = jnp.asarray(np.asarray(pages, np.int32))
        if self.quantized:
            self._flush_scale_resets()
            fn = _jitted_import_quantized(self.pool_layout,
                                          self._sharding,
                                          self._scale_sharding)
            self._k, self._v, self._ks, self._vs = fn(
                self._k, self._v, self._ks, self._vs, pg,
                jnp.asarray(np.asarray(k, np.int8)),
                jnp.asarray(np.asarray(v, np.int8)),
                jnp.asarray(np.asarray(k_scale, np.float32)),
                jnp.asarray(np.asarray(v_scale, np.float32)))
            return
        fn = _jitted_import(self.pool_layout, self._sharding)
        self._k, self._v = fn(
            self._k, self._v, pg,
            jnp.asarray(k).astype(self.dtype),
            jnp.asarray(v).astype(self.dtype))

    def _copy_page_storage(self, src, dst):
        """The COW page copy as ONE donated in-trace dispatch across
        every layer — the payload never crosses the host<->device
        boundary (page-to-page inside the resident pools).  Quantized
        pools copy the scale rows with the bytes."""
        jnp = self._jnp
        if self.state_layers:
            raise UnsupportedCachePathError(
                "a shared page was asked of a cache with state layers: "
                "a prefix's pages say nothing of the recurrent state at "
                "its end, so no page of it is ever shared")
        if self.rows is not None:
            self._k = _jitted_latent_page_copy()(
                self._k, jnp.int32(src), jnp.int32(dst))
            return
        if self.quantized:
            self._flush_scale_resets()
            fn = _jitted_page_copy_quantized(self.pool_layout,
                                             self._sharding,
                                             self._scale_sharding)
            self._k, self._v, self._ks, self._vs = fn(
                self._k, self._v, self._ks, self._vs, jnp.int32(src),
                jnp.int32(dst))
            return
        fn = _jitted_page_copy(self.pool_layout, self._sharding)
        self._k, self._v = fn(self._k, self._v, jnp.int32(src),
                              jnp.int32(dst))

    # --------------------------- reads ------------------------------
    def layer_pools(self, layer):
        """The live device arrays — nothing crosses the host<->device
        boundary here, unlike the host backend's O(pool) upload."""
        self._refuse_latent("a (K, V) pool pair")
        return self._k[layer], self._v[layer]

    # ------------------------- latent pools --------------------------
    # A latent pool (`rows`, a LatentRows) keeps the page tables, the
    # prefix tree, copy-on-write, truncate and eviction of any pool:
    # they are bookkeeping over page ids.  Its storage is one array a
    # layer, written only inside the ragged step (the model scatters a
    # token's row in the trace; `take_pool_state` hands the L pools
    # over) and copied page to page on a copy-on-write.  What reads or
    # writes per-head K and V (the eager and fused-decode paths, the
    # disaggregated fleet's page payloads) is refused by name.
    def _row_pool_shape(self, layer):
        """(shape, dtype) of one layer's array: [pages, page_size,
        lanes] of a row pool (the window group's pages for a window
        layer, else this cache's), [slots + 1, ...] of a state layer's
        recurrent state."""
        kind = self.layer_kinds[layer] if self.layer_kinds else "full"
        if kind == "state":
            return ((self.state_slots + 1,) + self.slot_state.state_shape,
                    self.slot_state.state_dtype)
        pages = (self.window_group.num_pages if kind == "window"
                 else self.num_pages)
        return (pages, self.page_size, self.rows.lanes), self.dtype

    @property
    def state_layers(self):
        """How many layers keep a state a slot and no pages."""
        return (self.layer_kinds or ()).count("state")

    def _refuse_latent(self, what):
        if self.rows is not None:
            raise UnsupportedCachePathError(
                f"{what} was asked of a row pool, which holds one "
                f"[{self.rows.lanes}]-lane row a token and no K or V "
                f"pool"
                + (f"; its {self.state_layers} state layers keep a "
                   f"recurrent state a slot that no page holds"
                   if self.state_layers else ""))

    def latent_pool(self, layer):
        """One layer's live latent pool [P, page_size, lanes]."""
        return self._k[layer]

    def _count_latent_payload(self, tokens, layers):
        self._bytes_moved += (tokens * layers * self.rows.width
                              * self.dtype.itemsize)

    def gather_prefix(self, seq_id, layer, length):
        """Device-resident prefix gather: rows come straight out of the
        live pool arrays (same values as the host override — the stored
        dtype is the stored dtype), nothing crosses the host<->device
        boundary."""
        self._refuse_latent("a K/V prefix gather")
        self._check_span(seq_id, 0, int(length))
        table = self._table(seq_id)
        length = int(length)
        jnp = self._jnp
        pages = jnp.asarray(
            np.asarray(table, np.int32)[:math.ceil(length
                                                   / self.page_size)])
        kp, vp = self._k[layer], self._v[layer]
        if self.pool_layout == "kernel":
            # [H, P, ps, D] -> [n_pages, ps, H, D] view of owned pages
            k = jnp.transpose(kp[:, pages], (1, 2, 0, 3))
            v = jnp.transpose(vp[:, pages], (1, 2, 0, 3))
        else:
            k, v = kp[pages], vp[pages]
        shape = (-1, self.num_heads, self.head_dim)
        k = k.reshape(shape)[:length]
        v = v.reshape(shape)[:length]
        if self.quantized:
            # hand back DEQUANTIZED rows — the same per-page factor the
            # in-kernel dequant applies to the same bytes
            from .quantized_kv import dequantize_int8

            self._flush_scale_resets()
            ks = jnp.repeat(self._ks[layer][pages], self.page_size,
                            axis=0)[:length][:, :, None]
            vs = jnp.repeat(self._vs[layer][pages], self.page_size,
                            axis=0)[:length][:, :, None]
            return (dequantize_int8(k, ks, jnp),
                    dequantize_int8(v, vs, jnp))
        return k, v

    @property
    def n_state_groups(self):
        """Length-L array groups in the donated pool state: k + v
        pools, plus k + v scale arrays when quantized; the one latent
        pool of a latent cache — what take_pool_state returns and the
        fused wrappers split on."""
        return self._groups

    @property
    def state_group_sizes(self):
        """The lengths of the array groups `take_pool_state` lays end to
        end: `n_state_groups` of L, and behind a row cache's L arrays
        the state layers' tails, one a state layer."""
        sizes = (self.num_layers,) * self._groups
        return sizes + ((self.state_layers,) if self.state_layers else ())

    def take_pool_state(self):
        """The WHOLE donated device state as one flat list —
        ``[*k_pools, *v_pools]`` plus ``[*k_scales, *v_scales]`` when
        quantized (scales are written in-trace by the quantized
        scatter, so they ride the same donation chain as the pools).
        Pending scale resets flush first: the executable must see
        zeroed rows for freshly allocated pages."""
        self._flush_scale_resets()
        state = list(self._k) + list(self._v)
        if self.quantized:
            state += list(self._ks) + list(self._vs)
        return state

    def put_pool_state(self, state):
        """Install the flat state list a donating dispatch returned
        (the donation chain's other half)."""
        want = sum(self.state_group_sizes)
        if len(state) != want:
            raise ValueError(
                f"expected {want} state arrays (groups of "
                f"{self.state_group_sizes}), got {len(state)}")
        ll = self.num_layers
        self._k = list(state[:ll])
        self._v = list(state[ll:ll + len(self._v)])
        if self.quantized:
            self._ks = list(state[2 * ll:3 * ll])
            self._vs = list(state[3 * ll:4 * ll])

    def reset_pools(self):
        """Reallocate zeroed pool storage after a donating dispatch died
        mid-flight (the donated buffers are invalid and no replacement
        was returned).  KV content is lost by construction — the engine
        fails every in-flight sequence on a poisoned step, so fresh
        zeroed storage is exactly the state later requests expect.
        Goes through _materialize_pools, so a mesh-sharded pool comes
        back in its NamedSharding — a recovery that silently rebuilt
        single-device pools would poison every later sharded dispatch
        (the AOT executables are lowered against the sharded layout).
        The prefix index is FLUSHED with the storage: its nodes alias
        pages whose bytes were just zeroed, and a later warm hit
        against them would silently generate from garbage — stale
        cache entries must die with the content they indexed."""
        self._materialize_pools(None if self.rows is not None
                                else self._k[0].shape)
        self.flush_prefix_cache()

    def _canonical(self, pool):
        """[H, P, ps, D] -> [P, ps, H, D] for kernel-layout pools."""
        pool = np.asarray(pool)
        if self.pool_layout == "kernel":
            pool = pool.transpose(1, 2, 0, 3)
        return pool

    @property
    def k_pool(self):
        """Host copy ``[L, P, page_size, H, D]`` in the canonical token
        layout whatever pool_layout is; a latent cache's
        ``[L, P, page_size, lanes]`` (debug/tests only)."""
        return np.stack([self._canonical(p) for p in self._k])

    @property
    def v_pool(self):
        self._refuse_latent("a V pool")
        return np.stack([self._canonical(p) for p in self._v])

    @property
    def k_scale(self):
        """Host copy ``[L, P, H]`` of the quantized K scales
        (debug/tests only — mirrors the host backend's attribute)."""
        self._flush_scale_resets()
        return np.stack([np.asarray(s) for s in self._ks])

    @property
    def v_scale(self):
        self._flush_scale_resets()
        return np.stack([np.asarray(s) for s in self._vs])


def _jitted_scatter(layout, sharding=None):
    """The shared jitted donated scatters, one pair per (pool layout,
    pool sharding) — NamedSharding is hashable, so sharded pools get
    their own cached executables with the output pinned to the pool's
    sharding (module-level cache: every pool instance reuses the same
    executables per shape signature)."""
    import functools

    key = (layout, sharding)
    if key not in _SCATTER_JIT:
        import jax

        _SCATTER_JIT[key] = (
            jax.jit(functools.partial(_scatter_kv, layout=layout,
                                      sharding=sharding),
                    donate_argnums=(0, 1)),
            jax.jit(functools.partial(_scatter_kv_all_layers,
                                      layout=layout, sharding=sharding),
                    donate_argnums=(0, 1)))
    return _SCATTER_JIT[key]


_SCATTER_JIT = {}
