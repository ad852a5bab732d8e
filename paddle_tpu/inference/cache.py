"""Paged KV cache manager (ref vLLM block manager, Kwon et al. SOSP 2023).

Host-side page accounting for the serving engine: a free list over a static
device pool (`models.gpt.init_paged_cache`), per-slot page-table rows,
per-slot lengths, per-page refcounts, and a content-hash prefix index.  All
methods are O(pages) host operations — the device only ever sees the
fixed-shape `[num_slots, max_pages_per_slot]` table and `[num_slots]`
lengths, so the compiled decode step never changes shape.

Two allocation disciplines (the engine's `admission=` knob):

- **reservation** (default): a request's full footprint
  (prompt + max_new_tokens, rounded up to pages) is reserved at admission, so
  a running sequence can never hit out-of-pages mid-decode.
- **optimistic** (vLLM-style, Kwon et al. §4.3): only the prompt footprint is
  reserved at admission and the slot's pages `grow()` token-granularly as
  decode proceeds — live tokens, not worst-case reservations, bound
  concurrency.  A failed `grow()` is the engine's preemption trigger: the
  victim's pages either swap to a host-side pool (its page count tracked
  here as the fourth `swapped` partition, `note_swap_out`/`note_swap_in`) or
  are simply released and the sequence recomputed later as a longer prompt.

Page 0 is reserved as the null page: unreserved table entries point at it,
inactive slots write to it, and attention masking by length guarantees it is
never read.

Prefix cache (vLLM copy-on-write page sharing): prompt pages whose KV has
been fully written are registered in a trie-shaped index keyed by
(parent node, token bytes) — i.e. by the token-id *content* of the whole
prefix up to that page.  A later request whose prompt shares a page-aligned
prefix maps the cached pages read-only into its table row (refcount++) and
only prefills the tail; a matched *partial* final page is shared
copy-on-write: the caller copies the page on device into a fresh page the
new slot owns before appending into it.  Pages are freed only when their
refcount returns to 0; registered pages at refcount 0 park in an LRU of
evictable prefixes and are reclaimed on demand, so cached prefixes can never
deadlock the pool.

Rolling-hash partial-page index: next to the page-granularity trie, every
registered page also indexes the PREFIXES of its token content under a
polynomial rolling hash, so a prompt sharing only a partial tail of a cached
page (any page, not just one that happened to be registered as a partial
node) COW-copies the matched fraction and prefills only the true remainder.
Hash hits are verified against the node's stored token bytes before use, so
a collision can never corrupt a match.

KV tiering (device -> host -> optional disk): when a `HostKVTier` is
attached (`attach_tier`), `_evict` no longer drops retired prefixes — their
page CONTENT spills to a bounded host tier through the engine's spill
callback (the PR-10 `swap_out_pages` gather over the evicted pages alone;
their copy to the host starts at once beside the engine's steps and the
entry stays PENDING until the bytes have landed) and the trie node stays
matchable with `page = HOST_PAGE`.  A
later `allocate_prefixed` whose prefix lives off-device assigns fresh pages
to those nodes and returns a restore plan (`take_restore`): the engine
scatters the parked KV back with ONE `swap_in_pages` dispatch and
`commit_restore` re-registers the nodes on device — a returning session's
conversation KV restores with one h2d scatter instead of a full re-prefill.
The host tier shares the engine's unified host-pool page budget with
preemption swap parking (`host_pool_room`); over budget it cascades to a
disk tier (`spill_dir=`) or drops, oldest first.

Durable tier index + PageStore (disaggregated serving PR): the disk level
writes through an object-store-shaped `PageStore` (`LocalDirStore` under
`spill_dir` by default), and `save_tier_index` / `load_tier_index`
serialize the trie + rolling-hash index beside the page objects
(versioned, atomic-rename writes) — so a restarted, or DIFFERENT, process
re-attaches any published session and restores it through the same
one-scatter path.  That transport is exactly the prefill->decode handoff
seam: a prefill-role engine exports its finished prompt's pages + index
into the shared store, and any decode-role replica's admission finds and
restores them.  A corrupted, version-skewed, or partially-deleted store
can only cost a re-prefill, never a crash or a wrong match (token content
rides in the index and every hash hit is verified against it).
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import os
from collections import OrderedDict
from typing import Callable, Dict, Iterable, List, Optional, Set, Tuple

import numpy as np

NULL_PAGE = 0
HOST_PAGE = -1      # node.page sentinel: content lives in the host/disk tier


@dataclasses.dataclass
class _PrefixNode:
    """One cached page of prompt KV: `page` holds the KV of `n_tokens` tokens
    whose identity (and that of the whole preceding prefix) is pinned by
    `key = (parent node id, token bytes)`.  n_tokens == page_size for full
    pages; a smaller n marks a partial page, shareable only via COW.
    page == HOST_PAGE marks a node whose KV content lives in the attached
    `HostKVTier` (host numpy or disk) instead of a device page.
    `partial_keys` are the rolling-hash partial-index entries this node
    registered — removed with the node so the index cannot dangle."""
    node_id: int
    key: Tuple[int, bytes]
    page: int
    n_tokens: int
    partial_keys: List[Tuple[int, int, int]] = \
        dataclasses.field(default_factory=list)


_ROOT = 0   # parent id of first-page nodes

# polynomial rolling hash over int32 token ids (base/modulus pairing keeps
# collisions rare; every hit is verified against the node's token bytes, so
# hash quality affects only lookup cost, never correctness)
_HASH_BASE = 1000003
_HASH_MOD = (1 << 61) - 1

# shortest partial-page tail worth matching: a 1-token hit costs a COW page
# copy (and, in bucketed mode, the chunk-tail prefill path) to save one
# token of prefill — and at small vocabularies single-token prefixes of
# unrelated prompts coincide often enough (~#root-children/vocab per
# admission) to tax the dispatch account with worthless hits
_MIN_PARTIAL = 2

# serialized tier-index format version: `load_tier_index` only merges index
# blobs whose version AND page geometry match — anything else is ignored and
# the affected sessions degrade to re-prefill (never a crash)
TIER_INDEX_VERSION = 1

# distinguishes page objects written by different tiers sharing one store
# (a disagg fleet's prefill + decode engines, or successive processes over
# one spill_dir): node ids are only unique per process, store names must be
# unique per writer
_TIER_TAGS = itertools.count()


class PageStore:
    """Object-store-shaped durable level under the host KV tier.

    The tier addresses content by NAME — ``kvnode_<tag>_<id>`` for page
    slabs, ``kvindex_<tag>`` for serialized index blobs — and a store maps
    names to bytes.  `LocalDirStore` below is the default; an S3/GCS-shaped
    backend only has to implement these six methods, because the tier, the
    durable index, and the cross-engine handoff never touch the filesystem
    directly."""

    def put(self, name: str, data: Dict[str, np.ndarray]) -> None:
        """Store one page slab ({lane name: array}) under `name`."""
        raise NotImplementedError

    def get(self, name: str) -> Dict[str, np.ndarray]:
        """Load a page slab; KeyError-family exceptions degrade upstream."""
        raise NotImplementedError

    def delete(self, name: str) -> None:
        raise NotImplementedError

    def exists(self, name: str) -> bool:
        raise NotImplementedError

    def put_blob(self, name: str, payload: bytes) -> None:
        """Store an opaque blob (index files); must be atomic — a reader
        may never observe a torn write."""
        raise NotImplementedError

    def blobs(self, prefix: str) -> Iterable[Tuple[str, bytes]]:
        """Iterate (name, payload) over stored blobs under `prefix`."""
        raise NotImplementedError


class LocalDirStore(PageStore):
    """The default `PageStore`: one npz file per page slab plus
    atomically-renamed index blobs, all under one directory (the engine's
    `spill_dir`) — the PR-15 disk-tier layout, now behind the store
    interface so any replica (or a restarted process) can read it."""

    def __init__(self, root: str):
        self.root = root
        os.makedirs(root, exist_ok=True)

    def _path(self, name: str) -> str:
        return os.path.join(self.root, name)

    def put(self, name: str, data: Dict[str, np.ndarray]) -> None:
        np.savez(self._path(name + ".npz"), **data)

    def get(self, name: str) -> Dict[str, np.ndarray]:
        with np.load(self._path(name + ".npz")) as z:
            return {k: z[k] for k in z.files}

    def delete(self, name: str) -> None:
        path = self._path(name + ".npz")
        if os.path.exists(path):
            os.remove(path)

    def exists(self, name: str) -> bool:
        return os.path.exists(self._path(name + ".npz"))

    def put_blob(self, name: str, payload: bytes) -> None:
        # tmp-write + atomic rename: a concurrent reader (another replica's
        # merge, a restarting process) sees the old blob or the new one,
        # never a torn one
        path, tmp = self._path(name), self._path(name + ".tmp")
        with open(tmp, "wb") as f:
            f.write(payload)
        os.replace(tmp, path)

    def blobs(self, prefix: str) -> Iterable[Tuple[str, bytes]]:
        try:
            names = sorted(os.listdir(self.root))
        except OSError:
            return
        for fn in names:
            if not fn.startswith(prefix) or fn.endswith(".tmp"):
                continue
            try:
                with open(self._path(fn), "rb") as f:
                    yield fn, f.read()
            except OSError:
                continue


class HostKVTier:
    """Bounded host-side storage for spilled prefix-page KV, with an optional
    disk level underneath (`spill_dir`).

    Pure storage + LRU ordering: entries are keyed by prefix-node id and hold
    either host numpy page slabs ({lane name: [L, page_size, ...]}), a
    PENDING marker (the engine gathered the page on device and its d2h copy
    is in flight, or has landed and waits for the next step boundary), or a
    disk path.  Budget policy
    lives in the owner: `PagedKVCache.tier_make_room` pushes LRU host entries
    down to disk (or drops them) and the ENGINE decides how many pages of the
    unified host pool the tier may hold (`LLMEngine.swap_pool_pages` shared
    with preemption swap parking)."""

    _PENDING = object()

    def __init__(self, spill_dir: Optional[str] = None,
                 disk_pages: Optional[int] = None,
                 store: Optional[PageStore] = None):
        self._host: "OrderedDict[int, object]" = OrderedDict()
        # durable level: node id -> store name.  _shared marks entries whose
        # store object is visible to OTHER readers — imported from another
        # writer's index, or published in ours via `mark_shared` — so local
        # pop/drop remove the entry without deleting the object (a replica
        # restoring a handoff must not destroy the store under its peers;
        # object garbage collection is a store-level concern).
        self._disk: "OrderedDict[int, str]" = OrderedDict()
        self._shared: Set[int] = set()
        self.spill_dir = spill_dir
        self.disk_pages = disk_pages
        if store is None and spill_dir is not None:
            store = LocalDirStore(spill_dir)
        self.store = store
        # per-writer namespace for store object names (node ids are only
        # unique per process; two tiers sharing a store must not collide)
        self.tag = f"{os.getpid()}x{next(_TIER_TAGS)}"
        # monotonic event counts (the engine mirrors the user-facing ones
        # into its MetricsRegistry; these back the invariant checks)
        self.disk_spills = 0
        self.disk_restores = 0
        self.tier_drops = 0

    # ---- occupancy --------------------------------------------------------
    @property
    def pages_host(self) -> int:
        """Host-resident pages, PENDING gathers included (they count against
        the unified host-pool budget: their bytes are committed)."""
        return len(self._host)

    @property
    def pages_disk(self) -> int:
        return len(self._disk)

    def has(self, node_id: int) -> bool:
        return node_id in self._host or node_id in self._disk

    def is_pending(self, node_id: int) -> bool:
        return self._host.get(node_id) is self._PENDING

    # ---- spill / fill -----------------------------------------------------
    def add_pending(self, node_id: int) -> None:
        """Reserve a host entry for a page whose device gather is in flight
        (the engine fills it once the bytes have landed: `_land_d2h`)."""
        if self.has(node_id):
            raise RuntimeError(f"tier node {node_id} already present")
        self._host[node_id] = self._PENDING

    def fill(self, node_id: int, data: Dict[str, np.ndarray]) -> None:
        """Land a pending entry's fetched page content."""
        if self._host.get(node_id) is not self._PENDING:
            raise RuntimeError(f"tier node {node_id} is not pending")
        self._host[node_id] = data

    # ---- read / restore ---------------------------------------------------
    def data(self, node_id: int) -> Dict[str, np.ndarray]:
        """The node's page content (host copy; read through from disk when
        it cascaded there — the entry STAYS at its level, so a read can
        never push the host level over its budget).  Raises KeyError when
        the node is unknown and RuntimeError while its d2h fetch is still
        pending (the engine drains pending gathers before restoring)."""
        if node_id in self._host:
            e = self._host[node_id]
            if e is self._PENDING:
                raise RuntimeError(f"tier node {node_id} still pending d2h")
            self._host.move_to_end(node_id)
            return e
        name = self._disk[node_id]      # KeyError: unknown node, degrade
        try:
            data = self.store.get(name)
        except (OSError, ValueError) as e:
            # object vanished/corrupted under us (shared store, another
            # process GC'd it): same degrade contract as an unknown node
            raise KeyError(f"tier node {node_id} store object {name!r} "
                           f"unreadable: {e}") from e
        self.disk_restores += 1
        return data

    def pop(self, node_id: int) -> None:
        """Remove an entry whose page moved back to the device tier.
        Shared store objects survive the pop — another replica (or a
        restarted process) may still restore from them."""
        if self._host.pop(node_id, None) is None:
            name = self._disk.pop(node_id)
            if node_id in self._shared:
                self._shared.discard(node_id)
            else:
                self.store.delete(name)

    def drop(self, node_id: int) -> None:
        """Discard an entry (node dropped from the index): host bytes
        released, and the store object too unless it is shared."""
        self._host.pop(node_id, None)
        name = self._disk.pop(node_id, None)
        if name is not None and node_id not in self._shared:
            self.store.delete(name)
        self._shared.discard(node_id)
        self.tier_drops += 1

    # ---- shared store (durable index / cross-engine handoff) --------------
    def import_entry(self, node_id: int, name: str) -> None:
        """Attach a store-resident page object (another writer's export, or
        a previous process's spill) as a disk-level entry of THIS tier,
        marked shared — restorable through the ordinary read path, never
        deleted by local bookkeeping."""
        if self.has(node_id):
            raise RuntimeError(f"tier node {node_id} already present")
        self._disk[node_id] = name
        self._shared.add(node_id)

    def mark_shared(self, node_ids: Iterable[int]) -> None:
        """Entries just published in a serialized index: their store objects
        may now be read by other replicas/processes, so local pop/drop must
        stop deleting them."""
        self._shared.update(nid for nid in node_ids if nid in self._disk)

    # ---- host -> disk cascade ---------------------------------------------
    def demotable(self) -> List[int]:
        """Host node ids oldest-first, pending entries excluded (their bytes
        do not exist on host yet, so they can neither demote nor drop)."""
        return [nid for nid, e in self._host.items()
                if e is not self._PENDING]

    def to_disk(self, node_id: int) -> bool:
        """Demote one host entry to the durable store level; False when no
        store is configured (the caller drops the node instead)."""
        if self.store is None:
            return False
        data = self._host[node_id]
        if data is self._PENDING:
            raise RuntimeError(f"cannot demote pending tier node {node_id}")
        name = f"kvnode_{self.tag}_{node_id}"
        self.store.put(name, data)
        del self._host[node_id]
        self._disk[node_id] = name
        self.disk_spills += 1
        return True


class RecurrentStateTable:
    """Which slots hold a live recurrent state (the second kind of serving
    state: per slot and state-space layer a convolution window and an SSM
    state of fixed size, indexed by SLOT and not by page — the device lanes
    are `models.hybrid.init_paged_cache`'s "conv.i"/"ssm.i").  `PagedKVCache`
    owns one (`attach_state`) and moves it with the slot's pages: allocated
    where the pages are reserved, freed where they are released, so admission
    asks one manager once.  Zeroing is the program's: a slot's first program
    (q_offset 0) starts it from zeros and counts the reset."""

    def __init__(self, num_slots: int, bytes_per_slot: int):
        self.num_slots = num_slots
        self.bytes_per_slot = int(bytes_per_slot)
        self.live: Set[int] = set()

    @property
    def pool_bytes(self) -> int:
        return self.num_slots * self.bytes_per_slot

    def alloc(self, slot: int) -> None:
        if slot in self.live:
            raise RuntimeError(f"slot {slot} already holds recurrent state")
        self.live.add(slot)

    def free(self, slot: int) -> None:
        self.live.discard(slot)


class PagedKVCache:
    """Page-table + free-list + prefix-index bookkeeping for `num_slots`
    decode slots over a pool of `num_pages` pages of `page_size` tokens."""

    def __init__(self, num_pages: int, page_size: int, num_slots: int,
                 max_pages_per_slot: int):
        if page_size & (page_size - 1):
            raise ValueError(f"page_size must be a power of 2, got {page_size}")
        if num_pages < 2:
            raise ValueError("need at least one real page beyond the null page")
        self.num_pages = num_pages
        self.page_size = page_size
        self.num_slots = num_slots
        self.max_pages_per_slot = max_pages_per_slot
        # page 0 reserved as the null page; ascending allocation order
        self._free = list(range(num_pages - 1, 0, -1))
        self.page_table = np.full((num_slots, max_pages_per_slot), NULL_PAGE,
                                  np.int32)
        self.lengths = np.zeros((num_slots,), np.int32)
        self._used: Dict[int, List[int]] = {s: [] for s in range(num_slots)}
        self._ref = np.zeros((num_pages,), np.int64)
        # prefix index: key -> node; page -> node (device nodes only); LRU of
        # refcount-0 device nodes; rolling-hash partial index
        # (parent, j, hash) -> node over every registered page's j-token
        # content prefixes
        self._index: Dict[Tuple[int, bytes], _PrefixNode] = {}
        self._page_node: Dict[int, _PrefixNode] = {}
        self._lru: "OrderedDict[int, _PrefixNode]" = OrderedDict()
        self._partial: Dict[Tuple[int, int, int], _PrefixNode] = {}
        self._node_ids = itertools.count(1)
        self.prefix_evictions = 0
        self._evictions_counter = None      # metrics mirror, see attach_metrics
        # KV tier (attach_tier): spilled-prefix storage + the engine's spill
        # callback; _restore_plan[slot] is the off-device part of the latest
        # allocate_prefixed match, consumed by the engine via take_restore
        self._tier: Optional[HostKVTier] = None
        self._spill_cb: Optional[
            Callable[[List[_PrefixNode]], Set[int]]] = None
        self._tier_nodes: Dict[int, _PrefixNode] = {}   # off-device nodes
        self._restore_plan: Dict[int, List[Tuple[int, _PrefixNode, int]]] = {}
        # fourth partition: pages whose KV content lives in the HOST swap
        # pool, keyed by request id (the device pages themselves were
        # released — this tracks the off-device obligation so drain checks
        # can prove nothing leaked there either)
        self._swapped: Dict[int, int] = {}
        # recurrent configurations: the slot-indexed state's bookkeeping
        self.state: Optional[RecurrentStateTable] = None

    def attach_state(self, table: RecurrentStateTable) -> None:
        self.state = table

    # ---- capacity queries -------------------------------------------------
    @property
    def num_free_pages(self) -> int:
        """Pages immediately allocatable without evicting cached prefixes."""
        return len(self._free)

    @property
    def num_evictable_pages(self) -> int:
        """Registered prefix pages at refcount 0 — reclaimable on demand."""
        return len(self._lru)

    def pages_needed(self, total_tokens: int) -> int:
        return -(-total_tokens // self.page_size)

    def can_allocate(self, total_tokens: int,
                     tokens: Optional[np.ndarray] = None) -> bool:
        """Whether a `total_tokens` footprint fits, counting evictable cached
        pages and (when the prompt `tokens` are given) pages the prefix cache
        would share instead of allocating fresh."""
        n = self.pages_needed(total_tokens)
        if n > self.max_pages_per_slot:
            return False
        fresh = n
        in_lru = 0
        if tokens is not None:
            full, partial = self._match(np.asarray(tokens, np.int32))
            # only DEVICE nodes share their page; off-device (tier) nodes
            # restore into fresh pages, so they reduce nothing here
            device_full = [nd for nd in full if nd.page >= 0]
            fresh = n - len(device_full)
            for node in device_full:
                if self._ref[node.page] == 0:
                    in_lru += 1         # shared, so not evictable for us
            if partial is not None and partial[0].page >= 0 and \
                    self._ref[partial[0].page] == 0:
                in_lru += 1             # COW source must survive the copy
        return fresh <= len(self._free) + len(self._lru) - in_lru

    def token_capacity(self) -> int:
        """Pool capacity in tokens (excludes the null page) — the number the
        engine's memory claim is measured against (vs num_slots * max_len)."""
        return (self.num_pages - 1) * self.page_size

    def pages_held(self, slot: int) -> int:
        """Pages currently mapped into `slot`'s table row (shared + private)
        — one of the three victim-selection signals."""
        return len(self._used[slot])

    def slot_pages(self, slot: int) -> List[int]:
        """The slot's page ids in table-row order (a copy)."""
        return list(self._used[slot])

    @property
    def swapped_page_count(self) -> int:
        """Pages whose KV currently lives in the host swap pool."""
        return sum(self._swapped.values())

    @property
    def swapped_requests(self) -> int:
        """Requests currently parked in the host swap pool."""
        return len(self._swapped)

    @property
    def tier_pages_host(self) -> int:
        """Spilled prefix pages resident on host (pending gathers included);
        0 with no tier attached."""
        return 0 if self._tier is None else self._tier.pages_host

    @property
    def tier_pages_disk(self) -> int:
        return 0 if self._tier is None else self._tier.pages_disk

    def host_pool_room(self, budget_pages: int) -> int:
        """Pages of host-pool room left under `budget_pages`: the budget
        minus everything already counted against the UNIFIED host pool —
        preemption swap parking AND spilled-prefix tier pages (disk pages
        are off-budget).  The PREEMPTION decision reads this number (can
        the victim park *now*, given what is already parked) so the
        parked-KV account cannot be double-spent; it may first reclaim tier
        room (`tier_make_room` — live victims outrank cached prefixes).
        Intake admission deliberately does NOT — it compares the request's
        worst case against the raw budget (could it EVER park, even in an
        empty pool: parked victims drain and tier pages are droppable on
        demand), because a transiently full pool must queue-and-drain, not
        reject (see `LLMEngine.add_request`).  Page counts are
        dtype-oblivious: an int8 pool parks the same page count in ~2-4x
        fewer host bytes (`LLMEngine.host_pool_bytes`)."""
        return budget_pages - self.swapped_page_count - self.tier_pages_host

    def attach_tier(self, tier: HostKVTier,
                    spill_cb: Callable[[List[_PrefixNode]], Set[int]]
                    ) -> None:
        """Enable KV tiering: `_evict` offers every retired prefix node to
        `spill_cb` (the engine's batched device gather) instead of dropping
        it; nodes the callback accepts (returned id set) stay in the index
        with their content parked in `tier`."""
        self._tier = tier
        self._spill_cb = spill_cb

    def tier_make_room(self, n_pages: int) -> int:
        """Reclaim up to `n_pages` of HOST-tier room for the unified host
        pool: LRU host entries demote to the disk level (when `spill_dir`
        is configured) or are dropped from the index outright.  Pending
        gathers cannot move.  Returns the pages actually freed — the
        preemption path calls this before parking a victim, so live work
        always outranks cached prefixes."""
        if self._tier is None or n_pages <= 0:
            return 0
        freed = 0
        for nid in self._tier.demotable():
            if freed >= n_pages:
                break
            node = self._node_by_id(nid)
            if self._tier.to_disk(nid):
                self._enforce_disk_cap()
            else:
                self._drop_node(node)
            freed += 1
        return freed

    def _enforce_disk_cap(self) -> None:
        if self._tier is None or self._tier.disk_pages is None:
            return
        while self._tier.pages_disk > self._tier.disk_pages:
            nid = next(iter(self._tier._disk))
            self._drop_node(self._node_by_id(nid))

    def _node_by_id(self, node_id: int) -> _PrefixNode:
        return self._tier_nodes[node_id]

    def tier_data(self, node: _PrefixNode) -> Dict[str, np.ndarray]:
        """The parked page content of an off-device node (loads from disk
        when it cascaded there).  KeyError/RuntimeError propagate — the
        engine degrades the restore to re-prefill."""
        if self._tier is None:
            raise KeyError(f"no tier attached (node {node.node_id})")
        return self._tier.data(node.node_id)

    def drop_tier_nodes(self, nodes: List[_PrefixNode]) -> None:
        """Drop off-device nodes entirely (failed d2h/h2d copy, vanished
        data): index + partial entries + tier bytes all released — the
        degrade path re-prefills instead."""
        for node in nodes:
            if self._index.get(node.key) is node:
                self._drop_node(node)

    # ---- durable tier index (restart re-attach / cross-engine handoff) ----
    def save_tier_index(self, tag: str = "main") -> int:
        """Serialize the store-resident part of the prefix index — trie
        topology, token content, page-object names — as ``kvindex_<tag>``
        beside the page objects (versioned, atomic-rename-written).  Only
        nodes whose WHOLE ancestor chain is store-resident are published: a
        chain broken by a device/host-only ancestor is unreachable to a
        reader anyway (`_match` walks from the root).  Publishing marks the
        referenced page objects shared, so this tier stops deleting them on
        pop/drop — another replica may now restore from them.  Returns the
        node count published (0 with no store attached)."""
        tier = self._tier
        if tier is None or tier.store is None:
            return 0
        nodes = {n.node_id: n for n in self._index.values()
                 if n.page < 0 and n.node_id in tier._disk}
        ok: Dict[int, bool] = {_ROOT: True}

        def _chain_ok(nid: int) -> bool:
            got = ok.get(nid)
            if got is None:
                node = nodes.get(nid)
                got = ok[nid] = node is not None and _chain_ok(node.key[0])
            return got

        rows = []
        for nid in sorted(nodes):       # node ids are parent-first monotonic
            node = nodes[nid]
            if not _chain_ok(nid):
                continue
            rows.append({"id": nid, "parent": node.key[0],
                         "tokens": np.frombuffer(node.key[1],
                                                 np.int32).tolist(),
                         "n_tokens": node.n_tokens,
                         "name": tier._disk[nid]})
        doc = {"version": TIER_INDEX_VERSION, "page_size": self.page_size,
               "nodes": rows}
        tier.store.put_blob(f"kvindex_{tag}",
                            json.dumps(doc, sort_keys=True).encode("utf-8"))
        tier.mark_shared(r["id"] for r in rows)
        return len(rows)

    def load_tier_index(self) -> int:
        """Merge every readable ``kvindex_*`` blob in the attached store
        into the live prefix index: each published node whose parent chain
        resolves (locally known, or imported by an earlier row) and whose
        page object still exists becomes an off-device node of THIS cache,
        restorable through the ordinary one-scatter tier path.  Remote node
        ids are remapped to fresh local ids as the rows are walked
        parent-first.  Rows that are corrupt, version- or geometry-skewed,
        already cached here, or missing their page object are skipped — a
        damaged store can only cost a re-prefill, never a crash or a wrong
        match (token content rides in the index, so the rebuilt
        rolling-hash entries verify exactly like locally-registered ones).
        Idempotent: re-merging is how a decode replica refreshes its view
        of a shared store between handoffs.  Returns nodes imported."""
        tier = self._tier
        if tier is None or tier.store is None:
            return 0
        imported = 0
        for _, payload in tier.store.blobs("kvindex_"):
            try:
                doc = json.loads(payload.decode("utf-8"))
            except (ValueError, UnicodeDecodeError):
                continue                # corrupt blob: ignore entirely
            if not isinstance(doc, dict) \
                    or doc.get("version") != TIER_INDEX_VERSION \
                    or doc.get("page_size") != self.page_size \
                    or not isinstance(doc.get("nodes"), list):
                continue                # version/geometry skew: ignore
            idmap = {_ROOT: _ROOT}
            for row in doc["nodes"]:
                try:
                    rid = int(row["id"])
                    parent = int(row["parent"])
                    toks = np.asarray(row["tokens"], np.int32)
                    ntok = int(row["n_tokens"])
                    name = str(row["name"])
                except (KeyError, TypeError, ValueError):
                    continue            # malformed row: skip
                if parent not in idmap or toks.ndim != 1 \
                        or toks.size != ntok \
                        or not 0 < ntok <= self.page_size:
                    continue
                key = (idmap[parent], toks.tobytes())
                known = self._index.get(key)
                if known is not None:   # already cached here (any level)
                    idmap[rid] = known.node_id
                    continue
                if not tier.store.exists(name):
                    continue            # page object gone: chain ends here
                nid = next(self._node_ids)
                node = _PrefixNode(nid, key, HOST_PAGE, ntok)
                self._index[key] = node
                self._register_partial(node)
                self._tier_nodes[nid] = node
                tier.import_entry(nid, name)
                idmap[rid] = nid
                imported += 1
        return imported

    def pool_pressure(self) -> float:
        """Fraction of the real pool in live use (0.0 idle .. 1.0 full) —
        the overload gauge victim selection and dashboards key on."""
        return self.pages_in_use() / max(1, self.num_pages - 1)

    def attach_metrics(self, registry) -> None:
        """Register page-accounting observability on a
        `inference.metrics.MetricsRegistry`: pull gauges over the free/in-use/
        evictable partition (evaluated only at scrape/snapshot time — the
        allocator hot path pushes nothing) and a monotonic counter mirroring
        `prefix_evictions` (the int attribute stays authoritative for
        `stats()`; the counter is the Prometheus face of the same events)."""
        self._evictions_counter = registry.counter(
            "prefix_evictions", "cached prefix pages reclaimed under pressure")
        registry.gauge("kv_pages_in_use", self.pages_in_use,
                       "pages with refcount > 0")
        registry.gauge("kv_pages_free", lambda: self.num_free_pages,
                       "pages immediately allocatable")
        registry.gauge("kv_pages_evictable", lambda: self.num_evictable_pages,
                       "refcount-0 cached prefix pages, reclaimable on demand")
        registry.gauge("prefix_cached_pages", lambda: len(self._index),
                       "pages registered in the prefix index")
        registry.gauge("kv_pages_swapped", lambda: self.swapped_page_count,
                       "pages whose KV lives in the host swap pool")
        registry.gauge("kv_tier_pages_host", lambda: self.tier_pages_host,
                       "spilled prefix pages resident in the host KV tier")
        registry.gauge("kv_tier_pages_disk", lambda: self.tier_pages_disk,
                       "spilled prefix pages serialized to the disk tier")
        # ratio gauge: a fleet merge folds it by MAX (a sum of per-replica
        # fractions would read >100% on a healthy fleet; the router's signal
        # is the worst member)
        registry.gauge("kv_pool_pressure", self.pool_pressure,
                       "fraction of the page pool in live use", agg="max")

    # ---- prefix index -----------------------------------------------------
    def _match(self, tokens: np.ndarray
               ) -> Tuple[List[_PrefixNode],
                          Optional[Tuple[_PrefixNode, int]]]:
        """Longest cached prefix of `tokens`, capped at len(tokens) - 1 so at
        least one position is always recomputed (its logits seed generation).
        Returns (full-page nodes, optional (partial node, matched tokens)
        extending them).  Full nodes may live off-device (page == HOST_PAGE)
        when a tier is attached — the caller restores them.  The partial
        match runs over the rolling-hash index: ANY registered page whose
        content starts with the prompt's tail yields a COW hit, not just a
        page registered under that exact partial content (the PR-2
        behavior this subsumes)."""
        page = self.page_size
        lp = tokens.size
        full: List[_PrefixNode] = []
        parent = _ROOT
        for i in range((lp - 1) // page):
            node = self._index.get((parent, tokens[i * page:(i + 1) * page]
                                    .tobytes()))
            if node is None:
                break
            full.append(node)
            parent = node.node_id
        base = len(full) * page
        partial = None
        h = 0
        for j in range(1, min(lp - base - 1, page - 1) + 1):
            h = (h * _HASH_BASE + int(tokens[base + j - 1]) + 1) % _HASH_MOD
            if j < _MIN_PARTIAL:
                continue
            node = self._partial.get((parent, j, h))
            if node is not None and \
                    node.key[1][:4 * j] == tokens[base:base + j].tobytes():
                partial = (node, j)     # longest verified hit wins
        return full, partial

    def _register_partial(self, node: _PrefixNode) -> None:
        """Index every proper prefix of `node`'s token content under the
        rolling hash (first registrant wins a colliding key — equal content
        hashes equally, so the match outcome is unaffected)."""
        toks = np.frombuffer(node.key[1], np.int32)
        cap = node.n_tokens if node.n_tokens < self.page_size \
            else self.page_size - 1
        h = 0
        parent = node.key[0]
        for j in range(1, cap + 1):
            h = (h * _HASH_BASE + int(toks[j - 1]) + 1) % _HASH_MOD
            if j < _MIN_PARTIAL:
                continue
            k = (parent, j, h)
            if k not in self._partial:
                self._partial[k] = node
                node.partial_keys.append(k)

    def _drop_node(self, node: _PrefixNode) -> None:
        """Remove a node from every index structure (its page, if any, is
        NOT touched — callers manage the free list)."""
        del self._index[node.key]
        for k in node.partial_keys:
            if self._partial.get(k) is node:
                del self._partial[k]
        node.partial_keys = []
        if node.page >= 0:
            self._page_node.pop(node.page, None)
        elif self._tier is not None:
            self._tier_nodes.pop(node.node_id, None)
            self._tier.drop(node.node_id)

    def register_prefix(self, slot: int, tokens: np.ndarray,
                        filled: int, upgrade: bool = False) -> None:
        """Publish `slot`'s prompt pages whose KV is complete (the first
        `filled` of `tokens`) into the prefix index.  Idempotent — call after
        every prefill chunk; already-indexed keys (including pages this slot
        itself shares) are left untouched, so duplicate concurrent prompts
        simply keep their private pages unregistered.  The final partial page
        is registered only once the whole prompt is in (filled == len) — its
        content hash must cover exactly the prompt tail, and the slot keeps
        appending decode tokens past it (harmless: the node only ever claims
        the first n_tokens of the page; COW borrowers overwrite the rest).

        `upgrade=True` (finish-time registration of GENERATED pages): a page
        this slot owns that is already claimed by a SHORTER partial node —
        the prompt-time claim over the prompt's tail, which the slot has
        since decoded past — is re-keyed in place to the longer content
        (`_upgrade_node`), instead of stopping the walk at it.  Both claims
        are true of the page's KV (the slot appended in place), so the
        upgrade only widens what future prompts can match."""
        tokens = np.asarray(tokens, np.int32)
        page = self.page_size
        pages = self._used[slot]
        parent = _ROOT
        for i in range(min(filled, tokens.size) // page):
            key = (parent, tokens[i * page:(i + 1) * page].tobytes())
            node = self._index.get(key)
            if node is None:
                holder = self._page_node.get(pages[i])
                if holder is None:
                    node = _PrefixNode(next(self._node_ids), key, pages[i],
                                       page)
                    self._index[key] = node
                    self._page_node[pages[i]] = node
                    self._register_partial(node)
                elif upgrade and holder.key[0] == parent and \
                        holder.n_tokens < page and \
                        key[1].startswith(holder.key[1]):
                    node = self._upgrade_node(holder, key, page)
            if node is None:        # page already published under another key
                return
            parent = node.node_id
        rem = tokens.size % page
        if rem and filled == tokens.size:
            i = tokens.size // page
            key = (parent, tokens[i * page:].tobytes())
            if key in self._index:
                return
            holder = self._page_node.get(pages[i])
            if holder is None:
                node = _PrefixNode(next(self._node_ids), key, pages[i], rem)
                self._index[key] = node
                self._page_node[pages[i]] = node
                self._register_partial(node)
            elif upgrade and holder.key[0] == parent and \
                    holder.n_tokens < rem and \
                    key[1].startswith(holder.key[1]):
                self._upgrade_node(holder, key, rem)

    def _upgrade_node(self, node: _PrefixNode, key: Tuple[int, bytes],
                      n_tokens: int) -> _PrefixNode:
        """Re-key `node` to a LONGER claim over the same page (finish-time
        registration: the owning slot decoded past the original claim, so
        the page now holds more verified content).  Identity — node_id,
        page, refcount/LRU state, trie children keyed by node_id — is
        preserved; only the content key and the rolling-hash partial
        entries move."""
        del self._index[node.key]
        for k in node.partial_keys:
            if self._partial.get(k) is node:
                del self._partial[k]
        node.partial_keys = []
        node.key = key
        node.n_tokens = n_tokens
        self._index[key] = node
        self._register_partial(node)
        return node

    def _evict(self, fresh_needed: int) -> None:
        """Reclaim LRU unreferenced cached prefixes until `fresh_needed`
        pages are on the free list (or the LRU runs dry).  With a tier
        attached, evicted nodes are offered to the engine's spill callback
        in ONE batch (the fixed-shape `swap_out_pages` gather, its output in
        pieces of a few pages, each wanted piece's d2h copy started at once
        off the engine thread):
        accepted nodes keep their index entry with `page = HOST_PAGE`; the
        rest drop as before.  The page returns to the free list either way —
        the gather dispatch is ordered before any dispatch that could
        overwrite the page, so its content is safe to fetch later."""
        evicted: List[_PrefixNode] = []
        while len(self._free) < fresh_needed and self._lru:
            _, node = self._lru.popitem(last=False)
            evicted.append(node)
            self._free.append(node.page)
            self.prefix_evictions += 1
            if self._evictions_counter is not None:
                self._evictions_counter.inc()
        if not evicted:
            return
        accepted: Set[int] = set()
        if self._spill_cb is not None:
            accepted = self._spill_cb(evicted)
        for node in evicted:
            if node.node_id in accepted:
                del self._page_node[node.page]
                node.page = HOST_PAGE
                self._tier_nodes[node.node_id] = node
                self._tier.add_pending(node.node_id)
            else:
                self._drop_node(node)

    # ---- slot lifecycle ---------------------------------------------------
    def allocate(self, slot: int, total_tokens: int) -> np.ndarray:
        """Reserve ceil(total_tokens / page_size) pages for `slot` and write
        them into its table row.  Returns the row (view)."""
        row, _, _ = self.allocate_prefixed(slot, total_tokens, None)
        return row

    def allocate_prefixed(self, slot: int, total_tokens: int,
                          tokens: Optional[np.ndarray] = None
                          ) -> Tuple[np.ndarray, int, Optional[Tuple[int, int]]]:
        """Reserve `slot`'s footprint, sharing the longest cached prefix of
        the prompt `tokens` (when given) instead of allocating fresh pages.

        Returns (table row view, matched_tokens, cow):
        - matched_tokens: prompt tokens whose KV the slot starts with —
          full shared pages (mapped read-only, refcount++), full pages
          restored from the KV tier (fresh pages the engine scatters the
          parked content into — see `take_restore`) plus, when `cow` is set
          or a tier partial matched, the matched tokens of a partial page;
        - cow: (src_page, dst_page) the CALLER must copy on device before the
          slot writes anything — dst is the slot's own fresh page at the
          partial boundary, src a cached DEVICE page it must not mutate (an
          off-device partial source rides the restore plan instead: the
          scatter IS the copy).

        When the match includes off-device nodes the engine MUST consume the
        restore plan (`take_restore(slot)`) and either scatter +
        `commit_restore` or roll the slot back (`release`) — `matched`
        already counts the planned tokens.
        """
        n = self.pages_needed(total_tokens)
        if n > self.max_pages_per_slot:
            raise ValueError(
                f"request footprint {total_tokens} tokens exceeds slot "
                f"capacity {self.max_pages_per_slot * self.page_size}")
        if self._used[slot]:
            raise RuntimeError(f"slot {slot} already has pages")
        full: List[_PrefixNode] = []
        partial = None
        if tokens is not None:
            full, partial = self._match(np.asarray(tokens, np.int32))
        shared = []                     # device pages shared (for rollback)
        for node in full:
            if node.page < 0:
                continue                # off-device: restored, not shared
            if self._ref[node.page] == 0:
                self._lru.pop(node.node_id, None)   # revive from evictable
            self._ref[node.page] += 1
            shared.append(node.page)
        pnode, pmatch = partial if partial is not None else (None, 0)
        # pin the COW source for the duration of this allocation: it must not
        # be evicted to satisfy our own fresh-page demand
        if pnode is not None and pnode.node_id in self._lru:
            self._lru.move_to_end(pnode.node_id)
            pinned = self._lru.pop(pnode.node_id)
        else:
            pinned = None
        fresh_needed = n - len(shared)
        self._evict(fresh_needed)
        if pinned is not None:
            self._lru[pinned.node_id] = pinned
        if fresh_needed > len(self._free) and pnode is not None:
            # the partial hit is a luxury the pool cannot afford: its pinned
            # COW source may be the very page this allocation needs (a
            # full-footprint request would otherwise wait forever on an
            # idle engine).  Drop the partial match — the source returns to
            # the LRU, evictable like any other parked page — and retry.
            pnode, pmatch = None, 0
            self._evict(fresh_needed)
        if fresh_needed > len(self._free):
            for p in reversed(shared):              # roll back the sharing
                self._ref[p] -= 1
                if self._ref[p] == 0:
                    self._lru[self._page_node[p].node_id] = self._page_node[p]
            raise RuntimeError(
                f"out of KV pages: need {fresh_needed}, "
                f"free {len(self._free)}")
        fresh = [self._free.pop() for _ in range(fresh_needed)]
        for p in fresh:
            self._ref[p] = 1
        # lay the row out chain-position-accurately: device nodes keep their
        # shared page at their prefix position, off-device nodes take the
        # next fresh page (the engine scatters their parked KV into it), and
        # the remaining fresh pages fill the tail
        pages: List[int] = []
        plan: List[Tuple[int, _PrefixNode, int]] = []
        fi = 0
        for node in full:
            if node.page >= 0:
                pages.append(node.page)
            else:
                pages.append(fresh[fi])
                plan.append((fresh[fi], node, self.page_size))
                fi += 1
        boundary = len(pages)
        pages.extend(fresh[fi:])
        self._used[slot] = pages
        self.page_table[slot, :] = NULL_PAGE
        self.page_table[slot, :n] = pages
        matched = boundary * self.page_size
        cow = None
        if pnode is not None:
            if pnode.page >= 0:
                cow = (pnode.page, pages[boundary])
            else:
                # off-device partial: the restore scatter into the slot's own
                # boundary page IS the copy; the node stays in the tier (the
                # slot appends past the matched fraction, so the page cannot
                # re-register under the node)
                plan.append((pages[boundary], pnode, pmatch))
            matched += pmatch
        if plan:
            self._restore_plan[slot] = plan
        if self.state is not None:
            self.state.alloc(slot)
        return self.page_table[slot], matched, cow

    def take_restore(self, slot: int
                     ) -> List[Tuple[int, _PrefixNode, int]]:
        """Pop the off-device part of `slot`'s latest `allocate_prefixed`
        match: [(dst_page, node, n_tokens)] the engine must scatter from the
        tier into the slot's fresh pages (ONE `swap_in_pages` dispatch)
        before the slot computes anything.  Empty when the match was
        all-device."""
        return self._restore_plan.pop(slot, [])

    def commit_restore(self, slot: int,
                       plan: List[Tuple[int, _PrefixNode, int]]) -> None:
        """The restore scatter landed: full-page nodes move back to the
        device tier (their fresh page now holds their exact content, so
        they are matchable/shareable/re-spillable like any registered
        page); a partial node stays in the tier — the slot appends past the
        matched fraction, so its page diverges from the node content."""
        for dst, node, ntok in plan:
            if ntok == self.page_size and node.n_tokens == self.page_size:
                node.page = dst
                self._page_node[dst] = node
                self._tier_nodes.pop(node.node_id, None)
                self._tier.pop(node.node_id)

    def grow(self, slot: int, total_tokens: int) -> None:
        """Optimistic admission's token-granular growth: extend `slot`'s
        mapping so it covers `total_tokens` positions, allocating fresh pages
        (evicting LRU-parked prefixes on demand) past what it already holds.
        No-op when the slot already covers the footprint — the engine calls
        this before every decode/verify dispatch, so the common case must be
        one integer compare.  Raises RuntimeError when the pool cannot supply
        the pages — the engine's preemption trigger."""
        n = self.pages_needed(total_tokens)
        have = len(self._used[slot])
        if n <= have:
            return
        if n > self.max_pages_per_slot:
            raise ValueError(
                f"slot {slot} growth to {total_tokens} tokens exceeds slot "
                f"capacity {self.max_pages_per_slot * self.page_size}")
        need = n - have
        self._evict(need)
        if need > len(self._free):
            raise RuntimeError(
                f"out of KV pages growing slot {slot}: need {need}, "
                f"free {len(self._free)}")
        fresh = [self._free.pop() for _ in range(need)]
        for p in fresh:
            self._ref[p] = 1
        self.page_table[slot, have:n] = fresh
        self._used[slot].extend(fresh)

    # ---- host swap pool accounting (fourth partition) ---------------------
    def note_swap_out(self, request_id: int, n_pages: int) -> None:
        """Record that `n_pages` of KV for `request_id` now live in the host
        swap pool (the device pages are released separately — this partition
        tracks the off-device obligation)."""
        if n_pages < 1:
            raise ValueError(f"swap-out of {n_pages} pages")
        if request_id in self._swapped:
            raise RuntimeError(f"request {request_id} already swapped out")
        self._swapped[request_id] = n_pages

    def note_swap_in(self, request_id: int) -> int:
        """Clear `request_id`'s swap-pool obligation (swap-in completed, the
        request was aborted/timed out, or the swap degraded to recompute).
        Returns the page count released from the host pool (0 if unknown)."""
        return self._swapped.pop(request_id, 0)

    def release(self, slot: int) -> None:
        """Retire a slot: decrement its pages' refcounts; pages reaching 0 go
        back to the free list, unless they are registered cached prefixes —
        those park in the LRU and stay matchable until evicted.  An abort
        landing between `allocate_prefixed` and `take_restore` (or after a
        failed restore) must not leak the un-consumed restore plan: the plan
        is discarded here — the planned nodes simply stay in the tier."""
        self._restore_plan.pop(slot, None)
        if self.state is not None:
            self.state.free(slot)
        for p in reversed(self._used[slot]):
            self._ref[p] -= 1
            if self._ref[p] == 0:
                node = self._page_node.get(p)
                if node is not None:
                    self._lru[node.node_id] = node
                    self._lru.move_to_end(node.node_id)
                else:
                    self._free.append(p)
        self._used[slot] = []
        self.page_table[slot, :] = NULL_PAGE
        self.lengths[slot] = 0

    def pages_in_use(self) -> int:
        """Distinct pages with refcount > 0 (cached-but-unreferenced prefixes
        do not count — they are reclaimable).  O(1) via the free/LRU/in-use
        partition over the real pages (asserted by check_invariants) — this
        runs on the scheduler hot path every step for the trace ring, so it
        must not scan refcounts on a production-sized pool."""
        return self.num_pages - 1 - len(self._free) - len(self._lru)

    def check_invariants(self) -> None:
        """Assert the refcount/free-list/LRU partition is consistent — every
        real page is exactly one of {free, refcounted-in-use, parked in the
        evictable LRU}, and refcounts equal the number of slot rows mapping
        the page.  Tests call this around speculative rollback and abort to
        prove neither path can leak or double-free a page."""
        assert (self._ref >= 0).all(), "negative refcount"
        if self.state is not None:
            held = {s for s, pages in self._used.items() if pages}
            assert self.state.live == held, \
                (f"recurrent state live in slots {sorted(self.state.live)} "
                 f"but pages held by slots {sorted(held)}")
        assert self._ref[NULL_PAGE] == 0, "null page must never be refcounted"
        counts = np.zeros((self.num_pages,), np.int64)
        for pages in self._used.values():
            for p in pages:
                counts[p] += 1
        assert (counts == self._ref).all(), \
            f"refcounts {self._ref.tolist()} != slot usage {counts.tolist()}"
        free = set(self._free)
        lru = {n.page for n in self._lru.values()}
        used = {p for p in range(1, self.num_pages) if self._ref[p] > 0}
        assert len(free) == len(self._free), "duplicate page on free list"
        assert not (free & lru) and not (free & used) and not (lru & used), \
            "page in more than one of free/LRU/in-use"
        assert free | lru | used == set(range(1, self.num_pages)), \
            "page leaked out of free/LRU/in-use partition"
        assert self.pages_in_use() == len(used), \
            "O(1) pages_in_use diverged from the refcount scan"
        for node in self._lru.values():
            assert self._index.get(node.key) is node, "LRU node unregistered"
            assert node.page >= 0, "off-device node parked in the device LRU"
        for page, node in self._page_node.items():
            assert node.page == page
        # fourth (host-side) partition: every swap-pool obligation is a
        # positive page count, and the total matches the O(1) mirror — a
        # swapped request that was aborted/resumed without clearing its entry
        # is a host-pool leak even though the device partition looks clean
        for rid, n in self._swapped.items():
            assert 0 < n <= self.max_pages_per_slot, \
                f"swapped request {rid} records {n} pages"
        # fifth (tier) partition: every indexed node is EITHER a device node
        # (page mapped in _page_node) or an off-device node whose content the
        # tier tracks (host, pending, or disk) — and vice versa, the tier
        # holds no entry the index forgot (a dropped node whose tier bytes
        # survive is a host-memory leak)
        off_device = 0
        for node in self._index.values():
            if node.page >= 0:
                assert self._page_node.get(node.page) is node, \
                    f"device node {node.node_id} not in the page map"
            else:
                off_device += 1
                assert self._tier is not None and \
                    self._tier.has(node.node_id), \
                    f"off-device node {node.node_id} has no tier entry"
                assert self._tier_nodes.get(node.node_id) is node, \
                    f"off-device node {node.node_id} missing from _tier_nodes"
        if self._tier is not None:
            assert off_device == self._tier.pages_host + \
                self._tier.pages_disk, \
                (f"tier holds {self._tier.pages_host}+"
                 f"{self._tier.pages_disk} pages but the index has "
                 f"{off_device} off-device nodes")
            assert len(self._tier_nodes) == off_device
        else:
            assert off_device == 0, "off-device node with no tier attached"
        for k, node in self._partial.items():
            assert self._index.get(node.key) is node, \
                f"partial-index entry {k} points at an unregistered node"
            assert k in node.partial_keys
        # sixth (restore-plan) partition: a pending plan may only exist for a
        # slot that is still allocated (release() discards the plan, so an
        # aborted admission cannot strand one), and every planned placement
        # targets a page the slot actually holds, sourced from a registered
        # off-device node — the plan is a view over live state, never an
        # owner of pages or tier entries
        for slot, plan in self._restore_plan.items():
            assert self._used[slot], \
                f"restore plan pending for released slot {slot}"
            row = set(self._used[slot])
            for dst, node, n_tokens in plan:
                assert dst in row, \
                    f"slot {slot} restore plan targets foreign page {dst}"
                assert self._index.get(node.key) is node and node.page < 0, \
                    (f"slot {slot} restore plan sources node {node.node_id} "
                     f"that is no longer an off-device index node")

    def prefix_stats(self) -> Dict[str, int]:
        return {
            "cached_pages": len(self._index),
            "evictable_pages": len(self._lru),
            "prefix_evictions": self.prefix_evictions,
            "tier_pages_host": self.tier_pages_host,
            "tier_pages_disk": self.tier_pages_disk,
        }
