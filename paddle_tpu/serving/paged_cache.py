"""Paged KV cache: fixed-size pages, block tables, and prefix sharing.

The dense serving cache (``GPT.init_cache``) allocates
``B × H × max_len × Dh`` per layer — every request pays for the longest
request's horizon. Here K/V live in fixed-size *pages* shared by all
slots; a host-side allocator hands pages to slots as their sequences
grow and reclaims them the step a sequence finishes, so HBM scales with
**live tokens** (plus one page of rounding per slot).

Device state (threaded through the jitted step, donated):
  pages[layer] = (k_pages, v_pages), each (num_pages, page_size, H*Dh)
    — a token's heads folded head-major into the last axis, the shape
    the paged kernels stream (see :class:`PagedKVCache`)

Host state (plain numpy, mutated by the allocator):
  block_tables (num_slots, max_pages_per_slot) int32 — page ids, row-
    filled in sequence order; unused entries hold 0 (the null page)
  lengths      (num_slots,) int32 — live tokens per slot

Page 0 is reserved as the **null page**: never allocated, the write
target for masked/inactive lanes inside the fixed-shape step, and the
harmless gather target for unused block-table entries.

Prefix sharing (ISSUE 6): pages are **refcounted**, and prompt prefixes
are published to a hash-chained index at *page* granularity once their
content has actually been prefilled. A new request whose prompt matches
a published chain maps those pages straight into its block table
(refcount bump — the shared system-prompt case: prefilled once, mapped
by every follower) and skips prefilling them. Rules that keep it exact:

- Only the *owner* (the slot that allocated a page) ever writes it; a
  borrowed page is read-only for the borrower.
- Matching is verified against the **stored tokens**, never the hash
  alone — a hash collision can cost a copy, never correctness.
- A *tail* page (partially filled) can be borrowed too, but the
  borrower will append into it, so ``reserve`` maps a fresh
  **copy-on-write** page in its place and records a pending device copy
  (src → dst) the engine performs before the slot's first prefill.
  Allocating the CoW page at reservation time preserves the
  all-or-nothing guarantee: an admitted request can never OOM later.
- At most ``len(prompt) - 1`` tokens are ever shared, so every request
  prefills at least one token — the one that produces its first output.
- A page whose refcount drops to zero while still published parks in an
  LRU **cached** pool: reusable by future matches, evicted (and
  unpublished) only when the allocator runs dry.

What a layer's pool entry holds is its **kind**'s business
(:mod:`~paddle_tpu.serving.layer_kinds`): ``config.kinds`` names one a
layer, and this manager asks it for the arrays' shapes, what a page id or
a slot commits in bytes, and its part of the self-check. The allocator
below knows pages, slots and refcounts, and no kind by name.

Per-slot state (ISSUE 32): a program whose layers carry a recurrence
declares ``slot_state`` and the one manager then holds a second kind of
cache: per layer and entry an array ``(num_slots + 1,) + shape`` beside
the layer's page pools, in the same ``pages[layer]`` tuple (so it threads
through, and is donated into, the same jitted steps); where the program
names its state layers, in those layers' tuples only, which then hold
nothing else (``layer_kinds.State``: no page pool, no page). It is indexed by
SLOT, not by page: row ``slot + 1`` is the slot's, row 0 the null row that
pad lanes and non-decoding slots point at. Fixed size whatever the
sequence length; never shared, copied on write, published, spilled or
shipped (``copy_page_step`` / ``read_page`` / ``write_page`` leave the
entries alone), so a pool with slot state has prefix sharing off. A row
holds whatever its last request left: the step that starts a prompt
starts from zeros (``fresh`` in :mod:`~paddle_tpu.serving.program`), which
is the reset at admission with no program of its own.

Tensor parallel (ISSUE 15): pass ``mesh=`` (a mesh with a ``tp`` axis
of size > 1) and the page pool becomes **per-shard**: each pool array is
placed as its kind says (K and V sharded over ``tp`` on the folded HEAD
axis, each shard holding every page's slice of its own ``H/tp`` whole
heads; int8 scale rows replicated), while the block tables, lengths and
allocator books stay replicated. The host-side allocator and the
prefix-sharing index are untouched: page identity is global, only the page
*contents* are sharded.
"""

from __future__ import annotations

import dataclasses
import hashlib
import threading
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.analysis.concurrency import guarded_by
from paddle_tpu.serving import layer_kinds
from paddle_tpu.serving.layer_kinds import quantize_kv  # noqa: F401 (public)
from paddle_tpu.serving.program import ServingSpec


@dataclasses.dataclass
class PagedCacheConfig:
    num_layers: int
    num_heads: int
    head_dim: int
    num_slots: int
    page_size: int = 16
    num_pages: int = 256
    max_pages_per_slot: int = 16
    dtype: object = jnp.float32
    share_prefix: bool = True
    #: state kept per SLOT and layer, ``(name, shape)`` each: one more
    #: array a layer, ``(num_slots + 1,) + shape`` of ``slot_state_dtype``
    #: (row 0 the null row), beside the page pools and not paged
    slot_state: Tuple[Tuple[str, Tuple[int, ...]], ...] = ()
    slot_state_dtype: object = jnp.float32
    #: each layer's kind (:func:`layer_kinds.build`), which lays out its
    #: pool entry; empty: K and V of ``num_heads`` x ``head_dim`` in
    #: ``dtype`` under the block table, every layer
    kinds: Tuple[layer_kinds.Kind, ...] = ()

    def __post_init__(self):
        if self.page_size < 1 or self.num_pages < 2:
            raise ValueError("need page_size >= 1 and num_pages >= 2 "
                             "(page 0 is the reserved null page)")
        if self.max_pages_per_slot < 1:
            raise ValueError("max_pages_per_slot must be >= 1")
        if not self.kinds:
            self.kinds = layer_kinds.build(
                ServingSpec(num_layers=self.num_layers,
                            num_heads=self.num_heads,
                            kv_heads=self.num_heads, head_dim=self.head_dim,
                            vocab_size=0, max_position=0,
                            slot_state=self.slot_state),
                num_slots=self.num_slots, page_size=self.page_size,
                num_pages=self.num_pages, dtype=self.dtype,
                share_prefix=self.share_prefix)
        if len(self.kinds) != self.num_layers:
            raise ValueError("kinds name every layer or none")

    @property
    def max_tokens_per_slot(self) -> int:
        return self.max_pages_per_slot * self.page_size

    def pages_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)


class PageOverflowError(RuntimeError):
    """No free pages (or slot capacity exceeded) for a reservation."""


_ROOT_KEY = hash("paddle_tpu.serving.prefix_root")


def _chain(parent_key: int, chunk: np.ndarray) -> int:
    return hash((parent_key, chunk.tobytes()))


def _chain_walk(prompt, page_size: int, upto: int,
                key: int = _ROOT_KEY, start_page: int = 0):
    """Yield ``(page_index, chain_key, chunk)`` for each FULL page of
    ``prompt[:upto]`` starting at ``start_page``, chaining from
    ``key``. The ONE page-chain loop behind prefix matching, prefix
    publication, AND the router's :func:`prompt_prefix_digests` — the
    three must agree bit-for-bit or affinity prediction silently
    diverges from what ``publish_prefix`` commits."""
    k = key
    p = start_page
    while (p + 1) * page_size <= upto:
        chunk = np.asarray(prompt[p * page_size:(p + 1) * page_size],
                           np.int32)
        k = _chain(k, chunk)
        yield p, k, chunk
        p += 1


def prompt_prefix_digests(prompt, page_size: int) -> List[int]:
    """The hash-chain keys of ``prompt``'s page-aligned full prefix
    pages — digest ``k`` covers tokens ``[0, (k+1)*page_size)``. These
    are EXACTLY the keys :meth:`PagedKVCache.publish_prefix` commits to
    the full-page index, so intersecting them with a cache's
    :meth:`~PagedKVCache.published_digests` predicts how many prefix
    pages a new request would map instead of prefill — the fleet
    router's cache-locality signal. Capped at ``len(prompt) - 1``
    tokens, mirroring the at-least-one-token-prefills rule. In-process
    only (python ``hash`` is seed-randomized per interpreter); a
    cross-process transport must re-digest with a stable hash."""
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    limit = int(prompt.shape[0]) - 1
    return [key for _p, key, _c in _chain_walk(prompt, page_size, limit)]


def payload_digest(payload: Tuple[np.ndarray, ...]) -> str:
    """sha256 over a spilled page's host arrays — the int8 KV and its
    fp32 scale rows hash as ONE digest (a scale-only corruption must be
    refused exactly like a KV corruption)."""
    h = hashlib.sha256()
    for a in payload:
        h.update(np.asarray(a).tobytes())
    return h.hexdigest()


@dataclasses.dataclass
class SpilledPage:
    """One published full page parked in host memory: its hash-chain
    key, the stored token content (match verification stays
    content-checked, never hash-only), the host copies of the page's
    device arrays (``(kv,)`` fp, ``(kv, scales)`` int8 — scale rows
    always travel WITH their page), and the sha256 stamped at spill
    time that restore/export re-verify."""

    key: int
    tokens: np.ndarray
    payload: Tuple[np.ndarray, ...]
    sha256: str
    nbytes: int


@guarded_by("_lock", "_entries")
class HostPagePool:
    """Host-memory LRU tier for spilled KV pages (ISSUE 20).

    When the device cached pool would evict (and destroy) a published
    page under allocator pressure, the page's bytes land here instead,
    keyed by its prefix-chain digest; the next prefix hit restores it
    with an async ``device_put`` that overlaps admission, and a fleet
    peer fetch can export straight from here without touching HBM.
    Bounded in pages — over ``capacity`` the LRU entry is dropped (the
    only path that truly destroys a published page's content now).

    ``gen`` bumps on EVERY mutation (spill, restore, drop, discard):
    together with the device index's ``_index_gen`` it forms
    :attr:`PagedKVCache.prefix_gen`, the generation the fleet's
    affinity snapshots key on — a silently-dropped prefix must change
    the advertised digest set, never linger in a stale memo.

    Thread-safe (one ``threading.Lock``, a leaf in the committed lock
    order): the engine mutates it from the step thread while a fleet
    router thread reads ``keys()``/``len()`` through
    ``advertised_digests``/``health``.
    """

    def __init__(self, capacity: int):
        if capacity < 1:
            raise ValueError("HostPagePool needs capacity >= 1")
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._entries: "OrderedDict[int, SpilledPage]" = OrderedDict()
        self.gen = 0
        self.spilled_total = 0
        self.restored_total = 0
        self.dropped_total = 0
        self.spilled_bytes_total = 0
        self.restored_bytes_total = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def keys(self) -> frozenset:
        with self._lock:
            return frozenset(self._entries)

    def entries(self) -> List[SpilledPage]:
        with self._lock:
            return list(self._entries.values())

    def spilled_bytes(self) -> int:
        """Host bytes resident right now."""
        with self._lock:
            return sum(e.nbytes for e in self._entries.values())

    def put(self, entry: SpilledPage):
        """Admit one spilled page (newest = most recently used); LRU
        entries past capacity are dropped and counted."""
        with self._lock:
            self._entries[entry.key] = entry
            self._entries.move_to_end(entry.key)
            self.gen += 1
            self.spilled_total += 1
            self.spilled_bytes_total += entry.nbytes
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)
                self.dropped_total += 1
                self.gen += 1

    def get(self, key: int) -> Optional[SpilledPage]:
        """Peek (and LRU-touch) without removing."""
        with self._lock:
            ent = self._entries.get(key)
            if ent is not None:
                self._entries.move_to_end(key)
            return ent

    def pop(self, key: int) -> Optional[SpilledPage]:
        with self._lock:
            ent = self._entries.pop(key, None)
            if ent is not None:
                self.gen += 1
            return ent

    def discard(self, key: int):
        """Drop an entry that became device-resident again (restore,
        peer fetch, or a fresh local publication of the same chain) —
        the pool holds COLD pages only, never a device duplicate."""
        self.pop(key)

    def note_restored(self, pages: int, nbytes: int):
        with self._lock:
            self.restored_total += pages
            self.restored_bytes_total += nbytes


class PagedKVCache:
    """Device pages + host-side page allocator, block tables, and the
    refcounted prefix-sharing index.

    ``pages[layer]`` is the arrays the layer's kind lays out
    (``config.kinds[layer].pools``), then one array ``(num_slots + 1,) +
    shape`` for each entry of ``config.slot_state``. A pool of K and V is
    ``(num_pages, page_size, num_heads * head_dim)``: a token's heads
    folded head-major into the last axis (head ``h`` is lanes ``h*Dh ..
    (h+1)*Dh``), the shape the paged kernels stream page blocks of. With
    ``(page_size, H*Dh)`` as the minor dims the TPU keeps the arrays
    row-major and unpadded as long as ``H*Dh`` (per shard under tp) is a
    multiple of 128 lanes; with ``(H, Dh)`` minor, or a lane width it has
    to pad, it keeps a pool page_size-minor and every step that calls the
    kernels copies it whole, in and out.

    ``mesh=`` (tp > 1): each pool array is placed over the mesh's ``tp``
    axis as its kind says (:meth:`page_specs`). Allocator/index state is
    host-side and unaffected."""

    def __init__(self, config: PagedCacheConfig, mesh=None,
                 host_spill_pages: int = 0):
        self.config = config
        self.mesh = mesh if (mesh is not None
                             and int(mesh.shape.get("tp", 1)) > 1) else None
        c = config
        tp = int(self.mesh.shape["tp"]) if self.mesh is not None else 1
        if any(kind.geo.tp != tp for kind in c.kinds):
            raise ValueError(f"the pool's kinds were not built for tp={tp}")
        self._bytes = (     # a page id's, a slot's: asked every step
            sum(kind.page_bytes for kind in c.kinds),
            sum(kind.slot_bytes for kind in c.kinds))
        self.pages: List[Tuple[jnp.ndarray, ...]] = [
            (*(jnp.zeros(shape, dtype) for shape, dtype, _ in kind.pools),
             *(jnp.zeros((c.num_slots + 1,) + tuple(shape),
                         c.slot_state_dtype)
               for _name, shape in (c.slot_state if kind.state else ())))
            for kind in c.kinds]
        if self.mesh is not None:
            from jax.sharding import NamedSharding
            self.pages = [
                tuple(jax.device_put(a, NamedSharding(self.mesh, spec))
                      for a, spec in zip(ent, specs))
                for ent, specs in zip(self.pages, self.page_specs())]
        self.block_tables = np.zeros((c.num_slots, c.max_pages_per_slot),
                                     np.int32)
        self.lengths = np.zeros((c.num_slots,), np.int32)
        # page 0 reserved: null page
        self._free = list(range(c.num_pages - 1, 0, -1))
        self._slot_pages: List[List[int]] = [[] for _ in range(c.num_slots)]
        # -- sharing state --
        self._ref = np.zeros((c.num_pages,), np.int32)   # mappers per page
        self._owned: List[set] = [set() for _ in range(c.num_slots)]
        self._cached: "OrderedDict[int, bool]" = OrderedDict()  # LRU, ref 0
        self._full_index: Dict[int, int] = {}    # chain key -> page id
        self._tail_index: Dict[int, int] = {}    # chain key -> tail page id
        self._page_pub: Dict[int, Tuple[str, int]] = {}  # pid -> (kind, key)
        self._page_tokens: Dict[int, np.ndarray] = {}    # published content
        self._published_upto: List[int] = [0] * c.num_slots
        # per-slot publish cursor: hash-chain key covering the first
        # _published_upto // page_size pages, so each publish_prefix
        # call hashes only NEW pages (not the whole prompt again)
        self._pub_chain: List[int] = [_ROOT_KEY] * c.num_slots
        # slot -> (src, dst): device copy the engine owes before writing
        self._pending_copy: Dict[int, Tuple[int, int]] = {}
        # admission calls can_reserve once per queued candidate per wave
        # and reserve() repeats the match — memoize on (prompt identity,
        # index generation) so each prompt is matched once per index
        # change, not once per scheduler pass; entries pin the array
        self._index_gen = 0
        self._match_cache: "OrderedDict[Tuple[int, int], tuple]" = \
            OrderedDict()
        # published_digests() memo: the router reads it per candidate
        # per submit; rebuild only when the index actually changed
        self._digests = frozenset()
        self._digests_gen = -1
        self.shared_tokens_total = 0     # prefill tokens skipped via sharing
        self.cow_copies_total = 0
        # HBM -> host spill tier (ISSUE 20), off by default (0 pages):
        # _alloc_page pages evicted published pages into the host pool
        # instead of destroying them, via the engine-installed reader
        # (attach_spill_io) so page bytes leave the device through the
        # warmed ("page_read",) signature
        self.spill_pool: Optional[HostPagePool] = (
            HostPagePool(host_spill_pages) if host_spill_pages > 0
            else None)
        self._spill_reader: Optional[Callable] = None
        # advertised_digests() memo: device index keys + spilled keys,
        # keyed on prefix_gen (either tier changing invalidates it)
        self._adv_digests = frozenset()
        self._adv_gen = -1

    # -- allocator --------------------------------------------------------

    @property
    def free_pages(self) -> int:
        """Pages immediately allocatable (free + evictable cached)."""
        return len(self._free) + len(self._cached)

    @property
    def pages_in_use(self) -> int:
        return int((self._ref[1:] > 0).sum())

    def utilization(self) -> float:
        """Live-token fraction of the allocatable page pool."""
        cap = (self.config.num_pages - 1) * self.config.page_size
        return float(self.lengths.sum()) / cap if cap else 0.0

    def page_specs(self) -> list:
        """PartitionSpec pytree of ``pages`` under tp, one tuple a layer:
        each pool array as its kind shards it, slot state replicated.
        Drops straight into ``shard_map`` in/out specs."""
        from jax.sharding import PartitionSpec as P
        state = (P(),) * len(self.config.slot_state)
        return [tuple(P(*axes) for _, _, axes in kind.pools)
                + (state if kind.state else ())
                for kind in self.config.kinds]

    def bytes_per_page(self) -> int:
        """HBM bytes one page id commits across every layer's pools (scale
        rows included; a ring a slot: none) — global bytes under tp
        sharding (each shard holds its head slice of the same page)."""
        return self._bytes[0]

    def bytes_per_slot(self) -> int:
        """HBM bytes a slot holds whatever its length, state not counted."""
        return self._bytes[1]

    def state_layers(self) -> int:
        """The layers that keep slot state (0 for a pool without)."""
        return sum(kind.state for kind in self.config.kinds) \
            if self.config.slot_state else 0

    def state_bytes_per_slot(self) -> int:
        """Bytes of slot state one slot holds across the layers that keep
        it (0 for a pool without)."""
        c = self.config
        return self.state_layers() * np.dtype(
            c.slot_state_dtype).itemsize * sum(
                int(np.prod(shape)) for _name, shape in c.slot_state)

    def capacity_bytes(self) -> int:
        """HBM bytes of the allocatable pool (null page excluded) and of
        what every slot holds whatever its length."""
        return self.bytes_per_page() * (self.config.num_pages - 1) \
            + self.bytes_per_slot() * self.config.num_slots

    def live_bytes(self) -> int:
        """HBM bytes committed to allocated pages right now (page
        granularity — reservations count the moment they are made,
        which is what admission headroom must see), and what the slots
        that hold a reservation hold whatever their length."""
        return self.bytes_per_page() * self.pages_in_use \
            + self.bytes_per_slot() * sum(
                1 for sp in self._slot_pages if sp)

    def _alloc_page(self) -> int:
        if self._free:
            return self._free.pop()
        if self._cached:     # evict the LRU published-but-idle page
            pid, _ = self._cached.popitem(last=False)
            self._spill_page(pid)
            self._unpublish(pid)
            return pid
        raise PageOverflowError("page pool exhausted")

    def attach_spill_io(self, reader: Callable):
        """Install the engine's page reader (``pid -> tuple of host
        arrays``, the full stacked page the jitted ``read_page_step``
        returns). Spilling stays a no-op until both a pool AND a reader
        exist, so a bare cache (unit tests, draft caches) never tries
        device IO."""
        self._spill_reader = reader

    def _spill_page(self, pid: int):
        """Page an evicted published FULL page out to the host pool
        (kv + scale rows together, sha256-stamped) instead of letting
        ``_unpublish`` destroy its content. Tail pages are not spilled:
        they are at most ``page_size - 1`` tokens of recompute and do
        not participate in fleet digests."""
        if self.spill_pool is None or self._spill_reader is None:
            return
        pub = self._page_pub.get(pid)
        if pub is None or pub[0] != "full":
            return
        payload = tuple(np.asarray(a) for a in self._spill_reader(pid))
        self.spill_pool.put(SpilledPage(
            key=pub[1], tokens=self._page_tokens[pid].copy(),
            payload=payload, sha256=payload_digest(payload),
            nbytes=sum(int(a.nbytes) for a in payload)))

    def _acquire(self, pid: int):
        """Take a reference on a published page (reviving it from the
        cached pool if idle)."""
        if pid in self._cached:
            del self._cached[pid]
        self._ref[pid] += 1

    def _release(self, pid: int):
        self._ref[pid] -= 1
        assert self._ref[pid] >= 0, f"page {pid} over-released"
        if self._ref[pid] == 0:
            if pid in self._page_pub:
                self._cached[pid] = True     # reusable via the index
            else:
                self._free.append(pid)

    def _unpublish(self, pid: int):
        kind, key = self._page_pub.pop(pid)
        index = self._full_index if kind == "full" else self._tail_index
        if index.get(key) == pid:
            del index[key]
        self._page_tokens.pop(pid, None)
        self._index_gen += 1

    # -- prefix matching --------------------------------------------------

    def _match_prefix(self, prompt: Optional[np.ndarray]):
        """Longest published, content-verified prefix of ``prompt``.
        Returns (full_page_ids, tail_src_page_or_None, shared_tokens);
        caps sharing at ``len(prompt) - 1`` so at least one token always
        prefills (producing the request's first output token). Also
        returns ``key_after_full``, the hash-chain key covering the
        matched full pages — ``reserve`` seeds the slot's publish cursor
        with it so ``publish_prefix`` never rehashes them. Memoized
        per (prompt identity, index generation): the result only depends
        on the publication indices, which bump ``_index_gen`` on every
        change, never on page refcount/cached state. Keying on
        ``id(prompt)`` keeps the hot path free of whole-prompt copies or
        hashing — admission probes the same queued Request's array every
        wave — and the entry pins the array, so its id cannot be reused
        while the entry lives (prompts are never mutated after submit)."""
        if prompt is None or not self.config.share_prefix:
            return [], None, 0, _ROOT_KEY
        mkey = (id(prompt), self._index_gen)
        hit = self._match_cache.get(mkey)
        if hit is not None and hit[0] is prompt:
            return hit[1]
        res = self._match_prefix_uncached(prompt)
        self._match_cache[mkey] = (prompt, res)
        while len(self._match_cache) > 512:
            self._match_cache.popitem(last=False)
        return res

    def _match_prefix_uncached(self, prompt: np.ndarray):
        ps = self.config.page_size
        limit = int(prompt.shape[0]) - 1
        key, k, full = _ROOT_KEY, 0, []
        for p, key2, chunk in _chain_walk(prompt, ps, limit):
            pid = self._full_index.get(key2)
            if pid is None or not np.array_equal(
                    self._page_tokens[pid], chunk):
                break
            full.append(pid)
            key, k = key2, p + 1
        shared = k * ps
        tail_pid = self._tail_index.get(key)
        if tail_pid is not None:
            stored = self._page_tokens[tail_pid]
            rem = np.asarray(prompt[shared:limit], np.int32)
            n = 0
            m = min(len(stored), len(rem))
            while n < m and stored[n] == rem[n]:
                n += 1
            if n > 0:
                return full, (tail_pid, n), shared + n, key
            return full, None, shared, key
        return full, None, shared, key

    def can_reserve(self, n_tokens: int,
                    prompt: Optional[np.ndarray] = None) -> bool:
        need = self.config.pages_for(n_tokens)
        if need > self.config.max_pages_per_slot:
            return False
        full, _tail, _shared, _key = self._match_prefix(prompt)
        borrowed_cached = sum(1 for p in full if p in self._cached)
        fresh = need - len(full)
        # tail sharing is dropped by reserve() when pinning the CoW src
        # would not fit, so feasibility only needs the full-page math
        return fresh <= len(self._free) + len(self._cached) - borrowed_cached

    def reserve(self, slot: int, n_tokens: int,
                prompt: Optional[np.ndarray] = None) -> int:
        """Pre-allocate every page ``slot`` will need for ``n_tokens``
        total tokens (prompt + generation horizon). All-or-nothing, so
        an admitted request can never OOM mid-decode. With ``prompt``
        given and sharing enabled, published prefix pages are mapped
        instead of allocated; returns the number of prompt tokens
        already covered by shared pages (the engine starts prefill after
        them and sets ``lengths[slot]`` accordingly — done here)."""
        if self._slot_pages[slot]:
            raise PageOverflowError(f"slot {slot} already holds pages")
        need = self.config.pages_for(n_tokens)
        if need > self.config.max_pages_per_slot:
            raise PageOverflowError(
                f"{n_tokens} tokens needs {need} pages > max_pages_per_slot"
                f"={self.config.max_pages_per_slot}")
        full, tail, shared, chain_key = self._match_prefix(prompt)
        borrowed_cached = sum(1 for p in full if p in self._cached)
        fresh = need - len(full)
        if (tail is not None
                and fresh > len(self._free) + len(self._cached)
                - borrowed_cached
                - (1 if tail[0] in self._cached else 0)):
            # pinning the CoW src would leave too few evictable pages:
            # degrade to sharing the full pages only (the tail tokens
            # just get recomputed) rather than refusing the request
            tail, shared = None, len(full) * self.config.page_size
        if fresh > len(self._free) + len(self._cached) - borrowed_cached:
            raise PageOverflowError(
                f"{fresh} pages needed, {len(self._free)} free "
                f"+ {len(self._cached)} cached")
        mapped: List[int] = []
        owned = set()
        for pid in full:
            self._acquire(pid)
            mapped.append(pid)
        if tail is not None:
            # pin the CoW src BEFORE allocating fresh pages: _alloc_page
            # evicts from the cached pool when free runs dry, and the
            # idle published tail is exactly the kind of page it would
            # recycle — after which the pending copy would read garbage
            self._acquire(tail[0])
        for _ in range(fresh):
            pid = self._alloc_page()
            self._ref[pid] = 1
            owned.add(pid)
            mapped.append(pid)
        if tail is not None:
            src, _n = tail
            # the borrower appends into this page: map a fresh CoW page
            # in its place (already counted in ``fresh`` — it replaces
            # the tail slot position) and owe a device copy
            self._pending_copy[slot] = (src, mapped[len(full)])
            self.cow_copies_total += 1
        self._slot_pages[slot] = mapped
        self._owned[slot] = owned
        self._published_upto[slot] = shared
        self._pub_chain[slot] = chain_key
        self.block_tables[slot, :] = 0
        self.block_tables[slot, :need] = mapped
        self.lengths[slot] = shared
        self.shared_tokens_total += shared
        return shared

    def pending_copy(self, slot: int) -> Optional[Tuple[int, int]]:
        """(src, dst) device page copy the engine must perform before
        the slot's first write (CoW of a borrowed tail page)."""
        return self._pending_copy.get(slot)

    def copy_done(self, slot: int):
        src, _dst = self._pending_copy.pop(slot)
        self._release(src)

    def publish_prefix(self, slot: int, prompt: np.ndarray, upto: int):
        """Publish the slot's OWN prompt pages whose content has been
        prefilled through token ``upto``: full pages always; the partial
        tail page once the whole prompt is in (``upto >= len(prompt)``).
        Borrowed pages are already published; first publisher wins."""
        if not self.config.share_prefix:
            return
        ps = self.config.page_size
        upto = min(int(upto), int(prompt.shape[0]))
        if upto <= self._published_upto[slot]:
            return
        # resume from the publish cursor: pages before it are already
        # published (or borrowed) and their chain key is saved
        key = self._pub_chain[slot]
        k = self._published_upto[slot] // ps
        for p, key2, chunk in _chain_walk(prompt, ps, upto,
                                          key=key, start_page=k):
            pid = self._slot_pages[slot][p]
            if (key2 not in self._full_index and pid in self._owned[slot]
                    and pid not in self._page_pub):
                self._full_index[key2] = pid
                self._page_pub[pid] = ("full", key2)
                self._page_tokens[pid] = chunk.copy()
                self._index_gen += 1
                if self.spill_pool is not None:
                    # a fresh local prefill re-committed this chain key
                    # device-side: the cold host copy is now redundant
                    # (the pool never shadows a device-resident page)
                    self.spill_pool.discard(key2)
            key, k = key2, p + 1
        self._pub_chain[slot] = key
        if upto >= int(prompt.shape[0]) and upto % ps:
            tail = np.asarray(prompt[k * ps:upto], np.int32)
            pid = self._slot_pages[slot][k]
            if (key not in self._tail_index and pid in self._owned[slot]
                    and pid not in self._page_pub):
                self._tail_index[key] = pid
                self._page_pub[pid] = ("tail", key)
                self._page_tokens[pid] = tail.copy()
                self._index_gen += 1
        self._published_upto[slot] = upto

    def writable(self, slot: int, page_index: int) -> bool:
        """True when the slot may write the page at this block-table
        position (it allocated it — borrowed pages are read-only)."""
        return self._slot_pages[slot][page_index] in self._owned[slot]

    def free_slot(self, slot: int):
        """Drop the slot's references; pages hit the free pool (or the
        cached pool, when published) only at refcount zero — continuous
        batching's whole point, minus whatever prefix sharers still
        hold."""
        if slot in self._pending_copy:
            self.copy_done(slot)     # never materialized; release the src
        for pid in self._slot_pages[slot]:
            self._release(pid)
        self._slot_pages[slot] = []
        self._owned[slot] = set()
        self._published_upto[slot] = 0
        self._pub_chain[slot] = _ROOT_KEY
        self.block_tables[slot, :] = 0
        self.lengths[slot] = 0

    def slot_pages(self, slot: int) -> List[int]:
        return list(self._slot_pages[slot])

    def published_digests(self) -> frozenset:
        """The full-page prefix digests currently resolvable through the
        index (live or parked in the cached pool) — the set a replica
        advertises to the fleet router; compare against
        :func:`prompt_prefix_digests` of a candidate prompt. Memoized
        on ``_index_gen`` (the same discipline as ``_match_prefix``):
        the router polls this on every submit, the index changes only
        on publish/unpublish."""
        if self._digests_gen != self._index_gen:
            self._digests = frozenset(self._full_index)
            self._digests_gen = self._index_gen
        return self._digests

    # -- HBM -> host spill tier (ISSUE 20) --------------------------------

    @property
    def prefix_gen(self) -> int:
        """Monotonic generation over BOTH publication tiers: bumps when
        the device index changes (publish/unpublish/adopt) AND when the
        host spill pool changes (spill/restore/drop). A replica
        publishes this through ``health()`` so fleet affinity snapshots
        can never keep routing to a replica that silently dropped a
        prefix — eviction of a published page is a generation change,
        not a private event."""
        return self._index_gen + (self.spill_pool.gen
                                  if self.spill_pool is not None else 0)

    @property
    def idle_free_pages(self) -> int:
        """Pages allocatable WITHOUT evicting a published cached page —
        the budget spill restores and peer-fetch installs spend (taking
        more would evict-and-respill other cold pages: churn, not
        progress)."""
        return len(self._free)

    def advertised_digests(self) -> frozenset:
        """What this replica advertises fleet-wide: device-published
        digests plus host-spilled ones — a spilled page is still
        servable (restored on the next local prefix hit, exported on a
        peer fetch), so affinity must keep counting it. Memoized on
        :attr:`prefix_gen`, same discipline as ``published_digests``."""
        if self.spill_pool is None:
            return self.published_digests()
        g = self.prefix_gen
        if self._adv_gen != g:
            self._adv_digests = (self.published_digests()
                                 | self.spill_pool.keys())
            self._adv_gen = g
        return self._adv_digests

    def spill_restore_plan(self, prompt) -> List[SpilledPage]:
        """The spilled full pages that would extend ``prompt``'s
        device-resident published chain if restored — in chain order,
        content-verified against the stored tokens like every other
        match. Walks the same hash chain as ``_match_prefix``; stops at
        the first page held by NEITHER tier (later pages cannot map —
        prefix pages only chain onto a present parent). Capped at
        :attr:`idle_free_pages` so restoring never evicts."""
        if (self.spill_pool is None or len(self.spill_pool) == 0
                or prompt is None or not self.config.share_prefix):
            return []
        ps = self.config.page_size
        limit = int(np.asarray(prompt).reshape(-1).shape[0]) - 1
        plan: List[SpilledPage] = []
        for _p, key, chunk in _chain_walk(prompt, ps, limit):
            pid = self._full_index.get(key)
            if pid is not None:
                if np.array_equal(self._page_tokens[pid], chunk):
                    continue
                break
            ent = self.spill_pool.get(key)
            if ent is None or not np.array_equal(ent.tokens, chunk):
                break
            plan.append(ent)
            if len(plan) >= len(self._free):
                break
        return plan

    def adopt_published_page(self, key: int, tokens) -> int:
        """Publish an externally-written page (spill restore or fleet
        peer fetch): allocate a page, commit it to the full-page index
        parked in the cached pool (refcount 0 — the next match borrows
        it exactly like a locally-published page), and drop any host
        copy of the same key. Returns the page id; the caller owes the
        device write immediately after (nothing can read the page
        before the caller's own next cache operation). New adoptions
        enter the LRU at the hot end, so a same-wave ``_alloc_page``
        eviction cannot immediately recycle them."""
        pid = self._alloc_page()
        self._full_index[key] = pid
        self._page_pub[pid] = ("full", key)
        self._page_tokens[pid] = np.asarray(tokens, np.int32).copy()
        self._cached[pid] = True
        self._index_gen += 1
        if self.spill_pool is not None:
            self.spill_pool.discard(key)
        return pid

    def lookup_prefix_page(self, key: int):
        """Resolve one advertised digest for the engine's peer-export
        path: ``("device", pid, tokens)`` when the page is resident,
        ``("host", SpilledPage)`` when spilled, None when this cache
        no longer holds it (dropped under host-pool pressure)."""
        pid = self._full_index.get(key)
        if pid is not None:
            return ("device", pid, self._page_tokens[pid])
        if self.spill_pool is not None:
            ent = self.spill_pool.get(key)
            if ent is not None:
                return ("host", ent)
        return None

    # -- device views -----------------------------------------------------

    def check_invariants(self):
        """Allocator self-check (tests): per-page refcount equals the
        number of mappings holding it, free/cached/live partition the
        pool, the null page is never owned, published entries resolve."""
        c = self.config
        expect = np.zeros((c.num_pages,), np.int32)
        for sp in self._slot_pages:
            for p in sp:
                expect[p] += 1
        for (src, _dst) in self._pending_copy.values():
            expect[src] += 1
        assert expect[0] == 0, "null page mapped"
        assert (expect == self._ref).all(), (
            f"refcount drift: {np.nonzero(expect != self._ref)[0]}")
        free_s, cached_s = set(self._free), set(self._cached)
        assert len(free_s) == len(self._free), "page double-freed"
        assert not (free_s & cached_s), "page both free and cached"
        assert 0 not in free_s and 0 not in cached_s, "null page pooled"
        live = {int(p) for p in np.nonzero(self._ref)[0]}
        assert not (live & (free_s | cached_s)), "live page in a pool"
        assert free_s | cached_s | live == set(range(1, c.num_pages)), \
            "page leaked"
        for pid, (kind, key) in self._page_pub.items():
            index = self._full_index if kind == "full" else self._tail_index
            assert index.get(key) == pid, "publication index drift"
            assert pid in self._page_tokens, "published page lost tokens"
        for owned, sp in zip(self._owned, self._slot_pages):
            assert owned <= set(sp), "owned page not mapped"
        for kind, ent in zip(c.kinds, self.pages):
            kind.check(ent[:len(kind.pools)], self.lengths)
        if self.spill_pool is not None:
            spilled = self.spill_pool.keys()
            assert len(self.spill_pool) <= self.spill_pool.capacity, \
                "host spill pool over capacity"
            assert not (spilled & set(self._full_index)), \
                "page both device-published and host-spilled"
            for ent in self.spill_pool.entries():
                assert payload_digest(ent.payload) == ent.sha256, \
                    "spilled page payload corrupted in host pool"
