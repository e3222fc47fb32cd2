"""Paged KV cache with radix prefix reuse: the serving engine's memory
model.

The contiguous ``CachePool`` gives every slot a full ``block_size`` KV
buffer for its whole lifetime, so HBM — not compute — caps concurrent
occupancy, and every request pays full prefill even when thousands share
one system prompt. Here device KV storage is a pool of fixed-size PAGES
(``models.gpt.init_paged_kv_pool``) and each slot holds a fixed-shape
``(max_pages,)`` int32 page table: host-mirrored, device-fed as a traced
per-step input, so admissions / prefix hits / evictions / copy-on-write
never change a compiled program's shape (the zero-recompile steady state
survives paging — pinned in tests/test_pages.py).

Three host-side pieces:

- :class:`PageAllocator` — refcounted acquire/release of physical
  pages. A page's refcount counts SLOT references; pages referenced by
  the radix index alone (refcount 0) are the prefix cache, reclaimed
  LRU when allocation runs dry.
- :class:`RadixIndex` — a prefix tree over FULL pages of prompt tokens
  (node key = (parent, page-token bytes), so lookups are exact, not
  hash-collision-prone). Admission walks it to claim the longest cached
  prefix; chunked prefill then starts at the first uncached token.
- :class:`PagedCachePool` — the engine-facing pool: slot bookkeeping
  (drop-in for ``CachePool``'s host API) + page tables + the device
  page arrays.

Sharing discipline (what makes copy-on-write rare and safe): a full
prompt page is registered into the radix only once its owner's next
write position is PAST the page — the first decode step rewrites prompt
position P-1, so the page containing it is deferred until that write
lands. Shared pages are therefore never written through... with ONE
exception: a claimer whose ENTIRE prompt is cached starts decoding at
P-1, inside the last claimed page. That admission gets a copy-on-write
split — a fresh page, a device page copy, a remapped table entry — and
the shared original stays intact for the next claimer.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..config import ModelConfig
from ..models.families import family
from ..utils.telemetry import NULL
from .cache_pool import commit_default


def default_page_size(requested: int, block_size: int) -> int:
    """Effective page size: the requested (0 = the vLLM-conventional 16)
    clamped to block_size. No divisibility requirement — the paged
    programs route every write per-position and drop out-of-range
    padding, so a ragged last logical page just holds fewer usable
    positions."""
    return min(requested or 16, block_size)


def pool_geometry(cfg: ModelConfig, n_slots: int, page_size: int = 0,
                  max_pages: int = 0,
                  n_pages: int = 0) -> Tuple[int, int, int]:
    """Resolve the (page_size, max_pages_per_slot, n_pages) triple from
    the EngineConfig knobs — ONE definition shared by the pool's
    constructor and the sharded engine, which must size the page pool's
    PartitionSpec (parallel.mesh.page_pool_pspec divisibility) BEFORE
    the pool allocates its device arrays."""
    psz = default_page_size(page_size, cfg.block_size)
    mp = max_pages or -(-cfg.block_size // psz)
    return psz, mp, (n_pages or n_slots * mp)


def page_bytes(cfg: ModelConfig, page_size: int, kv_quant: str = "none",
               granularity: str = "page") -> int:
    """HBM bytes ONE physical page occupies across all layers: K + V
    rows at the storage dtype, plus the per-row f32 scale metadata a
    quantized pool carries (quant/kv.py). This is the denominator of
    the admission-capacity claim: page count is the admission currency,
    so at a fixed HBM budget ``n_pages = budget // page_bytes`` — int8
    storage roughly halves this number vs bf16 (2·C bytes/token
    -> C + 8/page_size... the scale overhead is 8 bytes/token/layer at
    page granularity), roughly doubling the pool."""
    from ..quant.kv import kv_itemsize, scale_bytes_per_token
    per_tok = (2 * cfg.kv_channels * kv_itemsize(kv_quant, cfg)
               + scale_bytes_per_token(kv_quant, granularity,
                                       cfg.n_head))
    # a page holds the layers that keep paged history (all of GPT-2's)
    return len(cfg.paged_layers) * page_size * per_tok


def n_pages_for_hbm(hbm_bytes: int, cfg: ModelConfig, page_size: int,
                    kv_quant: str = "none",
                    granularity: str = "page") -> int:
    """Physical pages a fixed HBM budget holds at the given KV storage
    mode — the fixed-HBM capacity comparison the quantization A/B
    (bench --quant-ab) and the pool-geometry acceptance test size
    their pools with."""
    return max(int(hbm_bytes) // page_bytes(cfg, page_size, kv_quant,
                                            granularity), 1)


class _RadixNode:
    __slots__ = ("id", "page", "parent", "key", "n_children", "last_use")

    def __init__(self, nid: int, page: int, parent: int,
                 key: Tuple[int, bytes]):
        self.id = nid
        self.page = page
        self.parent = parent
        self.key = key
        self.n_children = 0
        self.last_use = 0


class RadixIndex:
    """Prefix tree over full-page token runs -> physical pages.

    Every node is one FULL page of prompt tokens hanging off its
    parent's chain; edges are keyed by the page's exact token bytes
    (prefix identity, not a lossy hash). ``lookup`` walks the longest
    cached chain; eviction removes childless nodes only, so a surviving
    node's whole ancestry stays reachable.
    """

    ROOT = 0

    def __init__(self):
        self.nodes: Dict[int, _RadixNode] = {}
        self._edges: Dict[Tuple[int, bytes], int] = {}
        self._next_id = 1
        self._tick = 0

    def __len__(self) -> int:
        return len(self.nodes)

    def _touch(self, node: _RadixNode) -> None:
        self._tick += 1
        node.last_use = self._tick

    def lookup(self, prompt: np.ndarray, page_size: int,
               touch: bool = True) -> List[_RadixNode]:
        """Longest chain of cached full pages prefixing ``prompt`` (in
        order). ``touch`` refreshes LRU stamps — peeks (admission
        gating) pass False so a queued-but-unadmittable request cannot
        pin pages it never claims."""
        out: List[_RadixNode] = []
        parent = self.ROOT
        for g in range(int(prompt.size) // page_size):
            key = (parent, prompt[g * page_size:(g + 1) * page_size]
                   .tobytes())
            nid = self._edges.get(key)
            if nid is None:
                break
            node = self.nodes[nid]
            if touch:
                self._touch(node)
            out.append(node)
            parent = nid
        return out

    def insert(self, parent: int, tok_bytes: bytes,
               page: int) -> Tuple[_RadixNode, bool]:
        """Insert a full page under ``parent``; returns (node, inserted).
        An existing identical chain wins (two slots racing to register
        the same prompt): the caller's physical copy simply stays
        private and frees with its slot."""
        key = (parent, tok_bytes)
        nid = self._edges.get(key)
        if nid is not None:
            node = self.nodes[nid]
            self._touch(node)
            return node, False
        node = _RadixNode(self._next_id, page, parent, key)
        self._next_id += 1
        self.nodes[node.id] = node
        self._edges[key] = node.id
        if parent != self.ROOT:
            self.nodes[parent].n_children += 1
        self._touch(node)
        return node, True

    def remove(self, node: _RadixNode) -> None:
        assert node.n_children == 0, "evicting a non-leaf radix node"
        del self.nodes[node.id]
        del self._edges[node.key]
        if node.parent != self.ROOT and node.parent in self.nodes:
            self.nodes[node.parent].n_children -= 1


@dataclass
class PageClaim:
    """One slot's page reservation: the physical page per logical page
    (claimed prefix pages first, then fresh pages covering the prompt
    tail and the whole decode budget — reserved eagerly so an admitted
    request can never strand mid-decode on an empty pool)."""

    pages: List[int]
    claimed_tokens: int
    chain: List[int]                 # radix node ids along the prefix
    cow: List[Tuple[int, int]]       # (src, dst) device copies to apply
    prompt: np.ndarray
    next_reg: int                    # next full prompt page to register


class PageAllocator:
    """Refcounted physical-page allocator + radix prefix cache + LRU
    eviction. Pure host state — the device pool is the pool's concern —
    which is what makes the fuzz harness (tests/test_pages.py) cheap.
    """

    def __init__(self, n_pages: int, page_size: int,
                 prefix_cache: bool = True, telemetry=None):
        assert n_pages >= 1 and page_size >= 1
        self.n_pages = n_pages
        self.page_size = page_size
        self.prefix_cache = prefix_cache
        # prefix-hit / eviction instants on the request timeline
        # (utils.telemetry); NULL by default — zero cost, zero state
        self.tel = telemetry or NULL
        self._free: List[int] = list(range(n_pages - 1, -1, -1))
        self.ref = np.zeros((n_pages,), np.int32)
        self.radix = RadixIndex()
        self.page_node: Dict[int, _RadixNode] = {}   # phys -> radix node
        # counters surfaced through Engine.metrics_summary()["pages"]
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.prefix_hit_tokens = 0
        self.prompt_tokens = 0
        self.evictions = 0
        self.cow_copies = 0

    # ------------------------------------------------------------ sizing

    @property
    def pages_free(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self.n_pages - len(self._free)

    def in_use_by_block(self, n_blocks: int) -> List[int]:
        """Pages in use per contiguous block of the physical page axis
        — exactly per-CHIP occupancy when the pool's page axis shards
        over the serving mesh's 'data' axis (NamedSharding assigns
        contiguous blocks), so the router's least-loaded signal and the
        Prometheus gauges stay meaningful on a mesh. 'In use' matches
        ``pages_in_use``: slot-referenced pages AND radix-held
        refcount-0 prefix pages (both occupy HBM)."""
        free = np.zeros((self.n_pages,), bool)
        free[np.fromiter(self._free, np.int64, len(self._free))] = True
        blk = -(-self.n_pages // n_blocks)
        return [int((~free[i * blk:(i + 1) * blk]).sum())
                for i in range(n_blocks)]

    def n_pages_for(self, n_prompt: int, cap: int) -> int:
        """Logical pages a request needs END TO END: the last write
        position is P-1 + cap-1 (decode rewrites the last prompt index
        first), so reserve ceil((P + cap - 1) / page)."""
        return -(-(n_prompt + cap - 1) // self.page_size)

    def _reclaimable(self, protect) -> int:
        """Pages reclaimable by cascaded LRU eviction: every refcount-0
        radix page not protected. (Claims cover whole prefixes, so
        ref[parent] >= ref[child] along any chain — a refcount-0 node
        heads a fully refcount-0 subtree and leaf-first eviction always
        reaches it.)"""
        return sum(1 for page in self.page_node
                   if self.ref[page] == 0 and page not in protect)

    def _evict_one(self, protect) -> Optional[int]:
        best: Optional[Tuple[int, _RadixNode]] = None
        for page, node in self.page_node.items():
            if node.n_children or self.ref[page] or page in protect:
                continue
            if best is None or node.last_use < best[1].last_use:
                best = (page, node)
        if best is None:
            return None
        page, node = best
        self.radix.remove(node)
        del self.page_node[page]
        self._free.append(page)
        self.evictions += 1
        self.tel.instant("page_evict", page=page)
        return page

    # ----------------------------------------------------------- acquire

    def _plan(self, prompt: np.ndarray, cap: int, touch: bool):
        chain = (self.radix.lookup(prompt, self.page_size, touch=touch)
                 if self.prefix_cache else [])
        need = self.n_pages_for(int(prompt.size), cap) - len(chain)
        # full-prompt hit: the first decode write (position P-1) lands
        # inside the last claimed page -> copy-on-write needs one more
        cow = bool(chain) and len(chain) * self.page_size == prompt.size
        if cow:
            need += 1
        return chain, need, cow

    def can_acquire(self, prompt: np.ndarray, cap: int) -> bool:
        chain, need, _ = self._plan(prompt, cap, touch=False)
        claimed = {n.page for n in chain}
        return need <= len(self._free) + self._reclaimable(claimed)

    def acquire(self, prompt: np.ndarray, cap: int) -> Optional[PageClaim]:
        """Claim the longest cached prefix + fresh pages for the rest of
        the request's lifetime; None when even LRU eviction cannot free
        enough pages. A failed acquire refreshes NO LRU stamps (the plan
        walks untouched; touching happens only on commit) — a caller
        probing with acquire() directly cannot pin prefix pages it never
        claims."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        chain, need, cow_needed = self._plan(prompt, cap, touch=False)
        protect = {n.page for n in chain}
        while len(self._free) < need:
            if self._evict_one(protect) is None:
                return None
        for node in chain:
            self.radix._touch(node)
        self.prefix_lookups += 1
        self.prompt_tokens += int(prompt.size)
        pages = [n.page for n in chain]
        for p in pages:
            self.ref[p] += 1
        cow: List[Tuple[int, int]] = []
        if cow_needed:
            dst = self._free.pop()
            src = pages[-1]
            self.ref[src] -= 1
            self.ref[dst] = 1
            pages[-1] = dst
            cow.append((src, dst))
            self.cow_copies += 1
        n_total = self.n_pages_for(int(prompt.size), cap)
        for _ in range(n_total - len(pages)):
            p = self._free.pop()
            self.ref[p] = 1
            pages.append(p)
        claimed_tokens = len(chain) * self.page_size
        if chain:
            self.prefix_hits += 1
            self.tel.instant("prefix_hit", pages=len(chain),
                             tokens=claimed_tokens)
        self.prefix_hit_tokens += claimed_tokens
        return PageClaim(pages=pages, claimed_tokens=claimed_tokens,
                         chain=[n.id for n in chain], cow=cow,
                         prompt=prompt.copy(), next_reg=len(chain))

    # ------------------------------------------------- register / release

    def register(self, claim: PageClaim, next_write_pos: int) -> None:
        """Insert the claim's FINALIZED full prompt pages into the radix.
        A page is final once the slot's next write position is past it —
        which defers exactly the page containing prompt position P-1
        (rewritten by the first decode step) until that write lands, so
        no registered page is ever written by its owner again."""
        if not self.prefix_cache:
            return
        psz = self.page_size
        n_full = int(claim.prompt.size) // psz
        while (claim.next_reg < n_full
               and (claim.next_reg + 1) * psz <= next_write_pos):
            g = claim.next_reg
            parent = claim.chain[-1] if claim.chain else RadixIndex.ROOT
            node, inserted = self.radix.insert(
                parent, claim.prompt[g * psz:(g + 1) * psz].tobytes(),
                claim.pages[g])
            if inserted:
                self.page_node[claim.pages[g]] = node
            claim.chain.append(node.id)
            claim.next_reg += 1

    def pending_registration(self, claim: PageClaim) -> bool:
        return (self.prefix_cache
                and claim.next_reg < int(claim.prompt.size)
                // self.page_size)

    def release(self, claim: PageClaim) -> None:
        """Drop the claim's references; refcount-0 pages return to the
        free list unless the radix holds them (then they ARE the prefix
        cache, reclaimed later by LRU eviction)."""
        for p in claim.pages:
            self.ref[p] -= 1
            assert self.ref[p] >= 0, f"page {p} refcount underflow"
            if self.ref[p] == 0 and p not in self.page_node:
                self._free.append(p)


@dataclass
class Admission:
    """What the engine needs from a successful ``acquire``: the slot,
    how many prompt tokens the prefix cache already holds (prefill
    starts there), and the device page copies to apply before any
    compute touches the slot (copy-on-write splits)."""

    slot: int
    claimed: int
    cow: List[Tuple[int, int]]


class PagedCachePool:
    """Paged drop-in for ``CachePool``: same host API (acquire/release/
    slot_of/positions/occupancy), backed by a page pool + per-slot page
    tables instead of contiguous slot buffers."""

    def __init__(self, cfg: ModelConfig, n_slots: int, *,
                 page_size: int = 0, max_pages: int = 0, n_pages: int = 0,
                 prefix_cache: bool = True, dtype=None, telemetry=None,
                 sharding=None, scale_sharding=None,
                 mesh_shape: Tuple[int, int] = (1, 1), quant=None):
        """``sharding`` (a NamedSharding from
        ``parallel.mesh.serve_shardings().cache``) commits the page
        pool onto the serving mesh instead of one device: the physical
        page axis shards over 'data' (each chip stores
        ceil(n_pages / data) pages — the capacity multiplier) and the
        model dim over 'model'. All HOST state here (allocator, radix,
        tables) is mesh-agnostic: page ids are logical either way.
        ``mesh_shape`` is carried for stats()/gauges only.

        ``quant`` (a quant.QuantConfig with ``kv_dtype`` set) stores
        pages in int8/fp8 with per-row scale metadata riding the pool
        dict (``ks``/``vs``) — halving bytes/page, which at fixed HBM
        doubles the page count this pool can be sized with
        (``n_pages_for_hbm``). ``scale_sharding``
        (``ServeShardings.scale``) commits the scale arrays with their
        page axis over 'data' alongside the pool's; every host-side
        invariant (allocator, radix, COW planning) is byte-for-byte
        unchanged — a page is its rows plus their scales."""
        assert n_slots >= 1, n_slots
        fam = family(cfg)
        self._slot_entries = fam.slot_entries
        if prefix_cache and self._slot_entries:
            raise ValueError(
                "prefix_cache: the radix cache shares pages of layers that "
                "keep paged history; the family's "
                f"{'/'.join(kind for kind, _ in self._slot_entries)} state "
                "belongs to a slot and cannot be restored from it")
        self.cfg = cfg
        self.n_slots = n_slots
        self.quant = quant
        self.page_size, self.max_pages, self.n_pages = pool_geometry(
            cfg, n_slots, page_size, max_pages, n_pages)
        assert self.max_pages * self.page_size >= cfg.block_size, (
            f"max_pages={self.max_pages} x page_size={self.page_size} "
            f"cannot hold block_size={cfg.block_size}")
        # default physical pool = the contiguous pool's HBM exactly;
        # fewer pages is the point (admission then gates on free pages)
        assert self.n_pages >= self.max_pages, (
            "pool smaller than one slot's worst case")
        self.mesh_shape = (int(mesh_shape[0]), int(mesh_shape[1]))
        # effective shard count of the PAGE axis (may be 1 when the
        # page count was not divisible and the spec dropped the axis)
        self._page_shards = 1
        if sharding is not None and len(sharding.spec) > 1 \
                and sharding.spec[1] is not None:
            self._page_shards = int(
                sharding.mesh.shape[sharding.spec[1]])
        self.alloc = PageAllocator(self.n_pages, self.page_size,
                                   prefix_cache=prefix_cache,
                                   telemetry=telemetry)
        pool = fam.init_paged_kv_pool(cfg, self.n_pages, self.page_size,
                                      dtype=dtype, quant=quant,
                                      n_slots=n_slots)
        # per-entry placement: K/V take the pool spec, scale arrays
        # (different rank) their own page-axis spec
        self.cache: Dict = {
            name: commit_default(
                arr, sharding=(scale_sharding if name in ("ks", "vs")
                               else sharding))
            for name, arr in pool.items()}
        # host-mirrored, device-fed each step (fixed shape: the paged
        # programs never retrace on table contents)
        self.tables = np.zeros((n_slots, self.max_pages), np.int32)
        self.positions = np.zeros((n_slots,), np.int32)
        self._free_slots: List[int] = list(range(n_slots - 1, -1, -1))
        self._owner: Dict[int, str] = {}
        self._slot_by_request: Dict[str, int] = {}   # reverse index: O(1)
        self._claims: Dict[int, PageClaim] = {}
        # slots admitted with defer_commit=True (in-window prefill):
        # their radix registration is gated on commit_admission — the
        # engine calls it only once the writes are known landed, so
        # flush_pending can never pre-register a page a still-flying
        # window is writing
        self._deferred: set = set()
        # disaggregation (serve/disagg.py): pages refcount-pinned under
        # a transfer key — an export pin keeps radix prefix pages alive
        # while their bytes stream out; an install pin holds freshly
        # allocated pages until commit_install registers them
        self._pins: Dict[str, List[int]] = {}
        self._installs: Dict[str, Tuple[np.ndarray, int, List[int]]] = {}
        self.pages_exported = 0
        self.pages_installed = 0

    # ---------------------------------------------------------- geometry

    def _is_slot_entry(self, name: str) -> bool:
        return any(name.startswith(p) for _, p in self._slot_entries)

    @property
    def pages(self) -> Dict:
        """The entries of ``cache`` that are pool PAGES (what admission
        reserves, what a page copy, export or install walks): all of
        GPT-2's; a family with per-slot state beside them (window rings,
        conv state) keeps that under names with the prefixes of its
        ``slot_entries``."""
        if not self._slot_entries:
            return self.cache
        return {n: a for n, a in self.cache.items()
                if not self._is_slot_entry(n)}

    @pages.setter
    def pages(self, new: Dict) -> None:
        self.cache = new if new.keys() == self.cache.keys() \
            else {**self.cache, **new}

    @property
    def kv_array(self):
        """One page array of the pool: its dtype and page count."""
        return next(iter(self.pages.values()))

    def slot_state(self) -> Dict[str, list]:
        """The per-slot state beside the pages: kind -> its arrays."""
        return {kind: [a for n, a in self.cache.items()
                       if n.startswith(prefix)]
                for kind, prefix in self._slot_entries}

    def bytes_by_kind(self) -> Dict[str, int]:
        """Bytes of the pool by kind of state: ``pages`` (what admission
        reserves) and each kind of per-slot state the family keeps."""
        return {"pages": sum(a.nbytes for a in self.pages.values()),
                **{kind: sum(a.nbytes for a in arrays)
                   for kind, arrays in self.slot_state().items()}}

    @property
    def seq_len(self) -> int:
        """LOGICAL per-slot capacity (positions are bounded by the
        learned positional table regardless of page count)."""
        return self.cfg.block_size

    @property
    def n_free(self) -> int:
        return len(self._free_slots)

    @property
    def n_used(self) -> int:
        return self.n_slots - len(self._free_slots)

    @property
    def occupancy(self) -> float:
        return self.n_used / self.n_slots

    # ------------------------------------------------------ slot lifecycle

    def can_admit(self, prompt: np.ndarray, cap: int) -> bool:
        return bool(self._free_slots) and self.alloc.can_acquire(
            np.asarray(prompt, np.int32), cap)

    def cached_prefix_tokens(self, prompt: np.ndarray) -> int:
        """Longest radix-cached prefix of ``prompt`` in TOKENS, without
        touching LRU stamps or claiming anything — the fleet router's
        affinity probe (route a session to the replica that already
        owns its prefix). 0 with the prefix cache off."""
        if not self.alloc.prefix_cache:
            return 0
        chain = self.alloc.radix.lookup(
            np.asarray(prompt, np.int32).reshape(-1), self.page_size,
            touch=False)
        return len(chain) * self.page_size

    def acquire(self, request_id: str, prompt: np.ndarray,
                cap: int, defer_commit: bool = False
                ) -> Optional[Admission]:
        """``defer_commit=True`` (the engine's windowed-admission path)
        holds the slot OUT of radix registration — including
        ``flush_pending`` — until ``commit_admission``: its prompt
        pages are being written by an in-flight mixed window, and a
        registered page must never be claimable before its writes have
        landed in dispatch order."""
        if not self._free_slots:
            return None
        claim = self.alloc.acquire(prompt, cap)
        if claim is None:
            return None
        slot = self._free_slots.pop()
        if defer_commit:
            self._deferred.add(slot)
        self._owner[slot] = request_id
        self._slot_by_request[request_id] = slot
        self._claims[slot] = claim
        row = self.tables[slot]
        row[:] = 0
        row[:len(claim.pages)] = claim.pages
        self.positions[slot] = int(prompt.size) - 1
        return Admission(slot=slot, claimed=claim.claimed_tokens,
                         cow=list(claim.cow))

    def commit_admission(self, slot: int) -> None:
        """Register the slot's already-final full prompt pages (called
        after prefill wrote them — registration order is what lets a
        same-step neighbor claim them safely). Lifts a
        ``defer_commit`` hold."""
        self._deferred.discard(slot)
        self.alloc.register(self._claims[slot], int(self.positions[slot]))

    def flush_pending(self) -> None:
        """Advance deferred registrations (the page containing prompt
        position P-1 becomes shareable once the first decode write
        passed it). Called once per engine step — cheap: at most one
        page per slot ever waits. Slots under a ``defer_commit`` hold
        are skipped: their prompt writes may still be in flight."""
        for slot, claim in self._claims.items():
            if slot in self._deferred:
                continue
            if self.alloc.pending_registration(claim):
                self.alloc.register(claim, int(self.positions[slot]))

    def release(self, slot: int) -> None:
        self._deferred.discard(slot)
        owner = self._owner.pop(slot, None)
        assert owner is not None, f"slot {slot} double-free"
        # conditional: duplicate request ids are rejected at submit, but
        # the reverse index must never KeyError another slot's mapping
        if self._slot_by_request.get(owner) == slot:
            del self._slot_by_request[owner]
        self.alloc.release(self._claims.pop(slot))
        self.tables[slot, :] = 0
        self._free_slots.append(slot)

    def owner(self, slot: int) -> Optional[str]:
        return self._owner.get(slot)

    def slot_of(self, request_id: str) -> Optional[int]:
        return self._slot_by_request.get(request_id)

    # ------------------------------------------- disaggregated transfer
    #
    # The page-level API serve/disagg.py moves KV between tiers with.
    # Export side: a prefill worker's finished prompt pages live in its
    # radix as refcount-0 prefix cache — pin_prefix refcounts them for
    # the duration of the copy-out so LRU eviction cannot reclaim a
    # page mid-transfer. Install side: install_prefix allocates fresh
    # physical pages (pinned, so nothing evicts them before their
    # bytes land), the engine's jitted scatter writes the transferred
    # blocks, and commit_install registers the chain into the local
    # radix keyed by the prompt's token bytes — after which a NORMAL
    # admission claims the prefix exactly like a locally warmed one
    # (table rebase to local physical indices is the radix chain
    # itself). Every failure path degrades to "prefix not cached":
    # the request re-prefills locally, token-identically.

    def pin_prefix(self, key: str, prompt: np.ndarray) -> List[int]:
        """Refcount-pin the radix-cached full prompt pages of
        ``prompt`` under ``key``; returns the physical pages in prefix
        order (possibly empty). Pin keys are single-owner: re-pinning
        an active key is a bug."""
        assert key not in self._pins, f"transfer pin {key!r} already held"
        chain = self.alloc.radix.lookup(
            np.asarray(prompt, np.int32).reshape(-1), self.page_size,
            touch=True)
        pages = [n.page for n in chain]
        for p in pages:
            self.alloc.ref[p] += 1
        self._pins[key] = pages
        return pages

    def unpin(self, key: str) -> None:
        """Drop a transfer pin (export finished, or install aborted).
        Pages whose refcount hits 0 return to the free list unless the
        radix holds them — same discipline as claim release."""
        self._installs.pop(key, None)
        for p in self._pins.pop(key, []):
            self.alloc.ref[p] -= 1
            assert self.alloc.ref[p] >= 0, f"page {p} pin underflow"
            if self.alloc.ref[p] == 0 and p not in self.alloc.page_node:
                self.alloc._free.append(p)

    def install_prefix(self, key: str, prompt: np.ndarray,
                       from_page: int,
                       n_pages: int) -> Optional[List[int]]:
        """Allocate ``n_pages`` fresh physical pages (pinned under
        ``key``) to receive transferred KV blocks for prompt pages
        ``from_page .. from_page+n_pages``. Requires the local radix to
        already hold the first ``from_page`` pages (the chain the
        placement probe saw) — if that prefix shrank since (eviction),
        returns None and the caller falls back to local prefill."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        chain = self.alloc.radix.lookup(prompt, self.page_size,
                                        touch=True)
        if len(chain) < from_page:
            return None
        protect = {n.page for n in chain}
        taken: List[int] = []
        for _ in range(n_pages):
            if not self.alloc._free and \
                    self.alloc._evict_one(protect) is None:
                for p in taken:                      # unwind: no pin
                    self.alloc.ref[p] = 0
                    self.alloc._free.append(p)
                return None
            p = self.alloc._free.pop()
            self.alloc.ref[p] = 1
            taken.append(p)
        self._pins[key] = list(taken)
        self._installs[key] = (prompt.copy(), int(from_page), taken)
        return taken

    def commit_install(self, key: str) -> int:
        """Register an installed chain into the radix (the transferred
        blocks are known landed — the caller sequences this after the
        scatter's result is committed) and drop the pin. Returns the
        number of pages that entered the radix; pages whose edge
        already existed (a concurrent local prefill won the race) stay
        private and free with the pin."""
        prompt, g0, pages = self._installs.pop(key)
        psz = self.page_size
        chain = self.alloc.radix.lookup(prompt, psz, touch=True)
        if len(chain) < g0 or not self.alloc.prefix_cache:
            self.unpin(key)
            return 0
        parent = chain[g0 - 1].id if g0 else RadixIndex.ROOT
        registered = 0
        for i, page in enumerate(pages):
            g = g0 + i
            node, inserted = self.alloc.radix.insert(
                parent, prompt[g * psz:(g + 1) * psz].tobytes(), page)
            if inserted:
                self.alloc.page_node[page] = node
                registered += 1
            parent = node.id
        self.pages_installed += registered
        self.unpin(key)
        return registered

    # ----------------------------------------------------------- metrics

    def stats(self) -> dict:
        a = self.alloc
        # mesh accounting: n_pages is the AGGREGATE admission currency
        # (the allocator is mesh-agnostic); each chip along the data
        # axis physically stores pages_per_chip of it, so per-chip
        # occupancy is what a capacity dashboard / the router's
        # least-loaded signal should watch on a mesh (on 1x1 the
        # per-chip numbers degenerate to the aggregate ones)
        d = self._page_shards
        by_chip = a.in_use_by_block(d)
        per_chip = -(-self.n_pages // d)
        kv_quant = (self.quant.kv_dtype
                    if self.quant is not None and self.quant.kv_enabled
                    else "none")
        gran = (self.quant.granularity if kv_quant != "none" else "page")
        return {
            "page_size": self.page_size,
            "max_pages_per_slot": self.max_pages,
            "n_pages": self.n_pages,
            # quantization gauges (ISSUE 15): bytes_per_page is the
            # admission-capacity denominator the fixed-HBM A/B keys on;
            # kv_quant_bits is the numeric Prometheus-friendly spelling
            # of the mode (8 = quantized storage)
            "kv_quant": kv_quant,
            "quant_granularity": gran,
            "bytes_per_page": page_bytes(self.cfg, self.page_size,
                                         kv_quant, gran),
            "kv_quant_bits": 8 * self.kv_array.dtype.itemsize,
            "pages_in_use": a.pages_in_use,
            "pages_free": a.pages_free,
            "page_utilization": round(a.pages_in_use / self.n_pages, 4),
            "mesh_shape": list(self.mesh_shape),
            "aggregate_pages": self.n_pages,
            "pages_per_chip": per_chip,
            "pages_in_use_by_chip": by_chip,
            "page_utilization_by_chip": [round(c / per_chip, 4)
                                         for c in by_chip],
            "radix_pages": len(a.page_node),
            "prefix_cache": a.prefix_cache,
            "prefix_lookups": a.prefix_lookups,
            "prefix_hits": a.prefix_hits,
            "prefix_hit_tokens": a.prefix_hit_tokens,
            "prefix_hit_rate": (round(a.prefix_hit_tokens
                                      / a.prompt_tokens, 4)
                                if a.prompt_tokens else 0.0),
            "evictions": a.evictions,
            "cow_copies": a.cow_copies,
            # disaggregated transfer counters (serve/disagg.py)
            "pages_exported": self.pages_exported,
            "pages_installed": self.pages_installed,
            "transfer_pins": len(self._pins),
        }
