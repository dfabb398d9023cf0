"""AGAS-managed paged KV cache (DESIGN.md §4a, sharded in §4c).

The ParalleX reading of KV memory: instead of a dense ``(slots,
max_len)`` cache statically owned by each decode slot, KV storage is a
pool of fixed-size *pages*, each a first-class globally-named object
allocated and freed through the AGAS directory (`core/agas.py`).  A
page's `GlobalAddress` is its immutable name; the AGAS (locality,
slot) it resolves to is the physical row in the device-side page
arrays, so a block-table lookup compiles to a gather index — the same
"nothing dynamic survives to run time" rendering used for AMR blocks.

Three layers live here:

* `PagePool` — the allocator: AGAS-backed gid -> physical-row mapping,
  per-page refcounts, a radix prefix index over position-normalized
  page-key chains (`serving/radix.py`) enabling pages shared between
  requests of *different lengths* (copy-on-write on first divergent
  append) — and,
  alongside each indexed page, the post-norm hidden state of the
  page's last position (the activation checkpoint prefix-cache
  compute skip resumes from, DESIGN.md §4e) — and the device arrays
  themselves.  Single locality (``n_shards == 1``):
  ``pages["k"]/pages["v"]`` of shape ``(L, n_pages + 1, page_size, KV,
  D)``; the extra trailing row is the *null page*, the write target of
  idle decode slots — never read because the per-slot masks exclude
  it.  Sharded (``n_shards > 1``, DESIGN.md §4c): one AGAS locality
  per KV shard, arrays of shape ``(L, n_shards, pages_per_shard + 1,
  page_size, KV, D)`` (each shard carries its own local null page),
  block-table rows encoded ``locality * rows_per_shard + slot``,
  allocation least-loaded-shard-first with prefix-shared pages pinned
  to their owner, and pool-imbalance-triggered page migration lowered
  through `core/parcels.migration_plan` into ppermute legs — a page's
  global name survives the move (the AGAS promise), only its
  (locality, slot) changes.

* `PagedKVCache` — the per-engine view: one block table per decode
  slot mapping token position ``p`` to the physical row of page
  ``p // page_size``, plus **per-slot** position counters (replacing
  the dense cache's shared ``len/cursor/abs`` clock).  Prompts attach
  either whole (`attach`) or one page-aligned chunk at a time
  (`begin_chunk`, DESIGN.md §4b) — the prefix hash-chain is computed
  over the full prefix either way, so the two paths share pages with
  each other.

* `PageExhausted` — the backpressure signal: raised when the pool has
  no free page; the serving engine reacts by preempting a request back
  to the queue (the LCO analogue of a parcel being deferred).
"""

from __future__ import annotations

import dataclasses
import hashlib
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.agas import AGAS, AGASError, GlobalAddress
from repro.core.localities import LocalityDomain
from repro.core.parcels import MigrationPlan, canonical_size, \
    migration_plan, plan_move_arrays
from repro.models.config import ArchConfig
from repro.models.transformer import PAGED_FAMILIES, init_paged_cache
from repro.obs.trace import NULL_TRACER
from repro.serving.radix import RadixPrefixIndex


def pages_that_fit(cfg: ArchConfig, page_size: int,
                   free_bytes: int) -> int:
    """Pool pages (K and V, every layer) that `free_bytes` of device
    memory can hold.  A decode or chunk step scans the pool layer by
    layer and holds its input and its updated copy at once, so each
    page is counted twice."""
    itemsize = jnp.dtype(cfg.dtype).itemsize
    page = (2 * cfg.n_layers * page_size * cfg.n_kv_heads * cfg.head_dim
            * itemsize)
    return max(int(free_bytes) // (2 * page), 0)


class PageExhausted(RuntimeError):
    """No free page in the pool; callers preempt or defer."""


def _chain_new(pad: int = 0) -> Any:
    """A fresh page-key chain for a layout with `pad` leading padding
    rows.  The pad count seeds the chain — RoPE positions differ
    across layouts, so a padded layout's pages must never alias a
    pad-free one's even when the real tokens agree."""
    h = hashlib.blake2b(digest_size=16)
    h.update(int(pad).to_bytes(4, "little", signed=True))
    return h


def _chain_extend(chain: Any, tokens: np.ndarray, start: int,
                  end: int, page_size: int, pad: int = 0
                  ) -> List[Tuple[bytes, int]]:
    """Extend a page-key chain over layout rows [start, end) (`start`
    page-aligned), returning one (digest, fill) key per page.

    Each page hashes its start row (so keys stay distinct even for
    pages holding zero real tokens) followed by its REAL tokens —
    `tokens` is the full layout and rows below `pad` are padding,
    excluded from the digest.  Byte-for-byte the continuation of
    `page_keys` over the same layout: update() chunking never changes
    a blake2b digest, and the per-page update sequence here is
    identical.
    """
    keys: List[Tuple[bytes, int]] = []
    # one serialization of the layout, byte-sliced per page: this runs
    # on every admission attempt, so it must stay microseconds
    buf = np.ascontiguousarray(tokens, np.int32).tobytes()
    for pstart in range(start, end, page_size):
        pend = min(pstart + page_size, end)
        chain.update(int(pstart).to_bytes(4, "little", signed=True))
        chain.update(buf[4 * max(pstart, pad):4 * pend])
        keys.append((chain.digest(), pend - pstart))
    return keys


def _chain_seed(tokens: np.ndarray, start: int, page_size: int,
                pad: int = 0) -> Any:
    """A chain with rows [0, start) already consumed — what a slot's
    running chain would hold after attaching that prefix."""
    chain = _chain_new(pad)
    if start:
        _chain_extend(chain, tokens, 0, start, page_size, pad)
    return chain


def page_keys(tokens: np.ndarray, page_size: int, pad: int = 0
              ) -> List[Tuple[bytes, int]]:
    """Position-normalized chained prefix hashes, one per layout page.

    Key i commits to the layout's pad count plus every REAL token
    through page i (and the page's row count as its fill), so two
    layouts share page i iff they agree on the pad count and on every
    real token up to and including it.  `tokens` is the full layout;
    `pad` declares how many of its leading rows are padding (excluded
    from the digests — their values are irrelevant, only their count
    names the position shift).  Pad-free layouts (``pad=0``, the paged
    engines') therefore share prefix pages across prompts of
    *different total lengths* — the mixed-length traffic DESIGN.md
    §4e's compute skip exists for.
    """
    return _chain_extend(_chain_new(pad), tokens, 0, len(tokens),
                         page_size, pad)


# Jitted + donated page mutations: on accelerators the update happens
# in place instead of copying the whole pool per call (CPU falls back
# to a copy with a one-time donation warning).  The *_sharded variants
# operate on the (L, n_shards, rows_per_shard, ...) layout with the
# flat row already decoded into (locality, slot) index arrays.
@partial(jax.jit, donate_argnums=(0,))
def _scatter_rows(arr, idx, spans):
    return arr.at[:, idx].set(spans)


@partial(jax.jit, donate_argnums=(0,))
def _clone_row(arr, src, dst):
    return arr.at[:, dst].set(arr[:, src])


@partial(jax.jit, donate_argnums=(0,))
def _scatter_rows_sharded(arr, loc, slot, spans):
    return arr.at[:, loc, slot].set(spans)


@partial(jax.jit, donate_argnums=(0,))
def _clone_row_sharded(arr, src_loc, src_slot, dst_loc, dst_slot):
    return arr.at[:, dst_loc, dst_slot].set(arr[:, src_loc, src_slot])


# Migration payload permutation: the RHS gather is evaluated against
# the pre-update operand, so every payload is read before any
# destination is written regardless of move order (the snapshot
# semantics core/parcels.plan_move_arrays documents).
@partial(jax.jit, donate_argnums=(0,))
def _permute_rows_sharded(arr, src_loc, src_slot, dst_loc, dst_slot):
    return arr.at[:, dst_loc, dst_slot].set(arr[:, src_loc, src_slot])


class PagePool:
    """Refcounted AGAS page allocator + the device page arrays.

    ``n_shards > 1`` shards the pool across AGAS localities (DESIGN.md
    §4c): allocation is least-loaded-shard-first, every physical row is
    named ``locality * rows_per_shard + slot``, and `migrate_pages`
    moves pages between shards without changing their global names.
    ``mesh`` (optional, with a ``kv_axis`` axis of size n_shards)
    device-backs the localities: the page arrays are placed one shard
    per device and migration legs execute as `lax.ppermute` under
    `shard_map`; without a mesh the same legs lower to a single-device
    row permutation (simulated localities — bit-identical results).
    """

    def __init__(self, cfg: ArchConfig, n_pages: int, page_size: int,
                 dtype=None, *, n_shards: int = 1, mesh=None,
                 kv_axis: str = "kv", tracer=None,
                 pin_threshold: int = 4, pin_capacity: int = 0):
        if cfg.family not in PAGED_FAMILIES:
            raise ValueError(
                f"paged KV cache supports {PAGED_FAMILIES}, "
                f"not {cfg.family!r}")
        if n_shards < 1 or n_pages % n_shards:
            raise ValueError(
                f"n_pages {n_pages} must be a positive multiple of "
                f"n_shards {n_shards}")
        self.cfg = cfg
        self.capacity = int(n_pages)
        self.page_size = int(page_size)
        self.n_shards = int(n_shards)
        self.sharded = self.n_shards > 1
        self.pages_per_shard = self.capacity // self.n_shards
        # every shard carries its own null page, so a shard's rows are
        # pages_per_shard + 1 and the flat encoding below never
        # collides between shards
        self.rows_per_shard = self.pages_per_shard + 1
        # shard 0's local null row; any shard's null works as a write
        # sink (no mask ever reads one) and 0 * rows_per_shard +
        # pages_per_shard keeps the single-shard value n_pages
        self.null_row = self.pages_per_shard
        self.mesh = mesh
        self.kv_axis = kv_axis
        if mesh is not None and \
                mesh.shape.get(kv_axis) != self.n_shards:
            raise ValueError(
                f"mesh axis {kv_axis!r} must have size {self.n_shards}")
        # One AGAS locality per KV shard; per-locality capacity is the
        # shard's page count (the directory's free lists ARE the
        # least-loaded allocation signal).
        self.agas = AGAS(LocalityDomain.simulated(self.n_shards),
                         self.pages_per_shard, space="kvpage")
        self._refs: Dict[int, int] = {}            # gid -> refcount
        # the prefix index: a radix tree over page-key chains
        # (serving/radix.py) — longest-prefix covers walk it, point
        # lookups go through its O(1) digest directory, and its hit
        # statistics pin hot prefixes against tiered eviction
        self.prefix = RadixPrefixIndex(
            pin_threshold=pin_threshold,
            pin_capacity=pin_capacity or max(1, n_pages // 4))
        # gid -> last-position activation checkpoint (np, (D,)): lives
        # and dies with the page's prefix-index membership (§4e)
        self._hidden: Dict[int, np.ndarray] = {}
        self.pages: Dict[str, Any] = init_paged_cache(
            cfg, self.rows_per_shard, self.page_size, dtype,
            n_shards=self.n_shards)
        if mesh is not None:
            from repro.distributed.sharding import page_pool_shardings
            sh = page_pool_shardings(mesh, kv_axis)
            self.pages = {k: jax.device_put(v, sh)
                          for k, v in self.pages.items()}
        # performance counters (Fig 9 spirit: runtime overhead visible)
        self.allocs = 0
        self.shares = 0
        self.cow_copies = 0
        self.page_migrations = 0
        # recovery counters (DESIGN.md §4g)
        self.pages_rebuilt = 0       # dead-shard pages restored from a host copy
        self.pages_lost = 0          # dead-shard pages with no surviving copy
        self.localities_killed = 0
        self.trace = tracer if tracer is not None else NULL_TRACER
        # canonical migration programs (DESIGN.md §9.4): the flat path
        # pads move lists to power-of-two size classes; the mesh path
        # caches one compiled shard_map program per ppermute leg
        # structure with the slot indices as traced operands
        self._mig_cache: Dict[tuple, Any] = {}
        self._mig_sizes: set = set()

    # -- allocation / refcounting -------------------------------------
    @property
    def free_pages(self) -> int:
        # global count: least-loaded-first allocation keeps every shard
        # reachable, so n free pages really do admit n allocations —
        # summed over ACTIVE shards only, so a retired/dead shard's
        # empty pool never inflates the admission watermark (§4g)
        return sum(self.agas.free_count(l)
                   for l in self.active_shards())

    @property
    def used_pages(self) -> int:
        return len(self._refs)

    def occupancy(self) -> float:
        return self.used_pages / max(self.capacity, 1)

    def shard_used(self) -> List[int]:
        """Pages resident per shard (the load-balance signal)."""
        return [int(n) for n in self.agas.load()]

    def shard_occupancy(self) -> List[float]:
        per = max(self.pages_per_shard, 1)
        return [u / per for u in self.shard_used()]

    def metrics(self) -> Dict[str, Any]:
        """Counters under the unified ``subsystem.metric`` namespace
        (the engine mirrors these into its MetricsRegistry)."""
        return {
            "pool.capacity": self.capacity,
            "pool.page_size": self.page_size,
            "pool.kv_shards": self.n_shards,
            "pool.used_pages": self.used_pages,
            "pool.free_pages": self.free_pages,
            "pool.occupancy": self.occupancy(),
            "pool.allocs": self.allocs,
            "pool.shares": self.shares,
            "pool.cow_copies": self.cow_copies,
            "pool.page_migrations": self.page_migrations,
            "pool.pages_rebuilt": self.pages_rebuilt,
            "pool.pages_lost": self.pages_lost,
            "pool.localities_killed": self.localities_killed,
            **self.prefix.metrics(),
        }

    def alloc(self, locality: Optional[int] = None) -> GlobalAddress:
        """Allocate a page, least-loaded shard first.

        Prefix-shared pages are pinned to their owner by construction —
        sharing increfs an existing page wherever it lives; only FRESH
        pages go through placement.  An explicit `locality` pins the
        page (callers that want shard affinity); the default policy
        keeps the shards balanced without a planner.
        """
        if locality is None:
            # tier 0 = device: fresh pages always land in fast memory;
            # the host tier (tiered pools only) is reached exclusively
            # by percolation, never by allocation
            locality = self.agas.least_loaded(tier=0)
        try:
            addr = self.agas.allocate(locality)
        except AGASError:
            raise PageExhausted(
                f"page pool exhausted ({self.capacity} pages over "
                f"{self.n_shards} shard(s))") from None
        self._refs[addr.gid] = 1
        self.allocs += 1
        self.trace.instant("kvcache", "page_alloc", lane=locality,
                           gid=addr.gid)
        return addr

    def incref(self, addr: GlobalAddress) -> None:
        self._refs[addr.gid] += 1

    def _purge_index(self, gid: int) -> None:
        """Remove a departing page's prefix-index node AND its stored
        activation checkpoint in one step.  Every path a page leaves
        the pool by (decref-to-zero, rollback discard, cold drop under
        host-tier pressure) funnels through here, so `covered_prefix`
        can never observe a key whose page is freed but whose
        checkpoint — or index entry — lingers."""
        self._hidden.pop(gid, None)
        self.prefix.remove_gid(gid)

    def decref(self, addr: GlobalAddress) -> None:
        self._refs[addr.gid] -= 1
        if self._refs[addr.gid] == 0:
            del self._refs[addr.gid]
            self._purge_index(addr.gid)
            self.agas.free(addr)
            self.trace.instant("kvcache", "page_free", gid=addr.gid)

    def refcount(self, addr: GlobalAddress) -> int:
        return self._refs[addr.gid]

    def discard(self, addr: GlobalAddress) -> None:
        """Rollback decref for pages whose content was never written
        (attach/begin_chunk exception paths).  Identical to `decref`
        here; the tiered pool overrides it to bypass prefix-cache
        retention — a retained-but-unwritten page would serve garbage
        to a later prefix hit."""
        self.decref(addr)

    def ensure_device(self, addr: GlobalAddress) -> None:
        """Guarantee a page is resident in fast memory before its row
        is resolved.  Single-tier pools have nowhere else a page could
        be; the tiered pool (serving/tiering.py) promotes here."""

    def page_cost(self, key: Tuple[bytes, int]) -> int:
        """Fast-tier rows acquiring this prefix key will consume: 0 on
        a hit, 1 on a miss.  The tiered pool also charges 1 for a hit
        on a host-resident page (promotion takes a device row)."""
        return 0 if self.lookup_prefix(key) is not None else 1

    def row(self, addr: GlobalAddress) -> int:
        """Physical row of a page: ``locality * rows_per_shard + slot``
        (reduces to the plain AGAS slot when n_shards == 1).  The row
        changes when the page migrates; the global name never does."""
        loc, slot = self.agas.lookup(addr)
        return loc * self.rows_per_shard + slot

    def page_bytes(self) -> int:
        """Bytes one page occupies (k + v, all layers) — the payload
        unit of percolation copy parcels and §4f handoffs."""
        k = self.pages["k"]
        per_row = int(np.prod(k.shape[-3:])) * k.shape[0] \
            * k.dtype.itemsize
        return 2 * per_row

    def _split_rows(self, rows) -> Tuple[np.ndarray, np.ndarray]:
        r = np.asarray(rows, np.int32)
        return r // self.rows_per_shard, r % self.rows_per_shard

    # -- prefix sharing ------------------------------------------------
    def lookup_prefix(self, key: Tuple[bytes, int]
                      ) -> Optional[GlobalAddress]:
        return self.prefix.lookup(key)

    def register_prefix(self, key: Tuple[bytes, int],
                        addr: GlobalAddress,
                        parent: Optional[bytes] = None) -> None:
        # one key per page: a second registration (either direction)
        # is a no-op, so freeing a page can never leave a stale key
        # behind in the prefix index.  `parent` is the chain's
        # previous digest — the radix edge that makes root-to-node
        # paths prompt prefixes (None for a chain's first page).
        self.prefix.insert(key, addr, parent)

    # -- activation checkpoints (compute skip, DESIGN.md §4e) ---------
    def store_hidden(self, addr: GlobalAddress, hidden) -> None:
        """Attach the post-norm hidden state of a page's last position
        to a prefix-indexed page.  First write wins: the checkpoint is
        always the value the page's first writer computed, so repeated
        shares can never swap in a bit-different recomputation.  Pages
        outside the prefix index carry no checkpoint (nothing could
        ever look it up)."""
        gid = addr.gid
        if self.prefix.owns_gid(gid) and gid not in self._hidden:
            self._hidden[gid] = np.asarray(hidden)

    def hidden_for(self, key: Tuple[bytes, int]
                   ) -> Optional[np.ndarray]:
        """The activation checkpoint cached under a prefix key, or
        None (key unknown, or its page was written before compute
        skip could checkpoint it)."""
        addr = self.prefix.lookup(key)
        if addr is None:
            return None
        return self._hidden.get(addr.gid)

    def hidden_nbytes(self, addrs) -> int:
        """Bytes of activation checkpoints riding these pages — the
        tiered pool adds them to its percolation parcel byte counts,
        since a checkpoint moves (and dies) with its page chain."""
        return sum(self._hidden[a.gid].nbytes for a in addrs
                   if a.gid in self._hidden)

    # -- device-side page content -------------------------------------
    def write_pages(self, rows: List[int], k_spans, v_spans) -> None:
        """One batched scatter of whole pages: spans are
        (L, len(rows), page_size, KV, D)."""
        kd = k_spans.astype(self.pages["k"].dtype)
        vd = v_spans.astype(self.pages["v"].dtype)
        if self.sharded:
            loc, slot = self._split_rows(rows)
            loc, slot = jnp.asarray(loc), jnp.asarray(slot)
            self.pages["k"] = _scatter_rows_sharded(
                self.pages["k"], loc, slot, kd)
            self.pages["v"] = _scatter_rows_sharded(
                self.pages["v"], loc, slot, vd)
        else:
            idx = jnp.asarray(rows, jnp.int32)
            self.pages["k"] = _scatter_rows(self.pages["k"], idx, kd)
            self.pages["v"] = _scatter_rows(self.pages["v"], idx, vd)

    def copy_page(self, src_row: int, dst_row: int) -> None:
        """COW: clone a page's contents under a fresh global name (the
        clone may land on a different shard — on a mesh that copy is a
        parcel; GSPMD lowers the cross-shard read for us)."""
        if self.sharded:
            (sl, ss), (dl, ds) = (self._split_rows([src_row]),
                                  self._split_rows([dst_row]))
            self.pages["k"] = _clone_row_sharded(
                self.pages["k"], jnp.int32(sl[0]), jnp.int32(ss[0]),
                jnp.int32(dl[0]), jnp.int32(ds[0]))
            self.pages["v"] = _clone_row_sharded(
                self.pages["v"], jnp.int32(sl[0]), jnp.int32(ss[0]),
                jnp.int32(dl[0]), jnp.int32(ds[0]))
        else:
            src = jnp.int32(src_row)
            dst = jnp.int32(dst_row)
            self.pages["k"] = _clone_row(self.pages["k"], src, dst)
            self.pages["v"] = _clone_row(self.pages["v"], src, dst)
        self.cow_copies += 1
        self.trace.instant("kvcache", "cow_copy", src_row=src_row,
                           dst_row=dst_row)

    # -- locality failure / elastic membership (DESIGN.md §4g) --------
    def active_shards(self) -> List[int]:
        """Device shards currently accepting placement (not retired)."""
        return [l for l in range(self.n_shards)
                if self.agas.is_active(l)]

    def note_page_write(self, addr: GlobalAddress) -> None:
        """Hook: `addr` is about to receive an in-place decode write.

        Decode appends are the ONLY mutation of an existing page
        (attach/begin_chunk scatter into fresh pages; shared pages get
        the null row), so this is the one place a retained host-tier
        copy of a device page goes stale.  Single-tier pools retain no
        copies — no-op; the tiered pool invalidates its shadow."""

    def _rebuild_page(self, addr: GlobalAddress) -> bool:
        """Try to rebuild a dead locality's page on a surviving shard.
        The untiered pool holds no second copy of anything: False —
        the page is lost and its request re-prefills."""
        return False

    def _forget_dead_page(self, gid: int) -> None:
        """Hook: tier/staging bookkeeping for a page lost with its
        locality (the tiered pool drops any staged copy)."""

    def _drop_cold(self, gid: int) -> None:
        # refcount-0 residents only exist under the tiered pool's
        # cold-retention policy; the base pool frees at zero
        raise AssertionError(
            f"refcount-0 resident {gid} in an untiered pool")

    def kill_locality(self, locality: int) -> set:
        """Simulate the loss of one device shard (DESIGN.md §4g).

        The AGAS directory retires the locality — allocation,
        migration targets and least-loaded placement skip it until a
        later `activate` re-joins it — and every page homed there is
        swept: cold-retained prefix pages are dropped (nobody holds
        them), referenced pages are rebuilt on a surviving shard when
        a host-tier copy exists (`_rebuild_page`, tiered pools), and
        the rest are LOST — purged from the prefix index through
        `_purge_index` and freed.  Returns the lost gids: the serving
        engine drains every slot/snapshot referencing one and
        re-admits its request for re-prefill.  Block tables are NOT
        touched here; callers must drain and then `refresh_tables`.
        """
        if not 0 <= locality < self.n_shards:
            raise ValueError(f"no device shard {locality}")
        if not self.agas.is_active(locality):
            return set()
        self.agas.deactivate(locality)
        self.localities_killed += 1
        lost: set = set()
        rebuilt = 0
        for gid in sorted(self.agas.residents(locality)):
            if not self.agas.resident_on(gid, locality):
                continue      # a rebuild's own eviction moved/dropped it
            addr = GlobalAddress(gid, self.agas.space)
            if self._refs.get(gid, 0) == 0:
                self._drop_cold(gid)
                continue
            if self._rebuild_page(addr):
                rebuilt += 1
                continue
            del self._refs[gid]
            self._purge_index(gid)
            self._forget_dead_page(gid)
            self.agas.free(addr)
            lost.add(gid)
        self.pages_rebuilt += rebuilt
        self.pages_lost += len(lost)
        self.trace.instant("kvcache", "kill_locality",
                           locality=locality, rebuilt=rebuilt,
                           lost=len(lost))
        return lost

    def plan_evacuation(self, locality: int
                        ) -> Dict[GlobalAddress, int]:
        """Every resident page off `locality` (planned retire).

        Unlike `plan_rebalance`, refcounts don't gate movability — a
        retiring shard takes everything with it, so everything must
        move (block tables are one `refresh_tables` away either way).
        Raises `PageExhausted` when the surviving active shards cannot
        hold the residents; nothing is committed in that case.
        """
        dsts = [l for l in self.active_shards() if l != locality]
        if not dsts:
            raise PageExhausted(
                f"cannot retire locality {locality}: no surviving "
                f"active shard")
        free = {l: self.agas.free_count(l) for l in dsts}
        moves: Dict[GlobalAddress, int] = {}
        for gid in sorted(self.agas.residents(locality)):
            dst = max(dsts, key=lambda l: (free[l], -l))
            if free[dst] <= 0:
                raise PageExhausted(
                    f"cannot retire locality {locality}: surviving "
                    f"shards have no free rows")
            moves[GlobalAddress(gid, self.agas.space)] = dst
            free[dst] -= 1
        return moves

    # -- inter-shard page migration (DESIGN.md §4c) -------------------
    def plan_rebalance(self, tolerance: int
                       ) -> Dict[GlobalAddress, int]:
        """Moves that bring per-shard page counts within `tolerance`.

        Only movable pages (refcount == 1) migrate: a prefix-shared
        page stays pinned to its owner, so every block table pointing
        at it stays one refresh away from consistency.  Moves are
        simulated in commit (gid) order against the per-shard free
        lists, so the returned dict is always feasible.  Retired
        shards (§4g) neither donate nor receive — a dead shard's empty
        pool must not read as "the emptiest target".
        """
        act = self.active_shards()
        if len(act) < 2:
            return {}
        all_used = self.shard_used()
        used = {l: all_used[l] for l in act}
        free = {l: self.pages_per_shard - used[l] for l in act}
        movable = {l: sorted(g for g in self.agas.residents(l)
                             if self._refs.get(g, 0) == 1)
                   for l in act}
        moves: Dict[GlobalAddress, int] = {}
        while True:
            hi = max(act, key=lambda l: (used[l], -l))
            lo = min(act, key=lambda l: (used[l], l))
            if used[hi] - used[lo] <= max(int(tolerance), 1):
                break
            if free[lo] <= 0 or not movable[hi]:
                break
            gid = movable[hi].pop(0)
            moves[GlobalAddress(gid, self.agas.space)] = lo
            used[hi] -= 1
            used[lo] += 1
            free[hi] += 1
            free[lo] -= 1
        return moves

    def plan_rotation(self) -> Dict[GlobalAddress, int]:
        """Every movable page to the next ACTIVE shard (round-robin):
        the forced-migration drill that verifies a page's global name —
        and therefore every request's output — survives relocation.
        Feasibility is simulated in gid order, matching the order
        `migration_plan` commits moves in."""
        act = self.active_shards()
        if len(act) < 2:
            return {}
        nxt = {l: act[(i + 1) % len(act)] for i, l in enumerate(act)}
        all_used = self.shard_used()
        free = {l: self.pages_per_shard - all_used[l] for l in act}
        moves: Dict[GlobalAddress, int] = {}
        where = {g: l for l in act for g in self.agas.residents(l)}
        for gid in sorted(where):
            if self._refs.get(gid, 0) != 1:
                continue
            src = where[gid]
            dst = nxt[src]
            if dst == src or free[dst] <= 0:
                continue
            moves[GlobalAddress(gid, self.agas.space)] = dst
            free[dst] -= 1
            free[src] += 1
        return moves

    def migrate_pages(self, moves: Dict[GlobalAddress, int]
                      ) -> MigrationPlan:
        """Migrate pages between shards: the AGAS directory commits the
        (locality, slot) updates — global names unchanged — and the
        payload permutation is lowered through
        `core/parcels.migration_plan` into ppermute legs, executed with
        `lax.ppermute` under `shard_map` when the pool is mesh-backed
        and as one gather-before-scatter row permutation of the same
        legs on a single device."""
        with self.trace.span("kvcache", "migrate_pages", kind="parcel",
                             moves=len(moves)) as sp:
            plan = migration_plan(self.agas, moves)
            if plan.moves:
                if self.mesh is not None:
                    self._apply_plan_mesh(plan)
                else:
                    self._apply_plan_flat(plan)
                self.page_migrations += len(plan.moves)
            sp.args["gids"] = [m[0] for m in plan.moves]
        return plan

    def _apply_plan_flat(self, plan: MigrationPlan) -> None:
        # only reachable sharded: a 1-shard pool has no inter-locality
        # moves, so migration_plan always returns an empty plan there.
        # Moves are padded to a canonical power-of-two count with
        # null-row self-copies, so `_permute_rows_sharded` compiles
        # once per size class, not once per exact move count.
        pad = canonical_size(len(plan.moves))
        self._mig_sizes.add(pad)
        args = tuple(jnp.asarray(a) for a in plan_move_arrays(
            plan, pad_to=pad, pad_move=(0, self.null_row)))
        self.pages["k"] = _permute_rows_sharded(self.pages["k"], *args)
        self.pages["v"] = _permute_rows_sharded(self.pages["v"], *args)

    def _mesh_plan_fn(self, perms: tuple):
        """The compiled ppermute program for one leg structure.

        `perms` (the per-leg (src, dst) pairs) must be compile-time
        constants — they become the ppermute wiring — but the slot
        indices are TRACED operands, so every plan with the same leg
        structure reuses one cached program: repeated drills and
        rebalances stop paying a recompile per call (DESIGN.md §9.4).
        """
        fn = self._mig_cache.get(perms)
        if fn is not None:
            return fn
        from jax import lax
        from jax.sharding import PartitionSpec as P
        spec = P(None, self.kv_axis, None, None, None, None)
        axis = self.kv_axis

        def body(cur, gs, ss, recv):
            i = lax.axis_index(axis)
            orig = cur                   # pre-plan snapshot
            for leg, perm in enumerate(perms):
                payload = jnp.take(orig[:, 0], gs[leg, i], axis=1)
                got = lax.ppermute(payload, axis, perm)
                cur = jnp.where(recv[leg, i],
                                cur.at[:, 0, ss[leg, i]].set(got), cur)
            return cur

        fn = jax.jit(jax.shard_map(body, mesh=self.mesh,
                                   in_specs=(spec, P(), P(), P()),
                                   out_specs=spec, check_vma=False))
        self._mig_cache[perms] = fn
        return fn

    def _apply_plan_mesh(self, plan: MigrationPlan) -> None:
        """Execute a plan's legs as `lax.ppermute` between devices.

        Every leg gathers its payloads from a snapshot of the pre-plan
        array (`orig`), so in-plan src/dst aliasing across legs cannot
        clobber a payload before it is read — the same snapshot
        semantics the flat lowering gets from gather-before-scatter.
        """
        perms = tuple(tuple(p) for p in plan.lowering.perms)
        gs = jnp.asarray(np.stack(plan.lowering.gather_slots))
        ss = jnp.asarray(np.stack(plan.lowering.scatter_slots))
        recv = np.zeros((len(perms), self.n_shards), bool)
        for leg, perm in enumerate(perms):
            for _, d in perm:
                recv[leg, d] = True
        recv = jnp.asarray(recv)
        fn = self._mesh_plan_fn(perms)
        self.pages["k"] = fn(self.pages["k"], gs, ss, recv)
        self.pages["v"] = fn(self.pages["v"], gs, ss, recv)


@dataclasses.dataclass
class _SlotState:
    addrs: List[GlobalAddress]
    length: int                      # tokens stored = abs position clock
    # running blake2b prefix chain for chunked prefill: hashes exactly
    # the tokens already resident, so each chunk hashes only its own
    # tokens instead of re-walking the prefix (None = not chunking)
    chain: Optional[Any] = None


@dataclasses.dataclass
class PrefixCover:
    """The longest cached prefix run of a prompt layout (DESIGN.md
    §4e): `keys` are the covered pages' chain keys (each currently a
    live radix root-path hit), `covered` the layout rows they hold.  `full` means
    every page of the prompt hit AND the final page carries an
    activation checkpoint (`hidden`, the post-norm last-position
    hidden state) — the prompt can admit straight to decode with zero
    prefill compute.  Partial covers are page-aligned by construction
    (a partially-filled page key can only ever be a prompt's final
    page, so matching one implies a full cover), which is exactly
    what lets chunked prefill resume at `covered`."""

    covered: int
    keys: List[Tuple[bytes, int]]
    full: bool
    hidden: Optional[np.ndarray] = None


@dataclasses.dataclass
class KVSnapshot:
    """A preempted slot's KV, written back to the host tier
    (DESIGN.md §4d).  Holds one refcount on every page — the pages'
    global names — plus the position clock and the chunked-prefill
    hash chain, so `PagedKVCache.restore_slot` rebuilds the slot
    exactly as preemption found it: re-admission resumes decoding (or
    mid-prompt chunking) without re-running prefill."""

    addrs: List[GlobalAddress]
    length: int
    chain: Optional[Any] = None


class PagedKVCache:
    """Per-slot block tables over a shared PagePool.

    Every decode slot carries its own position counter (`lengths`) —
    the per-slot clock that replaces the dense cache's shared
    ``len/cursor/abs`` triple — and a block table row mapping its token
    positions onto physical page rows.
    """

    def __init__(self, cfg: ArchConfig, slots: int, max_len: int,
                 n_pages: int, page_size: int, dtype=None, *,
                 n_shards: int = 1, mesh=None, kv_axis: str = "kv",
                 host_pages: int = 0, tracer=None,
                 pin_threshold: int = 4):
        if host_pages > 0:
            from repro.serving.tiering import TieredPagePool
            self.pool: PagePool = TieredPagePool(
                cfg, n_pages, page_size, dtype, n_shards=n_shards,
                mesh=mesh, kv_axis=kv_axis, host_pages=host_pages,
                tracer=tracer, pin_threshold=pin_threshold)
        else:
            self.pool = PagePool(cfg, n_pages, page_size, dtype,
                                 n_shards=n_shards, mesh=mesh,
                                 kv_axis=kv_axis, tracer=tracer,
                                 pin_threshold=pin_threshold)
        self.trace = self.pool.trace
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.max_pages_slot = -(-self.max_len // page_size)
        null = self.pool.null_row
        self.tables = np.full((slots, self.max_pages_slot), null,
                              np.int32)
        self.lengths = np.zeros(slots, np.int32)
        self.write_rows = np.full(slots, null, np.int32)
        self.write_offs = np.zeros(slots, np.int32)
        self._state: List[_SlotState] = [
            _SlotState([], 0) for _ in range(slots)]

    # -- admission-time accounting ------------------------------------
    def pages_needed(self, tokens: np.ndarray, pad: int = 0) -> int:
        """Fresh pages a prefill would allocate (prefix hits excluded)."""
        ps = self.pool.page_size
        return sum(self.pool.page_cost(key)
                   for key in page_keys(tokens, ps, pad))

    def pages_needed_chunk(self, tokens: np.ndarray,
                           start: int, end: int, pad: int = 0) -> int:
        """Fresh pages one chunk [start, end) would allocate.

        The chain keys are computed over the full prefix up to `end`,
        so a chunk boundary never changes a page's identity: chunked
        and whole-prompt prefills of the same layout hash to the same
        pages (prefix sharing works across the two paths).
        """
        ps = self.pool.page_size
        keys = page_keys(tokens[:end], ps, pad)[start // ps:]
        return sum(self.pool.page_cost(key) for key in keys)

    # -- prefill attach ------------------------------------------------
    def attach(self, slot: int, tokens: np.ndarray,
               k, v, pad: int = 0) -> int:
        if not self.trace.enabled:
            return self._attach(slot, tokens, k, v, pad)
        with self.trace.span("kvcache", "attach", kind="pages",
                             slot=slot) as sp:
            covered = self._attach(slot, tokens, k, v, pad)
            sp.args["gids"] = [a.gid for a in self._state[slot].addrs]
            sp.args["covered"] = covered
            return covered

    def _attach(self, slot: int, tokens: np.ndarray,
                k, v, pad: int = 0) -> int:
        """Install a prefilled prompt layout into `slot`.

        k/v: (L, S, KV, D) KV for the full layout (the engines attach
        pad-free layouts, so S is the real prompt length).  Shared
        pages (prefix-hash hits) are reused by refcount instead of
        rewritten.  Returns the covered-token count of the longest
        cached prefix run (leading pages served by hits) — the memory
        the prefix cache saved, and the span compute skip could have
        skipped (DESIGN.md §4e).
        """
        ps = self.pool.page_size
        s = len(tokens)
        if s > self.max_len:
            raise ValueError(f"prompt {s} exceeds max_len {self.max_len}")
        st = self._state[slot]
        assert not st.addrs, f"slot {slot} already attached"
        keys = page_keys(tokens, ps, pad)
        acquired: List[GlobalAddress] = []
        fresh: List[int] = []               # page indices to write
        fresh_gids: set = set()
        covered = 0
        leading = True
        try:
            for i, key in enumerate(keys):
                shared = self.pool.lookup_prefix(key)
                if shared is not None:
                    # incref first (pin, and into `acquired` so a
                    # failed promotion rolls it back), THEN promote: a
                    # spilled page being promoted must not be
                    # eviction's candidate
                    self.pool.incref(shared)
                    acquired.append(shared)
                    self.pool.ensure_device(shared)
                    self.pool.shares += 1
                    if leading:
                        covered += key[1]
                else:
                    leading = False
                    addr = self.pool.alloc()
                    self.pool.register_prefix(
                        key, addr,
                        parent=keys[i - 1][0] if i else None)
                    acquired.append(addr)
                    fresh.append(i)
                    fresh_gids.add(addr.gid)
        except PageExhausted:
            # rollback: only THIS call's fresh (never-written) pages
            # must bypass retention; shared hits hold valid content
            # and go back to the cache via plain decref
            for a in acquired:
                if a.gid in fresh_gids:
                    self.pool.discard(a)
                else:
                    self.pool.decref(a)
            raise
        if fresh:
            # one batched whole-page scatter (zero-padded tail on the
            # partial page — never read: masks stop at the clock)
            tail = len(keys) * ps - s
            kp = jnp.pad(k, ((0, 0), (0, tail), (0, 0), (0, 0))) \
                .reshape(k.shape[0], len(keys), ps, *k.shape[2:])
            vp = jnp.pad(v, ((0, 0), (0, tail), (0, 0), (0, 0))) \
                .reshape(v.shape[0], len(keys), ps, *v.shape[2:])
            fi = jnp.asarray(fresh, jnp.int32)
            self.pool.write_pages(
                [self.pool.row(acquired[i]) for i in fresh],
                kp[:, fi], vp[:, fi])
        st.addrs = acquired
        st.length = s
        self.lengths[slot] = s
        for i, a in enumerate(acquired):
            self.tables[slot, i] = self.pool.row(a)
        return covered

    # -- prefix-cache compute skip (DESIGN.md §4e) --------------------
    def covered_prefix(self, tokens: np.ndarray,
                       pad: int = 0) -> PrefixCover:
        """The longest cached prefix run of a prompt layout.

        One radix-tree walk (`RadixPrefixIndex.match`, O(prompt
        pages)): the longest leading run of the chained page keys
        forming a live root path — the walk also stamps the hit
        statistics that drive hot-prefix pinning.  A full-cover result
        additionally requires the final page's activation checkpoint;
        when the KV is all cached but the checkpoint is missing (the
        pages were attached by a path that never computed hidden
        states), the final page is dropped from the cover so a resumed
        chunk recomputes it — the cover is then page-aligned and
        strictly inside the prompt, exactly what `begin_chunk` needs
        to resume.
        """
        keys = page_keys(tokens, self.pool.page_size, pad)
        nodes = self.pool.prefix.match(keys)
        ck: List[Tuple[bytes, int]] = [n.key for n in nodes]
        covered = sum(k[1] for k in ck)
        if covered == len(tokens) and ck:
            hidden = self.pool.hidden_for(ck[-1])
            if hidden is not None:
                return PrefixCover(covered, ck, True, hidden)
            last = ck.pop()
            covered -= last[1]
        return PrefixCover(covered, ck, False)

    def attach_covered(self, slot: int, tokens: np.ndarray,
                       keys: List[Tuple[bytes, int]]) -> None:
        if not self.trace.enabled:
            return self._attach_covered(slot, tokens, keys)
        with self.trace.span("kvcache", "attach_covered", kind="pages",
                             slot=slot) as sp:
            self._attach_covered(slot, tokens, keys)
            sp.args["gids"] = [a.gid for a in self._state[slot].addrs]
            sp.args["covered"] = sum(k[1] for k in keys)

    def _attach_covered(self, slot: int, tokens: np.ndarray,
                        keys: List[Tuple[bytes, int]]) -> None:
        """Install a covered prefix's cached pages into `slot` with
        ZERO prefill compute and zero KV writes: every key must
        currently hit the prefix index (the caller just computed the
        cover).  The slot is left exactly as a prefill of the covered
        span would have left it — block table and position clock — so
        `begin_chunk` resumes at the cover's end, or decode starts
        immediately on a full cover.  Atomic under PageExhausted
        (promoting a spilled page may need a device row, and a
        promotion-triggered cold drop can even evict a not-yet-pinned
        covered page): on failure every acquired page returns to the
        cache and the caller retries later.
        """
        st = self._state[slot]
        assert not st.addrs, f"slot {slot} already attached"
        pool = self.pool
        acquired: List[GlobalAddress] = []
        try:
            for key in keys:
                shared = pool.lookup_prefix(key)
                if shared is None:
                    raise PageExhausted(
                        "covered prefix page vanished before attach "
                        "(cold drop under promotion pressure)")
                pool.incref(shared)             # pin, then promote
                acquired.append(shared)
                pool.ensure_device(shared)
                pool.shares += 1
        except PageExhausted:
            for a in acquired:
                pool.decref(a)
            raise
        covered = sum(k[1] for k in keys)
        st.addrs = acquired
        st.length = covered
        self.lengths[slot] = covered
        for i, a in enumerate(acquired):
            self.tables[slot, i] = pool.row(a)

    def store_hidden_chunk(self, slot: int, start: int, end: int,
                           boundary: np.ndarray,
                           last: np.ndarray) -> None:
        """Checkpoint the page-boundary activations of chunk
        [start, end): ``boundary[j]`` is the post-norm hidden at
        chunk-local position ``(j + 1) * ps - 1``, ``last`` the hidden
        at ``end - 1`` (the partial final page of a prompt's last
        chunk).  First write wins (`PagePool.store_hidden`)."""
        ps = self.pool.page_size
        st = self._state[slot]
        base = start // ps
        for j in range(-(-(end - start) // ps)):
            addr = st.addrs[base + j]
            if start + (j + 1) * ps <= end:
                self.pool.store_hidden(addr, boundary[j])
            else:
                self.pool.store_hidden(addr, last)

    def store_hidden_prefill(self, slot: int, real: int,
                             boundary: np.ndarray,
                             last: np.ndarray) -> None:
        """Checkpoint a whole-prompt prefill's page-boundary
        activations — exactly the chunk case starting at 0 (attach
        created one addr per page of ``real``)."""
        self.store_hidden_chunk(slot, 0, real, boundary, last)

    # -- chunked prefill (DESIGN.md §4b) ------------------------------
    def begin_chunk(self, slot: int, tokens: np.ndarray,
                    start: int, end: int, pad: int = 0,
                    locality: Optional[int] = None
                    ) -> Tuple[List[int], int]:
        if not self.trace.enabled:
            return self._begin_chunk(slot, tokens, start, end, pad,
                                     locality)
        with self.trace.span("kvcache", "chunk_attach", kind="pages",
                             slot=slot, start=start, end=end) as sp:
            rows, covered = self._begin_chunk(slot, tokens,
                                              start, end, pad,
                                              locality)
            ps = self.pool.page_size
            base = start // ps
            sp.args["gids"] = [a.gid for a in
                               self._state[slot].addrs[base:]]
            return rows, covered

    def _begin_chunk(self, slot: int, tokens: np.ndarray,
                     start: int, end: int, pad: int = 0,
                     locality: Optional[int] = None
                     ) -> Tuple[List[int], int]:
        """Acquire the pages covering chunk [start, end) of a chunked
        prefill and install them in `slot`'s block table.

        `start` must be page-aligned and equal the slot's resident
        length (chunks arrive in order); `end` is page-aligned except
        on the prompt's final chunk, which may leave the last page
        partially filled — the slot holds that partial page between
        the chunk and its first decode write.  Prefix-shared pages are
        reused by refcount.  Returns ``(rows, covered)``: one physical
        write row per page of the chunk, with the pool's null row
        substituted for shared pages so the compiled scatter cannot
        clobber shared content, and the covered-token count of the
        chunk's leading run of prefix hits (DESIGN.md §4e telemetry).
        Atomic under PageExhausted: either every page of the chunk is
        acquired or none (the caller preempts a victim and retries).
        """
        ps = self.pool.page_size
        st = self._state[slot]
        if start % ps:
            raise ValueError(f"chunk start {start} not page-aligned")
        if start != st.length:
            raise ValueError(
                f"slot {slot}: chunk starts at {start} but {st.length} "
                f"tokens are resident")
        if end > self.max_len:
            raise ValueError(f"chunk end {end} exceeds {self.max_len}")
        # extend the slot's running prefix chain (committed only on
        # success, so a PageExhausted retry re-hashes just this chunk);
        # digests match page_keys over the whole layout exactly —
        # `_chain_extend` replays the identical per-page updates
        if st.chain is not None:
            chain = st.chain.copy()
        else:                        # resident tokens came via attach()
            chain = _chain_seed(tokens, start, ps, pad)
        # the radix parent of this chunk's first page: the digest of
        # the slot's resident prefix (root when the chunk starts the
        # prompt — the chain then holds only the pad-count seed, which
        # no node owns)
        prev = chain.digest() if start else None
        keys = _chain_extend(chain, tokens, start, end, ps, pad)
        acquired: List[GlobalAddress] = []
        rows: List[int] = []
        fresh_gids: set = set()
        covered = 0
        leading = True
        try:
            for key in keys:
                shared = self.pool.lookup_prefix(key)
                if shared is not None:
                    self.pool.incref(shared)        # pin, then promote
                    acquired.append(shared)
                    self.pool.ensure_device(shared)
                    self.pool.shares += 1
                    rows.append(self.pool.null_row)
                    if leading:
                        covered += key[1]
                else:
                    leading = False
                    # placement preference (§4f): a dispatched chunk
                    # allocates at its prefill worker's locality, so
                    # the prefix pages it registers make that worker
                    # the owner the NEXT matching prompt dispatches
                    # to.  Soft: an exhausted preferred shard falls
                    # back to the default least-loaded policy rather
                    # than preempting while other shards have room.
                    # a retired hint (§4g) falls back too: allocating
                    # on a dead shard would raise, and the resulting
                    # PageExhausted would read as pool pressure
                    loc = locality
                    if loc is not None and (
                            not self.pool.agas.is_active(loc)
                            or self.pool.agas.free_count(loc) == 0):
                        loc = None
                    addr = self.pool.alloc(loc)
                    self.pool.register_prefix(key, addr, parent=prev)
                    acquired.append(addr)
                    fresh_gids.add(addr.gid)
                    rows.append(self.pool.row(addr))
                prev = key[0]
        except PageExhausted:
            # fresh (unwritten) pages bypass retention; shared hits
            # return to the prefix cache with their content intact
            for a in acquired:
                if a.gid in fresh_gids:
                    self.pool.discard(a)
                else:
                    self.pool.decref(a)
            raise
        base = start // ps
        for i, a in enumerate(acquired):
            st.addrs.append(a)
            self.tables[slot, base + i] = self.pool.row(a)
        st.chain = chain
        st.length = end
        self.lengths[slot] = end
        return rows, covered

    # -- decode-step bookkeeping --------------------------------------
    def prepare_decode(self, slot: int) -> None:
        """Reserve the write target for this slot's next token.

        Allocates a fresh page at page boundaries; clones (COW) a
        shared page before the first divergent append.  Idempotent, so
        the engine can retry after preempting a victim on
        PageExhausted.
        """
        st = self._state[slot]
        ps = self.pool.page_size
        pos = st.length
        page_idx, off = divmod(pos, ps)
        if page_idx >= self.max_pages_slot:
            raise RuntimeError(
                f"slot {slot} overflows max_len {self.max_len}")
        if page_idx == len(st.addrs):
            addr = self.pool.alloc()
            st.addrs.append(addr)
        else:
            addr = st.addrs[page_idx]
            if self.pool.refcount(addr) > 1:
                fresh = self.pool.alloc()
                self.pool.copy_page(self.pool.row(addr),
                                    self.pool.row(fresh))
                self.pool.decref(addr)
                st.addrs[page_idx] = fresh
                addr = fresh
        # the write target mutates in place: any retained host-tier
        # copy of it is stale from here on (DESIGN.md §4g)
        self.pool.note_page_write(addr)
        row = self.pool.row(addr)
        self.tables[slot, page_idx] = row
        self.write_rows[slot] = row
        self.write_offs[slot] = off

    def needs_alloc(self, slot: int) -> bool:
        """Will this slot's next prepare_decode take a page from the
        pool?  True at page boundaries (fresh page) and on shared
        partial pages (COW clone) — the admission watermark."""
        st = self._state[slot]
        page_idx, _ = divmod(st.length, self.pool.page_size)
        if page_idx >= len(st.addrs):
            return True
        return self.pool.refcount(st.addrs[page_idx]) > 1

    def advance(self, slot: int) -> None:
        st = self._state[slot]
        st.length += 1
        self.lengths[slot] = st.length

    def release(self, slot: int) -> None:
        st = self._state[slot]
        if self.trace.enabled and st.addrs:
            self.trace.instant("kvcache", "release", slot=slot,
                               gids=[a.gid for a in st.addrs])
        for a in st.addrs:
            self.pool.decref(a)
        st.addrs = []
        st.length = 0
        st.chain = None
        null = self.pool.null_row
        self.tables[slot, :] = null
        self.lengths[slot] = 0
        self.write_rows[slot] = null
        self.write_offs[slot] = 0

    def drain_slot(self, slot: int, lost: set) -> None:
        """Release a slot some of whose pages died with their locality
        (DESIGN.md §4g): surviving pages decref normally; lost gids
        were already swept out of the pool by `kill_locality`, so the
        refcount this slot held on them died with the page and must
        NOT be returned again.  The slot is left empty for
        re-admission (its request re-prefills from the retained
        prompt + generated tokens)."""
        st = self._state[slot]
        if self.trace.enabled and st.addrs:
            self.trace.instant("kvcache", "drain_slot", slot=slot,
                               gids=[a.gid for a in st.addrs])
        for a in st.addrs:
            if a.gid not in lost:
                self.pool.decref(a)
        st.addrs = []
        st.length = 0
        st.chain = None
        null = self.pool.null_row
        self.tables[slot, :] = null
        self.lengths[slot] = 0
        self.write_rows[slot] = null
        self.write_offs[slot] = 0

    # -- prefill->decode handoff (DESIGN.md §4f) ----------------------
    def detach_slot(self, slot: int) -> Optional[KVSnapshot]:
        """Detach a slot's KV into a snapshot WITHOUT moving a page —
        the §4f handoff unit between a prefill worker and a decode
        worker.

        The snapshot keeps the slot's refcount on every page: the
        pages' global names are the handoff currency, and because
        both roles address the same AGAS directory no byte needs to
        move when the pages are already device-resident (a multi-host
        transport would stage the copy here; the tiered restore path
        commits it).  `restore_slot` rebuilds the receiving slot —
        block table, position clock, chunked-prefill hash chain —
        exactly as detach left it, mid-prefill chunk boundaries
        included.  Returns None for an empty slot."""
        st = self._state[slot]
        if not st.addrs:
            return None
        if self.trace.enabled:
            self.trace.instant("kvcache", "detach", slot=slot,
                               gids=[a.gid for a in st.addrs])
        snap = KVSnapshot(list(st.addrs), st.length,
                          st.chain.copy() if st.chain is not None
                          else None)
        st.addrs = []
        st.length = 0
        st.chain = None
        null = self.pool.null_row
        self.tables[slot, :] = null
        self.lengths[slot] = 0
        self.write_rows[slot] = null
        self.write_offs[slot] = 0
        return snap

    # -- percolation: offload / restore (DESIGN.md §4d) ---------------
    def offload_slot(self, slot: int) -> Optional[KVSnapshot]:
        st = self._state[slot]
        if not self.trace.enabled or not st.addrs:
            return self._offload_slot(slot)
        with self.trace.span("kvcache", "offload_slot", kind="copy",
                             slot=slot,
                             gids=[a.gid for a in st.addrs]) as sp:
            snap = self._offload_slot(slot)
            sp.args["offloaded"] = snap is not None
            return snap

    def _offload_slot(self, slot: int) -> Optional[KVSnapshot]:
        """Write back a preempted slot's KV to the host tier instead
        of freeing it.

        Exclusively-owned pages demote to host as one copy parcel;
        prefix-shared pages stay on device, pinned by their other
        holders — either way the snapshot keeps this slot's refcount
        on every page.  Returns None when the pool is untiered or the
        host tier cannot hold the write-back (the caller falls back to
        `release` + re-prefill).  The slot is left empty and reusable.
        """
        pool = self.pool
        st = self._state[slot]
        if not getattr(pool, "tiered", False) or not st.addrs:
            return None
        if pool.offload_pages(st.addrs, key=("offload", slot,
                                             st.length)) is None:
            return None
        snap = KVSnapshot(list(st.addrs), st.length,
                          st.chain.copy() if st.chain is not None
                          else None)
        st.addrs = []
        st.length = 0
        st.chain = None
        null = pool.null_row
        self.tables[slot, :] = null
        self.lengths[slot] = 0
        self.write_rows[slot] = null
        self.write_offs[slot] = 0
        return snap

    def restore_pages_needed(self, snap: KVSnapshot) -> int:
        """Device rows restoring this snapshot will consume (its
        host-resident pages; device-resident shared ones are free)."""
        return sum(1 for a in snap.addrs
                   if not self.pool.on_device(a))

    def stage_restore(self, key: Any, snap: KVSnapshot) -> bool:
        """Begin the host->device copy of a snapshot's pages NOW
        (double-buffered), so a later `restore_slot` commits a copy
        that already ran under compute."""
        return self.pool.stage_promote(key, snap.addrs)

    def restore_slot(self, slot: int, snap: KVSnapshot,
                     staged_key: Any = None) -> None:
        if not self.trace.enabled:
            return self._restore_slot(slot, snap, staged_key)
        with self.trace.span("kvcache", "restore", kind="pages",
                             slot=slot,
                             gids=[a.gid for a in snap.addrs]):
            return self._restore_slot(slot, snap, staged_key)

    def _restore_slot(self, slot: int, snap: KVSnapshot,
                      staged_key: Any = None) -> None:
        """Re-admit an offloaded request: promote its pages back to
        device (using the staged payload when one matches) and rebuild
        the slot — block table, position clock, hash chain — exactly
        as preemption left it.  Raises `PageExhausted` (snapshot still
        valid, retry later) when the device tier cannot hold it."""
        st = self._state[slot]
        assert not st.addrs, f"slot {slot} already attached"
        # untiered pools never have an off-device page (handoff
        # snapshots restore through this path too, DESIGN.md §4f)
        if getattr(self.pool, "tiered", False):
            self.pool.promote_pages(snap.addrs, staged_key=staged_key)
        st.addrs = list(snap.addrs)
        st.length = snap.length
        st.chain = snap.chain.copy() if snap.chain is not None else None
        self.lengths[slot] = snap.length
        for i, a in enumerate(st.addrs):
            self.tables[slot, i] = self.pool.row(a)

    def drop_snapshot(self, snap: KVSnapshot,
                      lost: Optional[set] = None) -> None:
        """Release a snapshot's refcounts (its request finished or
        failed while still queued) — host-resident pages free their
        host rows; prefix-owned ones may be retained cold.  `lost`
        (a dead locality's swept gids, §4g) are skipped: the refcount
        the snapshot held on them died with the page."""
        for a in snap.addrs:
            if lost is None or a.gid not in lost:
                self.pool.decref(a)
        snap.addrs = []

    def prefetch_chunk(self, slot: int, tokens: np.ndarray,
                       start: int, end: int, pad: int = 0) -> int:
        """Stage the promotion of any spilled prefix pages chunk
        [start, end) will share — percolation ahead of the chunk that
        needs them.  Returns pages staged (best effort: the double
        buffer may be full).

        Hashes only [start, end) by extending a copy of the slot's
        running chain (the begin_chunk trick), and bails immediately
        when nothing lives on the host tier — the common no-spill case
        costs one integer compare, not a prefix walk.
        """
        pool = self.pool
        if not getattr(pool, "tiered", False) or pool.host_used == 0:
            return 0
        ps = pool.page_size
        st = self._state[slot]
        if st.chain is not None:
            chain = st.chain.copy()
        else:
            chain = _chain_seed(tokens, start, ps, pad)
        staged = 0
        for key in _chain_extend(chain, tokens, start, end, ps, pad):
            addr = pool.lookup_prefix(key)
            if addr is not None and not pool.on_device(addr):
                if pool.stage_promote(("page", addr.gid), [addr]):
                    staged += 1
        return staged

    # -- inter-shard migration (DESIGN.md §4c) ------------------------
    def refresh_tables(self) -> None:
        """Re-resolve every block-table entry from the AGAS directory.

        After a migration a page's global name is unchanged but its
        (locality, slot) — and therefore its flat row — is not; one
        directory walk restores table consistency.  Write rows are NOT
        refreshed here: `prepare_decode` recomputes them before every
        decode write and `begin_chunk` returns fresh rows per chunk, so
        migration between steps can never race a stale write target.
        """
        for slot, st in enumerate(self._state):
            for i, a in enumerate(st.addrs):
                self.tables[slot, i] = self.pool.row(a)

    def migrate(self, moves: Dict[GlobalAddress, int]) -> int:
        """Migrate pages and restore table consistency; returns the
        number of pages actually moved."""
        plan = self.pool.migrate_pages(moves)
        if plan.moves:
            self.refresh_tables()
        return len(plan.moves)

    def maybe_rebalance(self, tolerance: int) -> int:
        """Imbalance-triggered migration: when per-shard page counts
        drift more than `tolerance` apart, move movable pages from the
        fullest shard to the emptiest (between engine steps)."""
        used = self.pool.shard_used()
        if max(used) - min(used) <= max(int(tolerance), 1):
            return 0
        return self.migrate(self.pool.plan_rebalance(tolerance))

    # -- the compiled-step view ---------------------------------------
    def batch_inputs(self) -> Dict[str, Any]:
        """Fixed-shape arrays for decode_step_paged (one compile).

        Built from host copies: a transfer may still read its numpy
        source after dispatch returns (on the CPU the device array can
        alias it outright, and `jnp.array` copies on the device, after
        that read), while the engine advances these tables in place."""
        return {
            "block_tables": jnp.asarray(self.tables.copy()),
            "positions": jnp.asarray(self.lengths.copy()),
            "write_rows": jnp.asarray(self.write_rows.copy()),
            "write_offs": jnp.asarray(self.write_offs.copy()),
        }
