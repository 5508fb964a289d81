"""Paged KV: a refcounted block allocator over a preallocated KV arena.

The serving path historically stored KV per request/cache-entry as one
CONTIGUOUS ``max_seq`` row. That shape is what the compiled executables
want, but it is brutal at rest: every prefix-cache entry pins a full
``max_seq`` row of HBM (~1 GB for llama3-8b bf16 at 8k — see the sizing
note in ``tpu/device.py``) even when the cached conversation is 300
tokens, an exact/LCP hit duplicates the whole row again, and admission
is all-or-nothing (a free "slot" implicitly owns ``max_seq`` worth of
cache).

This module replaces the at-rest unit with fixed-size TOKEN BLOCKS
carved from one preallocated arena (vLLM's PagedAttention storage
model, scoped to this engine's executables):

- a :class:`BlockPool` hands out block ids with REFCOUNTS, so the
  prefix cache becomes copy-free block aliasing — exact and LCP partial
  hits share blocks instead of copying rows, and a stored conversation
  aliases the prefix blocks it extends;
- COPY-ON-WRITE: extending a sequence whose boundary block is shared
  first copies that one block, never the row;
- cached entries are LRU-EVICTED under the arena budget the moment live
  traffic needs blocks — the cache yields to admission, block by block,
  instead of a whole-row all-or-nothing;
- free-list/refcount accounting is exposed to introspection
  (``GET /admin/engine`` ``kv_blocks``) and metrics
  (``gofr_tpu_kv_blocks{state}``, ``gofr_tpu_kv_evictions_total``).

Two arenas implement the storage side:

- :class:`HostTokenArena` — the echo runner's "KV" is the token ids
  themselves, so the whole allocator/aliasing/admission path runs
  compile-free in tier-1 (and :class:`HostPagedKV` is the engine the
  echo runner drives it through);
- :class:`JaxKVArena` — device-side block storage
  ``[layers, n_blocks, kv_heads, block_tokens, head_dim]`` with jitted
  scatter/gather between block tables and the contiguous rows the
  compiled prefill/decode executables consume. Compute still runs on
  gathered contiguous rows (bit-identity with the slot model is a hard
  requirement; block-native attention is a roadmap item), so the paged
  win on device is at-rest residency, store-path copy volume, and
  block-granular admission — not hit-time gather bytes.

``jax`` is imported lazily (inside :class:`JaxKVArena` only): the host
side must stay importable in no-JAX contexts.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Optional

import numpy as np


class KVExhausted(RuntimeError):
    """No free KV blocks (and nothing evictable): the caller's request
    cannot be admitted now. The decode pool has it wait for a pooled row
    to finish (``exhausted_rejects`` counts the failed reservation all the
    same); where none holds the budget, and on the echo runner, decode
    falls back to the solo path and the rejection is accounted as
    ``pool_reject{reason="kv_exhausted"}``."""


class ForeignKVRejected(RuntimeError):
    """A transferred (cross-replica) KV payload failed SEMANTIC
    verification on ingest — the wire checksums passed but the content
    does not describe the prompt being admitted. The receiver falls
    back to local prefill; nothing was installed."""


def blocks_for(tokens: int, block_tokens: int) -> int:
    """Blocks needed to hold ``tokens`` tokens (ceil division)."""
    return (max(int(tokens), 0) + block_tokens - 1) // block_tokens


def lcp_scan(items: list, ids: np.ndarray, limit: int,
             min_shared: int) -> tuple:
    """Longest-common-token-prefix donor among cached sequences — the
    ONE scan both paged engines use (host echo and the device prefix
    store; thresholds differ, the loop must not). ``items`` is
    ``BlockPool.cache_items()`` output; keys are int32 token bytes.
    Returns ``(shared_tokens, key, entry)`` or ``(0, None, None)`` when
    nothing clears ``max(min_shared, 1)``. Linear scan: the cache holds
    tens of entries and one vector compare per entry is nanoseconds
    against the prefill a hit saves."""
    best_shared, best_key, best_entry = 0, None, None
    for key, entry in items:
        cand = np.frombuffer(key, dtype=np.int32)
        n = min(cand.size, limit)
        if n <= best_shared:
            continue
        neq = np.nonzero(cand[:n] != ids[:n])[0]
        shared = int(neq[0]) if neq.size else n
        if shared > best_shared:
            best_shared, best_key, best_entry = shared, key, entry
    if best_entry is None or best_shared < max(min_shared, 1):
        return 0, None, None
    return best_shared, best_key, best_entry


class BlockTable:
    """One sequence's ordered block list + its valid token length.

    ``blocks[i]`` holds tokens ``[i*block_tokens, (i+1)*block_tokens)``;
    content in the boundary block past ``length`` belongs to whoever
    the block is shared with (readers must respect ``length`` — the
    same contract attention's per-request ``lengths`` already enforces
    for stale row positions)."""

    __slots__ = ("blocks", "length")

    def __init__(self, blocks: Optional[list] = None, length: int = 0):
        self.blocks: list[int] = blocks if blocks is not None else []
        self.length = length

    def __repr__(self) -> str:  # debugging/postmortem friendliness
        return f"BlockTable(n={len(self.blocks)}, length={self.length})"


class _CacheEntry:
    """A cached sequence: its block table plus caller metadata (length,
    next_token, logits... — opaque to the pool)."""

    __slots__ = ("table", "meta")

    def __init__(self, table: BlockTable, meta: dict):
        self.table = table
        self.meta = meta


class BlockPool:
    """Refcounted block allocator + LRU registry of cached sequences.

    Thread-safe; ``lock`` is a public RLock so engines can make
    compound operations (LCP scan then alias) atomic against concurrent
    admission/eviction by wrapping them in ``with pool.lock:``.

    Block states (the ``gofr_tpu_kv_blocks{state}`` gauge):

    - ``free``: on the free list;
    - ``cached``: referenced by at least one cache entry (may ALSO be
      shared with live requests — cache wins the label);
    - ``active``: referenced only by live requests/reservations.

    ``scratch=True`` reserves block id 0 permanently (never allocated):
    the device arena's padded scatter/gather ops need a harmless target
    for table positions past a sequence's end.

    Two admission surfaces share one budget:

    - DATA blocks (``alloc``/``reserve``/``alias``...): physically
      backed by the arena — cache entries and host-path sequences;
    - the LEDGER (``reserve_ledger``/``release_ledger``): accounting
      for in-flight KV that physically lives elsewhere (the device
      decode pool's slot cache). ``ledger_blocks`` (default
      ``n_blocks``) is the combined budget; a ledger reservation
      treats cached blocks as reclaimable (they evict on demand when
      data is actually needed), so admission is gated on
      ``ledger - reserved - active``, and a finished request's
      ``release_ledger`` admits the next one immediately.
    """

    def __init__(
        self,
        n_blocks: int,
        block_tokens: int,
        arena: Any = None,
        block_bytes: int = 0,
        hbm_budget_bytes: int = 0,
        cache_entries: int = 0,
        metrics: Any = None,
        scratch: bool = False,
        ledger_blocks: Optional[int] = None,
    ):
        if n_blocks < 1:
            raise ValueError(f"n_blocks must be >= 1, got {n_blocks}")
        if block_tokens < 1:
            raise ValueError(
                f"block_tokens must be >= 1, got {block_tokens}"
            )
        self.n_blocks = n_blocks
        self.block_tokens = block_tokens
        self.arena = arena
        self.block_bytes = block_bytes or getattr(arena, "block_bytes", 0)
        self.hbm_budget_bytes = hbm_budget_bytes
        self.cache_entries = cache_entries  # 0 = unbounded (budget still caps)
        self.lock = threading.RLock()
        self._ref = [0] * n_blocks
        self._cache_ref = [0] * n_blocks  # refs held by cache entries
        first = 1 if scratch else 0
        self._scratch = scratch
        if scratch and n_blocks < 2:
            raise ValueError("scratch pool needs n_blocks >= 2")
        if scratch:
            self._ref[0] = 1  # permanently held, never freed
        # LIFO free list: recently freed blocks are re-handed first
        # (their arena pages are the warmest)
        self._free = list(range(n_blocks - 1, first - 1, -1))
        self._cache: "OrderedDict[bytes, _CacheEntry]" = OrderedDict()
        self._cached_unique = 0  # blocks with _cache_ref > 0
        self.ledger_blocks = (
            ledger_blocks if ledger_blocks is not None else self.total_blocks
        )
        self.reserved = 0  # ledger blocks claimed by in-flight requests
        # counters surfaced by stats() and the bench delta report
        self.evictions = 0
        self.cow_copies = 0
        self.copied_kv_bytes = 0
        self.exhausted_rejects = 0
        self._blocks_gauge = self._evict_counter = None
        if metrics is not None:
            self._blocks_gauge = metrics.gauge(
                "gofr_tpu_kv_blocks",
                "paged KV arena blocks by state "
                "(total/free/active/cached/reserved)",
                labels=("state",),
            )
            self._evict_counter = metrics.counter(
                "gofr_tpu_kv_evictions_total",
                "prefix-cache entries LRU-evicted to free KV blocks",
            )
            self._publish()

    # -- accounting helpers (lock held) --------------------------------------
    @property
    def total_blocks(self) -> int:
        """Allocatable blocks (the scratch block is bookkeeping)."""
        return self.n_blocks - (1 if self._scratch else 0)

    def _publish(self) -> None:
        if self._blocks_gauge is None:
            return
        free = len(self._free)
        self._blocks_gauge.set(self.total_blocks, state="total")
        self._blocks_gauge.set(free, state="free")
        self._blocks_gauge.set(self._cached_unique, state="cached")
        self._blocks_gauge.set(
            self.total_blocks - free - self._cached_unique, state="active"
        )
        self._blocks_gauge.set(self.reserved, state="reserved")

    def note_copied(self, nbytes: int) -> None:
        """Engines report bytes they physically copied moving KV between
        blocks and rows — the number the bench's paged-vs-slot delta is
        built on."""
        with self.lock:
            self.copied_kv_bytes += int(nbytes)

    # -- raw block ops -------------------------------------------------------
    def alloc(self, n: int) -> list[int]:
        """Take ``n`` free blocks (refcount 1 each), LRU-evicting cached
        entries as needed; raises :class:`KVExhausted` when live
        references alone exceed the arena."""
        if n <= 0:
            return []
        with self.lock:
            if len(self._free) < n:
                # satisfiability FIRST: a doomed request must not wipe
                # the whole cache as collateral before failing anyway.
                # Reclaimable = blocks whose only refs are the cache's
                # (evicting everything frees exactly these).
                reclaimable = sum(
                    1 for b in range(self.n_blocks)
                    if self._ref[b] > 0 and self._ref[b] == self._cache_ref[b]
                )
                if len(self._free) + reclaimable < n:
                    self.exhausted_rejects += 1
                    raise KVExhausted(
                        f"need {n} KV blocks, {len(self._free)} free + "
                        f"{reclaimable} reclaimable of {self.total_blocks} "
                        "(the rest held by live requests)"
                    )
            while len(self._free) < n and self._cache:
                self._evict_lru()
            if len(self._free) < n:
                self.exhausted_rejects += 1
                raise KVExhausted(
                    f"need {n} KV blocks, {len(self._free)} free of "
                    f"{self.total_blocks} (cache empty — all blocks held "
                    "by live requests)"
                )
            out = [self._free.pop() for _ in range(n)]
            for b in out:
                self._ref[b] = 1
            self._publish()
            return out

    def incref(self, blocks: list) -> None:
        with self.lock:
            for b in blocks:
                if self._ref[b] <= 0:
                    raise RuntimeError(
                        f"incref of free block {b} (use-after-free)"
                    )
                self._ref[b] += 1

    def release_blocks(self, blocks: list) -> None:
        """Drop one reference per block; blocks reaching zero return to
        the free list immediately (continuous admission feeds on this)."""
        with self.lock:
            for b in blocks:
                r = self._ref[b] - 1
                if r < 0:
                    raise RuntimeError(f"double free of block {b}")
                self._ref[b] = r
                if r == 0:
                    self._free.append(b)
            self._publish()

    # -- ledger reservations (device decode-pool admission) ------------------
    def reserve_ledger(self, n_tokens: int) -> int:
        """Claim admission budget for ``n_tokens`` of in-flight KV that
        physically lives OUTSIDE the arena (the pool's slot cache).
        Cached blocks count as reclaimable (data allocation evicts them
        on demand), so the gate is
        ``ledger - reserved - active >= needed``. Returns the block
        count to hand back via :meth:`release_ledger`; raises
        :class:`KVExhausted` when live KV alone exceeds the budget."""
        n = blocks_for(n_tokens, self.block_tokens)
        with self.lock:
            active = (
                self.total_blocks - len(self._free) - self._cached_unique
            )
            if self.ledger_blocks - self.reserved - active < n:
                self.exhausted_rejects += 1
                raise KVExhausted(
                    f"need {n} KV blocks, "
                    f"{self.ledger_blocks - self.reserved - active} of "
                    f"{self.ledger_blocks} unclaimed (reserved="
                    f"{self.reserved}, active={active})"
                )
            self.reserved += n
            self._publish()
            return n

    def release_ledger(self, n: int) -> None:
        """Return admission budget — called the moment a request
        finishes, so the freed capacity admits the next request
        mid-flight."""
        with self.lock:
            self.reserved = max(self.reserved - int(n), 0)
            self._publish()

    # -- table ops -----------------------------------------------------------
    def reserve(self, n_tokens: int) -> BlockTable:
        """A fresh table with capacity for ``n_tokens`` (length 0): the
        admission primitive — DecodePool reserves a request's whole KV
        budget here so it can never OOM mid-generation."""
        return BlockTable(self.alloc(blocks_for(n_tokens, self.block_tokens)))

    def ensure(self, table: BlockTable, n_tokens: int) -> None:
        """Grow ``table``'s capacity to ``n_tokens`` tokens."""
        need = blocks_for(n_tokens, self.block_tokens) - len(table.blocks)
        if need > 0:
            table.blocks.extend(self.alloc(need))

    def release(self, table: BlockTable) -> None:
        with self.lock:
            blocks, table.blocks, table.length = table.blocks, [], 0
            self.release_blocks(blocks)

    def trim(self, table: BlockTable) -> int:
        """Free capacity beyond ``length`` (reserved-but-unused tail —
        a finished request hands these back instantly). Returns the
        number of blocks released."""
        with self.lock:
            keep = blocks_for(table.length, self.block_tokens)
            tail = table.blocks[keep:]
            del table.blocks[keep:]
            if tail:
                self.release_blocks(tail)
            return len(tail)

    def alias(self, donor: BlockTable, n_tokens: int) -> BlockTable:
        """Copy-free sharing: a new table referencing the donor's blocks
        covering the first ``n_tokens`` tokens. The boundary block may
        be shared mid-block — extending through it later triggers
        :meth:`cow_boundary`."""
        if n_tokens > donor.length:
            raise ValueError(
                f"alias of {n_tokens} tokens from a {donor.length}-token table"
            )
        with self.lock:
            shared = donor.blocks[: blocks_for(n_tokens, self.block_tokens)]
            self.incref(shared)
            return BlockTable(list(shared), n_tokens)

    def alias_full_blocks(self, donor: BlockTable, n_tokens: int) -> tuple:
        """Share only WHOLE blocks within ``n_tokens`` — the store-path
        variant (the boundary block must stay private to the donor, the
        extender writes its own). Returns ``(table, shared_tokens)``."""
        full = (min(n_tokens, donor.length) // self.block_tokens)
        shared_tokens = full * self.block_tokens
        with self.lock:
            shared = donor.blocks[:full]
            self.incref(shared)
            return BlockTable(list(shared), shared_tokens), shared_tokens

    def cow_boundary(self, table: BlockTable) -> Optional[tuple]:
        """Copy-on-write before appending: if the boundary block (the
        partially filled last block) is shared, replace it with a
        private copy. Returns ``(old, new)`` block ids when a copy
        happened, else None."""
        frac = table.length % self.block_tokens
        if frac == 0 or not table.blocks:
            return None  # boundary is block-aligned: next append opens fresh
        with self.lock:
            i = table.length // self.block_tokens
            old = table.blocks[i]
            if self._ref[old] <= 1:
                return None  # private already
            new = self.alloc(1)[0]
            copied = 0
            if self.arena is not None:
                copied = self.arena.copy_partial(new, old, frac)
            table.blocks[i] = new
            self.release_blocks([old])
            self.cow_copies += 1
            self.copied_kv_bytes += copied
            return old, new

    # -- cached sequences (the prefix cache's storage half) ------------------
    def cache_put(self, key: bytes, table: BlockTable, meta: dict) -> None:
        """Insert/replace a cached sequence. OWNERSHIP TRANSFER: the
        caller's block references become the cache's (copy-free store —
        a finished request's table IS the entry); the caller must not
        release the table afterwards."""
        with self.lock:
            old = self._cache.pop(key, None)
            if old is not None:
                self._cache_release(old)
            self._cache[key] = _CacheEntry(table, meta)
            for b in table.blocks:
                if self._cache_ref[b] == 0:
                    self._cached_unique += 1
                self._cache_ref[b] += 1
            while self.cache_entries and len(self._cache) > self.cache_entries:
                self._evict_lru()
            self._publish()

    def cache_lookup(self, key: bytes) -> Optional[_CacheEntry]:
        """Exact-key entry (LRU order refreshed) or None. Callers doing
        device work against the entry must pin its blocks (``incref``)
        under ``pool.lock`` before leaving it — eviction can otherwise
        free them mid-gather."""
        with self.lock:
            entry = self._cache.get(key)
            if entry is not None:
                self._cache.move_to_end(key)
            return entry

    def cache_items(self) -> list:
        """Snapshot of (key, entry) pairs, LRU-first — the LCP scan's
        iteration surface. Take ``pool.lock`` around scan+alias to keep
        the chosen donor alive."""
        with self.lock:
            return list(self._cache.items())

    def cache_touch(self, key: bytes) -> None:
        with self.lock:
            if key in self._cache:
                self._cache.move_to_end(key)

    def cache_clear(self) -> None:
        """Release every cached sequence (live aliases keep their own
        refs); eviction counters are NOT incremented — this is an
        administrative purge, not budget pressure."""
        with self.lock:
            while self._cache:
                _, entry = self._cache.popitem(last=False)
                self._cache_release(entry)
            self._publish()

    def cache_discard(self, key: bytes) -> None:
        with self.lock:
            entry = self._cache.pop(key, None)
            if entry is not None:
                self._cache_release(entry)
                self._publish()

    def _cache_release(self, entry: _CacheEntry) -> None:
        for b in entry.table.blocks:
            self._cache_ref[b] -= 1
            if self._cache_ref[b] == 0:
                self._cached_unique -= 1
        self.release_blocks(entry.table.blocks)
        entry.table.blocks = []

    def _evict_lru(self) -> None:
        """Drop the least-recently-used cached sequence (lock held).
        Blocks shared with live requests survive via their remaining
        refs — eviction only removes the CACHE's claim."""
        _, entry = self._cache.popitem(last=False)
        self._cache_release(entry)
        self.evictions += 1
        if self._evict_counter is not None:
            self._evict_counter.inc()

    def __len__(self) -> int:
        with self.lock:
            return len(self._cache)

    # -- introspection -------------------------------------------------------
    def stats(self) -> dict:
        """Point-in-time accounting for ``GET /admin/engine`` and the
        bench artifact — all host-side reads."""
        with self.lock:
            free = len(self._free)
            used = self.total_blocks - free
            out = {
                "total": self.total_blocks,
                "ledger": self.ledger_blocks,
                "block_tokens": self.block_tokens,
                "block_bytes": self.block_bytes,
                "free": free,
                "cached": self._cached_unique,
                "active": used - self._cached_unique,
                "reserved": self.reserved,
                "cached_entries": len(self._cache),
                "evictions": self.evictions,
                "cow_copies": self.cow_copies,
                "copied_kv_bytes": self.copied_kv_bytes,
                "kv_exhausted_rejects": self.exhausted_rejects,
                "hbm_budget_bytes": self.hbm_budget_bytes or None,
                "budget_utilization": (
                    round(
                        (used + self.reserved) * self.block_bytes
                        / self.hbm_budget_bytes, 4,
                    )
                    if self.hbm_budget_bytes and self.block_bytes else None
                ),
            }
        return out


class TransferPin:
    """A bounded-lifetime pin on a set of blocks held for an in-flight
    cross-replica KV transfer.

    The export handler increfs the entry's blocks so eviction cannot
    free them mid-send, and releases them when the response stream
    closes. But the serving side of a transfer is exactly where threads
    die ungracefully — the client vanishes mid-pull, the event loop
    tears the response task down, the worker is killed — and a pin
    whose release never runs would leak refcounts FOREVER (the blocks
    become unevictable, and enough aborted pulls starve admission). So
    every pin arms a named daemon timer: if nobody released it within
    ``ttl_s``, the timer does — and the late releaser finds an
    idempotent no-op. ``expired`` records that the guard fired (the
    export path uses it to stop streaming a pin it no longer holds).
    """

    def __init__(self, pool: BlockPool, blocks: list, ttl_s: float = 60.0):
        self.pool = pool
        self.blocks = list(blocks)
        self._lock = threading.Lock()
        self._released = False
        self.expired = False
        with pool.lock:
            pool.incref(self.blocks)
        self._timer = threading.Timer(max(ttl_s, 0.001), self._expire)
        # gofrlint GFL003 contract by construction: named + daemon (the
        # guard must survive nobody joining it — that is its point)
        self._timer.name = "gofr-kv-transfer-pin"
        self._timer.daemon = True
        self._timer.start()

    def _expire(self) -> None:
        self.expired = True
        self.release()

    def release(self) -> None:
        """Idempotent: first caller (normal close, abort, or the TTL
        timer) drops the refs; everyone else no-ops."""
        with self._lock:
            if self._released:
                return
            self._released = True
        self._timer.cancel()
        self.pool.release_blocks(self.blocks)

    @property
    def released(self) -> bool:
        with self._lock:
            return self._released


class HostTokenArena:
    """Host block storage for the echo runner: a block's "KV" is the
    token ids it covers, so aliasing/COW fidelity is directly checkable
    (read the sequence back, compare to the prompt) with zero compiles.

    ``shards`` is the host-mesh mode (echo's ``TPU_MESH`` analogue of
    the device arena's tp head sharding): every block's tokens are
    SPLIT contiguously across ``shards`` fake devices — shard ``s``
    owns positions ``[s*w, (s+1)*w)`` of each block (``w = block_tokens
    / shards``) — so block tables, aliasing, COW, and admission all run
    against genuinely distributed storage, compile-free. Per-shard
    write counts (``shard_writes``) let tests assert every fake device
    actually took traffic."""

    TOKEN_BYTES = 4  # int32 ids

    def __init__(self, n_blocks: int, block_tokens: int, shards: int = 1):
        if shards < 1:
            raise ValueError(f"shards must be >= 1, got {shards}")
        if block_tokens % shards:
            raise ValueError(
                f"tp={shards} does not divide KV_BLOCK_TOKENS="
                f"{block_tokens} — host-mesh blocks split their token "
                "axis evenly across the tp axis"
            )
        self.block_tokens = block_tokens
        self.block_bytes = block_tokens * self.TOKEN_BYTES
        self.shards = shards
        self._width = block_tokens // shards
        # [shards, n_blocks, width]: axis 0 is the fake-device axis —
        # shard-major reshape of a block reassembles its token order
        self._data = np.zeros((shards, n_blocks, self._width), np.int32)
        self.shard_writes = [0] * shards

    def _write_span(self, blk: int, at: int, ids: np.ndarray) -> None:
        """Write ``ids`` at block-local offset ``at`` of ``blk``: one
        direct slice store per shard the span overlaps (never a whole-
        block read-modify-write — a 1-token decode append must touch
        one element, not ``block_tokens`` of them)."""
        w = self._width
        hi = at + ids.size
        for s in range(at // w, (hi - 1) // w + 1):
            s_lo, s_hi = max(at, s * w), min(hi, (s + 1) * w)
            self._data[s, blk, s_lo - s * w : s_hi - s * w] = (
                ids[s_lo - at : s_hi - at]
            )
            self.shard_writes[s] += 1

    def write(self, table: BlockTable, start: int, ids: np.ndarray) -> int:
        """Write ``ids`` at token offset ``start`` of ``table``;
        capacity must already exist. Returns bytes copied."""
        ids = np.asarray(ids, np.int32).reshape(-1)
        bt = self.block_tokens
        pos = start
        off = 0
        while off < ids.size:
            blk = table.blocks[pos // bt]
            at = pos % bt
            n = min(bt - at, ids.size - off)
            self._write_span(blk, at, ids[off : off + n])
            pos += n
            off += n
        return ids.size * self.TOKEN_BYTES

    def read(self, table: BlockTable) -> np.ndarray:
        """The sequence's tokens (exactly ``length`` of them)."""
        bt = self.block_tokens
        if not table.blocks or table.length == 0:
            return np.zeros(0, np.int32)
        nb = blocks_for(table.length, bt)
        # [shards, nb, width] -> [nb, shards, width] -> token order
        flat = np.transpose(
            self._data[:, table.blocks[:nb], :], (1, 0, 2)
        ).reshape(-1)
        return flat[: table.length].copy()

    def copy_partial(self, dst_block: int, src_block: int, n_tokens: int) -> int:
        """COW copy of the boundary block's first ``n_tokens`` — only
        the prefix, shard by shard (the suffix belongs to whoever
        writes it next)."""
        w = self._width
        for s in range((n_tokens - 1) // w + 1):
            n_s = min(n_tokens - s * w, w)
            self._data[s, dst_block, :n_s] = self._data[s, src_block, :n_s]
            self.shard_writes[s] += 1
        return n_tokens * self.TOKEN_BYTES

    # -- cross-replica transfer codec (fleet/kvwire.py) ----------------------
    def wire_spec(self) -> dict:
        """The compatibility fields a transfer peer must match (the
        receiver refuses skewed donors before trusting any payload).
        ``shards`` is deliberately ABSENT: the shard split is local
        layout, not wire content — a tp=2 host arena and a tp=1 one
        exchange identical token payloads."""
        return {"kind": "host-tokens", "block_tokens": self.block_tokens}

    def export_block_payload(self, table: BlockTable, j: int) -> bytes:
        """Block ``j``'s valid tokens as int32 bytes (the boundary
        block ships only up to ``table.length`` — content past it
        belongs to whoever shares the block)."""
        bt = self.block_tokens
        lo = j * bt
        span = min(table.length, lo + bt) - lo
        # [shards, width] reshaped shard-major IS token order
        tokens = self._data[:, table.blocks[j], :].reshape(-1)[:span]
        return np.ascontiguousarray(tokens, np.int32).tobytes()

    def ingest_block_payload(self, table: BlockTable, j: int,
                             payload: bytes) -> int:
        """Install a transferred block payload into block ``j`` of a
        PRIVATE (freshly reserved) table. Returns bytes written."""
        if len(payload) % self.TOKEN_BYTES:
            raise ForeignKVRejected(
                f"block {j} payload is {len(payload)}B, not a whole "
                "number of int32 tokens"
            )
        ids = np.frombuffer(payload, np.int32)
        if ids.size == 0 or ids.size > self.block_tokens:
            raise ForeignKVRejected(
                f"block {j} carries {ids.size} tokens (block size "
                f"{self.block_tokens})"
            )
        self._write_span(table.blocks[j], 0, ids)
        return len(payload)


def install_foreign_entry(
    pool: BlockPool,
    arena: Any,
    ids: np.ndarray,
    payloads: list,
    meta_extra: dict,
    *,
    verify_readback: bool,
    count_copied: bool,
) -> bool:
    """The receiving end of a cross-replica KV transfer, shared by the
    host engine and the device prefix store: reserve blocks, ingest the
    verified payloads, and publish the result as a cache entry so the
    imminent admission of the same prompt aliases it copy-free.

    Returns False when the local pool cannot host it (exhausted — a
    LOCAL condition, not a transfer failure: the caller falls back
    without counting the donor as broken). Raises
    :class:`ForeignKVRejected` on a count mismatch or, with
    ``verify_readback`` (arenas whose payload has a semantic readback,
    i.e. host token arenas), when the installed blocks read back as a
    different token sequence than the prompt being admitted — in either
    case the reservation is rolled back leaving no trace in the pool.
    ``count_copied`` feeds the ingested bytes into the pool's
    copied-KV accounting (the device path's bench signal)."""
    ids = np.asarray(ids, np.int32).reshape(-1)
    key = ids.tobytes()
    need = blocks_for(int(ids.size), pool.block_tokens)
    if len(payloads) != need:
        raise ForeignKVRejected(
            f"{len(payloads)} block payloads for a {ids.size}-token "
            f"prompt needing {need}"
        )
    with pool.lock:
        if pool.cache_lookup(key) is not None:
            return True  # already warm locally; nothing to install
        try:
            table = pool.reserve(int(ids.size))
        except KVExhausted:
            return False
        table.length = int(ids.size)
    # ingest OUTSIDE pool.lock: the reservation owns the blocks, and
    # device ingests are real transfers the admission path must not
    # wait behind
    copied = 0
    try:
        for j, payload in enumerate(payloads):
            copied += arena.ingest_block_payload(table, j, payload) or 0
        if verify_readback and not np.array_equal(arena.read(table), ids):
            raise ForeignKVRejected(
                "transferred KV read back as a different token "
                "sequence than the prompt being admitted"
            )
    except Exception:
        pool.release(table)
        raise
    if count_copied:
        pool.note_copied(copied)
    entry_meta = {"length": int(ids.size)}
    entry_meta.update(meta_extra)
    pool.cache_put(key, table, entry_meta)
    return True


class PagedSequence:
    """One live request's handle on the host engine: its table, how it
    was admitted (for flight records), and the prompt length."""

    __slots__ = ("table", "prompt_len", "aliased_blocks", "kind")

    def __init__(self, table: BlockTable, prompt_len: int,
                 aliased_blocks: int, kind: str):
        self.table = table
        self.prompt_len = prompt_len
        self.aliased_blocks = aliased_blocks  # admitted copy-free
        self.kind = kind  # hit | partial_hit | miss


class HostPagedKV:
    """The echo runner's paged KV engine: block-table prompt storage,
    copy-free prefix aliasing (exact + LCP), COW on extension,
    reserve-at-admission (continuous batching's accounting half) — the
    whole paged path, compile-free for tier-1.

    ``copy_mode=True`` disables aliasing and deep-copies hit entries
    into fresh blocks — the slot-model behavior, kept as the bench's
    within-harness baseline for the copied-bytes/admission deltas."""

    def __init__(
        self,
        pool: BlockPool,
        arena: HostTokenArena,
        lcp_min: int = 8,
        copy_mode: bool = False,
    ):
        self.pool = pool
        self.arena = arena
        self.lcp_min = lcp_min
        self.copy_mode = copy_mode
        # same dict shape as the transformer runner's prefix_stats so
        # the device's hit-ratio gauges work unchanged
        self.prefix_stats = {"hits": 0, "partial_hits": 0, "misses": 0}
        self._stats_lock = threading.Lock()

    # -- admission -----------------------------------------------------------
    def admit(self, ids: np.ndarray, max_new: int) -> PagedSequence:
        """Admit a prompt: alias cached blocks where possible, write the
        rest, and reserve decode capacity up front. Raises
        :class:`KVExhausted` (rolled back) when the arena cannot cover
        it even after evicting the cache."""
        ids = np.asarray(ids, np.int32).reshape(-1)
        table = None
        try:
            with self.pool.lock:  # scan + alias must be atomic vs eviction
                table, aliased, kind = self._admit_table(ids)
                # capacity for the whole generation NOW: a request that
                # admits can never die to block starvation mid-decode,
                # and trim() hands the unused tail back at finish
                self.pool.ensure(table, ids.size + max_new)
                if kind != "hit":
                    # store the PROMPT entry as an alias of the live
                    # table (the transformer stores its prefill result
                    # the same way) — zero copies, and an exact repeat
                    # of this prompt now hits
                    self.pool.cache_put(
                        ids.tobytes(), self.pool.alias(table, ids.size),
                        {"length": int(ids.size)},
                    )
                if max_new > 0:
                    # pre-COW the (now shared) boundary block HERE, while
                    # exhaustion still rolls back to a clean reject: an
                    # ADMITTED request must never die to block starvation
                    # mid-decode, and after this no append can allocate
                    # (capacity is reserved, the boundary is private)
                    self.pool.cow_boundary(table)
        except KVExhausted:
            if table is not None:
                self.pool.release(table)
            raise
        with self._stats_lock:
            self.prefix_stats[
                "hits" if kind == "hit"
                else "partial_hits" if kind == "partial_hit" else "misses"
            ] += 1
        return PagedSequence(table, ids.size, aliased, kind)

    def _admit_table(self, ids: np.ndarray) -> tuple:
        """Build the admitted table (pool lock held): exact alias, LCP
        partial alias + tail write, or full write."""
        key = ids.tobytes()
        entry = self.pool.cache_lookup(key)
        if entry is not None:
            if self.copy_mode:
                return self._copy_entry(entry, ids.size), 0, "hit"
            table = self.pool.alias(entry.table, ids.size)
            return table, len(table.blocks), "hit"
        shared, donor = self._lcp_scan(ids)
        if donor is not None:
            if self.copy_mode:
                table = self._copy_entry(donor, shared)
                try:
                    # exception safety: _copy_entry already holds refs —
                    # a failed grow must release them, not strand them
                    # (the caller's rollback never sees this table)
                    self.pool.ensure(table, ids.size)
                except KVExhausted:
                    self.pool.release(table)
                    raise
                self.pool.note_copied(
                    self.arena.write(table, shared, ids[shared:])
                )
                table.length = ids.size
                return table, 0, "partial_hit"
            # share whole blocks copy-free; the boundary + tail are this
            # request's own writes
            table, shared_tokens = self.pool.alias_full_blocks(
                donor.table, shared
            )
            n_aliased = len(table.blocks)
            try:
                # same exception-safety contract: the alias increfed the
                # donor's blocks and this table is not yet the caller's
                self.pool.ensure(table, ids.size)
            except KVExhausted:
                self.pool.release(table)
                raise
            self.pool.note_copied(
                self.arena.write(table, shared_tokens, ids[shared_tokens:])
            )
            table.length = ids.size
            return table, n_aliased, "partial_hit"
        table = self.pool.reserve(ids.size)
        self.pool.note_copied(self.arena.write(table, 0, ids))
        table.length = ids.size
        return table, 0, "miss"

    def _copy_entry(self, entry: Any, n_tokens: int) -> BlockTable:
        """Slot-model baseline: materialize a PRIVATE copy of the entry
        (what the row cache did per hit), counting the copied bytes."""
        src = self.arena.read(entry.table)[:n_tokens]
        table = self.pool.reserve(n_tokens)
        self.pool.note_copied(self.arena.write(table, 0, src))
        table.length = n_tokens
        return table

    def _lcp_scan(self, ids: np.ndarray) -> tuple:
        """Longest-common-prefix donor among cached sequences (pool lock
        held) — the shared :func:`lcp_scan` at this engine's threshold."""
        shared, key, entry = lcp_scan(
            self.pool.cache_items(), ids, int(ids.size) - 1, self.lcp_min
        )
        if entry is None:
            return 0, None
        self.pool.cache_touch(key)
        return shared, entry

    # -- decode-time ---------------------------------------------------------
    def prompt_tokens(self, seq: PagedSequence) -> np.ndarray:
        """The prompt read back THROUGH the block tables — the echo
        decode loop cycles these, so aliasing fidelity is load-bearing,
        not decorative."""
        return self.arena.read(seq.table)[: seq.prompt_len]

    def append(self, seq: PagedSequence, token: int) -> None:
        """One decoded token lands in the sequence's KV: COW if the
        boundary block is shared, then write (capacity was reserved at
        admission)."""
        with self.pool.lock:
            self.pool.cow_boundary(seq.table)
            self.pool.ensure(seq.table, seq.table.length + 1)
            self.arena.write(
                seq.table, seq.table.length, np.asarray([token], np.int32)
            )
            seq.table.length += 1

    def rollback(self, seq: PagedSequence, n_tokens: int) -> None:
        """Speculative-decode reject: roll the sequence's valid length
        back to ``n_tokens`` (the committed prefix — accepted drafts +
        the bonus). The rejected tokens are un-emitted by construction:
        every reader honors ``length``, so the stale content past it is
        dead the moment this returns, and the next append overwrites it
        in place. The BLOCKS stay in the table — they are the capacity
        the request reserved at admission, and releasing them here
        would let a concurrent admission steal them and starve this
        (already admitted) request at its next append, breaking the
        no-mid-decode-exhaustion contract. They release at
        :meth:`finish` via ``trim`` exactly like any other unused
        reservation — the leak invariant the rollback tests pin."""
        if n_tokens < seq.prompt_len:
            raise ValueError(
                f"rollback to {n_tokens} would cut into the "
                f"{seq.prompt_len}-token prompt"
            )
        with self.pool.lock:
            if n_tokens > seq.table.length:
                raise ValueError(
                    f"rollback to {n_tokens} past the sequence's "
                    f"{seq.table.length}-token length"
                )
            seq.table.length = n_tokens

    # -- completion ----------------------------------------------------------
    def finish(self, seq: PagedSequence, store: bool = True) -> None:
        """Request done: trim the unused reservation (those blocks admit
        the NEXT request immediately), then either transfer the table to
        the cache (copy-free store, keyed by the full conversation) or
        release it."""
        self.pool.trim(seq.table)
        if store and seq.table.length > 0:
            key = self.arena.read(seq.table).tobytes()
            self.pool.cache_put(
                key, seq.table, {"length": seq.table.length}
            )
        else:
            self.pool.release(seq.table)
        seq.table = BlockTable()

    def abort(self, seq: PagedSequence) -> None:
        self.finish(seq, store=False)

    # -- cross-replica transfer (receiving end) ------------------------------
    def install_remote(self, ids: np.ndarray, payloads: list,
                       meta: dict) -> bool:
        """Install a verified transferred entry so the imminent
        :meth:`admit` of the same prompt aliases it copy-free (the
        whole point of the pull: skip the local prefill). Host "KV" is
        token ids, so :func:`install_foreign_entry` additionally reads
        the blocks back and verifies they ARE the prompt — wire
        checksums guard the transport, the readback guards the
        content."""
        return install_foreign_entry(
            self.pool, self.arena, ids, payloads, {},
            verify_readback=True, count_copied=False,
        )

    def stats(self) -> dict:
        out = self.pool.stats()
        with self._stats_lock:
            out["prefix"] = dict(self.prefix_stats)
        return out


class JaxKVArena:
    """Device-side block storage + the jitted block<->row bridge.

    Layout ``[n_layers, n_blocks, n_kv_heads, block_tokens, head_dim]``
    for k and v: a block is ``block_tokens`` positions of a row in the
    order the compute caches hold them (``models/transformer.py::
    init_cache``), so a block leaves a row as a slice and a row is its
    blocks side by side along the position axis. Block id 0 is the
    SCRATCH block (pair with ``BlockPool(scratch=True)``): the fixed-shape
    scatter/scan and gather/take ops pad every table to ``blocks_per_seq``
    entries, and the padding must land somewhere harmless.

    - ``scatter_row(row, table, skip_blocks)``: write a contiguous
      ``[L, 1, H, max_seq, D]`` row's first ``table.length`` tokens into
      the table's blocks, skipping the first ``skip_blocks`` (aliased
      blocks keep their donor's content — writing "equal" KV from a
      different executable's row would break bit-lineage);
    - ``gather_row(table, length)``: materialize the contiguous row the
      compiled executables consume (``lengths=[length]``); positions
      past ``length`` are scratch garbage, masked by attention exactly
      like the slot model's stale rows.

    Both are ONE dispatch each (a scan / a take), compiled once at
    construction — no lazy compile on the serving path.

    With a serving ``mesh`` (tp-only; the caller gates dp/fsdp) the
    arena itself is SHARDED: k/v split their kv-head axis over ``tp``
    (``parallel/sharding.py::kv_arena_spec``, the same head split the
    compute caches use), scatter/gather pin their outputs to the
    arena/cache placements, and the block/token axes stay unsharded —
    so block ids and table bookkeeping are mesh-agnostic while every
    device holds only its head slice of every block.
    """

    def __init__(self, cfg: Any, n_blocks: int, block_tokens: int,
                 max_seq: Optional[int] = None, mesh: Optional[Any] = None):
        import jax
        import jax.numpy as jnp

        self._jnp = jnp
        max_seq = max_seq or cfg.max_seq
        if max_seq % block_tokens:
            raise ValueError(
                f"KV_BLOCK_TOKENS={block_tokens} must divide max_seq="
                f"{max_seq} (block boundaries must tile the row)"
            )
        self.block_tokens = block_tokens
        self.max_seq = max_seq
        self.blocks_per_seq = max_seq // block_tokens
        self.mesh = mesh
        shape = (
            cfg.n_layers, n_blocks, cfg.n_kv_heads, block_tokens,
            cfg.head_dim,
        )
        arena_sharding = row_shardings = None
        if mesh is not None:
            from jax.sharding import NamedSharding

            from gofr_tpu.parallel.sharding import cache_specs, kv_arena_spec

            tp = mesh.shape.get("tp", 1)
            if cfg.n_kv_heads % tp:
                raise ValueError(
                    f"n_kv_heads={cfg.n_kv_heads} not divisible by tp="
                    f"{tp} — the paged-KV arena shards its head axis "
                    "over tp"
                )
            arena_sharding = NamedSharding(mesh, kv_arena_spec())
            row_shardings = {
                k_: NamedSharding(mesh, s)
                for k_, s in cache_specs(None).items()
            }
        self._arena_sharding = arena_sharding
        self._row_shardings = row_shardings
        if arena_sharding is not None:
            # allocate each shard IN PLACE: jnp.zeros-then-device_put
            # would transiently commit the whole tp-times-larger arena
            # to one device — an OOM (or peak-HBM spike) at exactly the
            # arena sizes tp exists to make fit
            zeros = jax.jit(
                lambda: jnp.zeros(shape, cfg.cache_dtype),
                out_shardings=arena_sharding,
            )
            self.k = zeros()
            self.v = zeros()
        else:
            self.k = jnp.zeros(shape, cfg.cache_dtype)
            self.v = jnp.zeros(shape, cfg.cache_dtype)
        itemsize = jnp.zeros((), cfg.cache_dtype).dtype.itemsize
        self.block_bytes = (
            2 * cfg.n_layers * block_tokens * cfg.n_kv_heads
            * cfg.head_dim * itemsize
        )
        bt = block_tokens
        nps = self.blocks_per_seq
        n_layers = cfg.n_layers

        def scatter(ak, av, rk, rv, ids):
            # one scan over the table: block j <- row[:, j*bt:(j+1)*bt]
            # (padded/skipped entries carry id 0 = scratch)
            def body(carry, x):
                ak, av = carry
                bid, start = x
                blk_k = jax.lax.dynamic_slice_in_dim(
                    rk[:, 0], start, bt, axis=2
                )
                blk_v = jax.lax.dynamic_slice_in_dim(
                    rv[:, 0], start, bt, axis=2
                )
                ak = jax.lax.dynamic_update_slice(
                    ak, blk_k[:, None], (0, bid, 0, 0, 0)
                )
                av = jax.lax.dynamic_update_slice(
                    av, blk_v[:, None], (0, bid, 0, 0, 0)
                )
                return (ak, av), None

            starts = jnp.arange(nps, dtype=jnp.int32) * bt
            (ak, av), _ = jax.lax.scan(body, (ak, av), (ids, starts))
            return ak, av

        def gather(ak, av, ids, length):
            def row_of(arena):
                # [L, nps, H, bt, D] -> [L, 1, H, nps * bt, D]: the blocks
                # side by side along a row's positions
                blocks = jnp.moveaxis(jnp.take(arena, ids, axis=1), 1, 2)
                return blocks.reshape(
                    n_layers, -1, nps * bt, cfg.head_dim)[:, None]

            gk, gv = row_of(ak), row_of(av)
            # a row as ``init_cache`` makes one (its one row live), so the
            # programs that take it were compiled for its leaves
            return {
                "k": gk, "v": gv,
                "lengths": jnp.reshape(length, (1,)).astype(jnp.int32),
                "live": jnp.ones((1,), jnp.int32),
            }

        # the arena is donated through scatter (updated in place — it is
        # the second-largest live buffer after the pool cache). Under a
        # mesh, outputs pin to the arena/cache placements so scatter
        # keeps the arena sharded and gathered rows land exactly where
        # the compiled executables expect their cache inputs.
        self._scatter = jax.jit(
            scatter, donate_argnums=(0, 1),
            out_shardings=(
                (arena_sharding, arena_sharding)
                if arena_sharding is not None else None
            ),
        )
        self._gather = jax.jit(
            gather,
            out_shardings=(
                dict(row_shardings) if row_shardings is not None else None
            ),
        )
        # warm both NOW: serving-path calls must reuse, never compile
        zero_row_k = jnp.zeros(
            (n_layers, 1, cfg.n_kv_heads, max_seq, cfg.head_dim),
            cfg.cache_dtype,
        )
        if row_shardings is not None:
            # warm with the EXACT row placement serving-path rows carry
            # (sharded prefill caches) or the first real store recompiles
            zero_row_k = jax.device_put(zero_row_k, row_shardings["k"])
        ids0 = jnp.zeros((nps,), jnp.int32)
        self.k, self.v = self._scatter(
            self.k, self.v, zero_row_k, zero_row_k, ids0
        )
        self._gather(self.k, self.v, ids0, 0)["lengths"].block_until_ready()

    def _padded_ids(self, table: BlockTable, skip_blocks: int = 0) -> Any:
        ids = np.zeros(self.blocks_per_seq, np.int32)  # 0 = scratch
        nb = min(
            blocks_for(table.length, self.block_tokens), len(table.blocks)
        )
        for j in range(skip_blocks, nb):
            ids[j] = table.blocks[j]
        return ids, nb

    def scatter_row(self, row: dict, table: BlockTable,
                    skip_blocks: int = 0) -> int:
        """Write ``row``'s tokens into the table's (non-aliased) blocks;
        returns the bytes physically copied into the arena."""
        ids, nb = self._padded_ids(table, skip_blocks)
        self.k, self.v = self._scatter(
            self.k, self.v, row["k"], row["v"], self._jnp.asarray(ids)
        )
        return max(nb - skip_blocks, 0) * self.block_bytes

    def gather_row(self, table: BlockTable, length: int) -> dict:
        """The contiguous compute row for a cached table (a fresh copy —
        the caller owns it; the arena blocks stay shared)."""
        ids, _ = self._padded_ids(table)
        return self._gather(
            self.k, self.v, self._jnp.asarray(ids), length
        )

    # -- cross-replica transfer codec (fleet/kvwire.py) ----------------------
    @property
    def _block_shape(self) -> tuple:
        # one block's k (or v) slice: [layers, heads, block_tokens, dim]
        s = self.k.shape
        return (s[0], s[2], s[3], s[4])

    def wire_spec(self) -> dict:
        """Compatibility fields a transfer peer must match: payload
        kind, block geometry, and dtype — a bf16 donor must not feed an
        f32 receiver byte soup that happens to checksum clean."""
        return {
            "kind": "device-kv",
            "block_tokens": self.block_tokens,
            "dtype": str(self.k.dtype),
            "block_shape": list(self._block_shape),
        }

    def export_block_payload(self, table: BlockTable, j: int) -> bytes:
        """Block ``j``'s raw k bytes + v bytes (device→host copy; the
        transfer endpoint is an admin pull, not the decode hot path)."""
        bid = table.blocks[j]
        k = np.ascontiguousarray(np.asarray(self.k[:, bid]))
        v = np.ascontiguousarray(np.asarray(self.v[:, bid]))
        return k.tobytes() + v.tobytes()

    def ingest_block_payload(self, table: BlockTable, j: int,
                             payload: bytes) -> int:
        """Install transferred k/v bytes into block ``j`` of a private
        table. Eager per-block ``.at[].set`` dispatches: constant
        shapes, so XLA caches one executable after the first block."""
        shape = self._block_shape
        half = int(np.prod(shape)) * self.k.dtype.itemsize
        if len(payload) != 2 * half:
            raise ForeignKVRejected(
                f"block {j} payload is {len(payload)}B, expected {2 * half}"
            )
        karr = np.frombuffer(payload[:half], self.k.dtype).reshape(shape)
        varr = np.frombuffer(payload[half:], self.v.dtype).reshape(shape)
        bid = table.blocks[j]
        self.k = self.k.at[:, bid].set(self._jnp.asarray(karr))
        self.v = self.v.at[:, bid].set(self._jnp.asarray(varr))
        return len(payload)

    def read(self, table: BlockTable) -> Any:
        """Semantic read-back is not possible for device KV (the
        content is model state, not the prompt); install paths verify
        transport checksums + spec only. Present so engines can feature-
        test arenas uniformly."""
        return None
