"""Paged KV-cache block accounting for the serving engine.

One fixed device-resident cache (allocated once by ``serve.Engine``)
is carved into ``num_blocks`` blocks of ``block_size`` token slots
each.  This module owns the HOST-side bookkeeping only: which physical
blocks belong to which request (the per-request *block table*), the
free list, refcounts, the content-addressed prefix index and the LRU
eviction tier — the device arrays never move.
``ops.attention.paged_attention`` consumes the tables to gather K/V.

Block id 0 is the permanent *null block*: it is never allocated, block
tables pad with it past a request's last real block, and padded scatter
positions write into it.  Its contents are garbage by design — every
consumer masks by context length before the softmax.

Prefix caching (RadixAttention/PagedAttention-style sharing)
-----------------------------------------------------------

With ``prefix_cache`` on (env ``MXTPU_SERVE_PREFIX_CACHE``, default
on), every FULL block whose token content is known is *published*
under a content-addressed key ``H(parent_key, block_token_ids)``.
Chaining the parent key into each block's hash makes the key table an
implicit radix tree over token prefixes: walking a new request's
prompt block-by-block down the chain yields the longest cached prefix,
as a chain of refcounted physical blocks.  ``allocate(rid, n,
token_ids=...)`` returns ``(table, cached_tokens)`` — the table starts
with the shared chain (each hit block's refcount incremented) and the
engine prefills only the suffix.

Sharing changes the lifecycle:

  allocate()  -> every table entry holds a reference (fresh blocks at
                 refcount 1, prefix hits incremented)
  free()      -> DECREF, never a blind release: blocks still referenced
                 by another request's table are untouched.  A block
                 reaching refcount 0 parks — published blocks in the
                 prefix LRU (K/V intact, a future ``allocate`` can hit
                 them again), unpublished blocks in the legacy
                 per-request retained tier
  evict       -> only refcount-0 blocks are ever reclaimed, and
                 published blocks only as radix LEAVES (no cached
                 children), oldest-first — an interior block is never
                 pulled out from under a cached descendant chain

Copy-on-write: a shared block is never partially overwritten.  The one
place that could happen — a prompt fully covered by cached blocks still
needs its last position's logits, so the final span must be recomputed
— is handled at lookup time by capping the hit at ``n_tokens - 1``: the
last matched block is dropped from the hit and the engine recomputes
its tokens into a FRESH private block (recomputation is the copy).

Host-RAM offload tier (``HostKVPool``)
--------------------------------------

With a pool attached (env ``MXTPU_SERVE_HOST_KV_BYTES`` > 0), a
refcount-0 published LEAF reclaimed by the prefix LRU no longer
discards its K/V: the block's device contents are copied device→host
(the engine's ``set_offload_source`` callback) and parked in a bounded
host-DRAM numpy pool under the block's existing content key — the
HBM-as-L1 / DRAM-as-L2 hierarchy vLLM-style engines use for swapped
blocks.  ``_walk`` extends the radix chain walk into the host tier: a
host hit claims a FRESH device block, queues an async host→device
restore (``take_pending_restores`` — the engine dispatches the copies
before the first program that reads the blocks) and counts the span as
cached.  Restored blocks are token-identical to recompute by
construction (content-addressed keys + per-slot KV quantization), so
the tier is a pure capacity extension: DRAM is 10-100x HBM, and the
pool has its own LRU with the same leaf-only discipline.  Without a
pool every prefix eviction throws K/V away; ``discarded_tokens``
counts exactly those tokens — the number this tier exists to drive
down.

Prefill/decode handoff (``export_blocks`` / ``import_blocks``)
--------------------------------------------------------------

The same content-keyed host copies double as the WIRE FORMAT for
disaggregated serving (DistServe-style role-split fleets): a
prefill-role replica serializes a finished prompt's cached chain with
``export_blocks`` (device blocks gathered D2H through the offload
fetch path, already-parked blocks peeked from the pool) and a
decode-role replica ingests the records with ``import_blocks`` into
ITS host pool under the same keys — the existing radix walk + async
restore program then pull them HBM-ward ahead of the first decode
read, so a transferred span counts as ``cached_tokens`` and no decode
program changes.  Every record is verified against the chain hash
``H(parent_key, token_ids)`` at import: a truncated or corrupted
payload fails verification, the chain stops there, and the receiver
simply recomputes the rest from the prompt (degradation, never
corruption).  Equal keys mean equal prefixes, so the radix key IS the
transfer dedup — a receiver that already holds a block (either tier)
skips its bytes.
"""

from __future__ import annotations

import base64
import hashlib
import threading
import time
from collections import OrderedDict, deque

import numpy as np

from .. import telemetry
from ..base import env_flag, env_float, env_int

__all__ = ["BlockManager", "HostKVPool", "NoFreeBlocks", "RadixSummary",
           "WindowGroup", "chain_keys"]

# chaos-harness fault: simulated seconds per host-tier restore claim (a
# slow DRAM copy); with a restore budget set, a delay past the budget
# DEGRADES the hit to recompute instead of stalling the step loop
ENV_HOST_RESTORE_DELAY = "MXTPU_FAULT_HOST_RESTORE_DELAY"
ENV_HOST_RESTORE_BUDGET = "MXTPU_SERVE_HOST_KV_RESTORE_BUDGET"

# chain anchor for the first block of every sequence (the radix root)
_ROOT = b"mxtpu-radix-root"


# why a block manager with a state pool keeps the radix prefix cache off
# (prefix_stats() and the engine's refusal both say it)
STATE_POOL_NO_PREFIX = ("a cached block holds K/V only; the recurrent state "
                        "at its edge was not kept")


# why a block manager with a window group keeps it off
WINDOW_NO_PREFIX = ("a window layer's blocks behind the window went back to "
                    "their free list; a cached prefix would need them again")


class NoFreeBlocks(Exception):
    """Raised when an allocation cannot be satisfied even after
    evicting every refcount-0 retained/cached block.  The scheduler
    catches this and preempts a running request instead of letting the
    cache OOM."""


def blocks_for(n_tokens, block_size):
    """Physical blocks needed to hold ``n_tokens`` cache slots."""
    return -(-n_tokens // block_size)


def _block_key(parent, token_ids):
    """Content-addressed key of one full block: chain-hash of the
    parent block's key and this block's token ids.  Chaining makes
    equal keys mean equal whole PREFIXES, not just equal blocks."""
    h = hashlib.sha1(parent)
    h.update(np.asarray(token_ids, np.int32).tobytes())
    return h.digest()


def salted_root(salt):
    """Radix root for a KV-affecting request condition — e.g. a LoRA
    ``adapter_id``, whose K/V projections differ from the base
    model's.  Equal salt means equal chain keys (same-adapter requests
    share prefixes exactly like before); a different salt yields a
    fully disjoint key space, so adapter K/V can never be reused for
    base rows or across adapters — not by the local radix walk, not by
    a handoff import, not by the fleet KV fabric.  ``None``/empty is
    the historical unsalted root: every pre-adapter chain key is
    byte-identical to what it always was."""
    if not salt:
        return _ROOT
    h = hashlib.sha1(_ROOT)
    h.update(str(salt).encode())
    return h.digest()


def chain_keys(token_ids, block_size, max_blocks=None, salt=None):
    """Chain keys of ``token_ids``'s full blocks, in prefix order.

    The tokenizer-side half of cache-aware routing: the fleet router
    hashes an incoming prompt with THIS function (same
    ``H(parent_key, block_tokens)`` chain as the radix index, no model
    loaded) and probes each replica's advertised ``RadixSummary`` for
    the longest cached ancestor.  Copy-on-write capped exactly like
    ``_walk``: the final token's block always recomputes, so it is
    never part of the routable prefix."""
    bs = int(block_size)
    if bs < 1 or token_ids is None:
        return []
    n_full = len(token_ids) // bs
    if n_full and n_full * bs > len(token_ids) - 1:
        n_full -= 1                    # COW: last span recomputes
    if max_blocks is not None:
        n_full = min(n_full, int(max_blocks))
    out = []
    parent = salted_root(salt)
    for b in range(n_full):
        key = _block_key(parent, token_ids[b * bs:(b + 1) * bs])
        out.append(key)
        parent = key
    return out


class RadixSummary:
    """Compact advertisement of the radix cache's contents — the
    ``kv_summary`` payload a replica publishes on ``/healthz`` /
    ``/statusz`` so the fleet router can score prefix affinity without
    ever walking the tree.

    Two complementary structures, both maintained O(1) per
    publish/evict event (incremental — never a full-tree walk, and
    ``snapshot()`` on the scrape path only packs bits):

    - a COUNTING Bloom filter over every published block key in either
      tier: the ``k`` probe positions are carved straight out of the
      key's sha1 bytes (the key already IS a uniform hash — no second
      hash family), ``add`` increments / ``remove`` decrements a
      uint16 count, and the snapshot packs ``count > 0`` into a base64
      bitmap (``m`` bits -> ``m/8`` bytes on the wire: ~512 B + ~1/3
      base64 overhead at the default m=4096).  The false-positive rate
      is bounded by ``(1 - e^(-k*n/m))^k`` (~2.4% at n=512 keys) and a
      false positive is HARMLESS by contract: the router sends a
      request to a replica that turns out cache-cold, which recomputes
      — never an error, never a wrong token.  False negatives cannot
      happen while counts stay below the uint16 ceiling (add saturates
      rather than wraps, so a saturated position just stays set).
    - ``top``: the most recently published chain keys (truncated hex,
      the handoff codec's 16-char idiom), bounded at ``top_k`` — an
      exact-membership fast path for the hottest chains.

    Mutations arrive under the BlockManager/HostKVPool locks; the
    summary keeps its own leaf lock anyway so the two tiers can never
    race an unguarded numpy increment."""

    def __init__(self, block_size, bloom_bits=None, top_k=None):
        self.block_size = int(block_size)
        m = (env_int("MXTPU_ROUTE_SUMMARY_BLOOM_BITS", 4096)
             if bloom_bits is None else int(bloom_bits))
        self.m = max(64, int(m))
        self.k = 4
        self.top_k = max(0, env_int("MXTPU_ROUTE_SUMMARY_TOPK", 32)
                         if top_k is None else int(top_k))
        self._lock = threading.Lock()
        self._counts = np.zeros(self.m, np.uint16)  # guarded-by: _lock
        self._top = OrderedDict()                   # guarded-by: _lock
        self.keys = 0                               # guarded-by: _lock
        self.version = 0                            # guarded-by: _lock

    def _positions(self, key):
        return [int.from_bytes(key[4 * i:4 * i + 4], "little") % self.m
                for i in range(self.k)]

    def add(self, key):
        """One block published (either tier) under ``key``."""
        with self._lock:
            for p in self._positions(key):
                if self._counts[p] < np.iinfo(np.uint16).max:
                    self._counts[p] += 1
            self.keys += 1
            self.version += 1
            if self.top_k:
                hexk = key.hex()[:16]
                self._top[hexk] = True
                self._top.move_to_end(hexk)
                while len(self._top) > self.top_k:
                    self._top.popitem(last=False)

    def remove(self, key):
        """One block unpublished/evicted (either tier)."""
        with self._lock:
            for p in self._positions(key):
                if self._counts[p] > 0:
                    self._counts[p] -= 1
            self.keys = max(0, self.keys - 1)
            self.version += 1
            self._top.pop(key.hex()[:16], None)

    def clear(self):
        with self._lock:
            self._counts[:] = 0
            self._top.clear()
            self.keys = 0
            self.version += 1

    def snapshot(self):
        """JSON-ready advertisement (the wire form ``match`` probes).
        Size-bounded by construction: m/8 bloom bytes + top_k hex
        keys, independent of how many blocks are cached."""
        with self._lock:
            bits = np.packbits(self._counts > 0).tobytes()
            return {"block_size": self.block_size,
                    "keys": self.keys,
                    "version": self.version,
                    "bloom": {"m": self.m, "k": self.k,
                              "bits": base64.b64encode(bits)
                              .decode("ascii")},
                    "top": list(self._top)}

    @staticmethod
    def match(snapshot, keys):
        """How many leading ``keys`` (full digests, prefix order) the
        ``snapshot`` advertises — the router-side probe.  Chaining
        makes the first miss final: a block cannot be cached without
        its ancestor, so a deeper bloom hit past a miss would be a
        guaranteed false positive.  Pure stdlib (bytes + int ops) so
        the per-request router path never touches numpy, and any
        malformed snapshot scores zero instead of raising."""
        if not snapshot or not keys:
            return 0
        bloom = snapshot.get("bloom") or {}
        try:
            m = int(bloom.get("m") or 0)
            k = int(bloom.get("k") or 0)
            raw = base64.b64decode(bloom.get("bits") or "")
        except (TypeError, ValueError):
            return 0
        bloom_ok = m > 0 and k > 0 and len(raw) * 8 >= m
        top = set(snapshot.get("top") or ())
        depth = 0
        for key in keys:
            if key.hex()[:16] in top:
                depth += 1
                continue
            if not bloom_ok:
                break
            pos = [int.from_bytes(key[4 * i:4 * i + 4], "little") % m
                   for i in range(k)]
            if all((raw[p >> 3] >> (7 - (p & 7))) & 1 for p in pos):
                depth += 1
            else:
                break
        return depth


class HostKVPool:
    """Bounded host-DRAM pool of evicted prefix-cache blocks.

    Entries are keyed by the block's content-addressed radix key and
    hold the block's K/V as host numpy arrays (plus the int8 scale
    slots under quantized KV) — the same content the device block held,
    so a restore is byte-identical to recompute by construction.  The
    pool runs its own LRU under ``max_bytes`` with the same leaf-only
    discipline as the device tier (an entry whose CHILD is hosted is
    never evicted first: without the interior, the deeper entries are
    unreachable by the chain walk and would be dead bytes — the child
    link is registered before any room-making eviction, so an insert
    can never reclaim its own chain's interior).  An entry whose
    parent has already left BOTH tiers (a niche partial-unpublish
    path) is unreachable until its parent re-parks; the LRU simply
    ages it out.

    Chaos hook: ``MXTPU_FAULT_HOST_RESTORE_DELAY`` simulates a slow
    DRAM copy per claim; with ``MXTPU_SERVE_HOST_KV_RESTORE_BUDGET``
    set, a delay past the budget degrades the claim to a miss (the
    entry stays hosted, the engine recomputes) instead of stalling the
    serving step loop on the copy.
    """

    def __init__(self, max_bytes, block_tokens=0):
        self.max_bytes = int(max_bytes)
        if self.max_bytes <= 0:
            raise ValueError(
                f"max_bytes must be > 0 (got {max_bytes}); an absent "
                "pool is host_pool=None, not a zero-byte pool")
        self.block_tokens = int(block_tokens)
        self._lock = threading.RLock()
        # key -> (parent_key, arrays tuple, nbytes), LRU order
        self._entries = OrderedDict()   # guarded-by: _lock
        # parent key -> number of hosted entries chained under it
        # (leaf == absent); survives the parent's own restore so a
        # re-offloaded interior keeps protecting its hosted children
        self._by_parent = {}            # guarded-by: _lock
        # (on_add, on_remove) key callbacks the owning BlockManager
        # registers so its RadixSummary tracks host-tier membership
        # incrementally (None = nobody advertising)
        self._listener = None           # guarded-by: _lock
        self.bytes_used = 0             # guarded-by: _lock
        self.bytes_peak = 0             # guarded-by: _lock
        self.offloads = 0               # guarded-by: _lock
        self.restores = 0               # guarded-by: _lock
        self.evictions = 0              # guarded-by: _lock
        self.rejects = 0                # guarded-by: _lock
        self.degraded = 0               # guarded-by: _lock
        self.discarded_tokens = 0       # guarded-by: _lock
        self.fault_delay_s = env_float(ENV_HOST_RESTORE_DELAY, 0.0)
        self.restore_budget_s = env_float(ENV_HOST_RESTORE_BUDGET, 0.0)
        self._m_offloads = telemetry.counter(
            "mxtpu_serve_host_kv_offloads_total",
            "prefix-cache blocks parked in the host-DRAM tier")
        self._m_discarded = telemetry.counter(
            "mxtpu_serve_prefix_discarded_tokens_total",
            "tokens whose cached K/V an eviction threw away for good")
        # a fleet silently degrading restores to recompute must be
        # visible in Prometheus, not only in the pool's local counter
        self._m_degraded = telemetry.counter(
            "mxtpu_serve_host_kv_degraded_total",
            "host-tier restore claims degraded to recompute "
            "(restore budget exceeded)")

    def __len__(self):
        with self._lock:
            return len(self._entries)

    def has(self, key):
        with self._lock:
            return key in self._entries

    def keys(self):
        """Every hosted content key (LRU order) — the summary rebuild
        after a ``BlockManager.reset()``, never the scrape path."""
        with self._lock:
            return list(self._entries)

    def set_listener(self, on_add, on_remove):
        """Register per-key add/remove callbacks (the BlockManager's
        RadixSummary maintenance).  Callbacks run under ``_lock`` and
        must be leaf operations — they get the key only."""
        with self._lock:
            self._listener = (on_add, on_remove)

    def _remove(self, key):
        """Drop one entry (called under ``_lock``); returns its
        ``(parent, arrays, nbytes)``."""
        with self._lock:
            parent, arrays, nbytes = self._entries.pop(key)
            self.bytes_used -= nbytes
            if parent is not None and parent in self._by_parent:
                self._by_parent[parent] -= 1
                if not self._by_parent[parent]:
                    del self._by_parent[parent]
            if self._listener is not None:
                self._listener[1](key)
            return parent, arrays, nbytes

    def _evict_leaf(self):
        """Reclaim the oldest hosted entry with no hosted children —
        the host tier's final discard (called under ``_lock``)."""
        with self._lock:
            for key in self._entries:          # oldest first
                if self._by_parent.get(key, 0) == 0:
                    self._remove(key)
                    self.evictions += 1
                    self.discarded_tokens += self.block_tokens
                    self._m_discarded.inc(self.block_tokens)
                    return True
            return False

    def _insert(self, key, parent, arrays):
        """Budget-checked insert (called under ``_lock``); returns
        whether the entry was parked."""
        with self._lock:
            nbytes = sum(int(a.nbytes) for a in arrays)
            if nbytes > self.max_bytes:
                self.rejects += 1
                return False
            if key in self._entries:
                # re-offload of a restored block: content-addressed
                # keys mean the bytes are identical — refresh recency
                self._remove(key)
            # register the parent link BEFORE making room: the budget
            # eviction below must never reclaim the incoming entry's
            # own hosted parent to fit the child — that would park
            # bytes the chain walk can no longer reach
            if parent is not None:
                self._by_parent[parent] = self._by_parent.get(parent, 0) + 1
            while self.bytes_used + nbytes > self.max_bytes:
                if not self._evict_leaf():
                    if parent is not None and parent in self._by_parent:
                        self._by_parent[parent] -= 1
                        if not self._by_parent[parent]:
                            del self._by_parent[parent]
                    self.rejects += 1
                    return False
            self._entries[key] = (parent, tuple(arrays), nbytes)
            self.bytes_used += nbytes
            self.bytes_peak = max(self.bytes_peak, self.bytes_used)
            if self._listener is not None:
                self._listener[0](key)
            return True

    def put(self, key, parent, arrays):
        """Park one evicted block's host copies under ``key``.  Returns
        False (the caller counts a discard) when the entry cannot fit
        even after evicting every hosted leaf."""
        with self._lock:
            if not self._insert(key, parent, arrays):
                return False
            self.offloads += 1
            self._m_offloads.inc()
            return True

    def claim(self, key):
        """Pop ``key``'s host copies for a device restore; None on
        miss — including the chaos-degraded case, where the simulated
        DRAM copy would exceed the restore budget and the entry STAYS
        hosted while the caller falls back to recompute."""
        with self._lock:
            if key not in self._entries:
                return None
            if self.fault_delay_s:
                if (self.restore_budget_s
                        and self.fault_delay_s > self.restore_budget_s):
                    self.degraded += 1
                    self._m_degraded.inc()
                    return None
                time.sleep(self.fault_delay_s)   # the simulated copy
            _, arrays, _ = self._remove(key)
            self.restores += 1
            return arrays

    def peek(self, key):
        """``key``'s host arrays WITHOUT claiming (the entry stays
        parked, recency untouched); None on miss.  The handoff export
        path reads parked blocks through this — an export must never
        chaos-delay, degrade, or pop the local tier."""
        with self._lock:
            entry = self._entries.get(key)
            return None if entry is None else entry[1]

    def unclaim(self, key, parent, arrays):
        """Return a claimed entry after a failed allocation (no new
        offload is counted — the bytes never left the pool's custody
        semantically)."""
        with self._lock:
            self._insert(key, parent, arrays)

    def clear(self):
        """Deterministic release of every hosted array (engine
        shutdown rides this alongside its device-buffer deletes)."""
        with self._lock:
            if self._listener is not None:
                for key in self._entries:
                    self._listener[1](key)
            self._entries.clear()
            self._by_parent.clear()
            self.bytes_used = 0

    def stats(self):
        """JSON-ready snapshot — the ``/statusz`` ``host_kv`` section
        and the replica load signal's host-tier occupancy."""
        with self._lock:
            return {"max_bytes": self.max_bytes,
                    "bytes_used": self.bytes_used,
                    "bytes_peak": self.bytes_peak,
                    "utilization": round(
                        self.bytes_used / self.max_bytes, 4),
                    "entries": len(self._entries),
                    "offloads": self.offloads,
                    "restores": self.restores,
                    "evictions": self.evictions,
                    "rejects": self.rejects,
                    "degraded": self.degraded,
                    "discarded_tokens": self.discarded_tokens}


class WindowGroup:
    """The blocks of the layers that see only the last ``window``
    positions: a LAYER GROUP with a cache stack, a free list and a table
    per request of its own, beside the global group's (the
    ``BlockManager`` proper, whose layers see the whole context).

    A request holds the blocks that cover the positions a later query can
    still see, a contiguous run of LOGICAL blocks ``[first, first +
    len(blocks))`` (logical block ``j`` covers positions ``[j * bs, (j +
    1) * bs)``): :meth:`cover` extends the run in front of a pass,
    :meth:`trim` returns the blocks whose last position has left the
    window of the NEXT query to the free list after it.  Between passes a
    request holds at most ``per_request = ceil(window / bs) + 1`` blocks;
    INSIDE a chunk pass it also holds the chunk's own span (the pass
    writes, then attends through the table), at most ``scratch`` blocks
    more, and one chunk pass runs at a time.  Admission is by
    reservation, so that no pass can find the free list short: a request
    is admitted while ``(admitted + 1) * per_request + scratch`` blocks
    exist.  Block 0 is the null block, as in the global group: a table
    reads 0 where the request holds nothing.

    Not locked on its own: ``BlockManager`` calls it under its lock."""

    def __init__(self, num_blocks, block_size, window, scratch=0):
        if window < 1:
            raise ValueError("a window group needs window >= 1")
        self.num_blocks, self.block_size = int(num_blocks), int(block_size)
        self.window, self.scratch = int(window), int(scratch)
        self.per_request = -(-self.window // self.block_size) + 1
        if self.capacity < 1:
            raise ValueError(
                f"a window group of {num_blocks} blocks cannot hold one "
                f"request: {self.per_request} blocks a request, "
                f"{self.scratch} for a chunk pass, and the null block")
        self.freed = 0               # blocks returned by trim(), lifetime
        self.reset()

    def reset(self):
        self._free = deque(range(1, self.num_blocks))
        self._first = {}             # rid -> first held logical block
        self._blocks = {}            # rid -> physical blocks, in order

    @property
    def total_blocks(self):
        return self.num_blocks - 1

    @property
    def capacity(self):
        """Requests the group can hold at once."""
        return (self.total_blocks - self.scratch) // self.per_request

    @property
    def blocks_in_use(self):
        return self.total_blocks - len(self._free)

    def can_admit(self):
        return len(self._blocks) < self.capacity

    def admit(self, rid):
        if not self.can_admit():
            raise NoFreeBlocks(
                f"request {rid!r}: the window group holds "
                f"{len(self._blocks)} requests, its {self.total_blocks} "
                f"blocks cover {self.capacity}")
        self._first[rid], self._blocks[rid] = 0, []

    def cover(self, rid, lo, hi):
        """Hold blocks for positions ``[lo, hi)`` in front of a pass that
        writes them (what is held already stays)."""
        bs, blocks = self.block_size, self._blocks[rid]
        if not blocks:
            self._first[rid] = lo // bs
        end = self._first[rid] + len(blocks)
        if lo // bs > end:
            raise ValueError(
                f"request {rid!r}: positions [{lo}, {hi}) leave a gap "
                f"behind logical block {end} of the window group")
        need = (hi - 1) // bs + 1 - end
        if need > len(self._free):
            raise NoFreeBlocks(
                f"request {rid!r} needs {need} window-group blocks, "
                f"{len(self._free)} free")
        blocks.extend(self._free.popleft() for _ in range(max(need, 0)))

    def trim(self, rid, next_pos):
        """Return the blocks no query at ``next_pos`` or later can see;
        the number returned."""
        blocks = self._blocks.get(rid)
        if not blocks:
            return 0
        # logical block j is dead when its last position, (j + 1) * bs - 1,
        # lies behind the window's first, next_pos - window + 1
        alive = max(next_pos - self.window + 1, 0) // self.block_size
        n = min(max(alive - self._first[rid], 0), len(blocks))
        self._free.extend(blocks[:n])
        del blocks[:n]
        self._first[rid] += n
        self.freed += n
        return n

    def release(self, rid):
        self._free.extend(self._blocks.pop(rid, ()))
        self._first.pop(rid, None)

    def held(self, rid):
        """(first logical block, physical blocks) of ``rid``."""
        return self._first[rid], list(self._blocks[rid])

    def table(self, rid, out):
        """``rid``'s LOGICAL table into ``out (width,)``, a zeroed int32
        row: the null block stays wherever it holds nothing."""
        first, blocks = self._first[rid], self._blocks[rid]
        out[first:first + len(blocks)] = blocks
        return out

    def stats(self):
        return {"in_use": self.blocks_in_use, "free": len(self._free),
                "total": self.total_blocks, "requests": len(self._blocks),
                "capacity": self.capacity, "window": self.window,
                "per_request": self.per_request, "scratch": self.scratch,
                "freed": self.freed}


class BlockManager:
    """Host-side block accounting.  Mutations are serialized by the
    RLock below: the scheduler drives allocation from the engine's step
    thread while /statusz snapshots and admission checks may read from
    others (reads of the annotated structures are point-in-time
    snapshots; every write path is lock-wrapped and enforced by
    mxtpu-lint's unlocked-shared-state checker).  Reentrant because
    ``allocate``/``ensure_capacity`` call ``_take`` under the lock."""

    def __init__(self, num_blocks, block_size, prefix_cache=None,
                 host_pool=None, state_slots=0, window_group=None):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is the null block)")
        if block_size < 1:
            raise ValueError("block_size must be >= 1")
        self.num_blocks = num_blocks
        self.block_size = block_size
        if prefix_cache is None:
            prefix_cache = env_flag("MXTPU_SERVE_PREFIX_CACHE", True)
        self.prefix_cache = bool(prefix_cache)
        # host-DRAM offload tier (None = off: every prefix eviction
        # discards, exactly the pre-offload lifecycle)
        self.host = host_pool
        self._lock = threading.RLock()
        # a hybrid decoder's per-request state pool (serve/hybrid.py):
        # a request takes ONE slot with its first blocks and gives it
        # back with them (finish, cancel, preempt), under the same lock.
        # Slot 0 is the null slot padded rows name; 0 slots = a
        # gpt() engine, and every path below is the pre-state one
        self.state_slots = int(state_slots)
        self.prefix_off_reason = None
        if self.state_slots:
            if self.prefix_cache:
                raise ValueError(
                    "a state pool and the radix prefix cache cannot go "
                    "together: nobody keeps the state at a block's edge")
            self.prefix_off_reason = STATE_POOL_NO_PREFIX
        self._slot_free = deque(range(1, self.state_slots + 1))  # guarded-by: _lock
        self._slot_of = {}                        # guarded-by: _lock
        # the window layers' group (a WindowGroup or None): a request is
        # admitted into both groups or neither, and free() leaves both
        self.window = window_group
        if self.window is not None:
            if self.prefix_cache:
                raise ValueError(
                    "a window group and the radix prefix cache cannot go "
                    "together: " + WINDOW_NO_PREFIX)
            if self.window.block_size != block_size:
                raise ValueError("both groups share one block size")
            self.prefix_off_reason = WINDOW_NO_PREFIX
        # block 0 reserved as the null/padding block
        self._free = deque(range(1, num_blocks))  # guarded-by: _lock
        self._tables = {}                         # guarded-by: _lock
        self._lens = {}                           # guarded-by: _lock
        self._retained = OrderedDict()            # guarded-by: _lock
        # block id -> live table references (entries removed at 0)
        self._refs = {}                           # guarded-by: _lock
        # content-addressed radix index: key -> published block id
        self._index = {}                          # guarded-by: _lock
        self._key_of = {}                         # guarded-by: _lock
        self._parent = {}                         # guarded-by: _lock
        # key -> number of cached (published) children; leaf == absent
        self._children = {}                       # guarded-by: _lock
        # refcount-0 published blocks, reusable AND evictable (LRU)
        self._lru = OrderedDict()                 # guarded-by: _lock
        # per-request published chain of block keys (prefix order)
        self._chain = {}                          # guarded-by: _lock
        # reclaim EVENTS, not blocks: one legacy retained SET (however
        # many blocks it held) or one published leaf block each count
        # 1 — trend block-granular cache pressure via prefix_evictions
        self.evictions = 0                        # guarded-by: _lock
        self.prefix_hits = 0                      # guarded-by: _lock
        self.prefix_misses = 0                    # guarded-by: _lock
        # the subset of prefix_hits that resurrected >= 1 refcount-0
        # block parked in the prefix LRU (vs hits that only shared
        # blocks another live table already pinned) — what separates
        # "the park saved us" from "concurrency saved us" in the
        # cache-route bench
        self.prefix_resurrections = 0             # guarded-by: _lock
        self.prefix_tokens_saved = 0              # guarded-by: _lock
        self.prefix_evictions = 0                 # guarded-by: _lock
        # tokens whose cached K/V a prefix eviction threw away FOR GOOD
        # (not parked in the host tier) — the recompute debt the
        # offload tier exists to drive down; the host pool adds its own
        # final-discard count on top in prefix_stats()
        self.prefix_discarded_tokens = 0          # guarded-by: _lock
        self.host_hits = 0                        # guarded-by: _lock
        self.host_restored_tokens = 0             # guarded-by: _lock
        # device→host extraction for offload, registered by the cache
        # owner (the engine) via set_offload_source; None = every
        # eviction discards even with a pool attached
        self._offload_fetch = None                # guarded-by: _lock
        # (block, host arrays) pairs awaiting the engine's host→device
        # restore dispatch — drained via take_pending_restores() before
        # the first program that reads the blocks
        self._pending_restores = []               # guarded-by: _lock
        # rid -> tokens of its table restored from the host tier (the
        # admission trace / statusz in-flight split of cached_tokens)
        self._host_tokens = {}                    # guarded-by: _lock
        self._m_hits = telemetry.counter(
            "mxtpu_serve_prefix_hits_total",
            "prefix-cache lookups that reused >= 1 cached block")
        self._m_misses = telemetry.counter(
            "mxtpu_serve_prefix_misses_total",
            "prefix-cache lookups that reused nothing")
        self._m_saved = telemetry.counter(
            "mxtpu_serve_prefix_tokens_saved_total",
            "prompt tokens whose prefill was skipped via the prefix cache")
        self._m_discarded = telemetry.counter(
            "mxtpu_serve_prefix_discarded_tokens_total",
            "tokens whose cached K/V an eviction threw away for good")
        self._m_restored = telemetry.counter(
            "mxtpu_serve_host_kv_restored_tokens_total",
            "prompt tokens restored host->device instead of recomputed")
        self._m_resurrections = telemetry.counter(
            "mxtpu_serve_prefix_resurrections_total",
            "prefix hits that revived >= 1 block parked refcount-0 "
            "in the prefix LRU")
        # the routable-cache advertisement (None with the prefix cache
        # off — nothing content-addressed to advertise); maintained
        # incrementally at every publish/unpublish site in BOTH tiers
        self._summary = (RadixSummary(block_size)
                         if self.prefix_cache else None)
        if self._summary is not None and host_pool is not None:
            host_pool.set_listener(self._summary.add,
                                   self._summary.remove)
            for key in host_pool.keys():
                self._summary.add(key)

    def set_offload_source(self, fetch):
        """Register the device→host block extractor the eviction path
        calls to park a reclaimed block in the host tier (``fetch(blk)
        -> tuple of host arrays``, or None to skip offload)."""
        with self._lock:
            self._offload_fetch = fetch

    # -- capacity ------------------------------------------------------------
    @property
    def total_blocks(self):
        """Allocatable blocks (the null block excluded)."""
        return self.num_blocks - 1

    @property
    def blocks_in_use(self):
        """Distinct physical blocks referenced by at least one table
        (a block shared by N requests counts ONCE — it occupies one
        physical block, whatever its refcount)."""
        with self._lock:
            return len(self._refs)

    @property
    def free_blocks(self):
        """Immediately or lazily reclaimable blocks."""
        with self._lock:
            return (len(self._free) + len(self._lru)
                    + sum(len(b) for b in self._retained.values()))

    @property
    def retained_blocks(self):
        """Blocks parked refcount-0 (reclaimable; published ones hold
        reusable K/V in the prefix LRU, unpublished ones are the legacy
        per-request retained tier)."""
        with self._lock:
            return (len(self._lru)
                    + sum(len(b) for b in self._retained.values()))

    def utilization(self):
        """Blocks in use over blocks that exist, both groups counted."""
        if self.window is not None:
            with self._lock:
                return ((self.blocks_in_use + self.window.blocks_in_use)
                        / max(1, self.total_blocks
                              + self.window.total_blocks))
        return self.blocks_in_use / max(1, self.total_blocks)

    def group_stats(self):
        """Per layer group, what it holds right now (None without a
        window group): the ``kv_groups`` section of ``/statusz``."""
        if self.window is None:
            return None
        with self._lock:
            return {"global": {"in_use": self.blocks_in_use,
                               "free": len(self._free),
                               "total": self.total_blocks,
                               "requests": len(self._tables)},
                    "window": self.window.stats()}

    def occupancy(self):
        """One JSON-ready snapshot of the block accounting — the
        /statusz and flight-dump occupancy section.  Counts are BLOCK
        counts and identical at every tensor-parallel degree; byte
        translation per chip lives with the cache owner
        (``Engine.kv_cache_stats``), which knows the sharding.  Taken
        under the lock: a /statusz scrape must see one consistent
        snapshot, not a dict resizing under its iteration."""
        with self._lock:
            return {"in_use": self.blocks_in_use,
                    "retained": self.retained_blocks,
                    "free": len(self._free),
                    "total": self.total_blocks,
                    "utilization": round(self.utilization(), 4),
                    "evictions": self.evictions,
                    "prefix_cache": self.prefix_stats()}

    def prefix_stats(self):
        """The prefix-cache section of ``occupancy()``/``/statusz``:
        how much of the radix index is populated, shared and reusable,
        and the hit/miss/evict counters that explain a cache-cold
        replica."""
        with self._lock:
            looked = self.prefix_hits + self.prefix_misses
            shared = sum(1 for r in self._refs.values() if r > 1)
            discarded = self.prefix_discarded_tokens
            if self.host is not None:
                # the host tier's own LRU evictions are the FINAL
                # discard — the two sites together are every token
                # whose cached K/V is gone for good
                discarded += self.host.discarded_tokens
            return {"enabled": self.prefix_cache,
                    # why it is off when the model, not the operator,
                    # turned it off (a hybrid decoder's state pool)
                    **({"disabled_reason": self.prefix_off_reason}
                       if self.prefix_off_reason else {}),
                    "cached_blocks": len(self._index),
                    "reusable_blocks": len(self._lru),
                    "shared_blocks": shared,
                    "max_refcount": max(self._refs.values(), default=0),
                    "hits": self.prefix_hits,
                    "misses": self.prefix_misses,
                    "resurrections": self.prefix_resurrections,
                    "hit_rate": (round(self.prefix_hits / looked, 4)
                                 if looked else None),
                    "tokens_saved": self.prefix_tokens_saved,
                    "evictions": self.prefix_evictions,
                    "discarded_tokens": discarded,
                    "host_hits": self.host_hits,
                    "host_restored_tokens": self.host_restored_tokens}

    def host_stats(self):
        """The host-tier occupancy snapshot (None without a pool)."""
        with self._lock:
            return None if self.host is None else self.host.stats()

    def summary(self):
        """The JSON-ready ``RadixSummary`` advertisement the replica
        publishes on ``/healthz``/``/statusz`` (None with the prefix
        cache off).  O(m/8) bit-packing, never a tree walk — safe on
        the scrape path at any cache size."""
        if self._summary is None:
            return None
        return self._summary.snapshot()

    def host_tokens(self, rid):
        """Tokens of ``rid``'s current table that were restored from
        the host tier rather than recomputed (0 for everyone else)."""
        with self._lock:
            return self._host_tokens.get(rid, 0)

    def has_pending_restores(self):
        """Whether an allocation has queued host-tier restores that the
        engine has not dispatched yet."""
        with self._lock:
            return bool(self._pending_restores)

    def take_pending_restores(self):
        """Atomically drain the queued (block, host arrays) restores —
        the engine dispatches the host→device copies before the first
        program that reads the blocks, so the step loop never blocks on
        a copy and the restored spans are in place by construction."""
        with self._lock:
            out, self._pending_restores = self._pending_restores, []
            return out

    def can_allocate(self, n_tokens, token_ids=None):
        """Whether ``allocate(n_tokens, token_ids=...)`` would succeed
        right now: blocks a prefix walk would reuse don't need to come
        off the free list.  Host-tier hits are counted on the TOKEN
        side only — a restored span still claims a fresh device block
        (the capacity math must never mistake DRAM bytes for HBM
        blocks), which ``prefix_probe``'s split encodes."""
        need = blocks_for(n_tokens, self.block_size)
        if token_ids is not None:
            cached_blocks, _ = self.prefix_probe(token_ids)
            need -= cached_blocks
        with self._lock:
            if self.state_slots and not self._slot_free:
                return False          # blocks AND a state slot, or neither
            if self.window is not None and not self.window.can_admit():
                return False          # both groups, or neither
        return need <= self.free_blocks

    def fits_at_all(self, n_tokens):
        """Whether a request of ``n_tokens`` could EVER hold the cache
        alone — the admission-time rejection test (back-pressure
        instead of a guaranteed later OOM)."""
        return blocks_for(n_tokens, self.block_size) <= self.total_blocks

    # -- prefix lookup -------------------------------------------------------
    def _walk(self, token_ids, salt=None):
        """Longest cached prefix of ``token_ids`` at block granularity
        (called under ``_lock``): returns the matched device
        ``[(key, block)]`` chain plus the ``[key]`` continuation the
        HOST tier holds past the device break (empty without a pool).
        Copy-on-write capped so at least ONE token is left for the
        engine to recompute (a fully-cached prompt still needs its last
        position's logits, and the recompute must never scribble into
        the shared final block) — host hits shed first: they are the
        deeper end of the chain.  ``salt`` scopes the chain (see
        :func:`salted_root`): an adapter request can only ever hit
        same-adapter K/V."""
        n = len(token_ids)
        bs = self.block_size
        hits = []
        parent = salted_root(salt)
        while (len(hits) + 1) * bs <= n:
            b = len(hits)
            key = _block_key(parent, token_ids[b * bs:(b + 1) * bs])
            blk = self._index.get(key)
            if blk is None:
                break
            hits.append((key, blk))
            parent = key
        host = []
        if self.host is not None:
            while (len(hits) + len(host) + 1) * bs <= n:
                b = len(hits) + len(host)
                key = _block_key(parent, token_ids[b * bs:(b + 1) * bs])
                if not self.host.has(key):
                    break
                host.append(key)
                parent = key
        while (len(hits) + len(host)) * bs > n - 1:
            (host or hits).pop()       # COW: recompute the final span
        return hits, host

    def prefix_probe(self, token_ids, salt=None):
        """(cached_blocks, cached_tokens) an ``allocate`` with these
        ``token_ids`` would reuse — admission-time capacity math, no
        state mutated.  ``cached_blocks`` counts only DEVICE hits (the
        blocks that need not come off the free list: a host-tier hit
        restores into a fresh device block); ``cached_tokens`` is the
        full prefill span skipped, device and host together."""
        with self._lock:
            if not self.prefix_cache or token_ids is None:
                return 0, 0
            hits, host = self._walk(token_ids, salt=salt)
            return len(hits), (len(hits) + len(host)) * self.block_size

    # -- prefill/decode handoff ----------------------------------------------
    def export_blocks(self, rid, token_ids, salt=None):
        """Serialize ``rid``'s cached prefix chain for ``token_ids``
        (its prompt) as wire records — the prefill side of a
        disaggregated prefill→decode handoff.

        Returns ``[(key, parent_key, block_token_ids, arrays), ...]``
        in prefix order: ``key``/``parent_key`` are the content-
        addressed radix keys (``parent_key`` None for the root block),
        ``arrays`` the block's host copies in the offload-tier layout
        (K, V[, int8 scale pairs]).  Derivation is purely content-
        addressed — the chain is re-walked from the token ids, so the
        export works both while ``rid`` is live and right after it
        finished (its published blocks park refcount-0 with K/V
        intact).  Device-resident blocks gather D2H through the
        registered offload fetch; already-parked blocks are peeked
        from the host pool without claiming.  A block missing from
        both tiers (evicted under pressure) ends the chain — the
        importer recomputes the rest, never a gap."""
        if self.state_slots:
            raise ValueError(
                "block export: a hybrid decoder's request is K/V blocks "
                "AND a recurrent state, and only the blocks would travel")
        if self.window is not None:
            raise ValueError("block export: " + WINDOW_NO_PREFIX)
        with self._lock:
            if not self.prefix_cache or self._offload_fetch is None:
                return []
            bs = self.block_size
            n = len(token_ids)
            out = []
            parent = salted_root(salt)
            parent_key = None
            while (len(out) + 1) * bs <= n:
                b = len(out)
                tok = [int(t) for t in token_ids[b * bs:(b + 1) * bs]]
                key = _block_key(parent, tok)
                blk = self._index.get(key)
                arrays = None
                if blk is not None:
                    arrays = self._offload_fetch(blk)
                elif self.host is not None:
                    arrays = self.host.peek(key)
                if arrays is None:
                    break
                out.append((key, parent_key, tok, tuple(arrays)))
                parent_key = key
                parent = key
            return out

    def import_blocks(self, records, salt=None):
        """Ingest handoff records into the host tier under their
        content keys — the decode side of a prefill→decode handoff.

        ``records`` is ``export_blocks``'s shape, in prefix order;
        ``arrays`` may be None for a block the sender's dedup probe
        found already hosted here (bytes skipped on the wire).  Every
        record is VERIFIED against the chain hash before it parks: a
        key that doesn't equal ``H(parent, token_ids)``, a record out
        of chain order, or a missing/undersized payload breaks the
        chain right there (content addressing is the integrity check —
        a truncated or corrupted handoff degrades to recompute, it can
        never poison the radix index).  Returns ``(imported, deduped,
        rejected)`` block counts; imported blocks are radix-walk hits
        from the very next ``allocate``, restored HBM-ward by the
        existing async restore path."""
        if self.state_slots:
            raise ValueError(
                "block import: a hybrid decoder cannot resume from K/V "
                "blocks alone (no recurrent state comes with them)")
        if self.window is not None:
            raise ValueError("block import: " + WINDOW_NO_PREFIX)
        imported = deduped = 0
        with self._lock:
            expect_parent = None
            parent = salted_root(salt)
            for key, parent_key, token_ids, arrays in records:
                if (parent_key != expect_parent
                        or len(token_ids) != self.block_size
                        or _block_key(parent, token_ids) != key):
                    break
                if key in self._index or (self.host is not None
                                          and self.host.has(key)):
                    deduped += 1
                elif (arrays is None or self.host is None
                        or not self.host.put(key, parent_key,
                                             tuple(arrays))):
                    break
                else:
                    imported += 1
                expect_parent = key
                parent = key
        return imported, deduped, len(records) - imported - deduped

    def has_blocks(self, keys):
        """The subset of ``keys`` cached in EITHER tier right now —
        the handoff dedup probe (a sender skips the bytes of blocks
        the receiver already holds; a probe-then-evict race just means
        the chain breaks at import and the tail recomputes)."""
        with self._lock:
            return [k for k in keys
                    if k in self._index
                    or (self.host is not None and self.host.has(k))]

    # -- allocation ----------------------------------------------------------
    def _take(self, n):
        """Pop n free blocks, evicting refcount-0 parked blocks as
        needed: legacy retained sets first (their K/V is stale by
        construction), then prefix-LRU radix LEAVES oldest-first (an
        interior block never leaves before its cached children)."""
        with self._lock:
            while len(self._free) < n:
                if self._retained:
                    _, blocks = self._retained.popitem(last=False)  # oldest
                    self._free.extend(blocks)
                    self.evictions += 1
                    continue
                if not self._evict_prefix_leaf():
                    raise NoFreeBlocks(
                        f"need {n} blocks, {len(self._free)} free and "
                        "nothing refcount-0 left to evict")
            taken = [self._free.popleft() for _ in range(n)]
            for blk in taken:
                self._refs[blk] = 1
            return taken

    def _evict_prefix_leaf(self):
        """Reclaim the oldest refcount-0 published block that is a
        radix leaf (no cached children).  With a host pool attached the
        block's K/V parks device→host under its existing content key
        before the device block is reused; otherwise (or when the pool
        rejects it) the K/V is gone for good and ``discarded_tokens``
        counts the loss.  Reentrant-locked: every caller already holds
        ``_lock``."""
        with self._lock:
            for key in self._lru:       # oldest first
                if self._children.get(key, 0) == 0:
                    blk = self._index[key]
                    parked = False
                    if (self.host is not None
                            and self._offload_fetch is not None):
                        arrays = self._offload_fetch(blk)
                        if arrays is not None:
                            parked = self.host.put(
                                key, self._parent.get(key), arrays)
                    if not parked:
                        self.prefix_discarded_tokens += self.block_size
                        self._m_discarded.inc(self.block_size)
                    self._unpublish(key)
                    self._free.append(blk)
                    self.evictions += 1
                    self.prefix_evictions += 1
                    return True
            return False

    def _unpublish(self, key):
        """Drop ``key`` from the radix index; returns its physical
        block.  Reentrant-locked: every caller already holds ``_lock``."""
        with self._lock:
            blk = self._index.pop(key)
            self._key_of.pop(blk, None)
            parent = self._parent.pop(key, None)
            if parent is not None and parent in self._children:
                self._children[parent] -= 1
                if not self._children[parent]:
                    del self._children[parent]
            self._children.pop(key, None)
            self._lru.pop(key, None)
            if self._summary is not None:
                self._summary.remove(key)
            return blk

    def _ref_hit(self, blk):
        """Take one reference on a cached block: a refcount-0 LRU
        resident leaves the evictable tier the moment a table starts
        reading it.  Returns whether the block was actually parked in
        the LRU (a RESURRECTION, as opposed to sharing a block another
        live table already pins).  Reentrant-locked: callers already
        hold ``_lock``."""
        with self._lock:
            self._refs[blk] = self._refs.get(blk, 0) + 1
            if self._refs[blk] == 1:
                return self._lru.pop(self._key_of[blk], None) is not None
            return False

    def allocate(self, rid, n_tokens, token_ids=None, salt=None):
        """Create ``rid``'s block table covering ``n_tokens`` slots.

        Without ``token_ids`` (legacy callers): fresh blocks only,
        returns the table list.  With ``token_ids`` (the sequence the
        engine is about to prefill): the longest cached prefix is
        reused — hit blocks head the table with their refcounts
        incremented, only the remainder comes off the free list — and
        the return is ``(table, cached_tokens)`` so the caller prefills
        just the suffix."""
        with self._lock:
            if rid in self._tables:
                raise ValueError(
                    f"request {rid!r} already has a block table")
            if rid in self._retained:
                # a preempted request resuming: its parked UNPUBLISHED
                # blocks hold stale K/V (resume recomputes), so reclaim
                # them up front rather than leaking the entry when this
                # rid is freed again later (its published blocks live
                # in the prefix index and may be hit again right here)
                self._free.extend(self._retained.pop(rid))
            if self.state_slots and not self._slot_free:
                raise NoFreeBlocks(
                    f"request {rid!r} needs a state slot, all "
                    f"{self.state_slots} are held")
            if self.window is not None and not self.window.can_admit():
                raise NoFreeBlocks(
                    f"request {rid!r}: the window group is full "
                    f"({self.window.capacity} requests)")
            hits, host_keys = [], []
            if self.prefix_cache and token_ids is not None:
                hits, host_keys = self._walk(token_ids, salt=salt)
            # clear-miss precheck BEFORE any mutation or eviction (the
            # same optimistic math as can_allocate, one walk instead of
            # two): a request that cannot fit even by reclaiming every
            # parked block must not evict anything, count a hit, or
            # take references on the way to failing.  Host hits never
            # discount the block need — a restored span still claims a
            # fresh device block
            if blocks_for(n_tokens, self.block_size) - len(hits) \
                    > self.free_blocks:
                raise NoFreeBlocks(
                    f"request {rid!r} needs "
                    f"{blocks_for(n_tokens, self.block_size)} blocks "
                    f"({len(hits)} cached), {self.free_blocks} "
                    "free/reclaimable")
            # claim host entries BEFORE _take: eviction inside _take
            # offloads more blocks, and the pool's own LRU churn could
            # otherwise evict the very entries this walk matched.  A
            # claim that degrades (chaos restore-delay past the budget)
            # truncates the restored span — the rest recomputes
            claimed = []
            parent_key = hits[-1][0] if hits else None
            for key in host_keys:
                arrays = self.host.claim(key)
                if arrays is None:
                    break
                claimed.append((key, parent_key, arrays))
                parent_key = key
            if self.prefix_cache and token_ids is not None:
                if hits or claimed:
                    saved = (len(hits) + len(claimed)) * self.block_size
                    self.prefix_hits += 1
                    self.prefix_tokens_saved += saved
                    self._m_hits.inc()
                    self._m_saved.inc(saved)
                else:
                    self.prefix_misses += 1
                    self._m_misses.inc()
                if claimed:
                    self.host_hits += 1
                    self.host_restored_tokens += \
                        len(claimed) * self.block_size
                    self._m_restored.inc(len(claimed) * self.block_size)
                resurrected = 0
                for _, blk in hits:
                    if self._ref_hit(blk):
                        resurrected += 1
                if resurrected:
                    self.prefix_resurrections += 1
                    self._m_resurrections.inc()
            n = blocks_for(n_tokens, self.block_size)
            try:
                fresh = self._take(n - len(hits))
            except NoFreeBlocks:
                # undo the hit references and re-park the claimed host
                # entries: a failed allocation must not leave cached
                # blocks pinned un-evictable or hosted K/V dropped
                for key, blk in hits:
                    self._deref(blk, retain=True)
                for key, parent, arrays in claimed:
                    self.host.unclaim(key, parent, arrays)
                raise
            # restored blocks publish immediately under their existing
            # content keys (they ARE the cached chain, back on device)
            # and queue their host→device copies for the engine to
            # dispatch before anything reads them
            for (key, parent, arrays), blk in zip(claimed, fresh):
                self._index[key] = blk
                self._key_of[blk] = key
                self._parent[key] = parent
                if parent is not None:
                    self._children[parent] = \
                        self._children.get(parent, 0) + 1
                if self._summary is not None:
                    self._summary.add(key)
                self._pending_restores.append((blk, arrays))
            self._tables[rid] = [blk for _, blk in hits] + fresh
            self._lens[rid] = n * self.block_size
            if self.state_slots:
                self._slot_of[rid] = self._slot_free.popleft()
            if self.window is not None:
                self.window.admit(rid)
            self._chain[rid] = ([key for key, _ in hits]
                                + [key for key, _, _ in claimed])
            if token_ids is not None:
                self._host_tokens[rid] = len(claimed) * self.block_size
                return (list(self._tables[rid]),
                        (len(hits) + len(claimed)) * self.block_size)
            return list(self._tables[rid])

    def ensure_capacity(self, rid, n_tokens):
        """Grow ``rid``'s table to cover ``n_tokens`` slots (decode
        appends).  Raises NoFreeBlocks when the cache is exhausted —
        the scheduler's preemption trigger."""
        with self._lock:
            table = self._tables[rid]
            need = blocks_for(n_tokens, self.block_size) - len(table)
            if need > 0:
                table.extend(self._take(need))
                self._lens[rid] = len(table) * self.block_size
            if self.window is not None:
                # the decode step's own position; what the window still
                # shows of the rest is held since the passes before
                self.window.cover(rid, n_tokens - 1, n_tokens)
            return list(table)

    def table(self, rid):
        with self._lock:
            return list(self._tables[rid])

    def window_cover(self, rid, lo, hi):
        """The window group holds positions ``[lo, hi)`` of ``rid`` from
        here on (``WindowGroup.cover``): in front of a prefill or chunk
        pass that writes them."""
        with self._lock:
            self.window.cover(rid, lo, hi)

    def window_trim(self, rid, next_pos):
        """After a pass: the window group's blocks of ``rid`` that no
        query at ``next_pos`` or later sees go back to its free list;
        how many went (0 for a request that has left)."""
        with self._lock:
            return self.window.trim(rid, next_pos)

    def window_table(self, rid, out):
        """``rid``'s logical window-group table into the zeroed row
        ``out``."""
        with self._lock:
            return self.window.table(rid, out)

    def state_slot(self, rid):
        """The state-pool slot ``rid`` holds (hybrid engines only)."""
        with self._lock:
            return self._slot_of[rid]

    @property
    def state_slots_in_use(self):
        with self._lock:
            return len(self._slot_of)

    def capacity(self, rid):
        """Token slots currently reserved for ``rid``."""
        with self._lock:
            return self._lens[rid]

    def reclaimable_blocks(self, rid):
        """Blocks ``free(rid)`` would actually park/release right now —
        the refcount-1 subset of its table.  A request whose blocks are
        all shared with other live tables reclaims nothing, which is
        what makes preempting it pointless (``Scheduler._pick_victim``
        consults this)."""
        with self._lock:
            return sum(1 for b in self._tables.get(rid, ())
                       if self._refs.get(b, 0) == 1)

    def truncate(self, rid, n_tokens):
        """Shrink ``rid``'s table to cover just ``n_tokens`` slots,
        releasing the tail blocks — the speculative-decoding rollback
        (rejected draft tokens' K/V lives in over-reserved tail blocks
        that the accepted sequence no longer needs).

        Bounded and share-safe by construction: only blocks BEYOND
        ``blocks_for(n_tokens)`` are candidates, and a candidate whose
        refcount exceeds 1 (shared through the prefix cache with
        another live table) stops the walk — truncation can never free,
        or even decref, a block another request still reads.  A
        released tail block that was published (cannot happen for a
        purely speculative tail — only accepted tokens are ever noted —
        but guarded anyway) is unpublished before returning to the
        free list.  Returns the number of blocks released."""
        with self._lock:
            table = self._tables.get(rid)
            if table is None:
                return 0
            keep = max(1, blocks_for(max(1, int(n_tokens)),
                                     self.block_size))
            freed = 0
            while len(table) > keep:
                blk = table[-1]
                if self._refs.get(blk, 0) > 1:
                    break          # shared prefix block — never touch
                table.pop()
                released = self._deref(blk, retain=False)
                if released is not None:
                    self._free.append(released)
                freed += 1
            self._lens[rid] = len(table) * self.block_size
            chain = self._chain.get(rid)
            if chain is not None and len(chain) > len(table):
                # the published chain can never extend past the table
                del chain[len(table):]
            return freed

    # -- publishing ----------------------------------------------------------
    def note_tokens(self, rid, token_ids, salt=None):
        """Publish ``rid``'s newly-FULL blocks under their chain keys.

        ``token_ids`` is the sequence whose K/V has been written so far
        (prompt prefix during prefill, prompt+generated during decode);
        every full block not yet in ``rid``'s chain is keyed and
        indexed.  A key already mapping to a DIFFERENT physical block
        (two identical prompts prefilled concurrently) keeps the
        existing mapping — this request's duplicate block simply stays
        private.  No-op with the prefix cache off."""
        if not self.prefix_cache:
            return
        with self._lock:
            table = self._tables.get(rid)
            if table is None:
                return
            chain = self._chain.setdefault(rid, [])
            n_full = min(len(token_ids) // self.block_size, len(table))
            while len(chain) < n_full:
                b = len(chain)
                parent = chain[-1] if chain else salted_root(salt)
                key = _block_key(
                    parent,
                    token_ids[b * self.block_size:(b + 1) * self.block_size])
                blk = table[b]
                if key not in self._index and blk not in self._key_of:
                    self._index[key] = blk
                    self._key_of[blk] = key
                    self._parent[key] = (parent if chain else None)
                    if chain:
                        self._children[parent] = \
                            self._children.get(parent, 0) + 1
                    if self._summary is not None:
                        self._summary.add(key)
                chain.append(key)

    # -- release -------------------------------------------------------------
    def _drop_pending(self, blk):
        """``blk`` left every table before its queued host→device
        restore was dispatched (cannot happen through the engine — it
        drains restores in the same step as the allocate — but the
        public API allows it): the device block never received the
        K/V, so it must NOT stay published as resurrectable.  Re-park
        the host copies and unpublish.  Called under ``_lock``."""
        with self._lock:
            kept, dropped = [], []
            for b, a in self._pending_restores:
                (dropped if b == blk else kept).append((b, a))
            if not dropped:
                return
            self._pending_restores[:] = kept
            key = self._key_of.get(blk)
            if key is not None:
                parent = self._parent.get(key)
                self._unpublish(key)
                if self.host is not None:
                    self.host.unclaim(key, parent, dropped[0][1])

    def _deref(self, blk, retain):
        """Drop one reference; returns the block if it reached
        refcount 0 UNPUBLISHED (the caller decides the retained-vs-free
        fate), else None.  Reentrant-locked: callers hold ``_lock``."""
        with self._lock:
            self._refs[blk] -= 1
            if self._refs[blk] > 0:
                return None            # another table still reads it
            del self._refs[blk]
            if self._pending_restores:
                self._drop_pending(blk)
            key = self._key_of.get(blk)
            if key is not None:
                if retain:
                    self._lru[key] = blk   # reusable AND evictable
                    self._lru.move_to_end(key)
                else:
                    self._unpublish(key)
                    self._free.append(blk)
                return None
            return blk

    def free(self, rid, retain=True):
        """Release ``rid``'s references.  DECREF semantics: blocks
        shared with another live table are untouched (preempting a
        sharer can never free blocks a running request still reads).
        Refcount-0 published blocks park in the prefix LRU (K/V intact,
        future prefix hits resurrect them); refcount-0 unpublished
        blocks park in the legacy retained tier with ``retain=True`` or
        return to the free list with ``retain=False``."""
        with self._lock:
            blocks = self._tables.pop(rid)
            self._lens.pop(rid)
            slot = self._slot_of.pop(rid, None)
            if slot is not None:
                self._slot_free.append(slot)
            if self.window is not None:
                self.window.release(rid)
            self._chain.pop(rid, None)
            self._host_tokens.pop(rid, None)
            loose = []
            for blk in blocks:
                released = self._deref(blk, retain)
                if released is not None:
                    loose.append(released)
            if loose:
                if retain:
                    self._retained[rid] = loose
                else:
                    self._free.extend(loose)

    def reset(self):
        with self._lock:
            self._free = deque(range(1, self.num_blocks))
            self._slot_free = deque(range(1, self.state_slots + 1))
            self._slot_of.clear()
            if self.window is not None:
                self.window.reset()
            self._tables.clear()
            self._lens.clear()
            self._retained.clear()
            self._refs.clear()
            self._index.clear()
            self._key_of.clear()
            self._parent.clear()
            self._children.clear()
            self._lru.clear()
            self._chain.clear()
            self._host_tokens.clear()
            # hosted entries stay: they are content-addressed, so their
            # K/V remains valid for the tokens they hash — but restores
            # queued against now-recycled device blocks must not land
            del self._pending_restores[:]
            # the advertisement rebuilds from the surviving host tier
            # (reset is rare and operator-driven — never the scrape
            # path, so the one-off pool walk is fine here)
            if self._summary is not None:
                self._summary.clear()
                if self.host is not None:
                    for key in self.host.keys():
                        self._summary.add(key)
