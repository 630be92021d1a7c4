"""Host-DRAM second tier for the paged KV pool: the swap arena.

Device HBM caps the prefix cache at a few dozen retained prompts;
host RAM is ~100x larger and a prefix page is pure *content* — written
once at registration, shared copy-on-write ever after, never mutated.
That makes cold prefix pages the perfect spill candidate: only bytes
need to move, because the hashing, token verification and refcount
machinery already live host-side (:mod:`~apex_tpu.serving
.prefix_cache`).

:class:`HostTier` is that spill target — a **bounded numpy arena** of
swapped-out prefix page blocks, keyed by the owning prefix-cache
entry's synthetic key:

- **put** (swap-out): the engine copies an evicted entry's page bytes
  device→host (``[layers, m, heads, head_dim, page_len]`` K and V, in
  the pool's storage dtype — int8 under the ``kv_quant`` tier, which
  halves the transfer bytes for free) and the arena stores them with a
  per-shard CRC32 checksum (one CRC per tensor-parallel shard of the
  heads axis; ``shards=1`` on a single-chip engine degenerates to the
  one whole-array CRC). Capacity is enforced at insert:
  least-recently-put entries are evicted (the ``on_evict`` hook tells
  the owner to drop the now-backingless index entry), and an entry
  larger than the whole arena is *declined* — the caller falls back to
  plain destruction.
- **put_pending / complete** (async swap-out): the admission-path half
  of an asynchronous swap RESERVES the entry's bytes synchronously
  (:meth:`put_pending` — capacity eviction and the LRU stamp happen
  NOW, on the caller's thread, so async and sync arena states evolve
  identically), and the :class:`SwapWorker` thread fills the bytes in
  later (:meth:`complete` — the forced device read, the defensive
  copy, the CRC). A pending record is the *swapping* state: it counts
  toward capacity, answers :meth:`contains` (the entry stays
  matchable mid-flight), and a capacity eviction can drop it (the
  worker's late ``complete`` then discards silently — the index entry
  was already dropped through ``on_evict``).
- **take** (swap-in): pops the entry and re-verifies every shard's
  checksum. A mismatch (bit rot, or the chaos harness's
  ``swap_corruption`` injection) returns ``valid=False`` — the engine
  degrades the hit to a **verified miss** (drop + re-prefill), never a
  wrong token. A still-pending record (the worker job died before
  completing) returns None, the same degradation. The checksum guards
  the *bytes*; the prefix cache's token-for-token verification
  continues to guard the *identity*, so the two layers together keep
  the hierarchical cache exact.
- **contains** is the read-only existence probe the prefix cache's
  match/probe walk uses (no LRU touch, no counters — the router's
  affinity probe rides it N times per request).

The arena is **thread-safe** (one re-entrant lock around every public
method): the :class:`SwapWorker` completes records from its own thread
while the scheduler thread matches, takes and audits. Structural
mutations that fire ``on_evict`` (put/put_pending capacity evictions)
only ever run on the caller's thread — :meth:`complete` fills bytes
into an existing record and never calls out — so the prefix-cache
index is only ever mutated from the scheduler thread.

Everything here is pure host numpy/python: no device work, no compiled
programs, no jax import. The engine owns all telemetry
(``serving.swap.*``) and all device-side data movement; the arena owns
bytes, bounds and checksums.
"""

from __future__ import annotations

import dataclasses
import itertools
import queue
import threading
import zlib
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from apex_tpu.log_util import get_logger

__all__ = ["HostTier", "HostTierRecord", "SwapWorker",
           "record_from_wire", "record_to_wire"]

_logger = get_logger("serving")


def _shard_checksums(k: np.ndarray, v: np.ndarray,
                     shards: int) -> Tuple[int, ...]:
    """Per-shard CRC32s over the HEADS axis (axis 2 of
    ``[layers, m, heads, head_dim, page_len]``): shard ``t`` covers
    heads ``[t*h/tp, (t+1)*h/tp)`` of K then V — exactly the slice a
    tensor-parallel shard owns, so a mesh engine's arena records carry
    one verifiable checksum per shard. ``shards=1`` is the classic
    whole-array CRC (same value bit-for-bit). Cheap (~GB/s, stdlib C)
    relative to the device→host copy it protects, and strong enough
    that a corrupt swap-in can only read as a verified miss, never as
    silently-wrong K/V. ``shards`` must divide the heads axis —
    otherwise the trailing heads would sit in NO shard's CRC and a
    bit flip there would verify clean, exactly the silent wrongness
    the checksum exists to forbid (the engine's tp geometry
    validation guarantees this; direct callers are checked loudly
    here)."""
    shards = max(int(shards), 1)
    heads = k.shape[2]
    if heads % shards:
        raise ValueError(
            f"shards={shards} must divide the heads axis ({heads}): a "
            "ragged split would leave the trailing heads outside every "
            "shard's checksum")
    hl = heads // shards
    out = []
    for t in range(shards):
        # crc32 reads the contiguous buffers directly — no tobytes
        # copy, and at shards=1 over the (already-contiguous) stored
        # arrays ascontiguousarray is a no-op view too, so the
        # single-chip checksum path is copy-free
        ks = np.ascontiguousarray(k[:, :, t * hl:(t + 1) * hl])
        vs = np.ascontiguousarray(v[:, :, t * hl:(t + 1) * hl])
        out.append(zlib.crc32(vs, zlib.crc32(ks)))
    return tuple(out)


@dataclasses.dataclass
class HostTierRecord:
    """One swapped-out prefix: the page-block K/V bytes (numpy, in the
    pool's storage dtype — None while the record is *pending*, i.e.
    the swap-out bytes are still in flight on the
    :class:`SwapWorker`), their byte count, the per-shard CRC32s
    computed at swap-out (``shards`` entries — one per tensor-parallel
    shard of the heads axis), and the validity verdict
    :meth:`HostTier.take` fills in when it re-verifies the checksums
    at swap-in."""

    k: Optional[np.ndarray]  # [layers, m, heads, head_dim, page_len]
    v: Optional[np.ndarray]
    nbytes: int
    crc: Tuple[int, ...]
    shards: int = 1
    last_used: int = 0
    valid: bool = True
    pending: bool = False
    # chaos racing an in-flight swap: corrupt_entry on a pending
    # record arms this flag; complete() flips a stored byte AFTER
    # computing the CRCs, so the next take fails verification exactly
    # as a post-completion corruption would
    corrupt_on_complete: bool = False


# --------------------------------------------------------------- wire forms
#
# The disaggregated handoff's arena record, addressable ACROSS
# processes: a prefill-role fleet worker exports the finished prefix's
# record as a versioned dict (raw bytes + dtype/shape + the swap-out
# CRCs), the controller ships it over the fleet transport, and the
# decode-role worker imports it into its OWN arena. The CRCs travel
# with the bytes and are re-verified by the importing side's ordinary
# :meth:`HostTier.take` at swap-in — so corruption anywhere along the
# journey degrades to the same VERIFIED MISS a local corruption would,
# never a wrong token. Versioned like the scheduler wire forms: a
# mismatched build fails loudly, never deserializes garbage.

RECORD_WIRE_VERSION = 1


def record_to_wire(key: int, record: HostTierRecord) -> dict:
    """``record`` (resident — a pending record has no bytes to ship)
    as its versioned dict wire form under arena key ``key``."""
    if record.pending or record.k is None or record.v is None:
        raise ValueError(
            f"arena record {key} is still pending — an in-flight "
            "swap-out has no bytes to put on the wire")
    return {
        "v": RECORD_WIRE_VERSION,
        "key": int(key),
        "nbytes": int(record.nbytes),
        "crc": [int(c) for c in record.crc],
        "shards": int(record.shards),
        "k_bytes": record.k.tobytes(),
        "k_dtype": str(record.k.dtype),
        "k_shape": [int(d) for d in record.k.shape],
        "v_bytes": record.v.tobytes(),
        "v_dtype": str(record.v.dtype),
        "v_shape": [int(d) for d in record.v.shape],
    }


def record_from_wire(wire: dict) -> Tuple[int, HostTierRecord]:
    """``(key, record)`` from a record wire form — the arrays rebuilt
    as owned, writable host copies (the arena must own mutable bytes).
    Loud ``ValueError`` on an unknown version, ``KeyError`` on a
    missing field."""
    v = wire.get("v")
    if v != RECORD_WIRE_VERSION:
        raise ValueError(
            f"unknown arena-record wire version {v!r} (this build "
            f"speaks {RECORD_WIRE_VERSION}) — controller and workers "
            "must run the same tree")
    k = np.frombuffer(wire["k_bytes"], dtype=wire["k_dtype"]) \
        .reshape(wire["k_shape"]).copy()
    vv = np.frombuffer(wire["v_bytes"], dtype=wire["v_dtype"]) \
        .reshape(wire["v_shape"]).copy()
    return int(wire["key"]), HostTierRecord(
        k=k, v=vv, nbytes=int(wire["nbytes"]),
        crc=tuple(int(c) for c in wire["crc"]),
        shards=int(wire["shards"]))


class HostTier:
    """Bounded host-DRAM arena for swapped-out prefix pages (see
    module docstring). ``capacity_bytes`` bounds the K+V bytes held
    (pending reservations included); ``on_evict(key)`` fires AFTER a
    capacity eviction removes an entry (the engine wires it to drop
    the matching swapped prefix-cache entry, so a prefix is never
    indexed without backing bytes).

    ``shared=True`` marks the arena as EXTERNALLY OWNED by several
    engines at once (the disaggregated-serving handoff bus): each
    engine then registers its drop-hook through :meth:`add_on_evict`
    instead of overwriting ``on_evict``, a capacity eviction notifies
    every registered engine (each drops the key from its OWN prefix
    index — :meth:`PrefixCache.drop` is a no-op for keys it never
    held), and the engines scope their cross-tier audits to the keys
    they own (an arena record owned by a sibling engine is not an
    orphan). A shared arena also survives any single engine's
    ``reset()`` — teardown belongs to whoever built it."""

    def __init__(self, capacity_bytes: int, *,
                 on_evict: Optional[Callable[[int], None]] = None,
                 shared: bool = False):
        capacity_bytes = int(capacity_bytes)
        if capacity_bytes < 1:
            raise ValueError("capacity_bytes must be >= 1")
        self.capacity_bytes = capacity_bytes
        self.on_evict = on_evict
        self.shared = bool(shared)
        # extra eviction listeners (shared-arena mode: one per engine);
        # fired after on_evict, caller's thread only, like on_evict
        self._evict_listeners: List[Callable[[int], None]] = []
        self._lock = threading.RLock()
        self._entries: Dict[int, HostTierRecord] = {}
        self._bytes_used = 0        # maintained incrementally: the
        # auditor re-derives the sum from the stored records and raises
        # on drift, so the two must be independent quantities
        self._clock = itertools.count(1)
        # raw counters (the engine mirrors the interesting ones into
        # serving.swap.*; these keep the class importable bare)
        self.puts = 0
        self.takes = 0
        self.evictions = 0
        self.declined = 0
        self.corruptions_detected = 0

    # ------------------------------------------------------------- geometry
    @property
    def bytes_used(self) -> int:
        """K+V bytes currently held or reserved by pending swaps
        (incremental accounting; the :class:`~apex_tpu.serving
        .PoolAuditor` re-derives it from the stored records and raises
        on drift)."""
        return self._bytes_used

    @property
    def size(self) -> int:
        return len(self._entries)

    def keys(self) -> List[int]:
        """The resident AND pending entry keys (the auditor's
        reconciliation view against :meth:`PrefixCache.swapped_keys` —
        a mid-flight swap is already swapped state on both sides)."""
        with self._lock:
            return list(self._entries)

    def pending_keys(self) -> List[int]:
        """Keys whose swap-out bytes are still in flight (the
        *swapping* state — reserved, matchable, not yet verifiable)."""
        with self._lock:
            return [k for k, r in self._entries.items() if r.pending]

    def contains(self, key: int) -> bool:
        """Read-only existence probe — touches NOTHING (no LRU
        refresh, no counters): the prefix cache's match AND probe
        walks both ride it, and probe must stay side-effect-free.
        Pending (in-flight) entries count: a hit on one joins the
        copy at swap-in time instead of missing."""
        with self._lock:
            return int(key) in self._entries

    def nbytes_of(self, key: int) -> int:
        """Stored (or pending-reserved) K+V bytes of one entry (0 when
        absent) — the auditor's per-entry accounting probe."""
        with self._lock:
            rec = self._entries.get(int(key))
            return 0 if rec is None else rec.nbytes

    @staticmethod
    def _own(arr: np.ndarray) -> np.ndarray:
        """A contiguous, writable, arena-owned copy of ``arr`` when it
        is not one already (``np.asarray`` of a device buffer hands
        back a READ-ONLY view — the arena must own mutable bytes so
        checksums, capacity accounting and the chaos harness's
        ``corrupt_entry`` all operate on its own storage)."""
        arr = np.asarray(arr)
        if arr.flags.owndata and arr.flags.writeable \
                and arr.flags.c_contiguous:
            return arr
        return np.array(arr, copy=True)

    # ------------------------------------------------------------ transfers
    def put_pending(self, key: int, nbytes: int, *,
                    shards: int = 1) -> bool:
        """Reserve arena space for an in-flight swap-out of ``key``
        (the asynchronous path's admission-side half — capacity
        eviction, the decline decision and the LRU stamp all happen
        HERE, on the caller's thread, so async and sync arenas evolve
        identically). Returns False — and reserves nothing — when
        ``nbytes`` alone exceeds the arena (the caller destroys
        instead, exactly the pre-tier behaviour). The
        :class:`SwapWorker` fills the bytes in via :meth:`complete`."""
        key, nbytes = int(key), int(nbytes)
        with self._lock:
            if nbytes > self.capacity_bytes:
                self.declined += 1
                _logger.debug("host tier declined %d-byte entry "
                              "(capacity %d)", nbytes,
                              self.capacity_bytes)
                return False
            old = self._entries.pop(key, None)  # replace, never double-count
            if old is not None:
                self._bytes_used -= old.nbytes
            while self._bytes_used + nbytes > self.capacity_bytes:
                self._evict_lru()
            self._entries[key] = HostTierRecord(
                k=None, v=None, nbytes=nbytes, crc=(),
                shards=max(int(shards), 1),
                last_used=next(self._clock), pending=True)
            self._bytes_used += nbytes
            if old is not None:
                _logger.debug("host tier replaced entry %d", key)
            return True

    def complete(self, key: int, k_pages: np.ndarray,
                 v_pages: np.ndarray) -> bool:
        """Fill a pending record's bytes in (the :class:`SwapWorker`'s
        half of an async swap-out): defensively copy, checksum per
        shard, flip pending→resident. False — and nothing stored —
        when the record was evicted (or the arena cleared) while the
        bytes were in flight: the index entry is already gone, so the
        late bytes are simply discarded. Never evicts and never fires
        ``on_evict`` — structural mutations stay on the scheduler
        thread. The heavy half (defensive copy + CRC) runs OUTSIDE
        the arena lock: an admission-path ``put_pending`` must never
        wait out a worker mid-checksum — that wait would be exactly
        the stall the async tier removes, smuggled back in through
        lock contention."""
        key = int(key)
        with self._lock:
            rec = self._entries.get(key)
            if rec is None or not rec.pending:
                return False
            shards = rec.shards
        k_pages = self._own(k_pages)        # heavy: outside the lock
        v_pages = self._own(v_pages)
        crc = _shard_checksums(k_pages, v_pages, shards)
        with self._lock:
            rec = self._entries.get(key)
            if rec is None or not rec.pending:
                return False        # evicted while we were checksumming
            actual = int(k_pages.nbytes + v_pages.nbytes)
            if actual != rec.nbytes:
                # the reservation was computed from shapes; drift means
                # the caller's arithmetic was wrong — keep the ledger
                # honest rather than letting the auditor trip later
                self._bytes_used += actual - rec.nbytes
                rec.nbytes = actual
            rec.k, rec.v = k_pages, v_pages
            rec.crc = crc
            rec.pending = False
            if rec.corrupt_on_complete:
                # chaos raced this in-flight swap: rot the stored
                # bytes AFTER the CRC so the next take fails exactly
                # like post-completion corruption
                rec.corrupt_on_complete = False
                flat = rec.k.reshape(-1).view(np.uint8)
                flat[0] ^= 0xFF
            self.puts += 1
            return True

    def put(self, key: int, k_pages: np.ndarray, v_pages: np.ndarray,
            *, shards: int = 1) -> bool:
        """Store one swapped-out prefix's page bytes under ``key`` in
        one synchronous step (reserve + complete — the sync escape
        hatch and the swap-in deferral path). Returns False — and
        stores nothing — when the entry alone exceeds the arena;
        otherwise evicts least-recently-put entries until it fits,
        firing ``on_evict`` per victim. The arrays are defensively
        copied (once, in :meth:`complete` — views only; arrays the
        caller already owns are adopted, the pre-async contract) and
        checksummed per shard at rest. No outer lock: the caller is
        the scheduler thread and the worker only ever completes its
        OWN keys, so nothing can race the fresh pending record —
        which keeps complete's copy+CRC off the arena lock here
        too."""
        nbytes = int(np.asarray(k_pages).nbytes
                     + np.asarray(v_pages).nbytes)
        if not self.put_pending(key, nbytes, shards=shards):
            return False
        return self.complete(key, k_pages, v_pages)

    def take(self, key: int) -> Optional[HostTierRecord]:
        """POP the entry for ``key`` and re-verify its per-shard
        checksums: ``record.valid`` is False when any shard's stored
        bytes no longer match the swap-out CRC (corruption — the
        engine must degrade the hit to a verified miss). None when the
        key is absent (e.g. evicted by capacity pressure since the
        match walk) or still pending (the worker job died before
        completing — same degradation; the engine joins the worker
        before taking, so a healthy in-flight swap is never consumed
        half-done)."""
        with self._lock:
            rec = self._entries.pop(int(key), None)
            if rec is None:
                return None
            self._bytes_used -= rec.nbytes
            if rec.pending:
                _logger.warning("host tier entry %d taken while still "
                                "pending (its swap-out never completed)"
                                " — degrading to a verified miss", key)
                return None
            self.takes += 1
            rec.valid = _shard_checksums(rec.k, rec.v,
                                         rec.shards) == rec.crc
            if not rec.valid:
                self.corruptions_detected += 1
                _logger.warning("host tier entry %d failed its swap-in "
                                "checksum — degrading to a verified "
                                "miss", key)
            return rec

    def export_record(self, key: int) -> Optional[dict]:
        """POP ``key``'s resident record and return its wire form —
        the cross-process half of a disaggregated handoff (ownership
        transfers to the wire: the exporting arena releases the bytes
        NOW, the importing arena adopts them). None when the key is
        absent (evicted since the handoff was collected) or still
        pending (bytes in flight) — both degrade to the key-less
        handoff, i.e. a decode-side re-prefill, per the verified-miss
        contract. No checksum walk here: the swap-out CRCs travel and
        the importer's :meth:`take` re-verifies at swap-in."""
        with self._lock:
            rec = self._entries.get(int(key))
            if rec is None or rec.pending:
                return None
            wire = record_to_wire(int(key), rec)
            del self._entries[int(key)]
            self._bytes_used -= rec.nbytes
            return wire

    def import_record(self, wire: dict) -> Optional[int]:
        """Adopt a wire-form record into THIS arena under its
        original key (handoff keys are request uids — positive, so
        they can never collide with a local engine's negative
        synthetic prefix keys). Same admission rules as a local put:
        an over-capacity record is declined (returns None — the
        caller degrades to a key-less handoff), otherwise LRU
        eviction makes room and the key is returned. Counted as a
        ``put`` — the record enters the arena exactly as a completed
        swap-out would."""
        key, rec = record_from_wire(wire)
        with self._lock:
            if rec.nbytes > self.capacity_bytes:
                self.declined += 1
                _logger.debug(
                    "host tier declined imported %d-byte record %d "
                    "(capacity %d)", rec.nbytes, key,
                    self.capacity_bytes)
                return None
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes_used -= old.nbytes
            while self._bytes_used + rec.nbytes > self.capacity_bytes:
                self._evict_lru()
            rec.last_used = next(self._clock)
            self._entries[key] = rec
            self._bytes_used += rec.nbytes
            self.puts += 1
            return key

    def add_on_evict(self, fn: Callable[[int], None]) -> None:
        """Register an ADDITIONAL eviction listener (shared-arena
        mode: every co-owning engine hooks its prefix-index drop here
        — overwriting ``on_evict`` would silently orphan the other
        engines' swapped entries). Listeners fire on the caller's
        thread, after ``on_evict``, once per evicted key; double
        registration is collapsed."""
        if fn not in self._evict_listeners:
            self._evict_listeners.append(fn)

    def _evict_lru(self) -> None:
        key, rec = min(self._entries.items(),
                       key=lambda kv: kv[1].last_used)
        del self._entries[key]
        self._bytes_used -= rec.nbytes
        self.evictions += 1
        _logger.debug("host tier evicted entry %d (capacity pressure)",
                      key)
        if self.on_evict is not None:
            self.on_evict(key)
        for fn in self._evict_listeners:
            fn(key)

    # ------------------------------------------------------------ lifecycle
    def corrupt_entry(self, key: int, *, byte_index: int = 0) -> None:
        """CHAOS/DEBUG ONLY: flip one byte of the stored K block so the
        next :meth:`take` fails its checksum — the
        ``swap_corruption`` fault kind's injection primitive (proving
        the verified-miss degradation, exactly as
        ``corrupt_page_table`` proves the auditor's sensitivity). On a
        PENDING record (the injection racing an in-flight swap) the
        corruption is armed instead and lands the moment
        :meth:`complete` stores the bytes — the race resolves to the
        same verified miss either way. Raises KeyError when the key is
        absent."""
        with self._lock:
            rec = self._entries[int(key)]
            if rec.pending:
                rec.corrupt_on_complete = True
                return
            flat = rec.k.reshape(-1).view(np.uint8)
            flat[int(byte_index) % flat.size] ^= 0xFF

    def discard(self, key: int) -> bool:
        """Drop ``key``'s record WITHOUT verifying or returning it (no
        ``on_evict``, no counters): the shared-arena reset path — an
        engine tearing down its own swapped entries must release their
        reserved bytes without the checksum walk :meth:`take` pays,
        and without clearing sibling engines' records the way
        :meth:`clear` would. False when absent."""
        with self._lock:
            rec = self._entries.pop(int(key), None)
            if rec is None:
                return False
            self._bytes_used -= rec.nbytes
            return True

    def clear(self) -> None:
        """Drop every entry — pending ones included; a worker's late
        ``complete`` finds its record gone and discards (counters
        survive — run-scoped, like the prefix cache's). No ``on_evict``
        callbacks: clear is the engine-driven teardown half of
        ``reset(clear_prefixes=True)``, where the index entries are
        being dropped anyway."""
        with self._lock:
            self._entries.clear()
            self._bytes_used = 0

    def stats(self) -> dict:
        """Host-side snapshot (the bench's host-tier honesty row).
        ``swapping`` counts records whose bytes are still in flight on
        the :class:`SwapWorker`."""
        with self._lock:
            return {
                "entries": self.size,
                "swapping": sum(r.pending
                                for r in self._entries.values()),
                "bytes_used": self.bytes_used,
                "capacity_bytes": self.capacity_bytes,
                "puts": self.puts,
                "takes": self.takes,
                "evictions": self.evictions,
                "declined": self.declined,
                "corruptions_detected": self.corruptions_detected,
            }


class SwapWorker:
    """One background thread that completes swap-outs off the
    admission path (the :class:`~apex_tpu.serving.DraftWorker`
    pattern: daemon thread, bounded queue, jobs as closures over
    snapshots, exceptions surfaced at the join, idempotent
    :meth:`stop`).

    The contract that keeps this SAFE to thread is snapshot purity
    plus single-writer structure: every submitted job closes over the
    DISPATCHED device gather's output buffers (an immutable snapshot
    of the pool bytes at eviction time — the pages can be reused the
    moment the gather is enqueued, because program order sequences the
    gather before any later overwrite) and only ever calls
    :meth:`HostTier.complete`, which fills bytes into a record the
    scheduler thread already reserved and never mutates the prefix
    index. Timing can change WHEN host bytes land, never what they
    are — which is why async and sync swap streams are bitwise
    identical.

    API: :meth:`submit` enqueues ``fn`` under ``key`` (the bounded
    queue applies backpressure — a full queue blocks the submitter,
    bounding in-flight host copies); :meth:`join` blocks until
    ``key``'s job has retired, re-raising the job's exception if it
    died (the engine degrades that to a verified miss); :meth:`drain`
    waits the whole queue out (the leak-free kill contract: a replica
    killed with a non-empty swap queue completes its puts, so the
    arena and the prefix index still reconcile); :meth:`stop` drains
    then shuts the thread down (idempotent — the engine registers it
    with ``weakref.finalize``). After stop, :meth:`submit` runs jobs
    inline — the sync degradation, never a dropped swap.

    Job closures MAY emit request-trace spans (:mod:`apex_tpu
    .telemetry.tracing`): the engine captures the admitting request's
    trace id at dispatch and the job's ``swap_out_store`` span lands
    on this thread (``serving-swap-worker`` in the Chrome trace) —
    the tracer is lock-protected and appends are token-invisible, so
    the purity contract above is untouched."""

    _MAX_ERRORS = 64

    def __init__(self, max_queue: int = 64):
        self._jobs: "queue.Queue" = queue.Queue(maxsize=int(max_queue))
        self._cond = threading.Condition()
        self._inflight: set = set()
        self._errors: Dict[Any, BaseException] = {}
        self._stopped = False
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="serving-swap-worker")
        self._thread.start()

    def _run(self) -> None:
        while True:
            item = self._jobs.get()
            if item is None:
                return
            key, fn = item
            try:
                fn()
            except BaseException as e:  # noqa: BLE001 — surfaced at join
                with self._cond:
                    self._errors[key] = e
                    while len(self._errors) > self._MAX_ERRORS:
                        self._errors.pop(next(iter(self._errors)))
            finally:
                with self._cond:
                    self._inflight.discard(key)
                    self._cond.notify_all()

    def submit(self, key, fn: Callable[[], None]) -> None:
        """Enqueue ``fn`` to run on the worker thread under ``key``.
        ``fn`` MUST close over snapshots (dispatched device buffers,
        immutable host values) — never live mutable state. After
        :meth:`stop`, runs inline (the sync degradation). A stale
        un-joined error parked under the same key is dropped — a new
        job's outcome must never be judged by a dead predecessor's
        exception."""
        with self._cond:
            self._errors.pop(key, None)
            if self._stopped:
                stopped = True
            else:
                stopped = False
                self._inflight.add(key)
        if stopped:
            fn()
            return
        self._jobs.put((key, fn))

    def in_flight(self, key) -> bool:
        with self._cond:
            return key in self._inflight

    def pending_keys(self) -> List[Any]:
        with self._cond:
            return list(self._inflight)

    def join(self, key) -> None:
        """Block until ``key``'s job has retired (the in-flight-hit
        join: a hit racing its own swap-out waits for the arena write
        instead of reading partial bytes). Re-raises the job's
        exception when it died — the caller degrades to a verified
        miss."""
        with self._cond:
            while key in self._inflight:
                self._cond.wait(timeout=1.0)
            err = self._errors.pop(key, None)
        if err is not None:
            raise err

    def drain(self, timeout: Optional[float] = 10.0) -> bool:
        """Wait until every submitted job has retired (True) or
        ``timeout`` elapses (False) — the kill-time contract: queued
        swap-outs COMPLETE (their arena records fill in), so a drained
        engine's cross-tier audit reconciles."""
        deadline = None if timeout is None \
            else threading.TIMEOUT_MAX if timeout < 0 else timeout
        with self._cond:
            return self._cond.wait_for(lambda: not self._inflight,
                                       timeout=deadline)

    def stop(self) -> None:
        """Drain then shut the thread down (idempotent; registered as
        the owning engine's finalizer)."""
        with self._cond:
            if self._stopped:
                return
            self._stopped = True
        self.drain()
        self._jobs.put(None)
        self._thread.join(timeout=2.0)
