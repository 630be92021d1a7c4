"""Content-addressed KV prefix reuse — the serving engine's prompt cache.

Real traffic is dominated by shared prompt prefixes (system prompts,
few-shot templates, multi-turn history); without reuse every request
re-runs chunk prefill over tokens whose K/V already sit byte-identical
on other pages of the pool. This module is the host-side index that
eliminates that recompute:

- **Content addressing**: a retained prefix is keyed by a *rolling hash
  over token blocks* — block ``i``'s key folds block ``i-1``'s, so key
  ``H_i`` identifies the entire ``(i+1)``-block prefix and matching a
  new prompt is one incremental walk over its blocks. Blocks are
  ``block_len`` tokens, aligned to the engine's ``chunk_len``: a match
  always ends on a chunk boundary, so the remaining suffix drops
  straight into the *existing* per-row-offset chunk-prefill program at
  the matched offset — reuse composes with chunked prefill and the
  chunk computations that produced the donor K/V are bitwise identical
  to the ones the cold path would run.
- **Storage**: an entry is its pages. Registration records the page
  ids that already hold a completed prompt's block-aligned K/V (the
  engine bumps their refcounts on ``"registered"``) and copies
  nothing; a hit shares them into the admitted slot's page table.
  Eviction hands them back through ``on_evict`` (the engine wires
  :meth:`PagePool.release`, so a page still shared with a live slot
  survives its entry). Sharing costs zero new pages — capacity
  pressure lives in the engine's admission reservation, which calls
  :meth:`evict_lru`.
- **Refcounts + LRU**: ``acquire`` pins an entry until ``release``;
  eviction is least-recently-used over entries at refcount 0 only.
  Hits need no pin (the pages protect themselves via the pool's
  refcounts; evicting a donor entry mid-request is harmless).
- **Exactness**: hash keys are a lookup accelerator, not the source of
  truth — every match is verified token-for-token against the entry's
  retained tokens before it is trusted, so a hash collision can only
  cost a miss, never a wrong-token hit. Matches are additionally capped
  below the full prompt (``aligned(n - 1)``): at least the final block
  always runs through chunk prefill, because that program samples the
  request's first output token.

The class is pure host bookkeeping (dicts and counters). Telemetry is
the caller's job (the scheduler mirrors
:meth:`stats` into ``serving.prefix.*``); the raw counters here keep the
class importable without a registry.

**Hierarchical KV** (an engine host tier): eviction under pool
pressure becomes a SWAP — the victim entry's page bytes migrate
device→host (the engine's ``swap_out`` hook, wired via
:meth:`PrefixCache.set_swap_hooks`; by default the hook only
DISPATCHES the migration — the copy completes on a
:class:`~apex_tpu.serving.SwapWorker` thread, off the admission path —
but the snapshot is taken by program order at dispatch, so the hook
returning True means the bytes are safe), its device pages return to
the pool immediately, and the entry stays in the index in the
``swapped`` state (arena-side it passes through *swapping* while the
copy is in flight), so :meth:`match` and :meth:`probe` still report it
(the router's affinity probe keeps seeing swapped AND swapping
prefixes — ``contains`` answers for both). A hit on a swapped entry
carries ``PrefixMatch.swapped=True``; the engine joins any in-flight
copy, migrates the bytes back into fresh pages (checksum-verified — a
corrupt or missing swap-in degrades to a verified miss via
:meth:`drop` + :meth:`unrecord_hit`, never a wrong token) and calls
:meth:`swap_in_complete` before sharing as usual. Prefix capacity is
then bounded by host RAM, not device HBM.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from apex_tpu.log_util import get_logger

__all__ = ["PrefixCache", "PrefixMatch"]

_logger = get_logger("serving")

# synthetic paged-entry keys: ONE process-wide negative counter, not a
# per-cache one. With a per-cache counter two engines sharing one
# HostTier arena (disaggregated serving) would both mint key -1, and a
# put under a colliding key REPLACES — engine A's swapped entry would
# silently come back backed by engine B's bytes, which pass the CRC
# (they are B's honest bytes) while being the WRONG prefix's K/V. The
# keys are opaque host bookkeeping, so global uniqueness costs nothing.
_paged_key = itertools.count(-1, -1)


def _roll(h: int, block: Tuple[int, ...]) -> int:
    """One step of the rolling block hash: fold the previous blocks'
    key with this block's tokens. Host-local (python ``hash``), so it
    needs no cross-process stability — collisions are tolerated because
    every lookup is verified against the entry's retained tokens."""
    return hash((h,) + block)


@dataclasses.dataclass
class _Entry:
    """One retained prefix: ``tokens`` (the full block-aligned prefix)
    living on pool pages ``pages`` under key ``row`` (a synthetic
    negative key, or a handoff's request uid); ``refcount`` pins the
    entry against eviction between ``acquire`` and ``release``.

    ``swapped`` is the hierarchical-KV tier's resident/swapped state:
    a swapped entry holds NO device pages (``pages`` is None,
    ``swapped_pages`` remembers how many it held) — its page bytes
    live in the engine's host-DRAM :class:`~apex_tpu.serving
    .HostTier` under key ``row``, and a hit migrates them back before
    sharing (:meth:`Engine.attach_prefix`'s swap-in path)."""

    row: int
    tokens: Tuple[int, ...]
    n_blocks: int
    refcount: int = 0
    last_used: int = 0
    pages: Optional[Tuple[int, ...]] = None
    swapped: bool = False
    swapped_pages: int = 0


@dataclasses.dataclass(frozen=True)
class PrefixMatch:
    """A verified admission-time hit: share ``pages`` (covering
    ``length`` positions) into the admitted slot's page table (``row``
    is the entry's key). ``swapped=True`` marks a hit
    whose page bytes sit in the host tier (``pages`` is None until
    the engine swaps them back in)."""

    row: int
    length: int
    pages: Optional[Tuple[int, ...]] = None
    swapped: bool = False


class PrefixCache:
    """Host-side index of retained prompt prefixes (see module
    docstring). ``block_len`` must equal the engine's ``chunk_len``;
    ``on_evict`` receives an evicted entry's pages."""

    def __init__(self, *, block_len: int,
                 on_evict: Optional[Callable[[Tuple[int, ...]],
                                             None]] = None):
        if block_len < 1:
            raise ValueError("block_len must be >= 1")
        self.block_len = int(block_len)
        self._entries: Dict[int, _Entry] = {}        # key -> entry
        self._index: Dict[int, Tuple[int, int]] = {}  # key -> (row, blocks)
        self._clock = itertools.count(1)
        # synthetic negative keys (never collide with handoff uids,
        # nor — being process-unique — with sibling caches sharing one
        # host arena) + the page-release hook eviction fires
        self._paged_key = _paged_key
        self._on_evict = on_evict
        # hierarchical-KV hooks (engine-wired via set_swap_hooks; both
        # None = no host tier, eviction destroys as always)
        self._swap_out_fn: Optional[Callable[[int, Tuple[int, ...]],
                                             bool]] = None
        self._swap_contains: Optional[Callable[[int], bool]] = None
        # raw counters (the scheduler mirrors them into serving.prefix.*)
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.tokens_reused = 0
        self.registrations = 0
        self.swap_outs = 0
        self.swap_ins = 0

    # ------------------------------------------------------------- geometry
    @property
    def size(self) -> int:
        return len(self._entries)

    @property
    def hit_rate(self) -> float:
        """Hits over admissions consulted so far (0.0 before the first)."""
        n = self.hits + self.misses
        return self.hits / n if n else 0.0

    # -------------------------------------------------------------- hashing
    def block_keys(self, tokens: Sequence[int], n_blocks: int) -> List[int]:
        """The first ``n_blocks`` rolling keys of ``tokens`` — ``H_i``
        covers blocks ``[0, i]`` (``(i+1) * block_len`` tokens)."""
        keys, h = [], 0
        for i in range(n_blocks):
            block = tuple(int(t) for t in
                          tokens[i * self.block_len:(i + 1) * self.block_len])
            h = _roll(h, block)
            keys.append(h)
        return keys

    # ------------------------------------------------------------- matching
    def match(self, prompt: Sequence[int],
              keys: Optional[Sequence[int]] = None) -> \
            Optional[PrefixMatch]:
        """Longest cached block-aligned prefix of ``prompt``, verified
        token-for-token; None on a miss. The match never covers the
        whole prompt (cap ``aligned(n - 1)``): the final block must run
        through chunk prefill so its logits produce the request's first
        token. Counts toward :attr:`hit_rate` either way.

        ``keys`` (optional) are ``prompt``'s PRECOMPUTED rolling block
        keys — at least ``(n - 1) // block_len`` of them, e.g. from
        :meth:`block_keys` run on a :class:`~apex_tpu.serving
        .DraftWorker` thread at submit time (the async heartbeat's
        hash offload). The hash is deterministic and every hit is
        still verified token-for-token below, so precomputed and
        inline keys are interchangeable bit-for-bit."""
        best = self._best_match(prompt, keys)
        if best is None:
            self.misses += 1
            return None
        self.hits += 1
        self.tokens_reused += best.length
        entry = self._entries[best.row]
        entry.last_used = next(self._clock)
        return best

    def probe(self, prompt: Sequence[int],
              keys: Optional[Sequence[int]] = None) -> int:
        """READ-ONLY affinity probe: the length of the longest cached
        block-aligned prefix of ``prompt`` (0 on a miss), verified
        token-for-token exactly like :meth:`match` — but touching
        NOTHING: no hit/miss counters, no LRU refresh, no refcounts.
        This is the :class:`~apex_tpu.serving.Router`'s routing signal
        — it probes EVERY replica's cache per request, and a probe that
        counted would poison :attr:`hit_rate` (and churn LRU order) on
        the N-1 replicas the request never lands on. Same ``keys``
        contract as :meth:`match`."""
        best = self._best_match(prompt, keys)
        return 0 if best is None else best.length

    def _best_match(self, prompt: Sequence[int],
                    keys: Optional[Sequence[int]] = None) -> \
            Optional[PrefixMatch]:
        """The pure match walk shared by :meth:`match` (which adds
        counter + LRU bookkeeping) and :meth:`probe` (which must not)."""
        n = len(prompt)
        max_blocks = (n - 1) // self.block_len       # strictly < n tokens
        if keys is None:
            keys = self.block_keys(prompt, max_blocks)
        best: Optional[PrefixMatch] = None
        for i in range(max_blocks):
            h = keys[i]
            hit = self._index.get(h)
            if hit is None:
                continue
            row, blocks = hit
            entry = self._entries.get(row)
            length = blocks * self.block_len
            if entry is None or len(entry.tokens) < length:
                continue
            # hash keys accelerate, tokens decide: a collision (or an
            # entry the key outlived) can only cost a miss here
            if tuple(entry.tokens[:length]) != tuple(
                    int(t) for t in prompt[:length]):
                continue
            if entry.swapped:
                # hierarchical KV: the entry's page bytes live in the
                # host tier. A hit is still a hit — the engine swaps
                # them back in at attach time — but only while the
                # tier actually holds the bytes (contains is a pure
                # read: probe stays side-effect-free through it)
                if self._swap_contains is None \
                        or not self._swap_contains(row):
                    continue
                best = PrefixMatch(row=row, length=length, pages=None,
                                   swapped=True)
                continue
            # the entry's page_len: its tokens spread evenly over
            # its pages (both block- and page-aligned by the
            # engine's registration contract)
            page_len = len(entry.tokens) // len(entry.pages)
            pages = entry.pages[:length // page_len]
            best = PrefixMatch(row=row, length=length, pages=pages)
        return best

    # ------------------------------------------------------------ refcounts
    def acquire(self, match: PrefixMatch) -> None:
        """Pin the matched entry against eviction until
        :meth:`release`."""
        self._entries[match.row].refcount += 1

    def release(self, match: PrefixMatch) -> None:
        entry = self._entries.get(match.row)
        if entry is not None and entry.refcount > 0:
            entry.refcount -= 1

    def unrecord_hit(self, match: PrefixMatch) -> None:
        """Reverse one :meth:`match`'s hit accounting — the failed
        swap-in path (missing or checksum-failed host bytes): the
        engine degrades the hit to a verified miss and re-prefills, so
        the counters must read a miss too or :attr:`hit_rate` would
        claim reuse that never happened."""
        self.hits -= 1
        self.misses += 1
        self.tokens_reused -= match.length

    # ---------------------------------------------------------- registration
    def register(self, prompt: Sequence[int], *, pages: Sequence[int],
                 keys: Optional[Sequence[int]] = None) -> str:
        """Retain ``prompt``'s block-aligned prefix: ``pages`` are the
        page ids already holding it; nothing is copied, and the CALLER
        bumps the pages' refcounts iff the outcome
        is ``"registered"`` (eviction releases them through
        ``on_evict``). Returns the outcome:

        - ``"registered"`` — the prefix's pages were recorded;
        - ``"duplicate"`` — the exact prefix is already retained (LRU
          refreshed, no extra refcounts);
        - ``"too_short"`` — the prompt spans no full block.

        ``keys`` (optional) are the prompt's precomputed rolling block
        keys (at least ``n_blocks`` of them) — same contract as
        :meth:`match`.
        """
        n_blocks = len(prompt) // self.block_len
        if n_blocks == 0:
            return "too_short"
        length = n_blocks * self.block_len
        keys = self.block_keys(prompt, n_blocks) if keys is None \
            else list(keys[:n_blocks])
        hit = self._index.get(keys[-1])
        if hit is not None:
            row, blocks = hit
            entry = self._entries.get(row)
            if entry is not None and blocks == n_blocks and tuple(
                    entry.tokens[:length]) == tuple(
                    int(t) for t in prompt[:length]):
                entry.last_used = next(self._clock)
                return "duplicate"
        if not len(pages) or length % len(pages):
            raise ValueError(
                f"{len(pages)} pages cannot evenly hold a "
                f"{length}-token prefix")
        row = next(self._paged_key)
        entry = _Entry(row=row,
                       tokens=tuple(int(t) for t in prompt[:length]),
                       n_blocks=n_blocks, last_used=next(self._clock),
                       pages=tuple(int(p) for p in pages))
        self._entries[row] = entry
        for i, key in enumerate(keys):
            # shorter-prefix keys already owned by another entry keep
            # their owner (it is just as valid a donor); this entry
            # claims every depth not yet addressed
            if key not in self._index:
                self._index[key] = (row, i + 1)
        self.registrations += 1
        return "registered"

    def register_handoff(self, key: int, prompt: Sequence[int], *,
                         pages: Optional[Sequence[int]] = None,
                         n_pages: int = 0,
                         keys: Optional[Sequence[int]] = None) -> str:
        """Register a disaggregated-serving HANDOFF prefix under an
        EXTERNALLY supplied key (the request uid — positive, globally
        unique, so records from N engines sharing one
        :class:`~apex_tpu.serving.HostTier` arena can never collide
        the way each cache's private negative synthetic keys would).
        Two sides of the same handoff:

        - **exporter** (prefill-role engine): pass ``pages`` — the
          slot's page ids holding the ingested prefix. The entry is
          registered RESIDENT exactly like an ordinary paged
          registration (the caller bumps page refcounts on
          ``"registered"``), ready for :meth:`swap_out_key` to land it
          in the shared arena.
        - **importer** (decode-role engine): pass ``n_pages`` with
          ``pages=None`` — the entry is born directly in the
          ``swapped`` state, backed by the arena record the exporter
          already published; the ordinary admission match + swap-in
          machinery then restores and shares it (or degrades to a
          verified miss) with zero handoff-specific code.

        Either way the entry is an ORDINARY swapped/resident prefix
        afterwards: affinity probes see it, host-capacity eviction
        drops it, ``drop``/``swap_in_complete`` treat it like any
        other. An existing entry under ``key`` is replaced (uid keys
        are single-writer by construction). Returns ``"registered"``
        or ``"too_short"`` (no full block — nothing worth handing
        off)."""
        if (pages is not None) and n_pages:
            raise ValueError("register_handoff takes pages (exporter) "
                             "or n_pages (importer), not both")
        key = int(key)
        if key < 0:
            raise ValueError("handoff keys are request uids (>= 0); "
                             "negative keys are the cache's private "
                             "synthetic namespace")
        n_blocks = len(prompt) // self.block_len
        if n_blocks == 0:
            return "too_short"
        length = n_blocks * self.block_len
        if pages is not None and length % len(pages):
            raise ValueError(
                f"{len(pages)} pages cannot evenly hold a "
                f"{length}-token prefix")
        keys = self.block_keys(prompt, n_blocks) if keys is None \
            else list(keys[:n_blocks])
        self.drop(key)              # uid re-registration replaces
        entry = _Entry(
            row=key, tokens=tuple(int(t) for t in prompt[:length]),
            n_blocks=n_blocks, last_used=next(self._clock),
            pages=(tuple(int(p) for p in pages)
                   if pages is not None else None),
            swapped=pages is None,
            swapped_pages=0 if pages is not None else int(n_pages))
        self._entries[key] = entry
        for i, k in enumerate(keys):
            if k not in self._index:
                self._index[k] = (key, i + 1)
        self.registrations += 1
        return "registered"

    def swap_out_key(self, key: int) -> bool:
        """Targeted resident→swapped migration of entry ``key`` (the
        handoff export: the entry's bytes must land in the shared
        arena NOW, not whenever LRU pressure would have picked it).
        Same contract as the :meth:`evict_lru` swap path — the engine
        hook snapshots the bytes before the device pages are released.
        False when the key is unknown, already swapped, or the tier
        declined (the caller hands off without a record and the
        importer re-prefills)."""
        entry = self._entries.get(int(key))
        if entry is None or entry.swapped:
            return False
        return self._swap_out(entry)

    def evict_lru(self) -> bool:
        """Evict the least-recently-used refcount-0 entry (pool-pressure
        valve: the engine calls this when an admission reservation
        cannot be covered — retained prefixes are a cache, the admitted
        request is not). False when nothing is evictable.

        With a host tier wired (:meth:`set_swap_hooks`) eviction is a
        SWAP-OUT first: the victim's page bytes migrate device→host and
        the entry stays matchable in the ``swapped`` state — its device
        pages are released either way, which is what the caller's
        pressure loop needs. Only resident entries are victims: a
        swapped entry holds no device pages, so evicting it would free
        nothing (the pressure loop would spin) — swapped entries leave
        the tier through host-capacity eviction or a failed swap-in,
        never through this valve."""
        victims = [e for e in self._entries.values()
                   if e.refcount == 0 and not e.swapped]
        if not victims:
            return False
        victim = min(victims, key=lambda e: e.last_used)
        if self._swap_out(victim):
            return True
        self._evict(victim)
        return True

    # -------------------------------------------------- hierarchical KV
    def set_swap_hooks(self, *, swap_out: Callable[[int, Tuple[int, ...]],
                                                   bool],
                       contains: Callable[[int], bool]) -> None:
        """Wire the host-DRAM tier (engine-side): ``swap_out(key,
        pages)`` migrates an evicted entry's page bytes device→host
        and returns True on success — True may mean the copy is merely
        DISPATCHED (async swap-out): the engine guarantees the
        snapshot precedes any page reuse, so this cache treats the
        entry as swapped either way. False = tier off/declined → the
        entry is destroyed, the pre-tier behaviour. ``contains(key)``
        is the read-only backing probe the match walk consults for
        swapped entries (in-flight *swapping* entries answer True)."""
        self._swap_out_fn = swap_out
        self._swap_contains = contains

    def _swap_out(self, entry: _Entry) -> bool:
        """Migrate ``entry`` resident→swapped: bytes to the host tier
        (via the engine hook, which must SNAPSHOT the bytes — copy, or
        dispatch the compiled gather that program-orders the copy —
        BEFORE this releases the device pages), page refcounts back to
        the pool. False — and no state change — when no tier is wired
        or the tier declined the bytes."""
        if self._swap_out_fn is None:
            return False
        if not self._swap_out_fn(entry.row, entry.pages):
            return False
        if self._on_evict is not None:
            self._on_evict(entry.pages)
        entry.swapped_pages = len(entry.pages)
        entry.pages = None
        entry.swapped = True
        self.swap_outs += 1
        _logger.debug("prefix cache swapped out %d-block prefix "
                      "(key %d, %d pages)", entry.n_blocks, entry.row,
                      entry.swapped_pages)
        return True

    def swap_in_complete(self, key: int, pages: Sequence[int]) -> None:
        """Mark entry ``key`` resident again on freshly migrated
        ``pages`` (the engine already wrote the host bytes into them
        and holds one refcount per page on the entry's behalf — the
        same ownership shape registration leaves behind)."""
        entry = self._entries[key]
        if not entry.swapped:
            raise ValueError(f"entry {key} is not swapped")
        entry.pages = tuple(int(p) for p in pages)
        entry.swapped = False
        entry.swapped_pages = 0
        self.swap_ins += 1

    def drop(self, key: int) -> bool:
        """Fully evict entry ``key`` (resident or swapped): the failed-
        swap-in degradation and the host tier's capacity-eviction
        callback both land here. A resident victim's pages go back
        through ``on_evict``; a swapped victim holds none. False when
        the key is unknown (already dropped)."""
        entry = self._entries.get(key)
        if entry is None:
            return False
        self._evict(entry)
        return True

    def swapped_keys(self) -> List[int]:
        """Keys of entries currently in the swapped state — the
        :class:`~apex_tpu.serving.PoolAuditor`'s cross-tier view:
        every one of these must be backed by a host-tier entry, and
        every host-tier entry must appear here."""
        return [e.row for e in self._entries.values() if e.swapped]

    def _evict(self, entry: _Entry) -> None:
        del self._entries[entry.row]
        for key, (_, blocks) in [(k, v) for k, v in self._index.items()
                                 if v[0] == entry.row]:
            # a shorter shared prefix the victim addressed may still be
            # resident inside a surviving longer entry — rebind instead
            # of orphaning the depth (keeps "longest cached prefix"
            # true after churn)
            heir = next(
                (e for e in self._entries.values()
                 if e.n_blocks >= blocks and e.tokens[:blocks
                    * self.block_len] == entry.tokens[:blocks
                    * self.block_len]), None)
            if heir is None:
                del self._index[key]
            else:
                self._index[key] = (heir.row, blocks)
        self.evictions += 1
        if entry.pages is not None and self._on_evict is not None:
            # hand the entry's page refcounts back (a page still shared
            # with a live slot survives — the pool frees it at zero)
            self._on_evict(entry.pages)
        _logger.debug("prefix cache evicted %d-block prefix (key %d)",
                      entry.n_blocks, entry.row)

    # ------------------------------------------------------------- lifecycle
    def clear(self) -> None:
        """Drop every entry and index key (counters survive — they are
        run-scoped, not cache-scoped). Entries hand their page
        refcounts back through ``on_evict`` so the pool reclaims them."""
        if self._on_evict is not None:
            for entry in self._entries.values():
                if entry.pages is not None:
                    self._on_evict(entry.pages)
        self._entries.clear()
        self._index.clear()

    def page_holds(self) -> List[Tuple[int, ...]]:
        """Every resident entry's retained page-id tuple — the refcounts
        the cache legitimately holds in the engine's
        :class:`~apex_tpu.serving.PagePool`, exposed for the
        :class:`~apex_tpu.serving.PoolAuditor`'s reconciliation walk."""
        return [entry.pages for entry in self._entries.values()
                if entry.pages is not None]

    def stats(self) -> dict:
        """One host-side snapshot of the cache's counters and occupancy
        (the scheduler mirrors this into ``serving.prefix.*``)."""
        return {
            "hits": self.hits,
            "misses": self.misses,
            "hit_rate": self.hit_rate,
            "tokens_reused": self.tokens_reused,
            "evictions": self.evictions,
            "registrations": self.registrations,
            "swap_outs": self.swap_outs,
            "swap_ins": self.swap_ins,
            "entries": self.size,
            "swapped_entries": len(self.swapped_keys()),
        }

    _DELTA_KEYS = ("hits", "misses", "tokens_reused", "evictions",
                   "registrations", "swap_outs", "swap_ins")

    def stats_since(self, baseline: dict) -> dict:
        """The counter DELTAS since ``baseline`` (a prior :meth:`stats`
        snapshot), with ``hit_rate`` recomputed over the window's own
        hits/misses. The raw counters are run-scoped, not cache-scoped —
        they survive :meth:`clear` and every engine ``reset()`` on
        purpose (cumulative totals stay honest across warm windows) —
        so any per-window reading (the router's per-replica affinity
        accounting, the bench's measured-window hit rate) must be a
        delta: reading :attr:`hit_rate` directly after a warm reset
        silently blends the warmup's hits in. Occupancy (``entries``)
        is reported as-of-now — it is state, not a counter."""
        now = self.stats()
        out = {k: now[k] - baseline.get(k, 0) for k in self._DELTA_KEYS}
        consulted = out["hits"] + out["misses"]
        out["hit_rate"] = out["hits"] / consulted if consulted else 0.0
        out["entries"] = self.size
        out["swapped_entries"] = len(self.swapped_keys())
        return out
