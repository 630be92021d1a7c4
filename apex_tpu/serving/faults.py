"""Fault isolation for the serving stack: injection, policy, auditing.

Production serving treats bad numerics and flaky steps as *expected
events to absorb*, not crashes — the same stance apex's dynamic loss
scaler takes toward training overflow (detect, skip, keep going). This
module is the serving-side counterpart, three pieces:

- :class:`FaultPlan` — a **seeded, deterministic fault injector**. A
  plan is a schedule of :class:`FaultSpec` events keyed by scheduler
  heartbeat (``tick``): non-finite logit injection into chosen decode
  slots (delivered through the compiled programs' ``fault_bias``
  operand, so the engine's in-program finiteness guard sees REAL
  NaN/Inf logits), transient exceptions raised at the chunk-prefill or
  decode call boundary (:class:`InjectedFault` — raised *instead of*
  the compiled call, so cache state is never half-mutated), heartbeat
  stalls (a plain sleep the watchdog must catch), whole-replica deaths
  consumed by the :class:`~apex_tpu.serving.Router`'s step loop (the
  router-tier fault: the dead replica's requests drain onto the
  survivors), and page-table corruption applied to **debug copies only**
  (:meth:`FaultPlan.corrupt_page_table` — proving the
  :class:`PoolAuditor` detects corruption; it is never pointed at the
  live tables). Deterministic by construction: explicit specs or
  :meth:`FaultPlan.random` from a seed — the chaos tests
  replay identical schedules.

- :class:`FaultPolicy` — the **per-request containment knobs** the
  scheduler applies when a fault (injected or real) surfaces: requeue
  with capped exponential backoff up to ``max_retries`` then a typed
  ``FAILED`` terminal status, a wall-clock watchdog budget per
  heartbeat (breach → ``serving.watchdog.stall`` + the ``on_stall``
  callback), and the :class:`PoolAuditor` sampling rate. The scheduler
  always runs with a policy (defaults are production-shaped);
  containment is not opt-in.

- :class:`PoolAuditor` — the **page-pool invariant checker**: an
  O(pages) host-side walk reconciling :class:`~apex_tpu.serving
  .PagePool` refcounts against every live slot's page table plus every
  prefix-cache entry's retained pages, plus free-list hygiene
  (no duplicates, refcount-0 only, disjoint from referenced pages) and
  page conservation (in-use + free == allocatable). Any mismatch
  raises :class:`PoolInvariantError` *loudly* — a leaked page
  (refcount above its visible readers: HBM that will never come back)
  or a double-free/dangling reference (refcount below: a table reading
  a page the allocator may hand to someone else) is corruption, not
  telemetry. Run it every event in tests (``every_n=1``); sample it in
  production (``FaultPolicy.audit_every_n``).

The guarantees this layer buys, pinned by ``tests/L0/test_faults.py``:
under an injected fault schedule every un-faulted greedy request
completes **bitwise token-identical** to a fault-free run (healthy
slots in a batch with a quarantined slot keep their exact tokens — the
guard is per-slot, the program is unchanged), every faulted request
reaches a typed terminal status, and the auditor reports zero
leaked/double-freed pages at drain.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from apex_tpu.log_util import get_logger

__all__ = ["FaultSpec", "FaultPlan", "FaultPolicy", "InjectedFault",
           "PoolAuditor", "PoolInvariantError", "fault_kind"]

_logger = get_logger("serving")


def fault_kind(error: Optional[str]) -> str:
    """Coarse classification of a quarantine error string — the
    ``kind`` annotation the request tracer stamps on ``quarantine``
    spans (and anything else that wants to bucket faults without
    parsing free text): ``"nonfinite"`` for guard-flagged NaN/Inf
    logits, ``"swap"`` for hierarchical-KV verification failures,
    ``"injected"`` for :class:`InjectedFault` transients (the chaos
    harness's signature), ``"exception"`` for every other transient.
    Checked in that order: an injected *non-finite* fault surfaces
    through the guard's error text and classifies as the numeric
    fault it manifested as."""
    low = (error or "").lower()
    if "non-finite" in low or "nan" in low or "inf " in low:
        return "nonfinite"
    if "swap" in low or "checksum" in low or "crc" in low:
        return "swap"
    if "injectedfault" in low:
        return "injected"
    return "exception"

# injection sites a FaultSpec(kind="exception") may name ("verify" is
# the speculative draft-and-verify call; it only fires on schedulers
# running speculative=True — see FaultPlan.random's ``sites``)
_EXCEPTION_SITES = ("chunk", "decode", "verify")


class InjectedFault(RuntimeError):
    """A :class:`FaultPlan`-scheduled transient failure, raised at the
    compiled-call boundary (the call itself never runs, so engine/cache
    state is exactly what it was before the heartbeat reached the
    call). ``slot`` names the victim slot when the site attributes one
    (decode faults), else -1 (the scheduler attributes the in-flight
    request at the call site)."""

    def __init__(self, message: str, slot: int = -1):
        super().__init__(message)
        self.slot = int(slot)
        self.transient = True


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """One scheduled fault.

    ``kind``:

    - ``"nonfinite"`` — add ``value`` (default NaN) to slot ``slot``'s
      decode logits at heartbeat ``tick`` via the decode program's
      ``fault_bias`` operand. The engine's in-program guard must flag
      the slot; every other slot's logits gain exactly ``+0.0``.
    - ``"exception"`` — raise :class:`InjectedFault` at heartbeat
      ``tick`` from injection site ``site`` (``"chunk"`` / ``"decode"``
      / ``"verify"``), instead of running the compiled call.
    - ``"stall"`` — sleep ``stall_s`` seconds at heartbeat ``tick``
      (the watchdog-budget breach the plan manufactures).
    - ``"replica_death"`` — the ROUTER-tier fault: kill replica
      ``replica`` at ROUTER tick ``tick``. Consumed by
      :meth:`FaultPlan.take_replica_deaths` from the
      :class:`~apex_tpu.serving.Router`'s step loop (a scheduler-tier
      plan never sees it) — the router drains the dead replica's
      queued and in-flight requests onto the survivors, so the death
      is a routing event, not an outage.
    - ``"swap_corruption"`` — the hierarchical-KV tier fault: at
      heartbeat ``tick``, flip one byte of a deterministically chosen
      entry in the engine's host-DRAM swap arena
      (:meth:`FaultPlan.maybe_corrupt_swap`, consumed from the
      scheduler's step loop on engines with a
      :class:`~apex_tpu.serving.HostTier`). The NEXT swap-in of the
      victim fails its CRC and must degrade to a verified miss
      (re-prefill, ``serving.swap.verify_failed``) — never a wrong
      token. An injection landing on an entry whose async swap-out is
      still IN FLIGHT (the *swapping* state) is armed instead and rots
      the bytes the moment the worker stores them — the race resolves
      to the same verified miss.
    - ``"handoff_corruption"`` — the disaggregated-serving fault, the
      same arena bit-flip as ``swap_corruption`` but victimizing only
      **handoff records** (arena keys >= 0 — request uids; ordinary
      paged prefixes use negative synthetic keys), via
      :meth:`FaultPlan.maybe_corrupt_handoff`. The decode-side import's
      CRC fails and the request re-prefills on the decode replica
      (``serving.disagg.reprefills``) — never a wrong token, with zero
      retries charged to the request.
    - ``"worker_hang"`` — the PROCESS-fleet fault: worker ``replica``
      stops answering its transport at controller tick ``tick``
      (alive but unresponsive — the failure mode a hard kill can't
      exercise). Consumed by :meth:`FaultPlan.take_worker_hangs` from
      the :class:`~apex_tpu.serving.FleetController`'s step loop; the
      heartbeat's missed-beat detector must declare the worker dead
      and re-route its requests, exactly as if the process had died.
    """

    kind: str
    tick: int
    slot: int = -1
    site: str = "decode"
    value: float = float("nan")
    stall_s: float = 0.0
    replica: int = -1

    def __post_init__(self):
        if self.kind not in ("nonfinite", "exception", "stall",
                             "replica_death", "swap_corruption",
                             "handoff_corruption", "worker_hang"):
            raise ValueError(f"unknown fault kind {self.kind!r}")
        if self.kind == "nonfinite" and self.slot < 0:
            raise ValueError("nonfinite faults need a victim slot")
        if self.kind == "exception" and self.site not in _EXCEPTION_SITES:
            raise ValueError(f"exception site {self.site!r} not in "
                             f"{_EXCEPTION_SITES}")
        if self.kind == "stall" and self.stall_s <= 0:
            raise ValueError("stall faults need stall_s > 0")
        if self.kind == "replica_death" and self.replica < 0:
            raise ValueError("replica_death faults need a victim "
                             "replica index")
        if self.kind == "worker_hang" and self.replica < 0:
            raise ValueError("worker_hang faults need a victim "
                             "replica index")


class FaultPlan:
    """A deterministic schedule of :class:`FaultSpec` events, consulted
    by the scheduler once per heartbeat (see module docstring). Plans
    are replayable: the same specs (or the same :meth:`random` seed)
    produce the same injections in the same heartbeats, which is what
    lets the chaos tests compare a chaos run against a fault-free run
    token-for-token."""

    def __init__(self, specs: Sequence[FaultSpec] = ()):
        self.specs: Tuple[FaultSpec, ...] = tuple(specs)
        self._nonfinite: Dict[int, List[FaultSpec]] = {}
        self._exceptions: Dict[Tuple[str, int], FaultSpec] = {}
        self._stalls: Dict[int, FaultSpec] = {}
        self._deaths: Dict[int, List[FaultSpec]] = {}
        self._swap_corruptions: Dict[int, FaultSpec] = {}
        self._handoff_corruptions: Dict[int, FaultSpec] = {}
        self._hangs: Dict[int, List[FaultSpec]] = {}
        for s in self.specs:
            if s.kind == "nonfinite":
                self._nonfinite.setdefault(int(s.tick), []).append(s)
            elif s.kind == "exception":
                self._exceptions[(s.site, int(s.tick))] = s
            elif s.kind == "replica_death":
                self._deaths.setdefault(int(s.tick), []).append(s)
            elif s.kind == "worker_hang":
                self._hangs.setdefault(int(s.tick), []).append(s)
            elif s.kind == "swap_corruption":
                self._swap_corruptions[int(s.tick)] = s
            elif s.kind == "handoff_corruption":
                self._handoff_corruptions[int(s.tick)] = s
            else:
                self._stalls[int(s.tick)] = s
        # raw injection counters (the chaos bench reads them)
        self.injected_nonfinite = 0
        self.injected_exceptions = 0
        self.injected_stalls = 0
        self.injected_replica_deaths = 0
        self.injected_swap_corruptions = 0
        self.injected_handoff_corruptions = 0
        self.injected_worker_hangs = 0

    @classmethod
    def random(cls, seed: int, ticks: int, *, slots: int,
               nonfinite_rate: float = 0.0, exception_rate: float = 0.0,
               stall_rate: float = 0.0, stall_s: float = 0.05,
               sites: Sequence[str] = ("chunk", "decode"),
               replica_death_rate: float = 0.0,
               replicas: int = 0,
               swap_corruption_rate: float = 0.0,
               handoff_corruption_rate: float = 0.0,
               worker_hang_rate: float = 0.0) -> "FaultPlan":
        """A seeded random schedule over ``ticks`` heartbeats: each
        tick independently draws a non-finite injection (uniform victim
        slot), a transient exception (site uniform over ``sites``),
        and/or a stall at the given per-tick rates. Same seed → same
        schedule, always. ``sites`` defaults to the two call sites every
        scheduler has — include ``"verify"`` only for speculative runs
        (a verify-site fault on a non-speculative scheduler never
        fires). ``replica_death_rate`` > 0 (router-tier plans only;
        requires ``replicas`` >= 1) additionally draws a replica death
        with a uniform victim — the draw is SKIPPED entirely at the
        default rate 0, so pre-router seeds replay bit-for-bit.
        ``swap_corruption_rate`` > 0 (hierarchical-KV engines only)
        draws a host-arena corruption per tick — same skipped-at-0
        contract, so every pre-host-tier seed also replays
        bit-for-bit. ``handoff_corruption_rate`` > 0 (disaggregated
        fleets only) draws a handoff-record corruption per tick — the
        draw is again skipped entirely at the default 0, preserving
        every pre-disaggregation seed. ``worker_hang_rate`` > 0
        (process-fleet plans only; requires ``replicas`` >= 1) draws a
        worker hang with a uniform victim — drawn LAST in the per-tick
        order and skipped entirely at the default 0, so every
        pre-fleet seed replays bit-for-bit."""
        for s in sites:
            if s not in _EXCEPTION_SITES:
                raise ValueError(f"exception site {s!r} not in "
                                 f"{_EXCEPTION_SITES}")
        if replica_death_rate > 0 and replicas < 1:
            raise ValueError("replica_death_rate > 0 needs replicas "
                             ">= 1 to draw victims from")
        if worker_hang_rate > 0 and replicas < 1:
            raise ValueError("worker_hang_rate > 0 needs replicas "
                             ">= 1 to draw victims from")
        rng = np.random.default_rng(seed)
        specs: List[FaultSpec] = []
        for t in range(int(ticks)):
            if rng.random() < nonfinite_rate:
                specs.append(FaultSpec(
                    kind="nonfinite", tick=t,
                    slot=int(rng.integers(0, max(1, slots)))))
            if rng.random() < exception_rate:
                specs.append(FaultSpec(
                    kind="exception", tick=t,
                    site=sites[int(rng.integers(0, len(sites)))]))
            if rng.random() < stall_rate:
                specs.append(FaultSpec(kind="stall", tick=t,
                                       stall_s=stall_s))
            if replica_death_rate > 0 \
                    and rng.random() < replica_death_rate:
                specs.append(FaultSpec(
                    kind="replica_death", tick=t,
                    replica=int(rng.integers(0, replicas))))
            if swap_corruption_rate > 0 \
                    and rng.random() < swap_corruption_rate:
                specs.append(FaultSpec(kind="swap_corruption", tick=t))
            if handoff_corruption_rate > 0 \
                    and rng.random() < handoff_corruption_rate:
                specs.append(FaultSpec(kind="handoff_corruption",
                                       tick=t))
            if worker_hang_rate > 0 \
                    and rng.random() < worker_hang_rate:
                specs.append(FaultSpec(
                    kind="worker_hang", tick=t,
                    replica=int(rng.integers(0, replicas))))
        return cls(specs)

    # ------------------------------------------------------------ injection
    def decode_bias(self, tick: int, slots: int) -> Optional[np.ndarray]:
        """The decode program's per-slot logit bias for this heartbeat:
        ``None`` (no operand worth building) on fault-free ticks, else
        a float32 ``[slots]`` array that is 0.0 everywhere except the
        victim slots' injected values. Victims outside ``[0, slots)``
        are ignored (a random plan drawn for a wider engine stays
        usable)."""
        specs = self._nonfinite.get(int(tick))
        if not specs:
            return None
        bias = np.zeros(int(slots), np.float32)
        hit = False
        for s in specs:
            if 0 <= s.slot < slots:
                bias[s.slot] = np.float32(s.value)
                hit = True
        if not hit:
            return None
        self.injected_nonfinite += 1
        return bias

    def take_nonfinite(self, tick: int, slot: int) -> Optional[float]:
        """CONSUME the non-finite injection scheduled for ``slot`` at
        this heartbeat, if any, returning its value (the verify call's
        scalar ``fault_bias``) — or None. The speculative scheduler
        calls this for each slot it verifies BEFORE building the decode
        batch's :meth:`decode_bias`, so a victim slot that takes the
        verify path this tick still gets its scheduled injection
        (through the verify program's guard instead of the decode
        program's) and is never double-injected."""
        specs = self._nonfinite.get(int(tick))
        if not specs:
            return None
        for i, s in enumerate(specs):
            if s.slot == int(slot):
                specs.pop(i)
                self.injected_nonfinite += 1
                return float(s.value)
        return None

    def maybe_raise(self, site: str, tick: int) -> None:
        """Raise the :class:`InjectedFault` scheduled for ``site`` at
        this heartbeat, if any — called by the scheduler *instead of*
        the compiled call it guards. The spec is CONSUMED when it
        fires: one scheduled fault is one injection with one victim,
        even when the heartbeat makes several calls at the same site
        (chunk budgets > 1, cold-queue bursts)."""
        spec = self._exceptions.pop((site, int(tick)), None)
        if spec is not None:
            self.injected_exceptions += 1
            raise InjectedFault(
                f"injected transient {site} failure at tick {tick}",
                slot=spec.slot)

    def take_replica_deaths(self, tick: int) -> List[int]:
        """CONSUME the replica deaths scheduled for this ROUTER tick,
        returning the victim replica indices (empty on death-free
        ticks). Called by the :class:`~apex_tpu.serving.Router` once
        per step — each spec fires exactly once, like every other
        injection."""
        specs = self._deaths.pop(int(tick), None)
        if not specs:
            return []
        self.injected_replica_deaths += len(specs)
        return [s.replica for s in specs]

    def take_worker_hangs(self, tick: int) -> List[int]:
        """CONSUME the worker hangs scheduled for this CONTROLLER
        tick, returning the victim replica indices (empty on
        hang-free ticks). Called by the
        :class:`~apex_tpu.serving.FleetController` once per step — a
        hung worker stays alive but stops answering its transport, so
        only the missed-beat heartbeat detector can catch it."""
        specs = self._hangs.pop(int(tick), None)
        if not specs:
            return []
        self.injected_worker_hangs += len(specs)
        return [s.replica for s in specs]

    def maybe_corrupt_swap(self, tick: int, tier) -> bool:
        """CONSUME the ``swap_corruption`` scheduled for this
        heartbeat, if any, by flipping one byte of a deterministically
        chosen entry in ``tier`` (a :class:`~apex_tpu.serving
        .HostTier` — victim = the ``tick``-th resident key in sorted
        order, so replays corrupt the same entry). Called by the
        scheduler once per heartbeat on hierarchical-KV engines. An
        empty arena makes the injection a no-op (nothing swapped yet —
        the spec is still consumed at its tick, like every other
        injection, but not counted as delivered). Returns True when a
        byte actually flipped."""
        spec = self._swap_corruptions.pop(int(tick), None)
        if spec is None:
            return False
        keys = sorted(tier.keys())
        if not keys:
            return False
        tier.corrupt_entry(keys[int(tick) % len(keys)])
        self.injected_swap_corruptions += 1
        return True

    def maybe_corrupt_handoff(self, tick: int, tier) -> bool:
        """CONSUME the ``handoff_corruption`` scheduled for this
        heartbeat, if any, by flipping one byte of a deterministically
        chosen HANDOFF record in ``tier`` — victims are the uid-keyed
        records only (arena keys >= 0; ordinary paged prefixes mint
        negative synthetic keys), so the injection lands on the
        cross-replica transfer path specifically. Rides the exact
        ``swap_corruption`` plumbing: an arena with no handoff records
        makes the injection a no-op (spec still consumed at its tick),
        and a victim whose swap-out is still in flight is armed to rot
        on store. Returns True when a byte actually flipped."""
        spec = self._handoff_corruptions.pop(int(tick), None)
        if spec is None:
            return False
        keys = sorted(k for k in tier.keys() if k >= 0)
        if not keys:
            return False
        tier.corrupt_entry(keys[int(tick) % len(keys)])
        self.injected_handoff_corruptions += 1
        return True

    def maybe_stall(self, tick: int) -> float:
        """Sleep through the stall scheduled for this heartbeat (if
        any); returns the seconds slept (0.0 on stall-free ticks)."""
        spec = self._stalls.get(int(tick))
        if spec is None:
            return 0.0
        self.injected_stalls += 1
        time.sleep(spec.stall_s)
        return spec.stall_s

    def corrupt_page_table(self, page_table: np.ndarray,
                           n_pages: np.ndarray, *, slot: int = 0,
                           entry: int = 0,
                           value: int = -1) -> np.ndarray:
        """Corrupt one entry of a **debug copy** of a page table (the
        auditor-sensitivity probe: a corrupted copy must make
        :meth:`PoolAuditor.audit` raise). Refuses to write through to
        what looks like live engine state — pass
        ``Engine.page_table_snapshot()`` output. Returns the corrupted
        table for chaining."""
        if not page_table.flags.writeable or not page_table.flags.owndata:
            raise ValueError(
                "corrupt_page_table mutates its argument and is meant "
                "for DEBUG COPIES (Engine.page_table_snapshot()) — "
                "refusing a view/read-only array that may be live "
                "engine state")
        if not int(n_pages[slot]):
            raise ValueError(f"slot {slot} holds no pages to corrupt")
        entry = int(entry) % int(n_pages[slot])
        page_table[slot, entry] = value
        return page_table

    def stats(self) -> dict:
        """Injection counts so far (the chaos bench's honesty row)."""
        return {
            "scheduled": len(self.specs),
            "injected_nonfinite": self.injected_nonfinite,
            "injected_exceptions": self.injected_exceptions,
            "injected_stalls": self.injected_stalls,
            "injected_replica_deaths": self.injected_replica_deaths,
            "injected_swap_corruptions": self.injected_swap_corruptions,
            "injected_handoff_corruptions":
                self.injected_handoff_corruptions,
            "injected_worker_hangs": self.injected_worker_hangs,
        }


@dataclasses.dataclass
class FaultPolicy:
    """The scheduler's containment knobs (always on; these defaults are
    the production shape — tests tighten ``audit_every_n`` to 1 and
    zero the backoff for speed).

    - ``max_retries``: transient faults a request may absorb before its
      typed ``FAILED`` terminal status (each fault releases the slot
      and its pages, then requeues).
    - ``backoff_base_s`` / ``backoff_cap_s``: capped exponential
      backoff between retries (``base * 2**(retries-1)``, capped) — a
      requeued request is not re-admitted before its backoff elapses.
    - ``watchdog_budget_s``: wall-clock budget per scheduler heartbeat;
      a breach emits ``serving.watchdog.stall`` (+ the breach duration
      into the ``serving.watchdog.stall_s`` histogram) and invokes
      ``on_stall(elapsed_s)``. ``None`` disables the watchdog.
      Heartbeats that TRACE a compiled program (first contact with
      chunk/decode/prefill/verify) are exempt — their wall time is
      one-off compile latency, observed separately as
      ``serving.watchdog.warmup_s`` — so tiny budgets no longer
      false-trip on tick 0 of a cold engine.
    - ``audit_every_n``: run the :class:`PoolAuditor` every N
      finish/eviction events (1 = every event — the test setting; the
      default samples). ``0`` disables auditing.
    """

    max_retries: int = 2
    backoff_base_s: float = 0.05
    backoff_cap_s: float = 2.0
    watchdog_budget_s: Optional[float] = None
    on_stall: Optional[Callable[[float], None]] = None
    audit_every_n: int = 64

    def backoff_s(self, retries: int) -> float:
        """Backoff before retry number ``retries`` (1-based)."""
        if self.backoff_base_s <= 0:
            return 0.0
        return min(self.backoff_base_s * (2.0 ** (max(int(retries), 1)
                                                  - 1)),
                   self.backoff_cap_s)


class PoolInvariantError(RuntimeError):
    """A page-pool invariant does not hold: leaked pages (refcounted
    above their visible readers), double-frees/dangling references
    (below), free-list corruption, or an out-of-range/sentinel page id
    in a live table. Raised loudly by :meth:`PoolAuditor.audit` —
    this is corruption, not a telemetry event."""


class PoolAuditor:
    """Reconcile a paged engine's :class:`~apex_tpu.serving.PagePool`
    refcounts with everything that can legitimately hold a page: live
    slot page tables and prefix-cache entries (see module docstring).
    O(pages + table entries) of pure numpy/python per audit — cheap
    enough for ``every_n=1`` in tests; sample in production.

    ``maybe_audit`` is the scheduler's hook (counts events, audits
    every ``every_n``-th); ``audit`` is the full check, callable with
    debug-copy overrides so the chaos tests can prove a corrupted
    table is *detected*."""

    def __init__(self, every_n: int = 1, registry=None):
        self.every_n = int(every_n)
        self._registry = registry
        self._events = 0
        self.audits = 0

    def maybe_audit(self, engine) -> Optional[dict]:
        """Count one auditable event (request finish, prefix eviction);
        run :meth:`audit` on every ``every_n``-th. No-op (None) when
        sampling skips this event or auditing is disabled."""
        if self.every_n <= 0:
            return None
        self._events += 1
        if self._events % self.every_n:
            return None
        return self.audit(engine)

    def audit(self, engine, page_table: Optional[np.ndarray] = None,
              n_pages: Optional[np.ndarray] = None) -> dict:
        """Walk the pool and raise :class:`PoolInvariantError` on any
        violation; returns a summary dict when everything reconciles.
        ``page_table``/``n_pages`` override the engine's live tables
        with debug copies (the corruption-detection probe)."""
        pool = engine.pool
        if page_table is None:
            page_table = engine._page_table
        if n_pages is None:
            n_pages = engine._n_pages
        num_pages = pool.num_pages
        problems: List[str] = []
        expected = np.zeros(num_pages, np.int64)
        for s in range(page_table.shape[0]):
            n = int(n_pages[s])
            for p in page_table[s, :n]:
                p = int(p)
                if not 0 < p < num_pages:
                    problems.append(
                        f"slot {s} table holds page id {p} outside the "
                        f"allocatable range (1, {num_pages}) — corrupt "
                        f"entry or sentinel in the live region")
                else:
                    expected[p] += 1
        pcache = getattr(engine, "prefix_cache", None)
        if pcache is not None:
            for pages in pcache.page_holds():
                for p in pages:
                    p = int(p)
                    if not 0 < p < num_pages:
                        problems.append(
                            f"prefix entry holds out-of-range page id "
                            f"{p}")
                    else:
                        expected[p] += 1
        ref = np.asarray(pool.refcount, np.int64)
        leaked = np.flatnonzero(ref > expected)
        dangling = np.flatnonzero(ref < expected)
        if leaked.size:
            problems.append(
                f"LEAKED pages {leaked.tolist()}: refcount "
                f"{ref[leaked].tolist()} exceeds visible readers "
                f"{expected[leaked].tolist()} — these pages can never "
                f"return to the free list")
        if dangling.size:
            problems.append(
                f"DOUBLE-FREED/dangling pages {dangling.tolist()}: "
                f"visible readers {expected[dangling].tolist()} exceed "
                f"refcount {ref[dangling].tolist()} — a table "
                f"references a page the allocator may reuse")
        free = [int(p) for p in pool.free_list()]
        free_set = set(free)
        if len(free_set) != len(free):
            problems.append("free list holds duplicate page ids")
        if 0 in free_set:
            problems.append("sentinel page 0 is on the free list")
        out_of_range = [p for p in free_set if not 0 <= p < num_pages]
        if out_of_range:
            problems.append(
                f"free list holds out-of-range page ids "
                f"{out_of_range} — a future alloc would hand out a "
                f"page that does not exist")
        bad_free = [p for p in free_set
                    if 0 < p < num_pages and ref[p] != 0]
        if bad_free:
            problems.append(
                f"pages {bad_free} are on the free list with nonzero "
                f"refcounts")
        # conservation against an INDEPENDENT quantity (pages_in_use is
        # derived from the free list, so comparing those two would be a
        # tautology): every allocatable page must be either free or
        # refcounted — a page that is neither has fallen out of the
        # allocator entirely and can never be handed out again
        lost = [p for p in range(1, num_pages)
                if ref[p] == 0 and p not in free_set]
        if lost:
            problems.append(
                f"pages {lost} are neither free nor referenced — lost "
                f"from the allocator (conservation broken)")
        # hierarchical KV: the host-DRAM tier must reconcile with the
        # prefix cache's swapped state — a swapped entry holds no
        # device pages (it already left the `expected` walk above), but
        # swap-in/out must never strand bytes on either side. Three
        # invariants: (1) every swapped index entry is backed by a
        # host-arena record (a dangling entry would swap in nothing —
        # or garbage), (2) every arena record backs a swapped entry
        # (an orphan is host DRAM that can never be read again — the
        # host-side leak), (3) the arena's byte accounting matches its
        # stored arrays and respects its capacity bound.
        tier = getattr(engine, "host_tier", None)
        if tier is not None:
            tier_keys = set(tier.keys())
            if not getattr(engine, "host_tier_shared", False):
                # the two set-inclusion directions are PER-ENGINE
                # invariants only when the engine owns the tier: in a
                # SHARED arena (disaggregated serving) other engines'
                # records legitimately coexist, and a handoff record is
                # momentarily ownerless between the exporter dropping
                # its entry and the importer registering one — the
                # disaggregation test asserts the FLEET-level union
                # equality instead. The byte ledger and capacity bound
                # below are tier-global and hold either way.
                swapped = set(pcache.swapped_keys()) \
                    if pcache is not None else set()
                dangling_swap = sorted(swapped - tier_keys)
                if dangling_swap:
                    problems.append(
                        f"swapped prefix entries {dangling_swap} have "
                        f"no host-tier backing — a hit would find "
                        f"nothing to swap in (dangling swap state)")
                orphaned = sorted(tier_keys - swapped)
                if orphaned:
                    problems.append(
                        f"host-tier entries {orphaned} back no swapped "
                        f"prefix entry — unreachable host bytes "
                        f"(host-side leak)")
            actual = sum(tier.nbytes_of(k) for k in tier_keys)
            if actual != tier.bytes_used:
                problems.append(
                    f"host-tier byte accounting drifted: reports "
                    f"{tier.bytes_used}, stored arrays hold {actual}")
            if tier.bytes_used > tier.capacity_bytes:
                problems.append(
                    f"host tier over capacity: {tier.bytes_used} bytes "
                    f"held against a {tier.capacity_bytes}-byte bound")
        self.audits += 1
        if self._registry is not None:
            self._registry.counter_inc("serving.faults.audits")
        if problems:
            raise PoolInvariantError(
                "page-pool invariant audit failed:\n  - "
                + "\n  - ".join(problems))
        return {
            "pages": num_pages,
            "pages_in_use": pool.pages_in_use,
            "pages_free": len(free),
            "cow_shares": pool.cow_shares,
            "audits": self.audits,
        }
