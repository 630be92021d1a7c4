"""Replica-parallel serving: a prefix-aware least-loaded router over N
engines.

One :class:`~apex_tpu.serving.Engine` — even tp-sharded, paged,
quantized and pipelined — is a hard ceiling on aggregate tokens/s. The
next multiplier is data parallelism: run N engine replicas (each
optionally ``mesh=``-sharded, so the fleet is a tp × dp grid) behind a
HOST-SIDE router that turns the load gauges, backpressure hints and
fault containment the serving stack already emits into scale-out. The
router is pure host bookkeeping — it owns one
:class:`~apex_tpu.serving.Scheduler` per engine and adds ZERO compiled
programs; every device byte stays inside its replica.

**Routing** (:meth:`Router.submit`) is a two-signal decision over the
live replicas:

1. **Prefix affinity.** Multi-turn and shared-template traffic is
   dominated by prompts whose K/V already lives in SOME replica's
   prefix cache — but only that replica's. The router hashes the
   prompt's rolling block keys ONCE
   (:meth:`PrefixCache.block_keys`) and probes every live replica's
   cache read-only (:meth:`PrefixCache.probe` — no counters, no LRU
   churn on the N-1 losers), preferring the replica holding the
   longest verified prefix: the request lands where its K/V is, turns
   chunk prefill into a copy-on-write page share, and the probe keys
   ride along to the chosen scheduler (``submit(prefix_keys=...)``) so
   the hash is never recomputed.
2. **Least-loaded admission.** Ties — and the no-match majority at
   cold start — fall to load: free slots (desc), queue depth (asc),
   then free pool pages (desc), read from each replica's host-only
   :meth:`Scheduler.load_snapshot` (the same quantities the
   ``serving.pool.*`` / occupancy gauges publish, sampled at routing
   time instead of scraped from telemetry).

**Backpressure composes across replicas**: a chosen replica at queue
capacity is not an error but a *spill* — the router retries the
next-best replica (counted as ``serving.router.spills``) and raises
:class:`~apex_tpu.serving.QueueFull` only when EVERY live replica is
saturated, with ``retry_after_s`` the MAX of the replicas'
data-driven hints (the fleet has space when its slowest-to-free
replica does; replicas with no measured decode EMA contribute None and
never fake a number).

**A dead replica is a routing event, not an outage.** The router-tier
:class:`~apex_tpu.serving.FaultPlan` kind ``"replica_death"``
(consumed by :meth:`FaultPlan.take_replica_deaths` in
:meth:`Router.step`) — or an operator's :meth:`Router.kill_replica` —
drains the victim through :meth:`Scheduler.drain_requests`: every
queued and in-flight request rolls back to a servable queued state
(outputs cleared, paid-compute counters and the original submit clock
kept — the PR 7 quarantine machinery, minus the retry charge: a
replica death is not the request's fault), its slots free their pages
so the dead pool audits leak-free, and the drained requests re-route
onto the survivors through the normal affinity/least-loaded path.
Requests on surviving replicas never notice: greedy decode depends
only on a slot's own K/V lineage, so their tokens stay BITWISE
identical to a fault-free run even as drained refugees join their
batches (pinned by ``tests/L0/test_router.py``).

Telemetry (all host-side, through the shared registry): counters
``serving.router.routed`` / ``affinity_hits`` / ``spills`` /
``replica_deaths`` / ``requeued``, the ``serving.router.replicas_alive``
gauge, and per-replica load gauges namespaced as
``serving.router.replica<i>.{queue_depth, slots_busy, pages_free,
host_bytes_free}`` (the last only on hierarchical-KV replicas — the
swap arena's remaining headroom, the least-loaded tie-break's newest
input) so N replicas sharing one registry never clobber each other's
pool gauges. Replica-internal metrics (TTFT, step latencies, prefix
counters, fault counters) flow into the SAME shared registry as
fleet-wide aggregates — which is what a capacity dashboard wants —
while per-replica prefix accounting uses
:meth:`PrefixCache.stats_since` deltas, immune to the counters'
cumulative-across-reset semantics.

**Disaggregated serving** (``roles=[...]``): replica role is a
first-class routing policy. A ``"prefill"`` replica ingests prompts
through chunk prefill and — at ingestion completion — exports the
finished block-aligned prefix into the fleet's SHARED
:class:`~apex_tpu.serving.HostTier` arena (``shared=True``, one
instance co-owned by every engine) via the async per-shard-CRC'd
swap-out; it never decodes a token. The router collects the ready
hand-over (:meth:`Scheduler.take_handoffs` — the record's swap-out has
completed, so an importer can never race the CRC), transfers record
ownership (the exporter's cache entry stands down, the arena record
survives), registers the record as a born-swapped prefix on the best
``"decode"``-capable replica and re-submits the request there
(``_handoff=True``). The decode replica's ordinary admission path —
prefix match, CRC-verified swap-in scatter, copy-on-write page share —
resumes prefill at the exact committed offset and samples the first
token bitwise-identically to a single-replica run: zero re-prefill on
the happy path. A corrupt, evicted or failed record degrades per the
hierarchical-KV contract to a VERIFIED MISS (the decode side
re-prefills cold, counted as ``serving.disagg.reprefills``), never a
wrong token. ``roles=None`` (every replica ``"both"``) is the
verbatim default and leaves every code path above untouched. In a
mixed fleet quarantine requeues also flow back through the router
(:class:`Scheduler` ``on_requeue``), so a re-routed request re-probes
the LIVE replicas and the arena at re-route time instead of being
pinned to its first home.

CPU-regime note (same shape as every serving PR): replicas on this
box's CPU backend share cores, so N-replica tokens/s is NOT a scaling
measurement here — the CPU-honest columns are prefix-affinity hit rate
vs the random-routing control, bitwise parity across replica counts,
and leak-free drains; the aggregate-throughput scaling claim is
silicon's. For ``roles`` fleets the CPU-honest columns are
decode-beat isolation (``serving.disagg.decode_isolation``) and the
handoff byte/latency histograms — not tokens/s.
"""

from __future__ import annotations

import collections
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from apex_tpu.log_util import get_logger

from .routing_policy import (ROUTE_POLICIES, fleet_retry_hint,
                             note_placement, random_order,
                             rank_replicas)
from .scheduler import QueueFull, Request, Scheduler
from .slo import TenantLedger

__all__ = ["Router"]

_logger = get_logger("serving")

# The decision core lives in routing_policy (shared with the
# process-level FleetController — both fronts provably rank, spill
# and hint through the SAME functions); these aliases keep the
# router's historical names importable.
_ROUTE_POLICIES = ROUTE_POLICIES


class Router:
    """N ``Scheduler``+``Engine`` replicas behind one prefix-aware
    least-loaded ``submit()`` (see module docstring).

    Parameters
    ----------
    engines:
        The replica engines, pre-built by the caller (so tp meshes,
        quantized tiers and pool geometry compose per replica exactly
        as on a single engine). Serving geometry (``slots`` /
        ``max_len`` / ``prefill_len`` / ``chunk_len``) must agree
        across replicas — routing treats them as interchangeable — and
        with ``retain_prefixes=True`` so must the prefix block length.
    registry:
        Shared :class:`~apex_tpu.telemetry.MetricsRegistry`: the router
        emits ``serving.router.*`` and hands the SAME registry to every
        replica scheduler (counters and histograms aggregate
        fleet-wide; per-replica load gauges are namespaced — see
        module docstring).
    route_policy:
        ``"affinity"`` (default): longest probed prefix first, load as
        the tie-break — degrades to pure least-loaded when retention
        is off (nothing to probe). ``"least_loaded"``: gauges only.
        ``"random"``: seeded uniform routing — the bench's control row,
        not a production mode.
    seed:
        The ``"random"`` policy's RNG seed (unused otherwise).
    fault_plan:
        A ROUTER-TIER :class:`~apex_tpu.serving.FaultPlan`: only its
        ``"replica_death"`` specs are consumed here (per-replica chaos
        belongs in ``replica_plans``). Ticks are router steps.
    replica_plans:
        Optional per-replica scheduler fault plans (length N), passed
        through to each :class:`~apex_tpu.serving.Scheduler` — replica-
        tier chaos composes with router-tier deaths.
    tracer:
        Optional :class:`~apex_tpu.telemetry.Tracer`: request-level
        lifecycle tracing. The router emits one ``route`` span per
        submitted request (chosen replica, probed affinity length,
        spill count) and hands each replica a ``for_replica(i)`` view
        so every downstream span carries the replica index as its
        Chrome ``pid``. ``None`` (default) is the zero-cost off
        switch — no span objects exist and token streams are bitwise
        unchanged.
    **scheduler_kw:
        Everything else a :class:`~apex_tpu.serving.Scheduler` takes
        (``max_queue`` — PER REPLICA — ``eos_id``,
        ``retain_prefixes``, ``speculative``, ``pipeline_depth``,
        ``fault_policy``, ...), applied uniformly to every replica.
    """

    def __init__(self, engines: Sequence, *, registry=None,
                 route_policy: str = "affinity", seed: int = 0,
                 roles: Optional[Sequence[str]] = None,
                 fault_plan=None, replica_plans=None, tracer=None,
                 **scheduler_kw):
        engines = list(engines)
        if not engines:
            raise ValueError("Router needs at least one engine")
        if route_policy not in _ROUTE_POLICIES:
            raise ValueError(f"route_policy {route_policy!r} not in "
                             f"{_ROUTE_POLICIES}")
        for fleet_kw in ("role", "on_requeue"):
            if fleet_kw in scheduler_kw:
                raise ValueError(
                    f"{fleet_kw!r} is fleet policy — pass "
                    "Router(roles=[...]) instead of a per-scheduler "
                    "keyword")
        self.roles: List[str] = [str(r) for r in roles] \
            if roles is not None else ["both"] * len(engines)
        if len(self.roles) != len(engines):
            raise ValueError(
                f"roles has {len(self.roles)} entries for "
                f"{len(engines)} replicas")
        self._mixed = any(r != "both" for r in self.roles)
        self._tier = None
        if self._mixed:
            # a split fleet is only a fleet if BOTH halves exist: an
            # all-prefill fleet can never emit a token, an all-decode
            # fleet can never accept a prompt — both are configuration
            # errors, not degraded modes
            if not any(r in ("prefill", "both") for r in self.roles):
                raise ValueError(
                    f"roles {self.roles} has no prefill-capable "
                    "replica ('prefill' or 'both'): nothing can "
                    "ingest a prompt")
            if not any(r in ("decode", "both") for r in self.roles):
                raise ValueError(
                    f"roles {self.roles} has no decode-capable "
                    "replica ('decode' or 'both'): nothing can emit "
                    "a token")
            tiers = {id(getattr(e, "host_tier", None)) for e in engines}
            tier0 = getattr(engines[0], "host_tier", None)
            if tier0 is None or len(tiers) != 1:
                raise ValueError(
                    "a roles fleet hands K/V over through ONE shared "
                    "host arena: build every engine with the same "
                    "HostTier(shared=True) instance "
                    "(host_tier=tier on each Engine)")
            if not getattr(tier0, "shared", False):
                raise ValueError(
                    "the fleet's common HostTier must be built with "
                    "shared=True: per-engine audits and resets must "
                    "know the arena is co-owned")
            self._tier = tier0
        geo0 = self._geometry(engines[0])
        for i, e in enumerate(engines[1:], 1):
            if self._geometry(e) != geo0:
                raise ValueError(
                    f"replica {i} serving geometry {self._geometry(e)} "
                    f"differs from replica 0's {geo0} — the router "
                    "routes any request to any replica, so slots/"
                    "max_len/prefill_len/chunk_len must agree")
        if replica_plans is not None \
                and len(replica_plans) != len(engines):
            raise ValueError(
                f"replica_plans has {len(replica_plans)} entries for "
                f"{len(engines)} replicas")
        self.registry = registry
        self.route_policy = route_policy
        self.fault_plan = fault_plan
        self.tracer = tracer
        self._rng = np.random.default_rng(seed)
        # one SLO policy governs the whole in-process fleet: routing
        # reads base_priority from it (SLO-aware rank order), and all
        # replicas share ONE TenantLedger so weighted-fair accounting
        # is fleet-wide, not per-replica (the process fleet can't share
        # a lock across processes — its workers each build their own;
        # see docs/serving.md "Overload & SLO")
        self._slo = scheduler_kw.get("slo")
        if self._slo is not None \
                and scheduler_kw.get("tenant_ledger") is None:
            scheduler_kw = dict(scheduler_kw)
            scheduler_kw["tenant_ledger"] = TenantLedger(
                self._slo.tenant_weights)
        # each replica gets a for_replica(i) view of the tracer, so
        # every span its scheduler/engine/workers emit lands under
        # Chrome process i without threading pid through call sites
        self.replicas: List[Scheduler] = [
            Scheduler(e, registry=registry,
                      role=self.roles[i],
                      on_requeue=self._requeue if self._mixed
                      else None,
                      fault_plan=replica_plans[i]
                      if replica_plans is not None else None,
                      tracer=tracer.for_replica(i)
                      if tracer is not None else None,
                      **scheduler_kw)
            for i, e in enumerate(engines)]
        for i, s in enumerate(self.replicas):
            s.replica_index = i     # stamps completion records
        self.alive: List[bool] = [True] * len(self.replicas)
        # affinity needs something to probe: with retention off the
        # caches stay empty, so the policy honestly degrades to pure
        # least-loaded instead of paying N no-op probes per request
        self.affinity_enabled = (
            route_policy == "affinity"
            and all(s.retain_prefixes for s in self.replicas))
        if self.affinity_enabled:
            blocks = {s.engine.prefix_cache.block_len
                      for s in self.replicas}
            if len(blocks) > 1:
                raise ValueError(
                    f"prefix block_len differs across replicas "
                    f"({sorted(blocks)}): one set of rolling hashes "
                    "must probe every cache")
        # uid -> replica index of the CURRENT placement (rewritten when
        # a drain re-routes; tests and the bench read it). Bounded:
        # routing never reads it back, so it is observability state —
        # a long-running router must not grow one entry per request
        # forever (oldest placements age out past the cap)
        self.placements: Dict[int, int] = {}
        # requests drained from a dead replica that no survivor could
        # take yet (all queues full at drain time): re-routed at the
        # top of every step, ahead of new admissions
        self._overflow: collections.deque = collections.deque()
        # ready hand-overs no decode-capable replica could queue yet
        # (record ownership already transferred): retried every beat
        self._handoff_overflow: collections.deque = collections.deque()
        self._tick = 0              # router step index (FaultPlan clock)
        self._closed = False

    @staticmethod
    def _geometry(engine) -> tuple:
        return (engine.slots, engine.max_len, engine.prefill_len,
                engine.chunk_len)

    # ------------------------------------------------------------- routing
    def _alive_indices(self) -> List[int]:
        idx = [i for i, a in enumerate(self.alive) if a]
        if not idx:
            raise RuntimeError(
                "no live replicas — the fleet is an outage, not a "
                "routing event")
        return idx

    def _capable_indices(self, capability: Optional[str]) -> List[int]:
        """Live replicas eligible for ``capability`` (``"prefill"`` /
        ``"decode"`` / None for any). On the all-``"both"`` default
        fleet this is exactly :meth:`_alive_indices` — role filtering
        only exists once ``roles`` made the fleet mixed."""
        idx = self._alive_indices()
        if capability is None or not self._mixed:
            return idx
        want = ("prefill", "both") if capability == "prefill" \
            else ("decode", "both")
        idx = [i for i in idx if self.roles[i] in want]
        if not idx:
            raise RuntimeError(
                f"no live {capability}-capable replica — the fleet "
                "lost a whole role tier (outage, not a routing event)")
        return idx

    def _probe_keys(self, request: Request):
        """The prompt's rolling block keys, computed ONCE per routed
        request (every replica's cache hashes identically — block_len
        agreement is enforced at construction)."""
        pcache = self.replicas[self._alive_indices()[0]] \
            .engine.prefix_cache
        prompt = tuple(request.prompt)
        return pcache.block_keys(prompt,
                                 len(prompt) // pcache.block_len)

    def _route_order(self, request: Request,
                     capability: Optional[str] = None):
        """``(keys, ordered_replicas, match_lens)``: live (and, in a
        mixed-roles fleet, ``capability``-eligible) replicas
        best-first. Affinity ranks by probed prefix length, then load;
        least-loaded by load alone; random by a seeded shuffle."""
        alive = self._capable_indices(capability)
        if self.route_policy == "random":
            return None, random_order(alive, self._rng), \
                {i: 0 for i in alive}
        keys = None
        lens = {i: 0 for i in alive}
        if self.affinity_enabled:
            pc0 = self.replicas[alive[0]].engine.prefix_cache
            if len(request.prompt) < pc0.block_len:
                # a sub-block prompt can never match a cache entry:
                # skip the hash walk AND the N probes ([] is exactly
                # what block_keys returns for zero full blocks, so
                # downstream consumers see identical values)
                keys = []
            else:
                keys = self._probe_keys(request)
                for i in alive:
                    lens[i] = \
                        self.replicas[i].engine.prefix_cache.probe(
                            request.prompt, keys=keys)
        snaps = {i: self.replicas[i].load_snapshot() for i in alive}
        # static base priority only (no aging clock): deterministic
        # arithmetic both routing fronts reproduce identically
        pri = self._slo.base_priority(request) \
            if self._slo is not None else 0
        # LoRA adapter affinity: a replica whose device arena already
        # holds the request's adapter serves a bind as a hit, not a
        # swap-in — ranked right after the prefix match
        hits = None
        if request.adapter is not None:
            hits = {i: int(request.adapter
                           in (snaps[i].get("resident_adapters") or ()))
                    for i in alive}
        return keys, rank_replicas(alive, lens, snaps, priority=pri,
                                   adapter_hits=hits), lens

    def submit(self, request: Request) -> Request:
        """Route ``request`` to the best live replica (see module
        docstring). Raises :class:`~apex_tpu.serving.QueueFull` only
        when EVERY live replica's queue is at capacity —
        ``retry_after_s`` is then the max of the replicas' measured
        hints (None when no replica has measured a decode step yet)."""
        t_route = self.tracer.now() if self.tracer is not None else 0.0
        # a NEW prompt needs ingestion: in a mixed fleet only
        # prefill-capable replicas are candidates (decode-role
        # replicas serve router hand-overs, routed in step())
        keys, order, lens = self._route_order(request, "prefill")
        hints: List[Optional[float]] = []
        for n_spilled, i in enumerate(order):
            try:
                # count_rejection=False: a full replica here is a
                # SPILL candidate, not a caller-visible rejection —
                # the fleet-level raise below counts the real one
                self.replicas[i].submit(request, prefix_keys=keys,
                                        count_rejection=False)
            except QueueFull as e:
                hints.append(e.retry_after_s)
                continue
            note_placement(self.placements, request.uid, i)
            if self.registry is not None:
                self.registry.counter_inc("serving.router.routed")
                if lens[i] > 0:
                    self.registry.counter_inc(
                        "serving.router.affinity_hits")
                if n_spilled:
                    self.registry.counter_inc("serving.router.spills",
                                              n_spilled)
            if self.tracer is not None:
                # the routing decision, on the chosen replica's lane:
                # probed affinity length, spill count, policy
                self.tracer.event(request.uid, "route", t0=t_route,
                                  dur=self.tracer.now() - t_route,
                                  pid=i, replica=i,
                                  policy=self.route_policy,
                                  affinity_len=lens[i],
                                  spills=n_spilled)
            return request
        hint = fleet_retry_hint(hints)
        if self.registry is not None:
            # ONE caller-visible rejection (the per-replica probes
            # above were suppressed — spills are not rejections)
            self.registry.counter_inc("serving.requests.rejected")
        suffix = f" (retry_after_s~{hint:.3f})" if hint else ""
        raise QueueFull(
            f"all {len(order)} live replica queues at capacity; retry "
            f"after a step() or shed load{suffix}", retry_after_s=hint)

    # ------------------------------------------------------------- stepping
    def step(self) -> bool:
        """One router beat: consume scheduled replica deaths, re-route
        any drained overflow, then run one heartbeat on every live
        replica. Returns True if anything made progress (a replica
        beat did work, or an overflow request found a home)."""
        tick = self._tick
        self._tick += 1
        if self.fault_plan is not None:
            for victim in self.fault_plan.take_replica_deaths(tick):
                self.kill_replica(victim, tick=tick)
        progress = self._drain_overflow()
        for i in self._alive_indices():
            progress = self.replicas[i].step() or progress
        if self._mixed:
            progress = self._collect_handoffs() or progress
        self._emit_gauges()
        return progress

    # ------------------------------------------------------------ handoffs
    def _requeue(self, request: Request) -> bool:
        """Scheduler ``on_requeue`` seam (mixed-roles fleets): a
        quarantined request re-routes through the router — re-probing
        every LIVE replica's cache and load at re-route time — instead
        of being pinned to the replica that faulted. False (the
        replica keeps it queued locally) only when every eligible
        queue is full."""
        try:
            self.submit(request)
        except QueueFull:
            return False
        if self.registry is not None:
            self.registry.counter_inc("serving.router.requeued")
        return True

    def _collect_handoffs(self) -> bool:
        """Collect READY hand-overs from prefill-role replicas and
        re-route each to a decode-capable replica. Ownership of the
        arena record transfers here: the exporter's cache entry is
        dropped (:meth:`PrefixCache.drop` on a swapped entry leaves
        the arena bytes alone), then the record is re-registered as a
        born-swapped prefix on the importer. A record the arena
        evicted in flight degrades to a key-less handoff — the decode
        side re-prefills cold (the verified-miss contract), the
        request never faults."""
        ready = list(self._handoff_overflow)
        self._handoff_overflow.clear()
        for i in self._alive_indices():
            if self.roles[i] != "prefill":
                continue
            src_pc = self.replicas[i].engine.prefix_cache
            for r, key, keys in self.replicas[i].take_handoffs():
                if key is not None:
                    src_pc.drop(key)
                    if not self._tier.contains(key):
                        key = None      # evicted mid-flight
                ready.append((r, key, keys))
        placed = False
        for r, key, keys in ready:
            placed = self._dispatch_handoff(r, key, keys) or placed
        return placed

    def _dispatch_handoff(self, r: Request, key: Optional[int],
                          keys) -> bool:
        """Home one hand-over on the best decode-capable replica:
        queue the request (``_handoff=True`` — the decode-role submit
        gate admits router hand-overs only), then register the arena
        record as a born-swapped prefix under the request's uid and
        note the pairing so admission resolves it (swap-in + COW share
        on the happy path, counted re-prefill on a verified miss).
        All queues full → the hand-over waits in the router's overflow
        for the next beat, record intact."""
        if key is not None and not self._tier.contains(key):
            key = None                  # evicted while waiting
        t_route = self.tracer.now() if self.tracer is not None else 0.0
        _keys, order, lens = self._route_order(r, "decode")
        for n_spilled, i in enumerate(order):
            sched = self.replicas[i]
            try:
                sched.submit(r, prefix_keys=keys,
                             count_rejection=False, _handoff=True)
            except QueueFull:
                continue
            if key is not None:
                eng = sched.engine
                cap = ((len(r.prompt) - 1) // eng.chunk_len) \
                    * eng.chunk_len
                outcome = eng.prefix_cache.register_handoff(
                    key, r.prompt[:cap], n_pages=cap // eng.page_len,
                    keys=keys)
                if outcome == "registered":
                    sched.note_handoff(r.uid, key)
                else:
                    # unreachable for an aligned >=1-block prefix;
                    # never strand arena bytes on a defensive edge
                    self._tier.discard(key)
            note_placement(self.placements, r.uid, i)
            if self.registry is not None and n_spilled:
                self.registry.counter_inc("serving.router.spills",
                                          n_spilled)
            if self.tracer is not None:
                self.tracer.event(r.uid, "route", t0=t_route,
                                  dur=self.tracer.now() - t_route,
                                  pid=i, replica=i,
                                  policy=self.route_policy,
                                  affinity_len=lens[i],
                                  spills=n_spilled, handoff=True)
            return True
        self._handoff_overflow.append((r, key, keys))
        return False

    def _drain_overflow(self) -> bool:
        """Re-route requests stranded by a replica death; those the
        fleet still cannot queue stay for the next beat (replica
        heartbeats free queue space)."""
        placed = False
        for _ in range(len(self._overflow)):
            r = self._overflow.popleft()
            try:
                self.submit(r)
                placed = True
            except QueueFull:
                self._overflow.append(r)
        return placed

    def kill_replica(self, index: int, *,
                     tick: Optional[int] = None) -> List[Request]:
        """Take replica ``index`` out of service NOW — the router-tier
        containment boundary (chaos injection calls this from
        :meth:`step`, passing the beat's ``tick`` so the log line
        matches the :class:`FaultSpec` that fired; operators may call
        it directly for a real dead
        backend). Its queued and in-flight requests drain
        (:meth:`Scheduler.drain_requests`: transient state rolled
        back, pages freed, submit clocks kept) and re-route onto the
        survivors; its worker thread stops. Killing an already-dead
        replica is a no-op; killing the LAST live replica raises —
        that is an outage, and silently absorbing it would strand
        every drained request. Returns the drained requests."""
        index = int(index)
        if not 0 <= index < len(self.replicas):
            raise ValueError(f"replica {index} out of range "
                             f"[0, {len(self.replicas)})")
        if not self.alive[index]:
            return []
        if sum(self.alive) == 1:
            raise RuntimeError(
                f"replica {index} is the last one alive — a fleet of "
                "zero cannot absorb its requests (outage, not a "
                "routing event)")
        self.alive[index] = False
        sched = self.replicas[index]
        drained = sched.drain_requests()
        sched.close()
        # drain the victim's swap worker too: swap-outs queued at kill
        # time COMPLETE their arena puts (bytes already snapshotted at
        # dispatch), so the dead replica's cross-tier audit reconciles
        # — no dangling swapped entries, no leaked host bytes
        if hasattr(sched.engine, "close"):
            sched.engine.close()
        if self.registry is not None:
            self.registry.counter_inc("serving.router.replica_deaths")
            if drained:
                self.registry.counter_inc("serving.router.requeued",
                                          len(drained))
            # retire the dead replica's load gauges NOW — _emit_gauges
            # skips dead replicas, so without this a dashboard would
            # read its last pre-death load (phantom queue depth on an
            # empty corpse) forever. Zero is the honest reading: the
            # drain emptied it, and a dead pool has no capacity.
            prefix = f"serving.router.replica{index}."
            for gauge in ("queue_depth", "slots_busy", "pages_free",
                          "host_bytes_free"):
                self.registry.gauge_set(prefix + gauge, 0.0)
        _logger.warning(
            "replica %d died at router tick %d: %d request(s) drained "
            "onto %d survivor(s)", index,
            self._tick if tick is None else tick, len(drained),
            sum(self.alive))
        self._overflow.extend(drained)
        self._drain_overflow()
        return drained

    def _emit_gauges(self) -> None:
        """Fleet + per-replica load gauges. Replica gauges are
        NAMESPACED (``serving.router.replica<i>.<gauge>``) because N
        replicas share one registry — un-namespaced pool gauges would
        be last-writer-wins noise."""
        if self.registry is None:
            return
        self.registry.gauge_set("serving.router.replicas_alive",
                                float(sum(self.alive)))
        if self._mixed:
            # the tentpole's CPU-measurable claim: the fraction of
            # decode-role heartbeats that ran NO chunk prefill. On a
            # "both" fleet long prompts steal every replica's beats;
            # here only verified-miss re-prefills and the resumed
            # final chunk may dent it
            bt = bp = 0
            for i, role in enumerate(self.roles):
                if role == "decode":
                    bt += self.replicas[i].beats_total
                    bp += self.replicas[i].beats_with_prefill
            if bt:
                self.registry.gauge_set(
                    "serving.disagg.decode_isolation", 1.0 - bp / bt)
        for i, sched in enumerate(self.replicas):
            if not self.alive[i]:
                continue
            snap = sched.load_snapshot()
            prefix = f"serving.router.replica{i}."
            self.registry.gauge_set(prefix + "queue_depth",
                                    float(snap["queue_depth"]))
            self.registry.gauge_set(prefix + "slots_busy",
                                    float(snap["slots_busy"]))
            if snap["pages_free"] is not None:
                self.registry.gauge_set(prefix + "pages_free",
                                        float(snap["pages_free"]))
            if snap["host_bytes_free"] is not None:
                # arena headroom rides the same namespace so the
                # least-loaded tie-break's input is dashboard-visible
                self.registry.gauge_set(prefix + "host_bytes_free",
                                        float(snap["host_bytes_free"]))

    # ---------------------------------------------------------------- runs
    @property
    def pending(self) -> int:
        """Requests the fleet still owes: overflow awaiting a home plus
        every live replica's queued/running/in-flight count (a drained
        dead replica reads zero by construction)."""
        return len(self._overflow) + len(self._handoff_overflow) + sum(
            s.pending for i, s in enumerate(self.replicas)
            if self.alive[i])

    def run(self, requests: Sequence[Request] = (),
            max_steps: int = 100000) -> List[Request]:
        """Submit ``requests`` (stepping the fleet through
        :class:`QueueFull` backpressure rather than surfacing it) and
        step until every request reaches a terminal state. Returns the
        SUBMITTED list (in submission order — completion order
        interleaves across replicas, so compare by request, never by
        position in a completion stream) and records the fleet's
        aggregate ``serving.tokens_per_s``."""
        requests = list(requests)
        t0 = time.perf_counter()
        tok0 = sum(s.engine.tokens_generated for s in self.replicas)
        for r in requests:
            while True:
                try:
                    self.submit(r)
                    break
                except QueueFull:
                    if not self.step():
                        time.sleep(0.002)   # everything is backing off
        steps = 0
        while self.pending and steps < max_steps:
            if not self.step():
                time.sleep(0.002)
            steps += 1
        dt = time.perf_counter() - t0
        toks = sum(s.engine.tokens_generated
                   for s in self.replicas) - tok0
        if self.registry is not None and dt > 0:
            self.registry.gauge_set("serving.tokens_per_s", toks / dt)
        _logger.info(
            "router served %d request(s) over %d/%d live replica(s): "
            "%d tokens in %.3fs (%.1f tok/s)", len(requests),
            sum(self.alive), len(self.replicas), toks, dt,
            toks / dt if dt > 0 else float("inf"))
        return requests

    def close(self) -> None:
        """Stop every replica's worker threads — the scheduler's
        :class:`~apex_tpu.serving.DraftWorker` and the engine's
        :class:`~apex_tpu.serving.SwapWorker` (which drains queued
        swap-outs first, so arenas reconcile). Idempotent — safe after
        a partial kill, safe twice; each worker's own weakref
        finalizer covers the forgotten-router case."""
        if self._closed:
            return
        self._closed = True
        for sched in self.replicas:
            sched.close()
            if hasattr(sched.engine, "close"):
                sched.engine.close()
