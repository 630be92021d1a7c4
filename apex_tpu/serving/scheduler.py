"""Continuous-batching scheduler: requests in, token streams out.

The naive way to serve N requests is static batching — pad them to one
shape, decode until the LAST one finishes, waste every slot that
finished early. Continuous batching instead treats the engine's decode
step as a steady heartbeat and moves requests through slots between
beats:

1. **expire** — queued or running requests past their deadline finish
   with status ``"timeout"`` (their slot frees immediately);
2. **admit** — while a slot is free and the queue is non-empty, pop the
   oldest request into the slot as *prefilling* (its queue wait ends
   here — the first half of the TTFT decomposition).
   Admission is gated on the page pool first: the head request's
   worst-case page demand (padded prefill extent or prompt + token
   budget, whichever is larger) must be reservable —
   :meth:`Engine.try_reserve_slot` evicts LRU prefix entries under
   pressure, and when even that cannot cover the demand the request
   simply stays queued (FIFO holds; backpressure surfaces as
   :class:`QueueFull` at submit once the queue itself fills). The
   reservation is what makes mid-decode allocation infallible. With
   ``retain_prefixes=True`` admission then consults the engine's
   :class:`~apex_tpu.serving.PrefixCache`: the longest cached
   block-aligned prefix of the prompt is attached to the slot — by
   refcount-bumping the donor's pages into the slot's page table
   (copy-on-write: ZERO data movement, and the matched pages are
   refunded from the reservation) — and chunk prefill resumes at the
   matched offset — every matched chunk is attention+MLP compute that never
   runs;
3. **chunk prefill** — at most ``chunk_budget`` (default 1) compiled
   chunk-prefill steps across the prefilling slots, round-robin. A
   prompt of P tokens ingests over ``ceil(P / chunk_len)`` heartbeats;
   the final chunk samples the request's first token (the TTFT mark)
   and flips the slot to decoding. The budget bounds the stall imposed
   on IN-FLIGHT decodes — while nothing is decoding there is nothing
   to stall, so a cold queue bursts chunk-after-chunk (stopping the
   moment a slot flips to decoding) instead of idling between beats;
4. **draft** (``speculative=True``) — for each greedy decoding slot, a
   host-side prompt-lookup drafter (:mod:`~apex_tpu.serving
   .speculative`) proposes up to ``K`` next tokens from n-gram matches
   over ``prompt + generated``;
5. **verify-or-decode** — every slot with a non-empty draft shares ONE
   compiled ``[slots, K+1]`` batched verify call
   (:meth:`Engine.verify_batch`: accept-longest-prefix in-program per
   row, up to ``K + 1`` tokens emitted per slot-step, greedy output
   bitwise identical to plain decode; B verify-eligible slots cost one
   program invocation, not B); everything else — empty drafts, sampled
   requests, requests within ``K`` tokens of their budget — falls back
   to the ordinary fixed-shape decode step over the remaining slots.
   ``speculative=False`` (the default) skips the draft phase entirely
   and keeps today's path as the measurable baseline.

**Async pipelined heartbeat** (``pipeline_depth >= 1``; the default
is 1, the beat every server runs): the sync beat forces every sampled
token to the host (``np.asarray``) before the next step is dispatched,
so the device idles through all the host's time in between — the next
step's operands and launch, the read's tail, drafting, admission,
hashing, telemetry. Dispatch-ahead execution inverts that: decode step
t+1 is DISPATCHED against the speculated schedule (every in-flight
slot presumed to continue — EOS is the only finality the host cannot
know in advance; token-budget and ``max_len`` exhaustion are pure host
arithmetic and are never speculated past) with step t's un-forced
device tokens standing in, INSIDE the decode program, for every row
whose newest token the host has not read (one launch a beat, as in the
sync beat), and step t is only then RECONCILED: one batched
readback, per-slot emission through the same finish checks as the
sync path, and rollback of any mispredict — a slot that turned out to
finish (or quarantine, or expire) mid-pipeline simply discards its
speculated successors' tokens (matched by request uid, counted as
``serving.heartbeat.discarded``). Device state needs no undo: the
speculated step's K/V write lands past every reader exactly like
PR 8's rejected verify tail — lengths gate attention, dispatch order
is program order (the cache threads through every call), and the next
occupant's chunk prefill overwrites whole pages before attending them
(write-then-attend; a model with per-slot state beside its pages has
that state reset by the occupant's chunk at offset 0 the same way).
Host bookkeeping rollback is pure length arithmetic, already performed
by ``release_slot``. Chunk prefill is dispatched ahead too: a chunk's
token is read at the top of the next beat, so a final chunk's first
token is emitted, and its slot decodes, one beat after its dispatch.
``pipeline_depth=0`` keeps the fully synchronous beat as the bitwise
oracle the tests pin the default against; depth ``d`` keeps at most
``d`` decode steps in flight. ``serving.heartbeat.dispatched_ahead``
over ``serving.decode.steps`` says how often a step really was
dispatched behind an un-read one.
A :class:`~apex_tpu.serving.DraftWorker` thread overlaps n-gram
drafting and prefix block-hashing with device execution (pure
closures over snapshots — timing can reorder host work, never change
tokens), and the greedy output stream is BITWISE identical to the
sync path across chunked, speculative, prefix-hit and chaos streams
(pinned by ``tests/L0/test_async_heartbeat.py``).

Step 3 is the head-of-line fix (Orca-style continuous batching +
Sarathi-style chunked prefill): the monolithic alternative — pause the
heartbeat and run a whole ``[1, prefill_len]`` prefill at admit time —
stalls every in-flight decode for the full prompt length. Chunking
bounds that stall at one chunk, and short prompts stop paying full
``prefill_len`` padding compute.

Backpressure instead of OOM: the queue is bounded (``max_queue``);
:meth:`submit` raises :class:`QueueFull` when it is at capacity, so a
caller that outruns the engine gets a typed rejection to retry/shed —
never an unbounded host-side pileup. The rejection carries a
``retry_after_s`` hint derived from the measured decode throughput
(an EMA of decode-step wall time × the steps until the nearest running
request can finish), so a well-behaved client backs off by data, not
by guess. (:meth:`run` absorbs the same signal by stepping the engine
until space frees.)

**Fault isolation** (always on; knobs in :class:`~apex_tpu.serving
.FaultPolicy`): every engine call in the heartbeat is containment-
wrapped. A transient exception from a chunk-prefill or decode call —
real, or injected by a :class:`~apex_tpu.serving.FaultPlan` — costs
only its victim request: the slot is freed, its pages and prefix pins
released, and the request requeues with capped exponential backoff up
to ``max_retries`` before the typed ``FAILED`` terminal status. The
engine's in-program non-finite guard quarantines a NaN/Inf slot the
same way while its batchmates keep their exact tokens. A per-heartbeat
wall-clock watchdog (``watchdog_budget_s``) turns stalls into
``serving.watchdog.stall`` events plus an ``on_stall`` callback, and a
:class:`~apex_tpu.serving.PoolAuditor` (sampled via
``audit_every_n``) reconciles page refcounts after finish/eviction
events — leaks and double-frees raise loudly instead of rotting. The
headline guarantee, pinned by ``tests/L0/test_faults.py``: under an
injected fault schedule, un-faulted greedy requests complete bitwise
token-identical to a fault-free run, faulted requests reach a typed
terminal status, and the pool drains with zero leaked pages.

Terminal request states are one typed enum (:class:`RequestStatus`):
``FINISHED`` (served to completion), ``EXPIRED`` (deadline), and
``FAILED`` (fault policy exhausted) — used consistently across the
scheduler, the request records, and telemetry.

Prefix registration is the write half: when a retained-prefix run's
prompt finishes chunk prefill, the pages holding its block-aligned K/V
are recorded as a cache entry (zero copies; LRU eviction under pool
pressure). ``retain_prefixes=True`` requires an engine built with
``prefix_pool > 0``.

Telemetry (through the shared :class:`~apex_tpu.telemetry
.MetricsRegistry`): ``serving.ttft_s`` decomposed into
``serving.queue_wait_s`` (submit → admission) + per-chunk
``serving.prefill_chunk_s`` (the engine observes the latter),
``serving.decode.step_s`` histograms (p50/p95/p99 via the streaming
reservoir), ``serving.slot_occupancy`` / ``serving.padding_waste`` per
step, request outcome counters, one ``serving.request``-tagged
completion record per request (with ``chunks_per_prompt`` and
``reused_tokens``), a final ``serving.tokens_per_s`` gauge from
:meth:`run`, and the prefix-reuse layer: ``serving.prefix.hits`` /
``.misses`` / ``.hit_rate`` (gauge), ``serving.prefix.tokens_reused``,
``serving.prefix.chunks_skipped``, ``serving.prefix.evictions``,
and ``serving.prefix.registrations``.
Speculative runs add ``serving.spec.drafted`` / ``serving.spec
.accepted`` counters, the per-verify ``serving.spec.acceptance_rate``
histogram, the per-heartbeat ``serving.spec.tokens_per_step`` gauge
(tokens emitted per SLOT sequence-step — plain decode pins 1.0, the
>1 reading is the whole point), and per-request ``spec_accepted`` in
the completion record. The heartbeat watchdog separately accounts ticks that traced a
new compiled program as ``serving.watchdog.warmup_s`` instead of
breaching (first-contact compile time is not a stall).
"""

from __future__ import annotations

import collections
import dataclasses
import enum
import itertools
import time
import weakref
from typing import Dict, List, NamedTuple, Optional, Sequence

import numpy as np

from apex_tpu.log_util import get_logger
from apex_tpu.telemetry import tracing

from .faults import FaultPolicy, PoolAuditor, fault_kind
from .slo import SLOConfig, TenantLedger
from .speculative import DraftWorker, draft_tokens

__all__ = ["Request", "RequestStatus", "QueueFull",
           "DeadlineUnmeetable", "Scheduler",
           "request_from_wire", "request_to_wire",
           "snapshot_from_wire", "snapshot_to_wire"]

_logger = get_logger("serving")

_uid = itertools.count()


class RequestStatus(str, enum.Enum):
    """A request's lifecycle state — the ONE status vocabulary shared
    by the scheduler, the :class:`Request` record, and the telemetry
    completion records. A ``str`` subclass, so legacy comparisons
    against the transient literals (``"queued"``/``"prefilling"``/
    ``"running"``) keep working; the typed terminals are

    - ``FINISHED`` — served to completion (EOS / token budget / cache
      ``max_len``; see ``finish_reason`` for which);
    - ``EXPIRED`` — deadline passed while queued or running;
    - ``FAILED`` — the fault policy's retry budget ran out (transient
      step failures or non-finite quarantines; ``error`` carries the
      last fault).
    """

    NEW = "new"
    QUEUED = "queued"
    PREFILLING = "prefilling"
    RUNNING = "running"
    # transient, SLO scheduling only: evicted from its slot mid-decode
    # to make room for a higher-priority arrival — committed K/V
    # migrated to the host tier (or retained resident), the request
    # waits in the queue and resumes via swap-in + COW prefix share
    PREEMPTED = "preempted"
    FINISHED = "finished"
    EXPIRED = "expired"
    FAILED = "failed"

    def __str__(self) -> str:           # records/logs print the value
        return self.value

    @property
    def terminal(self) -> bool:
        return self in (RequestStatus.FINISHED, RequestStatus.EXPIRED,
                        RequestStatus.FAILED)


class QueueFull(RuntimeError):
    """Raised by :meth:`Scheduler.submit` when the bounded request queue
    is at capacity — the backpressure signal (shed or retry later).
    ``retry_after_s`` (when the scheduler has measured any decode
    throughput yet, else None) estimates how long until a queue
    position frees: decode-step EMA × the fewest steps any running
    request still needs."""

    def __init__(self, message: str,
                 retry_after_s: Optional[float] = None):
        super().__init__(message)
        self.retry_after_s = retry_after_s


class DeadlineUnmeetable(QueueFull):
    """Raised by :meth:`Scheduler.submit` under deadline-aware
    admission (``SLOConfig.deadline_admission``) when the request's
    ``deadline_s`` cannot be met at the measured decode-step EMA —
    accepting it would only burn capacity on work destined to miss.
    A :class:`QueueFull` subclass, so every existing backpressure
    handler (the router's spill, ``run()``'s absorb loop) treats it as
    the shed-or-retry signal it is; ``retry_after_s`` is the EMA ×
    queue-position estimate of when the queue ahead will have
    drained."""



@dataclasses.dataclass
class Request:
    """One generation request and, after serving, its outcome.

    Inputs: ``prompt`` (token ids), ``max_new_tokens``, ``temperature``
    (0 = greedy), optional ``timeout_s`` (else the scheduler default).

    Outputs (filled by the scheduler): ``output_tokens``, ``status`` (a
    :class:`RequestStatus`: terminally ``FINISHED`` / ``EXPIRED`` /
    ``FAILED``; transiently ``QUEUED`` / ``PREFILLING`` / ``RUNNING``),
    ``finish_reason`` (``"eos"`` / ``"max_new_tokens"`` / ``"max_len"``
    / ``"timeout"`` / ``"fault"``), ``spec_drafted`` / ``spec_accepted``
    (speculative tokens proposed / accepted for this request —
    cumulative across retries, like the other paid-compute counters;
    0 on non-speculative runs), ``ttft_s`` and its decomposition
    ``queue_wait_s`` (submit → admission) + ``prefill_s`` (summed
    chunk/prefill compute — cumulative across retries: it is compute
    actually paid), ``chunks`` (prefill steps paid, cumulative across
    retries), ``reused_tokens`` (prompt positions restored from the
    prefix cache instead of prefilled; 0 on a miss or with retention
    off), ``latency_s`` (from the ORIGINAL submit — retries don't reset
    the clock), ``retries`` (transient faults absorbed so far) and
    ``error`` (the last fault's description; None when never faulted).
    """

    prompt: Sequence[int]
    max_new_tokens: int = 16
    temperature: float = 0.0
    timeout_s: Optional[float] = None
    uid: int = dataclasses.field(default_factory=lambda: next(_uid))
    # SLO inputs (all inert when the scheduler runs without an
    # SLOConfig — the FIFO path never reads them): ``slo_class`` names
    # a class in SLOConfig.classes (its base priority); ``priority``
    # adds on top (the whole priority for class-less requests);
    # ``deadline_s`` is a completion deadline RELATIVE to submit
    # (deadline-aware admission + the deadline_missed verdict);
    # ``tenant`` joins the weighted-fair ledger and the per-tenant
    # concurrency quota
    priority: int = 0
    slo_class: Optional[str] = None
    deadline_s: Optional[float] = None
    tenant: Optional[str] = None
    # multi-tenant LoRA: the adapter this request decodes under (a
    # name previously registered with the engine's adapter arena), or
    # None for the base model. Admission binds the adapter to the slot
    # (refcount-pinning it resident) before pages are reserved;
    # ``_free_slot`` is the single unbind point. An unknown name fails
    # the request loudly at admission — never a silent base-model
    # fallback
    adapter: Optional[str] = None

    # filled in by the scheduler
    output_tokens: List[int] = dataclasses.field(default_factory=list)
    status: RequestStatus = RequestStatus.NEW
    finish_reason: Optional[str] = None
    ttft_s: Optional[float] = None
    queue_wait_s: Optional[float] = None
    prefill_s: float = 0.0
    chunks: int = 0
    reused_tokens: int = 0
    spec_drafted: int = 0
    spec_accepted: int = 0
    latency_s: Optional[float] = None
    retries: int = 0
    error: Optional[str] = None
    # SLO outputs: times this request was preempted (cumulative —
    # preemption is not a fault, ``retries`` never moves), and the
    # finish-time deadline verdict (latency_s > deadline_s; always
    # False without a deadline)
    preemptions: int = 0
    deadline_missed: bool = False
    _t_submit: Optional[float] = dataclasses.field(default=None,
                                                   repr=False)
    # the CURRENT queueing episode's start (reset when a quarantine
    # requeues): queue_wait_s measures time actually spent waiting for
    # a slot, never prior service time — _t_submit keeps the original
    # clock for latency_s and deadlines
    _t_queued: Optional[float] = dataclasses.field(default=None,
                                                   repr=False)
    _prefill_pos: int = dataclasses.field(default=0, repr=False)
    _not_before: Optional[float] = dataclasses.field(default=None,
                                                     repr=False)
    # preempt/resume state: the token stream the NEXT admission must
    # ingest — prompt + committed outputs for a preempted request
    # (resume re-samples the last committed position, which IS the
    # next token), None otherwise (admission ingests the prompt).
    # Cleared by _reset_transient: a quarantine rolls outputs back, so
    # a stale ingest stream here would replay them as prompt and shift
    # the output stream — the exact wrong-token bug the
    # quarantined-while-preempted chaos test pins
    _ingest_tokens: Optional[List[int]] = dataclasses.field(
        default=None, repr=False)
    # effective priority PINNED at admission (base + the aging boost
    # earned while queued): the victim-selection comparison reads this
    # for running requests, so an aged-up admission keeps its boost
    # and cannot be instantly re-preempted by a fresh arrival of the
    # same base class
    _eff_priority: Optional[int] = dataclasses.field(default=None,
                                                     repr=False)


# --------------------------------------------------------------- wire forms
#
# The process-level fleet ships requests and load snapshots between a
# controller and its worker processes as VERSIONED plain dicts —
# explicit serialize/deserialize pairs, not implicit pickling of live
# objects, so the wire contract is inspectable, testable without a
# socket, and LOUD when a version mismatch crosses the boundary (a
# controller and worker built from different trees must fail with a
# ValueError, never deserialize garbage silently). The private
# ``Request`` clock fields (``_t_submit`` etc.) deliberately do NOT
# cross: ``time.perf_counter`` bases are per-process, so a shipped
# clock would be meaningless on arrival — each side stamps its own.

REQUEST_WIRE_VERSION = 3    # v2: SLO fields (priority/slo_class/
#                             deadline_s/tenant in; preemptions/
#                             deadline_missed out); v3: adapter
SNAPSHOT_WIRE_VERSION = 3   # v2: oldest_deadline_s/preemptible_pages;
#                             v3: resident_adapters

#: The load-snapshot key set — part of the versioned wire contract
#: (routing_policy ranks on these fields, so both fronts must see the
#: same ones; bump SNAPSHOT_WIRE_VERSION when this tuple changes).
#: v2 adds ``oldest_deadline_s`` (tightest remaining deadline across
#: queued+running, RELATIVE seconds — perf_counter bases never cross a
#: process boundary — None when nothing carries one) and
#: ``preemptible_pages`` (pages held by running requests strictly
#: below the SLO config's top class — the headroom a top-priority
#: arrival could reclaim; None when SLO scheduling is off). v3 adds
#: ``resident_adapters`` (the adapter names currently resident in the engine's LoRA arena — the
#: adapter-affinity signal, ranked by routing_policy right after the
#: prefix-affinity match; None when LoRA serving is off).
_SNAPSHOT_KEYS = ("queue_depth", "queue_free", "slots", "slots_busy",
                  "slots_free", "inflight_steps", "pages_free",
                  "host_bytes_free", "oldest_deadline_s",
                  "preemptible_pages", "resident_adapters")


def request_to_wire(request: Request) -> dict:
    """``request`` as its versioned dict wire form: every public
    field, plain Python scalars only (token ids coerced through
    ``int`` so numpy scalars never leak into a frame). The private
    per-process clocks stay home (see the wire-forms note above)."""
    return {
        "v": REQUEST_WIRE_VERSION,
        "prompt": [int(t) for t in request.prompt],
        "max_new_tokens": int(request.max_new_tokens),
        "temperature": float(request.temperature),
        "timeout_s": request.timeout_s,
        "uid": int(request.uid),
        "priority": int(request.priority),
        "slo_class": request.slo_class,
        "deadline_s": request.deadline_s,
        "tenant": request.tenant,
        "adapter": request.adapter,
        "output_tokens": [int(t) for t in request.output_tokens],
        "status": request.status.value,
        "finish_reason": request.finish_reason,
        "ttft_s": request.ttft_s,
        "queue_wait_s": request.queue_wait_s,
        "prefill_s": float(request.prefill_s),
        "chunks": int(request.chunks),
        "reused_tokens": int(request.reused_tokens),
        "spec_drafted": int(request.spec_drafted),
        "spec_accepted": int(request.spec_accepted),
        "latency_s": request.latency_s,
        "retries": int(request.retries),
        "error": request.error,
        "preemptions": int(request.preemptions),
        "deadline_missed": bool(request.deadline_missed),
    }


def request_from_wire(wire: dict) -> Request:
    """The :class:`Request` a wire dict describes. Raises
    ``ValueError`` on an unknown wire version (the loud cross-build
    guard) and ``KeyError`` on a missing field — a truncated frame
    must never deserialize into a plausible half-request."""
    v = wire.get("v")
    if v != REQUEST_WIRE_VERSION:
        raise ValueError(
            f"unknown Request wire version {v!r} (this build speaks "
            f"{REQUEST_WIRE_VERSION}) — controller and workers must "
            "run the same tree")
    return Request(
        prompt=list(wire["prompt"]),
        max_new_tokens=wire["max_new_tokens"],
        temperature=wire["temperature"],
        timeout_s=wire["timeout_s"],
        uid=wire["uid"],
        priority=wire["priority"],
        slo_class=wire["slo_class"],
        deadline_s=wire["deadline_s"],
        tenant=wire["tenant"],
        adapter=wire["adapter"],
        output_tokens=list(wire["output_tokens"]),
        status=RequestStatus(wire["status"]),
        finish_reason=wire["finish_reason"],
        ttft_s=wire["ttft_s"],
        queue_wait_s=wire["queue_wait_s"],
        prefill_s=wire["prefill_s"],
        chunks=wire["chunks"],
        reused_tokens=wire["reused_tokens"],
        spec_drafted=wire["spec_drafted"],
        spec_accepted=wire["spec_accepted"],
        latency_s=wire["latency_s"],
        retries=wire["retries"],
        error=wire["error"],
        preemptions=wire["preemptions"],
        deadline_missed=wire["deadline_missed"],
    )


def snapshot_to_wire(snapshot: dict) -> dict:
    """A :meth:`Scheduler.load_snapshot` dict as its versioned wire
    form (the fixed key set, loud on a missing key)."""
    out = {"v": SNAPSHOT_WIRE_VERSION}
    for k in _SNAPSHOT_KEYS:
        out[k] = snapshot[k]
    return out


def snapshot_from_wire(wire: dict) -> dict:
    """The plain load-snapshot dict a wire form describes — exactly
    the shape :meth:`Scheduler.load_snapshot` returns, so
    ``routing_policy.rank_replicas`` consumes local and remote
    snapshots interchangeably. Loud ``ValueError`` on an unknown
    version, ``KeyError`` on a missing load key."""
    v = wire.get("v")
    if v != SNAPSHOT_WIRE_VERSION:
        raise ValueError(
            f"unknown load-snapshot wire version {v!r} (this build "
            f"speaks {SNAPSHOT_WIRE_VERSION}) — controller and "
            "workers must run the same tree")
    return {k: wire[k] for k in _SNAPSHOT_KEYS}


@dataclasses.dataclass
class _InflightStep:
    """Host-side record of one dispatch-ahead decode step: the
    engine's :class:`~apex_tpu.serving.PendingDecode` handle plus the
    ``slot -> request uid`` map it was computed for. Reconcile emits a
    slot's token only while the SAME request still runs there — any
    finality, quarantine or expiry that frees the slot drops its entry
    from every in-flight record on the spot (``_free_slot``), which is
    the whole host-side rollback; the uid+status re-check at reconcile
    is belt-and-braces on top."""

    pending: object
    uids: Dict[int, int]
    tick: int

    # ``uids`` is mutated by Scheduler._free_slot: the moment a slot
    # frees (finish, quarantine, expiry), its entry is DROPPED from
    # every in-flight record and counted as discarded — eager
    # invalidation, because a requeued request keeps its uid, so a
    # reconcile-time uid comparison alone could mistake a stale
    # pre-quarantine step for the retried occupant's.


class _InflightChunk(NamedTuple):
    """One dispatch-ahead prefill chunk ``[lo, hi)`` of request ``uid``:
    the engine's :class:`~apex_tpu.serving.PendingPrefill` handle, when
    it was dispatched (``t0``) and in which beat (``tick``: a later
    beat retires it before anything else)."""

    pending: object
    uid: int
    lo: int
    hi: int
    t0: float
    tick: int


class Scheduler:
    """Continuous-batching front of an :class:`~apex_tpu.serving.Engine`
    (see module docstring for the step anatomy). ``pipeline_depth=1``
    (default) is the dispatch-ahead beat: the next decode step is on
    the device before the last one's tokens are read (bitwise-greedy
    identical to the synchronous beat, see the module docstring's
    async-heartbeat section); ``0`` is that synchronous beat, the
    oracle; deeper keeps more steps in flight."""

    def __init__(self, engine, *, max_queue: int = 64,
                 default_timeout_s: Optional[float] = None,
                 eos_id: Optional[int] = None, registry=None,
                 chunk_budget: int = 1,
                 retain_prefixes: bool = False,
                 speculative: bool = False,
                 pipeline_depth: int = 1,
                 role: str = "both",
                 on_requeue=None,
                 fault_policy: Optional[FaultPolicy] = None,
                 fault_plan=None,
                 auditor: Optional[PoolAuditor] = None,
                 tracer=None,
                 slo: Optional[SLOConfig] = None,
                 tenant_ledger: Optional[TenantLedger] = None):
        if max_queue < 1:
            raise ValueError("max_queue must be >= 1")
        if chunk_budget < 1:
            raise ValueError("chunk_budget must be >= 1")
        beside = getattr(engine, "beside_pages", None)
        if beside:
            # a model with per-slot state or an index pool beside its K/V
            # pages: what re-enters a request mid-stream from pages alone
            # is refused by name, as serving.Engine refuses its own side
            for on, what in (
                    (retain_prefixes, "prefix_cache retention "
                     "(retain_prefixes)"),
                    (slo is not None and slo.preempt,
                     "slo preemption with resume"),
                    (speculative, "speculative verify"),
                    (role != "both", f"disaggregated role={role!r} (the "
                     "KV handoff)")):
                if on:
                    raise NotImplementedError(
                        f"serving.Scheduler: {what} is not built for a "
                        f"model with {beside} "
                        f"({getattr(engine, 'model_kind', '?')!r}): it is "
                        "not carried with the request's K/V pages")
        if pipeline_depth < 0:
            raise ValueError("pipeline_depth must be >= 0 (0 = the "
                             "synchronous oracle beat)")
        if speculative and getattr(engine, "spec", None) is None:
            raise ValueError(
                "speculative=True requires an engine built with "
                "spec=SpecConfig(...) — the verify program's shape is "
                "fixed at engine construction")
        if retain_prefixes:
            if getattr(engine, "prefix_cache", None) is None:
                raise ValueError(
                    "retain_prefixes requires an engine built with "
                    "prefix_pool > 0 (no pool rows to retain into)")
        if slo is not None:
            if slo.preempt and not retain_prefixes:
                raise ValueError(
                    "slo.preempt requires "
                    "retain_prefixes=True: a preempted request's "
                    "committed K/V survives as a prefix-cache "
                    "entry (host-tier swap or resident COW share) "
                    "and resume is an ordinary prefix attach")
        if role not in ("prefill", "decode", "both"):
            raise ValueError(
                f"role must be 'prefill', 'decode' or 'both', got "
                f"{role!r}")
        if role != "both":
            if not retain_prefixes:
                raise ValueError(
                    f"role={role!r} requires retain_prefixes=True: the "
                    "KV handoff travels as an ordinary swapped prefix, "
                    "so both sides need the prefix-cache machinery")
            if getattr(engine, "host_tier", None) is None:
                raise ValueError(
                    f"role={role!r} requires an engine with a "
                    "host_tier: the handoff's KV travels through the "
                    "(shared) host arena's swap programs")
        self.engine = engine
        self.max_queue = int(max_queue)
        self.default_timeout_s = default_timeout_s
        self.eos_id = eos_id
        self.chunk_budget = int(chunk_budget)
        self.retain_prefixes = bool(retain_prefixes)
        self.speculative = bool(speculative)
        # disaggregated serving (role != "both"): "prefill" replicas
        # ingest prompts and export the finished prefix to the shared
        # host arena instead of ever decoding; "decode" replicas accept
        # only router hand-overs (plus their verified-miss re-prefills)
        self.role = str(role)
        # SLO scheduling: None keeps the verbatim FIFO admission path
        # (the baseline every SLO claim is benchmarked against — zero
        # new compiled programs, pinned); a config switches admission
        # to priority order with optional preemption, deadline
        # admission and tenant fairness. The ledger is process-local
        # shared state: the Router passes ONE across its replicas so
        # fairness spans the process; each fleet worker builds its own
        self.slo = slo
        if tenant_ledger is not None:
            self.tenants: Optional[TenantLedger] = tenant_ledger
        elif slo is not None:
            self.tenants = TenantLedger(slo.tenant_weights)
        else:
            self.tenants = None
        # uids preempted since their last admission: the resume marker
        # _consult_prefix_cache reads (and clears) to count/trace the
        # resume rather than a disagg handoff import
        self._preempted_uids: set = set()
        # re-probe-at-requeue seam: when set, a quarantine offers the
        # requeued request back to the router (which re-probes LIVE
        # replicas and the arena) instead of this replica's own queue;
        # returns True when the router took it
        self.on_requeue = on_requeue
        self.registry = registry if registry is not None \
            else getattr(engine, "_registry", None)
        # request tracing (None = off, the zero-cost default: every
        # hook below is an `is not None` guard around pure host-clock
        # reads — no span objects exist, no tokens change, pinned by
        # tests/L0/test_tracing.py). The tracer propagates to the
        # engine so swap-path spans (which never see a Request) attach
        # to the admitting request via the thread-local binding the
        # admission path holds. ``replica_index`` stamps completion
        # records and is rewritten by the Router (replica i).
        self.tracer = tracer
        self.replica_index = 0
        if tracer is not None and hasattr(engine, "set_tracer"):
            engine.set_tracer(tracer)
        # registry wiring: several engine-side metrics (the guard's
        # serving.faults.nonfinite above all) are emitted by the
        # ENGINE's registry — a scheduler-only registry would silently
        # miss them, so propagate ours to a registry-less engine; when
        # BOTH are set and differ, keep them (the split may be
        # deliberate) but say so loudly
        eng_reg = getattr(engine, "_registry", None)
        if self.registry is not None and hasattr(engine, "set_registry"):
            if eng_reg is None:
                engine.set_registry(self.registry)
            elif eng_reg is not self.registry:
                _logger.warning(
                    "scheduler and engine carry DIFFERENT telemetry "
                    "registries: engine-side metrics (e.g. "
                    "serving.faults.nonfinite, serving.prefill.*) land "
                    "in the engine's, scheduler-side in the "
                    "scheduler's — pass one registry to both unless "
                    "the split is deliberate")
        self._queue: collections.deque = collections.deque()
        self._running: List[Optional[Request]] = [None] * engine.slots
        self._last_tokens = np.zeros(engine.slots, np.int32)
        self._temps = np.zeros(engine.slots, np.float32)
        self._pf_rr = 0           # round-robin start for chunk budgeting
        self.completed: List[Request] = []
        # fault isolation: containment is ALWAYS on (the policy has
        # production defaults); the plan is the chaos harness's
        # injection schedule (None in production); the auditor
        # reconciles page refcounts after finish/eviction events,
        # sampled by the policy's audit_every_n
        self.fault_policy = fault_policy if fault_policy is not None \
            else FaultPolicy()
        self.fault_plan = fault_plan
        if auditor is not None:
            self.auditor = auditor
        else:
            self.auditor = PoolAuditor(
                every_n=self.fault_policy.audit_every_n,
                registry=self.registry)
        self._tick = 0            # heartbeat index (the FaultPlan clock)
        self._step_s_ema: Optional[float] = None   # decode-step seconds
        # ---- async pipelined heartbeat state (pipeline_depth >= 1):
        # dispatched-but-unreconciled decode steps, oldest first, and
        # the worker thread that overlaps drafting + prefix hashing
        # with device execution. Depth 0 never touches any of it — the
        # sync beat stays the bitwise oracle path. Depth 1 is the
        # default: one step in flight hides the host's side of a beat
        # under the device's.
        self.pipeline_depth = int(pipeline_depth)
        self._pipeline: collections.deque = collections.deque()
        self._worker: Optional[DraftWorker] = None
        if self.pipeline_depth > 0:
            self._worker = DraftWorker()
            # stop the thread when the scheduler is collected (the
            # finalizer closes over the WORKER, not self — no cycle)
            weakref.finalize(self, self._worker.stop)
        # per-slot precomputed prefix block keys (admission stashes the
        # worker's hash for the registration that follows ingestion)
        self._slot_hash_keys: List[Optional[list]] = \
            [None] * engine.slots
        # uid -> rolling block keys handed in at submit (the router's
        # pre-probed hashes); consumed at admission, dropped at finish
        self._presubmitted_keys: Dict[int, list] = {}
        # prefill-role: finished prompt ingestions awaiting collection
        # by the router as (request, arena key or None, block keys) —
        # ready once the record's async swap-out completes
        self._handoffs: List[tuple] = []
        # decode-role: uid -> arena key for routed handoffs awaiting
        # admission (resolved — imported or verified-miss re-prefilled —
        # by _consult_prefix_cache)
        self._handoff_uids: Dict[int, int] = {}
        # dispatch-ahead chunk prefill (pipeline_depth >= 1): per-slot
        # dispatched-but-unreconciled chunk; depth 0 never populates it
        self._pending_prefill: List[Optional[_InflightChunk]] = \
            [None] * engine.slots
        # decode-beat isolation accounting: beats taken vs beats that
        # ran any chunk-prefill work (the router aggregates these into
        # the serving.disagg.decode_isolation gauge)
        self.beats_total = 0
        self.beats_with_prefill = 0

    # ------------------------------------------------------------ ingestion
    def submit(self, request: Request,
               prefix_keys: Optional[Sequence[int]] = None,
               count_rejection: bool = True,
               _handoff: bool = False) -> Request:
        """Queue ``request``; raises :class:`QueueFull` at capacity and
        ``ValueError`` for prompts the engine can never serve.

        ``count_rejection=False`` suppresses the
        ``serving.requests.rejected`` tick on a capacity raise — the
        router probes replicas with it so an absorbed SPILL (placed
        and served on the next-best replica) never reads as a
        caller-visible rejection; the router counts one rejection
        itself only when the WHOLE fleet turns the request away.

        ``prefix_keys`` (optional) are the prompt's PRECOMPUTED rolling
        block hashes — the :class:`~apex_tpu.serving.Router` already
        computed them once to probe every replica's prefix cache, so
        the chosen replica takes them here instead of re-hashing (the
        hash is deterministic: precomputed and inline keys are
        interchangeable bit-for-bit). At least ``len(prompt) //
        block_len`` keys, as :meth:`PrefixCache.block_keys` returns.

        A request whose ``_t_submit`` clock is already running (a
        router requeue after a replica death) keeps it — like a
        quarantine requeue, re-submission never resets ``latency_s``
        or the deadline."""
        n = len(request.prompt)
        if not 0 < n <= self.engine.prefill_len:
            raise ValueError(
                f"prompt length {n} not in (0, prefill_len="
                f"{self.engine.prefill_len}] — the fixed-shape prefill "
                "program cannot admit it")
        if request.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if request.adapter is not None \
                and getattr(self.engine, "lora", None) is None:
            raise ValueError(
                f"request names adapter {request.adapter!r} but the "
                "engine was built without lora=LoRAConfig(...) — "
                "LoRA serving is off")
        if self.slo is not None:
            # validates slo_class loudly (unknown names raise here, at
            # the door, instead of silently scheduling as priority 0)
            self.slo.base_priority(request)
        if self.role == "decode" and not _handoff:
            raise ValueError(
                "role='decode' replica serves router hand-overs only — "
                "submit to a prefill-capable replica (the Router's "
                "role policy routes new prompts there)")
        # deadline-aware admission: once any decode throughput has
        # been measured, estimate this request's completion as EMA ×
        # (queue positions ahead + its own chunk count + its token
        # budget) — one heartbeat is at least one EMA'd step. An
        # estimate past the deadline is rejected NOW with an honest
        # retry hint (EMA × queue depth: when the queue ahead has
        # drained, the estimate shrinks below the deadline) instead of
        # admitting work destined to miss. Deliberately conservative
        # in neither direction: no prefix-hit discount (unknowable
        # pre-admission), no slot-parallelism credit.
        if self.slo is not None and self.slo.deadline_admission \
                and request.deadline_s is not None \
                and self._step_s_ema is not None:
            est = self._step_s_ema * (
                len(self._queue) + self.engine.chunks_for(n)
                + request.max_new_tokens)
            if est > request.deadline_s:
                if self.registry is not None:
                    self.registry.counter_inc(
                        "serving.slo.deadline_rejected")
                hint = round(self._step_s_ema
                             * max(1, len(self._queue)), 6)
                raise DeadlineUnmeetable(
                    f"deadline_s={request.deadline_s:.3f} unmeetable: "
                    f"estimated completion ~{est:.3f}s at the current "
                    f"decode rate (retry_after_s~{hint:.3f})",
                    retry_after_s=hint)
        # paged note: no page-demand check is needed here — a request's
        # worst case is capped at ceil(max_len / page_len) pages, which
        # the Engine constructor guarantees every pool can hold, so the
        # queue head always admits eventually as running slots drain
        if len(self._queue) >= self.max_queue:
            if self.registry is not None and count_rejection:
                self.registry.counter_inc("serving.requests.rejected")
            hint = self._retry_after_hint()
            suffix = f" (retry_after_s~{hint:.3f})" if hint else ""
            raise QueueFull(
                f"request queue at capacity ({self.max_queue}); retry "
                f"after a step() or shed load{suffix}",
                retry_after_s=hint)
        request.status = RequestStatus.QUEUED
        now = time.perf_counter()
        if request._t_submit is None:
            request._t_submit = now
        request._t_queued = now
        if self.tracer is not None:
            self.tracer.event(request.uid, "submit", t0=now,
                              prompt_tokens=n,
                              max_new_tokens=request.max_new_tokens,
                              retry=request.retries)
        self._queue.append(request)
        if self.retain_prefixes and prefix_keys is not None:
            # the router's pre-probed hashes: admission consumes them
            # in place of a worker/inline computation
            self._presubmitted_keys[request.uid] = list(prefix_keys)
        elif self._worker is not None and self.retain_prefixes:
            # hash offload: the prompt's rolling block keys start
            # computing NOW on the worker thread, overlapping whatever
            # the device is executing — admission takes the result (or
            # computes inline on a miss; identical bits either way)
            pcache = self.engine.prefix_cache
            prompt = tuple(request.prompt)
            n_blocks = len(prompt) // pcache.block_len
            self._worker.submit(
                ("hash", request.uid),
                lambda: pcache.block_keys(prompt, n_blocks))
        if self.registry is not None:
            self.registry.counter_inc("serving.requests.submitted")
        return request

    # ----------------------------------------------------------- accounting
    def _retry_after_hint(self) -> Optional[float]:
        """The :class:`QueueFull` backoff hint, derived from measured
        decode throughput: a queue position frees when the nearest
        running request finishes, which costs at least (fewest
        remaining tokens across running slots) decode steps at the
        EMA'd step latency. None before the first measured decode step
        (nothing honest to say yet)."""
        if self._step_s_ema is None:
            return None
        remaining = [max(1, r.max_new_tokens - len(r.output_tokens))
                     for r in self._running if r is not None]
        steps = min(remaining) if remaining else 1
        return round(steps * self._step_s_ema, 6)

    def _free_slot(self, slot: int) -> None:
        """Detach whatever occupies ``slot``: clear the running entry
        and return its pages plus any unused admission reservation to
        the pool NOW. Shared by normal finishes and fault quarantines."""
        self._running[slot] = None
        self._temps[slot] = 0.0
        self._slot_hash_keys[slot] = None
        if self._pending_prefill[slot] is not None:
            # a dispatched-ahead prefill chunk nobody will read: the
            # same speculated-finality rollback as the decode pipeline
            self._pending_prefill[slot] = None
            if self.registry is not None:
                self.registry.counter_inc("serving.heartbeat.discarded")
        if self._pipeline:
            # invalidate the slot's in-flight dispatch-ahead steps NOW
            # (speculated-finality rollback): a uid check at reconcile
            # is NOT enough on its own — a quarantined request keeps
            # its uid through requeue, so if it re-admits into this
            # same slot before the stale steps retire, their
            # garbage-lineage tokens would pass a uid+status test and
            # be emitted into the retried stream
            dropped = sum(rec.uids.pop(slot, None) is not None
                          for rec in self._pipeline)
            if dropped and self.registry is not None:
                self.registry.counter_inc("serving.heartbeat.discarded",
                                          dropped)
        if getattr(self.engine, "lora", None) is not None:
            # the single LoRA unbind point: drops the slot's adapter
            # refcount (the adapter STAYS resident for affinity — only
            # arena pressure evicts it). Not in Engine.release_slot,
            # which cold-start prefill calls mid-request
            self.engine.lora_unbind(slot)
        self.engine.release_slot(slot)

    def _finish(self, request: Request, reason: str,
                slot: Optional[int] = None,
                status: Optional[RequestStatus] = None) -> None:
        request.finish_reason = reason
        if status is None:
            status = RequestStatus.EXPIRED if reason == "timeout" \
                else RequestStatus.FINISHED
        request.status = status
        self._presubmitted_keys.pop(request.uid, None)
        self._preempted_uids.discard(request.uid)
        if self._handoff_uids:
            hkey = self._handoff_uids.pop(request.uid, None)
            if hkey is not None:
                # the request died (expired/failed) before admission
                # could import its handoff: release the orphaned cache
                # entry and its arena record
                if self.engine.prefix_cache.drop(hkey):
                    tier = getattr(self.engine, "host_tier", None)
                    if tier is not None:
                        tier.discard(hkey)
        if request._t_submit is not None:
            request.latency_s = time.perf_counter() - request._t_submit
        if request.deadline_s is not None \
                and request.latency_s is not None:
            request.deadline_missed = \
                request.latency_s > request.deadline_s
        if self.tenants is not None and request.tenant is not None:
            # finish-time charge: only work actually delivered moves
            # the weighted-fair ledger
            self.tenants.charge(request.tenant,
                                len(request.output_tokens))
        if self.tracer is not None:
            # the trace's single TERMINAL span, spelled as three
            # explicit literals (the span-name lint reads literals):
            # sealing is first-wins, so a late double-finish is inert
            tr = self.tracer
            if status is RequestStatus.EXPIRED:
                tr.end_trace(request.uid, "expired", reason=reason)
            elif status is RequestStatus.FAILED:
                tr.end_trace(request.uid, "failed", reason=reason,
                             error=request.error)
            else:
                tr.end_trace(request.uid, "finish", reason=reason,
                             output_tokens=len(request.output_tokens))
        if slot is not None:
            self._free_slot(slot)
        self.completed.append(request)
        if self.registry is not None:
            key = {RequestStatus.EXPIRED: "serving.requests.timeout",
                   RequestStatus.FAILED: "serving.requests.failed"}.get(
                       status, "serving.requests.completed")
            self.registry.counter_inc(key)
            # one completion record per request: the TTFT decomposition
            # and chunk count ride the ring/sinks alongside the
            # aggregate histograms (observe=False: uid is not a series
            # and the latencies already live in dedicated serving.*
            # histograms — don't grow junk reservoirs per request)
            self.registry.record_step({
                "uid": request.uid,
                "trace_id": request.uid,
                "replica": self.replica_index,
                "status": request.status.value,
                "finish_reason": reason,
                "prompt_tokens": len(request.prompt),
                "output_tokens": len(request.output_tokens),
                "chunks_per_prompt": request.chunks,
                "reused_tokens": request.reused_tokens,
                "spec_drafted": request.spec_drafted,
                "spec_accepted": request.spec_accepted,
                "retries": request.retries,
                "error": request.error,
                "queue_wait_s": request.queue_wait_s,
                "prefill_s": request.prefill_s,
                "ttft_s": request.ttft_s,
                "latency_s": request.latency_s,
                "slo_class": request.slo_class,
                "priority": request.priority,
                "tenant": request.tenant,
                "preemptions": request.preemptions,
                "deadline_missed": request.deadline_missed,
            }, tag="serving.request", observe=False)
            if self.slo is not None:
                # per-class SLO telemetry: one namespaced family per
                # class (the emitted⇔documented lint reduces the
                # f-string to its serving.slo.class literal)
                cls = request.slo_class if request.slo_class \
                    is not None else "none"
                self.registry.counter_inc(
                    f"serving.slo.class.{cls}.completed")
                if request.ttft_s is not None:
                    self.registry.observe(
                        f"serving.slo.class.{cls}.ttft_s",
                        request.ttft_s)
                if request.deadline_missed:
                    self.registry.counter_inc(
                        "serving.slo.deadline_missed")
                    self.registry.counter_inc(
                        f"serving.slo.class.{cls}.deadline_missed")
                if request.tenant is not None \
                        and self.tenants is not None:
                    self.registry.counter_inc(
                        f"serving.slo.tenant.{request.tenant}.tokens",
                        len(request.output_tokens))
        # finish events move refcounts (page release, reservation
        # return): reconcile on the policy's sampling cadence
        self.auditor.maybe_audit(self.engine)

    def _quarantine(self, request: Request, slot: Optional[int],
                    error: str) -> None:
        """Contain one per-request fault: free the slot (pages,
        reservation, prefix pin), then either requeue the request with
        capped exponential backoff — its transient outputs reset, its
        paid-compute counters (``chunks``, ``prefill_s``) and the
        original submit clock kept — or, past ``max_retries``, finish
        it with the typed ``FAILED`` terminal status. The engine and
        every other slot are untouched: this is the blast-radius
        boundary."""
        request.retries += 1
        request.error = error
        policy = self.fault_policy
        if self.tracer is not None:
            self.tracer.event(
                request.uid, "quarantine", kind=fault_kind(error),
                error=error, retry=request.retries,
                requeued=request.retries <= policy.max_retries)
        if slot is not None:
            self._free_slot(slot)
        if request.retries > policy.max_retries:
            _logger.warning(
                "request %d FAILED after %d retries: %s", request.uid,
                request.retries - 1, error)
            self._finish(request, "fault", status=RequestStatus.FAILED)
            return
        now = self._reset_transient(request)
        request._not_before = now + policy.backoff_s(request.retries)
        # re-probe on requeue: offer the request back to the router
        # first (it re-probes LIVE replicas and the arena at re-route
        # time, so the retry can home onto a prefix or handoff that
        # registered after the original submit); the local queue is the
        # fallback when no router is wired or it declined
        rerouted = self.on_requeue is not None \
            and bool(self.on_requeue(request))
        if not rerouted:
            self._queue.append(request)
        if self.registry is not None:
            self.registry.counter_inc("serving.faults.requeued")
        _logger.info("request %d requeued%s (retry %d/%d): %s",
                     request.uid, " via router" if rerouted else "",
                     request.retries, policy.max_retries,
                     error)

    def _reset_transient(self, request: Request) -> float:
        """Roll ``request`` back to a servable queued state (the shared
        half of a quarantine requeue and a replica-death drain): its
        transient outputs reset, its paid-compute counters (``chunks``,
        ``prefill_s``, the spec counters) and the ORIGINAL submit clock
        kept — retries and drains never reset ``latency_s`` or the
        deadline. Returns ``now`` (the fresh queueing episode's
        start)."""
        request.output_tokens.clear()
        request._prefill_pos = 0
        request.reused_tokens = 0
        request.ttft_s = None
        # BUGFIX guard for the quarantined-while-preempted path: the
        # outputs just rolled back, so the preempt-time ingest stream
        # (prompt + those outputs) is now a lie — replaying it would
        # emit the request's tokens shifted by the replayed outputs, a
        # silent wrong-token stream. Clearing it degrades the resume
        # to the verified-miss contract: the next admission ingests
        # the PROMPT (any surviving prefix entry still prefix-matches
        # it token-verified; a corrupt swap record fails its CRC and
        # re-prefills cold), never a wrong token.
        request._ingest_tokens = None
        request._eff_priority = None
        request.status = RequestStatus.QUEUED
        now = time.perf_counter()
        request._t_queued = now     # a fresh queueing episode begins
        return now

    def _deadline(self, request: Request) -> Optional[float]:
        t = request.timeout_s if request.timeout_s is not None \
            else self.default_timeout_s
        if t is None or request._t_submit is None:
            return None
        return request._t_submit + t

    def _expire(self, now: float) -> None:
        for r in [r for r in self._queue
                  if (d := self._deadline(r)) is not None and now > d]:
            self._queue.remove(r)
            self._finish(r, "timeout")
        for slot, r in enumerate(self._running):
            if r is None:
                continue
            d = self._deadline(r)
            if d is not None and now > d:
                self._finish(r, "timeout", slot)

    # ------------------------------------------------------------ admission
    def _eligible_index(self, now: float) -> Optional[int]:
        """The queue index of the first request whose retry backoff (if
        any) has elapsed — FIFO order among eligible requests; a
        backing-off request never blocks the ones behind it (it already
        had its turn)."""
        for i, r in enumerate(self._queue):
            if r._not_before is None or r._not_before <= now:
                return i
        return None

    def _admit(self) -> None:
        if self.slo is not None:
            return self._admit_slo()
        for slot in range(self.engine.slots):
            if self._running[slot] is not None or not self._queue:
                continue
            idx = self._eligible_index(time.perf_counter())
            if idx is None:
                break               # everything queued is backing off
            gate = self._lora_gate(slot, idx)
            if gate == "failed":
                continue            # the queue changed: re-scan
            if gate == "blocked":
                break               # every arena row pinned: FIFO
                #                     holds until a finish unbinds one
            if not self._reserve_pages(slot, self._queue[idx]):
                # pool exhausted for the first eligible request: stop
                # admitting (FIFO — later, smaller requests must not
                # starve it); finishing requests release pages, so the
                # next beat retries
                if getattr(self.engine, "lora", None) is not None:
                    self.engine.lora_unbind(slot)
                break
            self._admit_one(slot, idx)

    def _lora_gate(self, slot: int, idx: int) -> str:
        """Admission-time LoRA bind for queue position ``idx`` into
        ``slot`` — runs BEFORE the page reservation so a blocked bind
        never strands reserved pages. Returns ``"ok"`` (bound, or a
        base-model request — nothing to do), ``"blocked"`` (the
        adapter is absent and every arena row is pinned by a running
        slot: the caller stops admitting; finishes unbind rows and the
        next beat retries) or ``"failed"`` (the adapter is unknown to
        the arena or failed its swap-in checksum: the request fails
        LOUDLY here — removed from the queue, FAILED, error recorded —
        never a silent base-model fallback)."""
        r = self._queue[idx]
        if r.adapter is None \
                or getattr(self.engine, "lora", None) is None:
            return "ok"
        try:
            bound = self.engine.lora_bind(slot, r.adapter)
        except KeyError as e:
            del self._queue[idx]
            r.error = str(e.args[0]) if e.args else str(e)
            self._finish(r, "fault", status=RequestStatus.FAILED)
            return "failed"
        return "ok" if bound else "blocked"

    def _admit_one(self, slot: int, idx: int) -> None:
        """Admit queue position ``idx`` into free ``slot`` (pages
        already reserved): the shared tail of the FIFO and SLO
        admission loops — bitwise the pre-SLO admission body, so the
        ``slo=None`` trace path is verbatim the old one."""
        r = self._queue[idx]
        del self._queue[idx]
        # admission ends the queue wait; prefill compute is paid one
        # chunk per heartbeat from here (_prefill_tick)
        r.queue_wait_s = time.perf_counter() - r._t_queued
        if self.registry is not None:
            self.registry.observe("serving.queue_wait_s",
                                  r.queue_wait_s)
        r.status = RequestStatus.PREFILLING
        r._prefill_pos = 0
        if self.retain_prefixes:
            if self.tracer is not None:
                # bind the trace to this thread so swap-in /
                # swap-out spans the prefix attach triggers inside
                # the engine attribute to the admitting request
                with self.tracer.bind(r.uid):
                    self._consult_prefix_cache(r, slot)
            else:
                self._consult_prefix_cache(r, slot)
        if self.tracer is not None:
            tr = self.tracer
            t_adm = tr.now()
            tr.event(r.uid, "queue_wait",
                     t0=t_adm - r.queue_wait_s, dur=r.queue_wait_s)
            tr.event(r.uid, "admit", t0=t_adm, slot=slot,
                     reused_tokens=r.reused_tokens,
                     pages=self.engine.pages_required(
                         len(r.prompt), r.max_new_tokens))
        self._running[slot] = r
        self._temps[slot] = r.temperature

    # -------------------------------------------- SLO admission + preemption
    def _admit_slo(self) -> None:
        """Priority-order admission (``slo`` set): repeatedly pick the
        most important eligible queued request — highest effective
        priority (base + queue-aging boost), then earliest deadline,
        then the tenant owed the most weighted service, then FIFO —
        and place it in a free slot. When no slot (or no page
        reservation) can be found and ``slo.preempt`` is on, the
        lowest-priority running request STRICTLY below the candidate
        preempts to the host tier instead of the candidate queueing
        behind it. The loop guard bounds pathological ladders (every
        iteration admits, preempts or returns)."""
        guard = 4 * (self.engine.slots + len(self._queue) + 2)
        while self._queue and guard > 0:
            guard -= 1
            now = time.perf_counter()
            idx = self._eligible_index_slo(now)
            if idx is None:
                return          # backing off / quota-blocked across the board
            cand = self._queue[idx]
            slot = next((s for s in range(self.engine.slots)
                         if self._running[s] is None), None)
            if slot is None:
                if not self._try_preempt(cand, now):
                    return
                continue        # a slot just freed: re-scan (the
                #                 candidate set may have re-ranked)
            gate = self._lora_gate(slot, idx)
            if gate == "failed":
                continue        # the queue changed: re-rank
            if gate == "blocked":
                return          # every arena row pinned: admission
                #                 holds until a finish unbinds one
            if not self._reserve_pages(slot, cand):
                # pool exhausted: preempting releases the victim's
                # pages (swap-out frees them at dispatch; a resident
                # retention frees them through try_reserve_slot's LRU
                # valve on the retry)
                if getattr(self.engine, "lora", None) is not None:
                    self.engine.lora_unbind(slot)
                if not self._try_preempt(cand, now):
                    return
                continue
            # pin the admission-time effective priority: the aging
            # boost earned while queued persists while running, so an
            # aged-up admission cannot be instantly re-preempted by
            # the next fresh arrival of a nominally higher class
            cand._eff_priority = self.slo.effective_priority(cand, now)
            self._admit_one(slot, idx)

    def _eligible_index_slo(self, now: float) -> Optional[int]:
        """The SLO analogue of :meth:`_eligible_index`: the queue
        index of the most important request whose retry backoff has
        elapsed and whose tenant is under its concurrency quota.
        Order: effective priority desc, remaining deadline asc
        (deadline-less last), tenant virtual service asc (owed more =
        first), queue position asc (FIFO among true ties)."""
        best = best_key = None
        for i, r in enumerate(self._queue):
            if r._not_before is not None and r._not_before > now:
                continue
            if self._tenant_blocked(r):
                continue
            pri = self.slo.effective_priority(r, now)
            if r.deadline_s is not None and r._t_submit is not None:
                remaining = r._t_submit + r.deadline_s - now
            else:
                remaining = float("inf")
            served = 0.0
            if self.tenants is not None and r.tenant is not None:
                served = self.tenants.virtual_served(r.tenant)
            key = (-pri, remaining, served, i)
            if best_key is None or key < best_key:
                best, best_key = i, key
        return best

    def _tenant_blocked(self, r: Request) -> bool:
        """Per-tenant concurrency quota (``slo.tenant_max_share``):
        True while the tenant already occupies its share of slots —
        the request stays QUEUED (not an error) and the block lifts as
        the tenant's running requests finish. At least one slot is
        always allowed, so a quota never starves a tenant outright."""
        share = self.slo.tenant_max_share
        if share is None or r.tenant is None:
            return False
        cap = max(1, int(share * self.engine.slots))
        held = sum(1 for q in self._running
                   if q is not None and q.tenant == r.tenant)
        return held >= cap

    def _try_preempt(self, cand: Request, now: float) -> bool:
        """Preempt the lowest-priority running request strictly below
        ``cand``'s effective priority (ties broken toward the newest
        submit — least sunk wait). False when preemption is off or no
        strictly-lower victim exists (equal priority never preempts:
        that would thrash between peers)."""
        if not self.slo.preempt:
            return False
        pri = self.slo.effective_priority(cand, now)
        victim = None
        victim_key = None
        for slot, r in enumerate(self._running):
            if r is None or r.status != "running":
                # only RUNNING requests preempt: a prefilling slot has
                # no committed output state worth migrating yet, and
                # its chunk loop holds engine state this path must not
                # yank mid-ingest
                continue
            if self.slo.max_preemptions is not None \
                    and r.preemptions >= self.slo.max_preemptions:
                continue
            if len(r.prompt) + len(r.output_tokens) \
                    > self.engine.prefill_len:
                # resume replays prompt + committed outputs through
                # the fixed-shape prefill window — a decode that has
                # grown past prefill_len can no longer be re-ingested
                # exactly, so the slot is not preemptible
                continue
            vpri = r._eff_priority if r._eff_priority is not None \
                else self.slo.base_priority(r)
            if vpri >= pri:
                continue
            key = (vpri, -(r._t_submit or 0.0), -r.uid)
            if victim_key is None or key < victim_key:
                victim, victim_key = slot, key
        if victim is None:
            return False
        self._preempt(victim)
        return True

    def _preempt(self, slot: int) -> None:
        """Preempt-to-host: migrate ``slot``'s committed K/V out and
        requeue its request in the PREEMPTED state. The committed
        stream is ``prompt + outputs`` — its last token is pending
        (decode writes a token's K/V one step after sampling it), so
        the aligned export cap ``aligned(len(seq) - 1)`` is exactly
        the prefix the slot has ingested. With a host tier the pages
        ride :meth:`Engine.export_handoff` (async CRC'd swap-out under
        the request's uid — the disagg machinery, one tier up);
        without one the prefix is retained RESIDENT (COW share, freed
        by LRU pressure if the pool needs it). Either way resume is an
        ordinary admission: prefix match at the committed offset, the
        final chunk re-samples the pending position, and a greedy
        stream continues bitwise. A failed/declined export degrades to
        a cold resume (re-ingest from the prompt) — never a wrong
        token, per the PR 13 verified-miss contract."""
        r = self._running[slot]
        seq = [int(t) for t in r.prompt] + [int(t)
                                            for t in r.output_tokens]
        committed = len(seq) - 1
        cap = (committed // self.engine.chunk_len) \
            * self.engine.chunk_len
        tier = getattr(self.engine, "host_tier", None)
        pcache = self.engine.prefix_cache
        # second-cycle hygiene: a prior resume's import may have left
        # this uid's entry (and arena bytes) behind — drop both before
        # re-exporting under the same single-writer key
        if pcache.drop(r.uid) and tier is not None:
            tier.discard(r.uid)
        t0 = time.perf_counter()
        exported = 0
        try:
            if self.tracer is not None:
                with self.tracer.bind(r.uid):
                    exported = self._preempt_export(slot, r, seq, cap,
                                                    tier)
            else:
                exported = self._preempt_export(slot, r, seq, cap,
                                                tier)
        except Exception as e:  # noqa: BLE001 — containment edge
            self._count_transient()
            _logger.warning(
                "preempt export for request %d failed (%s: %s) — it "
                "will resume cold", r.uid, type(e).__name__, e)
        if exported and tier is not None:
            # resume resolves the record through the handoff seam:
            # _finish/drain release it if the request dies queued, so
            # a preempted request can never leak an arena record
            self._handoff_uids[r.uid] = r.uid
        r.status = RequestStatus.PREEMPTED
        r.preemptions += 1
        r._ingest_tokens = seq
        r._prefill_pos = 0
        r._not_before = None
        self._preempted_uids.add(r.uid)
        if self.tracer is not None:
            self.tracer.event(r.uid, "preempt", t0=t0,
                              dur=time.perf_counter() - t0, slot=slot,
                              committed=committed, exported=exported)
        # free AFTER the export: the entry holds its own page
        # refcounts (or the arena holds the bytes), so the slot's
        # release destroys nothing the resume needs
        self._free_slot(slot)
        self._queue.append(r)
        if self.registry is not None:
            self.registry.counter_inc("serving.preempt.preemptions")
        self.auditor.maybe_audit(self.engine)

    def _preempt_export(self, slot: int, r: Request, seq, cap: int,
                        tier) -> int:
        """The export half of a preemption: through the host arena
        when a tier is wired (the importer-side CRC makes corruption a
        VERIFIED miss), else a resident retention. ``keys=None``
        everywhere — the slot's stashed hash keys cover the PROMPT's
        blocks only, and ``seq`` extends past them."""
        if tier is not None:
            return self.engine.export_handoff(slot, r.uid, seq,
                                              keys=None)
        if cap <= 0:
            return 0
        outcome = self.engine.retain_prefix(slot, seq[:cap], keys=None)
        # "duplicate" is a warm resume too: the exact prefix is
        # already retained (refreshed), so the match will find it
        return cap if outcome in ("registered", "duplicate") else 0

    def _reserve_pages(self, slot: int, r: Request) -> bool:
        """Admission gate: reserve ``r``'s worst-case page demand
        for ``slot``. Counts ``serving.pool.admit_blocked`` when
        the pool turns an admission away."""
        need = self.engine.pages_required(len(r.prompt),
                                          r.max_new_tokens)
        ok = self.engine.try_reserve_slot(slot, need)
        if not ok and self.registry is not None:
            self.registry.counter_inc("serving.pool.admit_blocked")
        return ok

    def _ingest(self, r: Request) -> Sequence[int]:
        """The token stream admission ingests for ``r``: its prompt,
        or — resuming a preemption — prompt + committed outputs (the
        final chunk re-samples the last committed position, which IS
        the next output token, so a greedy resume continues
        bitwise)."""
        return r._ingest_tokens if r._ingest_tokens is not None \
            else r.prompt

    def _consult_prefix_cache(self, r: Request, slot: int) -> None:
        """Admission-time read path: attach the longest cached
        block-aligned prefix of ``r``'s ingest stream to ``slot`` —
        share the donor's pages into the slot's table
        (copy-on-write, zero data movement, no pin needed: page
        refcounts outlive the entry).
        Chunk prefill then resumes at the matched offset. A miss
        changes nothing — the request prefills cold from offset 0.
        For a PREEMPTED request the stream is prompt + committed
        outputs, so the match lands exactly at the preempt-time export
        cap (warm resume) or degrades to the verified-miss cold
        re-ingest — and the resolution is counted and traced as a
        resume, not a disagg import."""
        pcache = self.engine.prefix_cache
        resume = r.uid in self._preempted_uids
        if resume:
            self._preempted_uids.discard(r.uid)
        keys = self._presubmitted_keys.pop(r.uid, None)
        if resume:
            # any presubmitted/worker hash covers the PROMPT's blocks
            # only — stale for the resumed stream; recompute inline
            keys = None
        elif keys is None and self._worker is not None:
            prompt = tuple(r.prompt)
            n_blocks = len(prompt) // pcache.block_len
            keys = self._worker.take(
                ("hash", r.uid),
                lambda: pcache.block_keys(prompt, n_blocks))
        if keys is not None:
            # registration after ingestion reuses the same keys
            self._slot_hash_keys[slot] = keys
        seq = self._ingest(r)
        m = pcache.match(seq, keys=keys)
        if m is not None and not self.engine.attach_prefix(slot, m):
            # hierarchical KV: the hit's host-tier bytes were
            # missing/corrupt (the engine dropped the entry and
            # counted serving.swap.verify_failed) or the pool
            # was too tight to restore them — degrade to a
            # VERIFIED MISS: nothing attached, the request
            # prefills cold from offset 0, and the hit/miss
            # accounting is reversed so hit_rate stays honest
            pcache.unrecord_hit(m)
            m = None
        if m is not None:
            r._prefill_pos = m.length
            r.reused_tokens = m.length
        if self.registry is not None:
            if m is None:
                self.registry.counter_inc("serving.prefix.misses")
            else:
                self.registry.counter_inc("serving.prefix.hits")
                self.registry.counter_inc("serving.prefix.tokens_reused",
                                          m.length)
                self.registry.counter_inc(
                    "serving.prefix.chunks_skipped",
                    m.length // self.engine.chunk_len)
            self.registry.gauge_set("serving.prefix.hit_rate",
                                    pcache.hit_rate)
        hkey = self._handoff_uids.pop(r.uid, None) \
            if self._handoff_uids else None
        if hkey is not None:
            imported = m is not None \
                and getattr(m, "row", None) == hkey
            if not imported:
                # the handoff record went missing, corrupt or evicted
                # (or the swap-in failed its CRC — the engine dropped
                # that entry itself): VERIFIED MISS. Release any
                # dangling entry plus its arena record, then
                # re-prefill — nothing was attached, so never a wrong
                # token. When an ordinary local prefix matched instead
                # (m covers the same tokens), the unused handoff
                # record is released the same way but no re-prefill is
                # charged.
                if pcache.drop(hkey):
                    tier = getattr(self.engine, "host_tier", None)
                    if tier is not None:
                        tier.discard(hkey)
                if m is None and not resume \
                        and self.registry is not None:
                    self.registry.counter_inc(
                        "serving.disagg.reprefills")
            if self.tracer is not None and not resume:
                self.tracer.event(r.uid, "handoff_import",
                                  imported=imported,
                                  reused_tokens=0 if m is None
                                  else m.length)
        if resume:
            # the resume resolution, whichever path backed it: warm
            # (swap-in + COW at the committed offset — m.length
            # tokens) or the verified-miss cold re-ingest
            if self.registry is not None:
                self.registry.counter_inc("serving.preempt.resumes")
                if m is None:
                    self.registry.counter_inc(
                        "serving.preempt.resume_reprefills")
            if self.tracer is not None:
                self.tracer.event(r.uid, "resume", slot=slot,
                                  resumed_tokens=0 if m is None
                                  else m.length, cold=m is None)

    def _count_transient(self) -> None:
        if self.registry is not None:
            self.registry.counter_inc("serving.faults.transient")

    def _prefill_tick(self, tick: Optional[int] = None) -> int:
        """Run at most ``chunk_budget`` chunk-prefill steps across the
        prefilling slots, round-robin from a rotating start so no slot
        starves. Returns the number of chunks run. Each engine call is
        containment-wrapped: a transient failure (real or
        plan-injected) or a non-finite sampled row quarantines ONLY the
        slot's request — the other prefilling/decoding slots never see
        it. ``tick`` is the heartbeat index faults are keyed by (the
        same clock every injection site reads)."""
        if tick is None:
            tick = self._tick
        slots = self.engine.slots
        if self.role != "prefill":
            # dispatch-ahead prefill: retire every chunk an EARLIER
            # beat dispatched, wherever the round-robin stands and
            # before the budget is spent - a final chunk's first token
            # is emitted, and its slot decodes, at the beat after its
            # dispatch, as in the synchronous beat. The wait lies under
            # the decode step that was dispatched behind the chunk and
            # is still running, so the device is not kept waiting by
            # it. (A prefill replica dispatches no decode step: there
            # a chunk is retired at its slot's next visit, below, so
            # that another slot's chunk keeps the device busy
            # meanwhile.)
            for slot, entry in enumerate(self._pending_prefill):
                if entry is not None and entry.tick < tick:
                    self._reconcile_prefill(slot)
        ran = 0
        start = self._pf_rr
        for i in range(slots):
            if ran >= self.chunk_budget:
                break
            slot = (start + i) % slots
            if self._pending_prefill[slot] is not None:
                # a chunk of this beat's own cold-queue burst (or a
                # prefill replica's): reconcile-then-dispatch keeps at
                # most one chunk per slot in flight
                self._reconcile_prefill(slot)
            r = self._running[slot]
            if r is None or r.status != "prefilling":
                continue
            if self.role == "prefill":
                cap = ((len(r.prompt) - 1) // self.engine.chunk_len) \
                    * self.engine.chunk_len
                if r._prefill_pos >= cap:
                    # ingestion complete (every full chunk; the final
                    # partial chunk belongs to the importer, whose
                    # chunk-prefill program samples the first token):
                    # export to the arena and free the slot
                    self._export_handoff(r, slot)
                    ran += 1
                    self._pf_rr = (slot + 1) % slots
                    continue
            seq = self._ingest(r)
            lo = r._prefill_pos
            hi = min(lo + self.engine.chunk_len, len(seq))
            final = hi == len(seq)
            if self.pipeline_depth > 0:
                self._dispatch_prefill(slot, r, lo, hi, final, tick)
                ran += 1
                self._pf_rr = (slot + 1) % slots
                continue
            t0 = time.perf_counter()
            try:
                if self.fault_plan is not None:
                    self.fault_plan.maybe_raise("chunk", tick)
                token = self.engine.prefill_chunk(
                    slot, list(seq[lo:hi]), lo, r.temperature,
                    final=final)
            except Exception as e:  # noqa: BLE001 — containment edge
                r.prefill_s += time.perf_counter() - t0
                ran += 1            # the heartbeat spent its budget here
                self._pf_rr = (slot + 1) % slots
                self._count_transient()
                self._quarantine(r, slot, f"{type(e).__name__}: {e}")
                continue
            r.prefill_s += time.perf_counter() - t0
            r._prefill_pos = hi
            r.chunks += 1
            ran += 1
            if self.tracer is not None:
                self.tracer.event(r.uid, "prefill_chunk", t0=t0,
                                  dur=time.perf_counter() - t0,
                                  lo=lo, hi=hi, final=final)
            # next tick resumes AFTER the last slot served, so slots
            # separated by gaps still ingest at the same rate (a +1
            # bump would serve the slot after a gap twice as often)
            self._pf_rr = (slot + 1) % slots
            if not self.engine.last_chunk_finite:
                # non-finite logits at the sampled row: the slot's K/V
                # is suspect end-to-end — quarantine the request (the
                # mid-prompt sampled token is discarded anyway; a final
                # chunk's token would have been the request's first
                # real output, which we must not emit from NaN logits)
                self._quarantine(r, slot,
                                 "non-finite chunk-prefill logits")
                continue
            if not final:
                continue
            self._complete_prompt(r, slot, token)
        return ran

    def _complete_prompt(self, r: Request, slot: int,
                         token: int) -> None:
        """Ingestion completion (shared by the sync and dispatch-ahead
        prefill paths): register the prefix, mark the TTFT, and emit
        the sampled token through the same finish checks as every
        other token. For a fresh request the token is the FIRST output
        (the checks below reduce verbatim to the pre-SLO forms); for a
        resumed one it is the next output after the committed stream —
        TTFT was already paid and is never overwritten."""
        if self.retain_prefixes:
            if self.tracer is not None:
                # registration can evict a prefix entry, which on a
                # hierarchical-KV engine dispatches a swap-out — bind
                # so those spans attribute to this request
                with self.tracer.bind(r.uid):
                    self._register_prefix(r, slot)
            else:
                self._register_prefix(r, slot)
        if r.ttft_s is None:
            r.ttft_s = time.perf_counter() - r._t_submit
            if self.registry is not None:
                self.registry.observe("serving.ttft_s", r.ttft_s)
        r.output_tokens.append(token)
        if self.eos_id is not None and token == self.eos_id:
            self._finish(r, "eos", slot)
        elif len(r.output_tokens) >= r.max_new_tokens:
            self._finish(r, "max_new_tokens", slot)
        elif len(self._ingest(r)) >= self.engine.max_len:
            # cache already full: a decode step would overwrite the
            # last ingested position's K/V and emit a corrupted token
            self._finish(r, "max_len", slot)
        else:
            r.status = RequestStatus.RUNNING
            self._last_tokens[slot] = token

    def _dispatch_prefill(self, slot: int, r: Request, lo: int,
                          hi: int, final: bool, tick: int) -> None:
        """DISPATCH-AHEAD REGION (prefill path): issue chunk
        ``[lo, hi)`` for ``slot`` without forcing its sampled token to
        host — the chunk executes on the device while the beat's
        remaining host work runs; :meth:`_reconcile_prefill` retires it
        at the slot's next visit. Nothing in this function may force a
        device value (no ``int()`` / ``np.asarray`` /
        ``jax.device_get`` — statically linted BY NAME in
        ``tests/L0/test_serving_metrics_lint.py``)."""
        t0 = time.perf_counter()
        try:
            if self.fault_plan is not None:
                self.fault_plan.maybe_raise("chunk", tick)
            pending = self.engine.prefill_chunk_dispatch(
                slot, list(self._ingest(r)[lo:hi]), lo, r.temperature,
                final=final)
        except Exception as e:  # noqa: BLE001 — containment edge
            r.prefill_s += time.perf_counter() - t0
            self._count_transient()
            self._quarantine(r, slot, f"{type(e).__name__}: {e}")
            return
        r.prefill_s += time.perf_counter() - t0
        r._prefill_pos = hi
        self._pending_prefill[slot] = _InflightChunk(pending, r.uid, lo,
                                                     hi, t0, tick)

    def _reconcile_prefill(self, slot: int) -> None:
        """Retire ``slot``'s dispatched-ahead prefill chunk: force its
        token, finish the chunk's accounting, and — when it was the
        prompt's final chunk — run the same completion path as the
        sync beat. A slot that churned while the chunk was in flight
        had its handle dropped by ``_free_slot`` already; the uid
        re-check here is belt-and-braces."""
        entry = self._pending_prefill[slot]
        if entry is None:
            return
        self._pending_prefill[slot] = None
        pending, uid, lo, hi, t0, _ = entry
        r = self._running[slot]
        if r is None or r.uid != uid or r.status != "prefilling":
            if self.registry is not None:
                self.registry.counter_inc("serving.heartbeat.discarded")
            return
        tr0 = time.perf_counter()
        try:
            token = self.engine.prefill_chunk_reconcile(pending)
        except Exception as e:  # noqa: BLE001 — containment edge
            # async backends can surface a dispatched chunk's failure
            # at its deferred force rather than at dispatch
            r.prefill_s += time.perf_counter() - tr0
            self._count_transient()
            self._quarantine(r, slot, f"{type(e).__name__}: {e}")
            return
        r.prefill_s += time.perf_counter() - tr0
        r.chunks += 1
        final = hi == len(self._ingest(r))
        if self.tracer is not None:
            self.tracer.event(r.uid, "prefill_chunk", t0=t0,
                              dur=time.perf_counter() - t0,
                              lo=lo, hi=hi, final=final)
        if not self.engine.last_chunk_finite:
            # same contract as the sync beat: non-finite logits at the
            # sampled row make the slot's K/V suspect end-to-end
            self._quarantine(r, slot, "non-finite chunk-prefill logits")
            return
        if final:
            self._complete_prompt(r, slot, token)

    # ------------------------------------------------- disaggregation
    def _export_handoff(self, r: Request, slot: int) -> None:
        """Prefill-role hand-over, at prompt-ingestion completion: land
        the slot's finished prefix in the (shared) host arena under the
        request's uid via the async CRC'd swap-out
        (:meth:`Engine.export_handoff`), roll the request back to a
        servable queued state and free the slot. The router collects
        ``(request, key, block keys)`` from :meth:`take_handoffs` once
        the record's swap-out completes and re-routes to a
        decode-capable replica. A failed export degrades to a key-less
        handoff — the decode side re-prefills cold, never a fault of
        the request (the PR 13 verified-miss contract)."""
        keys = self._slot_hash_keys[slot]
        t0 = time.perf_counter()
        exported = 0
        try:
            if self.tracer is not None:
                with self.tracer.bind(r.uid):
                    exported = self.engine.export_handoff(
                        slot, r.uid, r.prompt, keys=keys)
            else:
                exported = self.engine.export_handoff(
                    slot, r.uid, r.prompt, keys=keys)
        except Exception as e:  # noqa: BLE001 — containment edge
            self._count_transient()
            _logger.warning(
                "handoff export for request %d failed (%s: %s) — the "
                "decode side will re-prefill", r.uid,
                type(e).__name__, e)
        if self.tracer is not None:
            self.tracer.event(r.uid, "handoff_export", t0=t0,
                              dur=time.perf_counter() - t0, slot=slot,
                              exported_tokens=exported)
        self._reset_transient(r)
        r._not_before = None
        self._free_slot(slot)
        self._handoffs.append((r, r.uid if exported else None, keys))
        if self.registry is not None:
            self.registry.counter_inc("serving.disagg.handoffs")

    def take_handoffs(self) -> List[tuple]:
        """Pop every ``(request, arena_key_or_None, block_keys)``
        hand-over whose arena record is READY — its async swap-out has
        left the worker's pending set, so an importer's ``take`` can
        never race the CRC completion — or which never got a record
        (the cold handoff: short prompt, declined arena, failed
        export). Still-in-flight records stay for a later call."""
        if not self._handoffs:
            return []
        tier = getattr(self.engine, "host_tier", None)
        pending = set(tier.pending_keys()) if tier is not None \
            else set()
        ready = [h for h in self._handoffs
                 if h[1] is None or h[1] not in pending]
        if ready:
            self._handoffs = [h for h in self._handoffs
                              if h[1] is not None and h[1] in pending]
        return ready

    def note_handoff(self, uid: int, key: int) -> None:
        """Router seam (decode side): record that ``uid`` arrives with
        an arena handoff record under ``key``. Admission resolves it —
        zero re-prefill on the happy path, the VERIFIED-MISS re-prefill
        otherwise — and the resolution is counted and traced there."""
        self._handoff_uids[int(uid)] = int(key)

    def _register_prefix(self, r: Request, slot: int) -> None:
        """Write path, at prompt-ingestion completion: retain the
        prompt's block-aligned K/V prefix (now fully resident in
        ``slot``): share the slot's pages into a cache entry —
        zero copies, zero new pages (capacity pressure is the admission
        gate's job)."""
        pcache = self.engine.prefix_cache
        before = pcache.evictions
        keys = self._slot_hash_keys[slot]
        # the ingest stream, not r.prompt: a resumed request ingested
        # prompt+committed-outputs, and that is the prefix now resident
        # in the slot (keys are None on resume — stored hashes covered
        # the prompt only — so the cache re-hashes inline)
        seq = self._ingest(r)
        outcome = self.engine.retain_prefix(slot, seq, keys=keys)
        if self.registry is not None:
            evicted = pcache.evictions - before
            if evicted:
                self.registry.counter_inc("serving.prefix.evictions",
                                          evicted)
            if outcome == "registered":
                self.registry.counter_inc("serving.prefix.registrations")
        if pcache.evictions != before:
            # evictions release entry page refcounts: reconcile on the
            # policy's sampling cadence
            self.auditor.maybe_audit(self.engine)

    # ---------------------------------------------------------- speculative
    def _spec_tick(self, tick: int):
        """The draft → verify half of a speculative heartbeat: for each
        greedy decoding slot, prompt-lookup a draft over ``prompt +
        generated``; every slot that drafted something (and is within
        budget) then shares ONE compiled ``[slots, K+1]`` batched
        verify call (:meth:`Engine.verify_batch` — B verify-eligible
        slots per program invocation instead of B sequential calls),
        each emitting its accepted prefix plus the bonus token. Returns
        ``(verified_slots, slot_steps, emitted)``: slots that took a
        verify step this tick (excluded from the decode batch — they
        already advanced), per-SLOT verify sequence-steps run, and
        tokens emitted. Containment-wrapped exactly like chunk/decode:
        a transient failure during the shared call quarantines the
        slots that were IN it (the decode batch and prefilling slots
        never see it); a per-row non-finite verdict quarantines only
        that row's request. Slots that draft nothing, sampled requests,
        and requests within ``draft_len`` tokens of their budget (the
        padded verify window must stay inside the admission page
        reservation and ``max_len``) fall through to plain decode."""
        eng = self.engine
        cfg = eng.spec
        verified: set = set()
        calls = emitted = 0
        pending = []            # (slot, request, draft, offset)
        for slot, r in enumerate(self._running):
            if r is None or r.status != "running":
                continue
            if r.temperature != 0.0:
                continue    # acceptance verifies against argmax only
            owed = r.max_new_tokens - len(r.output_tokens)
            # the slot's committed length: everything but the pending
            # last token (which the verify step writes, like decode)
            offset = len(r.prompt) + len(r.output_tokens) - 1
            # endgame gate: require draft_len < owed, so a fully
            # accepted verify's n_accepted + 1 <= K + 1 <= owed tokens
            # ALL emit — emission never truncates, which keeps the
            # engine's tokens_generated, the bench's per-slot-step
            # arithmetic, and the padded window's page reservation all
            # exact. The last <= K tokens take plain decode.
            if cfg.draft_len >= owed \
                    or offset + cfg.draft_len + 1 > eng.max_len:
                continue
            draft = self._take_draft(r)
            if not draft:
                continue    # nothing to verify: plain-decode fallback
            pending.append((slot, r, draft, offset))
        if not pending:
            return verified, calls, emitted
        t0v = self.tracer.now() if self.tracer is not None else 0.0
        try:
            if self.fault_plan is not None:
                # the exception site raises INSTEAD of the call, so it
                # must fire before the nonfinite spec is consumed — a
                # co-scheduled nonfinite stays live for the retry
                # instead of being counted as delivered to a call that
                # never ran
                self.fault_plan.maybe_raise("verify", tick)
            bias = np.zeros(eng.slots, np.float32)
            if self.fault_plan is not None:
                for slot, _r, _d, _o in pending:
                    taken = self.fault_plan.take_nonfinite(tick, slot)
                    if taken is not None:
                        bias[slot] = taken
            # offsets= cross-checks our bookkeeping against the
            # engine's committed lengths — drift raises loudly instead
            # of silently diverging tokens (the old per-slot path's
            # guarantee, kept through the batching)
            toks, n_acc = eng.verify_batch(
                {slot: (int(self._last_tokens[slot]), draft)
                 for slot, _r, draft, _o in pending},
                fault_bias=bias,
                offsets={slot: off for slot, _r, _d, off in pending})
        except ValueError:
            # verify_batch's ValueErrors are all pre-mutation
            # validation (slot range, draft length, the offsets
            # cross-check): deterministic scheduler-vs-engine contract
            # bugs, not runtime faults — propagate loudly instead of
            # quarantining N-1 healthy batchmates over untouched
            # engine state
            raise
        except Exception as e:  # noqa: BLE001 — containment edge
            # the shared call produced no tokens: every slot that was
            # in it absorbs one retry (they share the blast radius the
            # way the decode batch shares a decode-site fault); the
            # decode batch and prefilling slots keep their progress
            self._count_transient()
            desc = f"{type(e).__name__}: {e}"
            for slot, r, _d, _o in pending:
                self._quarantine(r, slot, desc)
            return verified, calls, emitted
        # ONE batched readback per verify dispatch (the engine already
        # forces exactly once; these are host views) — the emission
        # loop below walks python ints, never per-element device reads
        toks = np.asarray(toks)
        n_acc = np.asarray(n_acc, np.int32)
        finite = eng.last_verify_finite_slots
        durv = self.tracer.now() - t0v if self.tracer is not None \
            else 0.0
        for slot, r, draft, offset in pending:
            if not finite[slot]:
                # the in-program guard flagged this row's logits: every
                # returned token is garbage — quarantine the request
                # (slot, pages, reservation freed); batchmates and the
                # decode batch never see it. Acceptance stats are NOT
                # recorded: n_accepted was argmaxed over NaN/Inf rows
                # and would pollute the acceptance histograms the
                # bench's p50/p99 read
                self._quarantine(r, slot, "non-finite verify logits")
                continue
            m = int(n_acc[slot])
            calls += 1
            r.spec_drafted += len(draft)
            r.spec_accepted += m
            if self.registry is not None:
                self.registry.counter_inc("serving.spec.drafted",
                                          len(draft))
                self.registry.counter_inc("serving.spec.accepted", m)
                self.registry.observe("serving.spec.acceptance_rate",
                                      m / len(draft))
            if self.tracer is not None:
                # one shared compiled call: every surviving row's span
                # covers the same interval, annotated per-slot
                self.tracer.event(r.uid, "verify", t0=t0v, dur=durv,
                                  slot=slot, drafted=len(draft),
                                  accepted=m)
            verified.add(slot)
            # emit the accepted prefix + bonus token through the SAME
            # per-token finish checks plain decode applies (EOS first,
            # then budget, then cache exhaustion) — the emitted stream
            # is the greedy stream, discovered several tokens per step
            # (m + 1 <= owed by the endgame gate: nothing truncates)
            for i, tok in enumerate(toks[slot, :m + 1].tolist()):
                r.output_tokens.append(tok)
                self._last_tokens[slot] = tok
                emitted += 1
                if self.eos_id is not None and tok == self.eos_id:
                    self._finish(r, "eos", slot)
                    break
                if len(r.output_tokens) >= r.max_new_tokens:
                    self._finish(r, "max_new_tokens", slot)
                    break
                if offset + i + 2 > eng.max_len:
                    # the cache position this token's successor would
                    # write at is past max_len — same check, same
                    # reason string as the decode loop
                    self._finish(r, "max_len", slot)
                    break
            else:
                # slot still running: its outputs are settled until the
                # next reconcile, so start the NEXT draft on the worker
                # now — it computes while this beat's decode dispatch
                # executes on the device
                self._presubmit_draft(r)
        return verified, calls, emitted

    def _draft_key(self, r: Request):
        """A draft job's identity: the request AND its settled output
        length — a stale precomputed draft (the slot emitted again, or
        a quarantine requeued the request) can never be taken, only
        aged out."""
        return ("draft", r.uid, len(r.output_tokens))

    def _take_draft(self, r: Request) -> list:
        """The slot's n-gram draft: the worker's precomputed result
        when one is ready (pipelined mode), else computed inline —
        byte-identical either way (``draft_tokens`` is pure)."""
        cfg = self.engine.spec
        toks = list(r.prompt) + list(r.output_tokens)
        fn = self._draft_fn(r.uid, toks, cfg)
        if self._worker is None:
            return fn()
        return self._worker.take(self._draft_key(r), fn)

    def _draft_fn(self, uid, toks, cfg):
        """The draft job closure. With a tracer attached it self-times
        and emits a ``draft`` span FROM INSIDE the closure, so the span
        lands on whichever thread actually ran the computation (the
        ``serving-draft-worker`` daemon in pipelined mode, the
        heartbeat thread inline) — honest cross-thread attribution."""
        tr = self.tracer
        if tr is None:
            return lambda: draft_tokens(toks, cfg)

        def job():
            t0 = tr.now()
            d = draft_tokens(toks, cfg)
            tr.event(uid, "draft", t0=t0, dur=tr.now() - t0,
                     drafted=len(d))
            return d
        return job

    def _presubmit_draft(self, r: Request) -> None:
        """Queue the request's next draft on the worker thread (no-op
        without one). Closes over a SNAPSHOT of prompt + outputs, so a
        concurrent host append cannot skew the computation — the key
        pins the length the snapshot was taken at."""
        if self._worker is None or r.temperature != 0.0:
            return
        cfg = self.engine.spec
        if cfg is None:
            return
        toks = list(r.prompt) + list(r.output_tokens)
        self._worker.submit(self._draft_key(r),
                            self._draft_fn(r.uid, toks, cfg))

    # ------------------------------------------------------------- stepping
    def step(self) -> bool:
        """One scheduler beat: expire → admit → chunk prefill → decode
        (``pipeline_depth >= 1``: dispatch-ahead decode with deferred
        readback — see the module docstring), every engine call
        containment-wrapped (see the fault-isolation contract), timed
        against the fault policy's watchdog budget. Returns True if any
        forward progress was made (a decode step ran or reconciled, a
        verify emitted, or a prefill chunk was ingested).

        Every beat's wall time is split into HOST-THINK vs DEVICE-WAIT
        (differencing the engine's :attr:`~apex_tpu.serving.Engine
        .device_wait_s` around the body): the ``serving.heartbeat
        .host_s`` / ``device_wait_s`` histograms and the
        ``serving.heartbeat.duty_cycle`` gauge (device-wait fraction of
        the beat). The watchdog budgets the HOST portion — a beat that
        spends its wall blocked on healthy device execution is the
        steady state, not a stall; a beat whose host think-time blows
        the budget is (under pipelining the whole point is that
        device-wait stops inflating beat wall, so budgeting wall would
        re-conflate the two)."""
        tick = self._tick
        self._tick += 1
        eng = self.engine
        dw0 = getattr(eng, "device_wait_s", 0.0)
        launch0 = getattr(eng, "launch_s", 0.0)
        read0 = getattr(eng, "readback_s", 0.0)
        compiled0 = getattr(eng, "compiled_programs", 0)
        # requests riding this beat, snapshotted BEFORE the body so
        # finish/quarantine churn inside it cannot drop participants
        # (None when tracing is off — no allocation on the hot path)
        uids0 = [r.uid for r in self._running if r is not None] \
            if self.tracer is not None else None
        beat = tracing.phase("serve.beat", tick=tick)
        try:
            with beat:
                if self.fault_plan is not None:
                    # injected heartbeat stall (the watchdog-breach
                    # probe)
                    self.fault_plan.maybe_stall(tick)
                    tier = getattr(eng, "host_tier", None)
                    if tier is not None:
                        # injected host-arena bit rot (the
                        # swap_corruption kind): the NEXT swap-in of
                        # the victim entry must fail its checksum and
                        # degrade to a verified miss
                        self.fault_plan.maybe_corrupt_swap(tick, tier)
                        # injected handoff bit rot (the
                        # handoff_corruption kind): victimizes
                        # uid-keyed handoff records only, so the next
                        # IMPORT's CRC fails and degrades to the
                        # verified-miss re-prefill on the decode side —
                        # never a wrong token
                        self.fault_plan.maybe_corrupt_handoff(tick,
                                                              tier)
                if self.pipeline_depth > 0:
                    return self._step_body_pipelined(tick)
                return self._step_body(tick)
        finally:
            t_tick, elapsed = beat.t0, beat.t1 - beat.t0
            dwait = max(0.0, getattr(eng, "device_wait_s", 0.0) - dw0)
            host_s = max(elapsed - dwait, 0.0)
            if self.tracer is not None and uids0:
                # one heartbeat span per request that rode this beat,
                # carrying the PR 11 host-think vs device-wait split —
                # attribution rides the EXISTING accounting, no new
                # forced reads
                for uid in uids0:
                    self.tracer.event(uid, "heartbeat", t0=t_tick,
                                      dur=elapsed, tick=tick,
                                      host_s=host_s,
                                      device_wait_s=dwait)
            if self.registry is not None:
                self.registry.observe("serving.heartbeat.host_s",
                                      host_s)
                self.registry.observe("serving.heartbeat.device_wait_s",
                                      dwait)
                # the device-wait's two ends, where the engine keeps
                # them apart: the compiled calls and the forced reads
                self.registry.observe(
                    "serving.heartbeat.launch_s",
                    getattr(eng, "launch_s", 0.0) - launch0)
                self.registry.observe(
                    "serving.heartbeat.readback_s",
                    getattr(eng, "readback_s", 0.0) - read0)
                if elapsed > 0:
                    self.registry.gauge_set(
                        "serving.heartbeat.duty_cycle", dwait / elapsed)
            if self.fault_policy.watchdog_budget_s is not None:
                if getattr(self.engine, "compiled_programs", 0) \
                        > compiled0:
                    # warm-start exemption: this heartbeat TRACED a
                    # compiled program, so its wall time is dominated
                    # by one-off compile latency, not a stall — tiny
                    # watchdog budgets must not false-trip on first
                    # contact (a dispatch-ahead beat traces at DISPATCH
                    # time, so the exemption lands on the right beat
                    # under pipelining too). Accounted separately so
                    # the compile cost stays visible instead of
                    # vanishing.
                    if self.registry is not None:
                        self.registry.observe(
                            "serving.watchdog.warmup_s", elapsed)
                elif host_s > self.fault_policy.watchdog_budget_s:
                    self._on_watchdog_breach(tick, host_s, beat)

    def _on_watchdog_breach(self, tick: int, host_s: float,
                            beat=None) -> None:
        """A heartbeat blew its HOST-portion budget (beat wall minus
        time blocked on device results — injected stalls, runaway
        drafting and slow bookkeeping all land here; healthy device
        execution does not): count the ``serving.watchdog.stall``
        event, record the breach duration, and hand it to the policy's
        ``on_stall`` callback (alerting / shedding is the caller's
        choice — the scheduler itself keeps beating)."""
        if self.registry is not None:
            self.registry.counter_inc("serving.watchdog.stall")
            self.registry.observe("serving.watchdog.stall_s", host_s)
        _logger.warning("heartbeat %d stalled: %.3fs of host time "
                        "against a %.3fs watchdog budget; largest "
                        "phases: %s", tick, host_s,
                        self.fault_policy.watchdog_budget_s,
                        self._largest_phases(beat))
        if self.fault_policy.on_stall is not None:
            self.fault_policy.on_stall(host_s)

    @staticmethod
    def _largest_phases(beat, top: int = 3) -> str:
        """The ``top`` phases of ``beat`` by self time, from the flight
        recorder (:data:`~apex_tpu.telemetry.tracing.phases`): what the
        breach line names instead of one number."""
        if beat is None or beat.id is None:
            return "not recorded"
        recs = [r for r in tracing.phases.records(since=beat.t0)
                if r.root == beat.id]
        own = tracing.phases.self_times(recs)
        by_name: Dict[str, float] = {}
        for r in recs:
            by_name[r.name] = by_name.get(r.name, 0.0) + own[r.id]
        return ", ".join(f"{n} {t * 1e3:.1f} ms" for n, t in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:top])

    def _step_body(self, tick: int) -> bool:
        with tracing.phase("serve.expire"):
            self._expire(time.perf_counter())
        with tracing.phase("serve.admit"):
            self._admit()
        with tracing.phase("serve.chunk") as ph:
            chunks = self._prefill_tick(tick)
            # the chunk budget bounds the stall imposed ON in-flight
            # decodes; while nothing is decoding there is nothing to
            # stall, so keep ingesting back-to-back (cold-start/queue-
            # drain bursts reach full slot occupancy without idle
            # heartbeats)
            while chunks and not any(
                    r is not None and r.status == "running"
                    for r in self._running):
                more = self._prefill_tick(tick)
                if not more:
                    break
                chunks += more
            ph.note(n=chunks)
        self.beats_total += 1
        if chunks:
            self.beats_with_prefill += 1
        if self.role == "prefill":
            # prefill replicas never decode: the beat is expire →
            # admit → ingest → export; finished ingestions sit in
            # _handoffs until the router collects them
            return chunks > 0
        spec_slots: set = set()
        spec_calls = spec_emitted = 0
        if self.speculative:
            # draft → verify-or-decode: verified slots already advanced
            # (possibly by several tokens) and sit out this tick's
            # decode batch; empty drafts fall through to plain decode
            with tracing.phase("serve.spec"):
                spec_slots, spec_calls, spec_emitted = \
                    self._spec_tick(tick)
        with tracing.phase("serve.decode"):
            active = np.array([r is not None and r.status == "running"
                               and slot not in spec_slots
                               for slot, r in enumerate(self._running)])
            self._emit_beat_gauges(active)
            if not active.any():
                self._set_spec_gauge(spec_calls, spec_emitted, 0, 0)
                return chunks > 0 or spec_calls > 0
            bias = None
            if self.fault_plan is not None:
                bias = self.fault_plan.decode_bias(tick, self.engine.slots)
            t0 = time.perf_counter()
            try:
                if self.fault_plan is not None:
                    self.fault_plan.maybe_raise("decode", tick)
                tokens = self.engine.decode_step(self._last_tokens, active,
                                                 self._temps,
                                                 fault_bias=bias)
            except Exception as e:  # noqa: BLE001 — containment edge
                # a failed decode call produced no tokens (injected faults
                # raise INSTEAD of the call; a real mid-call failure left
                # the host token state unconsumed either way): quarantine
                # the attributed victim when the exception names one, else
                # every running request absorbs one retry — the engine
                # survives and the next beat retries the survivors
                self._count_transient()
                victim = getattr(e, "slot", -1)
                desc = f"{type(e).__name__}: {e}"
                # honor the attribution only if the victim was actually in
                # the decode batch; otherwise charge the decoding requests
                # — prefilling slots (and slots that already took a verify
                # step this tick) were not in the failed call and keep
                # their progress either way
                if 0 <= victim < self.engine.slots \
                        and victim not in spec_slots \
                        and self._running[victim] is not None \
                        and self._running[victim].status == "running":
                    self._quarantine(self._running[victim], victim, desc)
                else:
                    for slot, r in enumerate(self._running):
                        if r is not None and r.status == "running" \
                                and slot not in spec_slots:
                            self._quarantine(r, slot, desc)
                return True
        dt = time.perf_counter() - t0
        self._step_s_ema = dt if self._step_s_ema is None \
            else 0.8 * self._step_s_ema + 0.2 * dt
        with tracing.phase("serve.emit") as ph:
            finite = self.engine.last_decode_finite
            lengths = self.engine.lengths()
            decode_emitted = 0
            for slot, r in enumerate(self._running):
                if r is None or r.status != "running" or slot in spec_slots:
                    continue
                if not finite[slot]:
                    # the in-program guard flagged this slot's logits:
                    # its sampled token is garbage — quarantine the slot's
                    # request; batchmates' tokens are untouched (the guard
                    # and the bias are per-slot, the program is shared)
                    self._quarantine(r, slot, "non-finite decode logits")
                    continue
                token = int(tokens[slot])
                r.output_tokens.append(token)
                self._last_tokens[slot] = token
                decode_emitted += 1
                if self.eos_id is not None and token == self.eos_id:
                    self._finish(r, "eos", slot)
                elif len(r.output_tokens) >= r.max_new_tokens:
                    self._finish(r, "max_new_tokens", slot)
                elif int(lengths[slot]) >= self.engine.max_len:
                    # cache exhausted: the NEXT token would have nowhere to
                    # attend from
                    self._finish(r, "max_len", slot)
            ph.note(tokens=decode_emitted)
        self._set_spec_gauge(spec_calls, spec_emitted, 1, decode_emitted)
        return True

    def _emit_beat_gauges(self, active: np.ndarray) -> None:
        """Per-beat occupancy / padding-waste / page-pool gauges over
        the decode batch's dispatch mask (shared by the sync and
        pipelined beats)."""
        if self.registry is None:
            return
        occ = float(active.mean())
        self.registry.gauge_set("serving.slot_occupancy", occ)
        self.registry.observe("serving.slot_occupancy", occ)
        self.registry.observe("serving.padding_waste", 1.0 - occ)
        # the paged pool's per-step health: HBM pressure
        # (pages_in_use/free), sharing efficiency (cow_shares —
        # pages serving >1 reader for one page of HBM) and
        # internal fragmentation (allocated-but-invalid slack)
        ps = self.engine.pool_stats()
        self.registry.gauge_set("serving.pool.pages_in_use",
                                float(ps["pages_in_use"]))
        self.registry.gauge_set("serving.pool.pages_free",
                                float(ps["pages_free"]))
        self.registry.gauge_set("serving.pool.cow_shares",
                                float(ps["cow_shares"]))
        self.registry.gauge_set("serving.pool.fragmentation",
                                float(ps["fragmentation"]))

    # ------------------------------------------- the pipelined heartbeat
    def _step_body_pipelined(self, tick: int) -> bool:
        """One dispatch-ahead beat (``pipeline_depth >= 1``): expire →
        admit → chunk prefill → [speculative: reconcile-all → draft →
        verify] → DISPATCH decode t+1 → RECONCILE step t (keeping at
        most ``pipeline_depth`` steps in flight). The decode dispatched
        here executes on the device while the NEXT beat's host work —
        expiry, admission, chunk bookkeeping, worker-thread drafting,
        telemetry — runs; the emitted greedy stream is bitwise the sync
        path's because every token still flows through the same
        compiled programs and the same per-token finish checks, just
        read back one batched transfer later."""
        with tracing.phase("serve.expire"):
            self._expire(time.perf_counter())
        with tracing.phase("serve.admit"):
            self._admit()
        with tracing.phase("serve.chunk") as ph:
            chunks = self._prefill_tick(tick)
            # cold-queue burst (same contract as the sync beat): only
            # while nothing is decoding AND nothing is in flight - a
            # dispatched FINAL chunk counts as decoding (its slot flips
            # the moment the chunk is read, at the next beat's top;
            # the sync beat stops bursting at that same chunk)
            while chunks and not self._pipeline \
                    and not any(r is not None and r.status == "running"
                                for r in self._running) \
                    and not any(e is not None and e.pending.final
                                for e in self._pending_prefill):
                more = self._prefill_tick(tick)
                if not more:
                    break
                chunks += more
            ph.note(n=chunks)
        self.beats_total += 1
        if chunks:
            self.beats_with_prefill += 1
        if self.role == "prefill":
            # prefill replicas never decode (dispatch-ahead applies to
            # their CHUNKS instead — _prefill_tick's reconcile-then-
            # dispatch split keeps one chunk per slot in flight)
            return chunks > 0
        spec_slots: set = set()
        spec_calls = spec_emitted = 0
        reconciled = 0
        if self.speculative:
            # drafting and the verify program need settled outputs:
            # retire everything in flight first (those flights already
            # overlapped this beat's expire/admit/chunk work), then
            # draft → verify-or-decode exactly like the sync beat
            reconciled += self._reconcile_all()
            with tracing.phase("serve.spec"):
                spec_slots, spec_calls, spec_emitted = \
                    self._spec_tick(tick)
        with tracing.phase("serve.decode") as ph:
            ph.note(inflight=len(self._pipeline))
            active = self._dispatch_decode(tick, spec_slots)
        self._emit_beat_gauges(active if active is not None
                               else np.zeros(self.engine.slots, bool))
        while len(self._pipeline) > self.pipeline_depth:
            reconciled += self._reconcile_oldest()
        drained = False
        if active is None and self._pipeline:
            # nothing newly dispatched: drain the pipeline rather than
            # strand finished device work (endgame/idle beats) — and
            # count the drain as progress even when every retired step
            # was a discard (an all-discard drain still moved state)
            drained = True
            reconciled += self._reconcile_all()
        self._set_spec_gauge(spec_calls, spec_emitted, 1, reconciled)
        return (chunks > 0 or spec_calls > 0 or active is not None
                or reconciled > 0 or drained)

    def _dispatch_decode(self, tick: int,
                         spec_slots) -> Optional[np.ndarray]:
        """DISPATCH-AHEAD REGION: issue one decode step against the
        speculated schedule — every running slot presumed to continue,
        EXCEPT past host-known finality (token budget / ``max_len``
        exhaustion counting the tokens already in flight — pure
        arithmetic, so only EOS is ever mispredicted). Returns the
        dispatch mask when a step went in flight (or a contained
        dispatch fault quarantined its batch), None when there was
        nothing to dispatch.

        Nothing between here and :meth:`_reconcile_oldest` may force a
        device value to host: no ``int()`` / ``float()`` /
        ``np.asarray`` on engine results (the foot-gun this refactor
        exists to remove — statically linted by
        ``tests/L0/test_serving_metrics_lint.py``)."""
        eng = self.engine
        inflight: collections.Counter = collections.Counter()
        for rec in self._pipeline:
            for slot, uid in rec.uids.items():
                r = self._running[slot]
                if r is not None and r.uid == uid:
                    inflight[slot] += 1
        uids: Dict[int, int] = {}
        active = np.zeros(eng.slots, bool)
        for slot, r in enumerate(self._running):
            if r is None or r.status != "running" or slot in spec_slots:
                continue
            n_have = len(r.output_tokens) + inflight[slot]
            if n_have >= r.max_new_tokens:
                continue    # host-known finality: never dispatch past it
            if len(r.prompt) + n_have - 1 >= eng.max_len:
                continue    # cache exhausted once the flights land
            active[slot] = True
            uids[slot] = r.uid
        if not uids:
            return None
        bias = None
        if self.fault_plan is not None:
            bias = self.fault_plan.decode_bias(tick, eng.slots)
        last_tokens, after = self._pipeline_last_tokens(active)
        try:
            if self.fault_plan is not None:
                self.fault_plan.maybe_raise("decode", tick)
            pending = eng.decode_dispatch(
                last_tokens, active, self._temps, fault_bias=bias,
                after=after)
        except Exception as e:  # noqa: BLE001 — containment edge
            # the dispatch produced no step (injected faults raise
            # INSTEAD of the call): same blast radius as the sync
            # decode site — the attributed victim, else every request
            # in the would-be batch; in-flight steps for quarantined
            # slots discard at their reconcile by uid mismatch
            self._count_transient()
            victim = getattr(e, "slot", -1)
            desc = f"{type(e).__name__}: {e}"
            if victim in uids:
                self._quarantine(self._running[victim], victim, desc)
            else:
                for slot in sorted(uids):
                    r = self._running[slot]
                    if r is not None and r.uid == uids[slot]:
                        self._quarantine(r, slot, desc)
            return active
        if self._pipeline and self.registry is not None:
            # the engagement share's numerator (over
            # serving.decode.steps): this step went to the device while
            # an earlier one was still un-read
            self.registry.counter_inc("serving.heartbeat.dispatched_ahead")
        self._pipeline.append(_InflightStep(pending=pending, uids=uids,
                                            tick=tick))
        return active

    def _pipeline_last_tokens(self, active: np.ndarray):
        """The dispatch's ``(last_tokens, after)`` operands: host values
        for settled slots, and -1 for every slot whose latest token is
        still on the device in the NEWEST in-flight step ``after`` —
        the decode program itself takes those rows from ``after``'s
        un-forced tokens (:meth:`Engine.decode_dispatch`), so the data
        dependency chains decode t+1 onto t without the host ever
        reading a token and without a launch of its own
        (dispatch-ahead region: linted force-free). A copy: reconcile
        writes ``_last_tokens`` while the step may not have read its
        operand yet."""
        host = self._last_tokens.copy()
        if not self._pipeline:
            return host, None
        newest = self._pipeline[-1]
        chained = [slot for slot, uid in newest.uids.items()
                   if active[slot] and self._running[slot] is not None
                   and self._running[slot].uid == uid]
        if not chained:
            return host, None
        host[chained] = -1
        return host, newest.pending

    def _reconcile_oldest(self) -> int:
        """RECONCILE the oldest in-flight decode step: ONE batched
        token readback (never per-slot ``int()`` against device
        arrays), emission through the same per-token finish checks as
        the sync path, and the speculated-finality rollback — a slot
        whose request finished, quarantined or expired while the step
        was in flight had its entry dropped by ``_free_slot`` already
        (counted as ``serving.heartbeat.discarded``); the uid+status
        check here is belt-and-braces. Returns tokens emitted."""
        rec = self._pipeline.popleft()
        eng = self.engine
        valid = np.zeros(eng.slots, bool)
        for slot, uid in rec.uids.items():
            r = self._running[slot]
            if r is not None and r.uid == uid \
                    and r.status == "running":
                valid[slot] = True
        try:
            with tracing.phase("serve.decode"):
                tokens, finite, dt = eng.decode_reconcile(rec.pending,
                                                          valid=valid)
        except Exception as e:  # noqa: BLE001 — containment edge
            # a dispatched-ahead step can fail at its DEFERRED force:
            # async backends surface runtime errors at the first read,
            # not at dispatch (the CPU backend's donated-call
            # synchronous execution hides this — errors land at the
            # wrapped dispatch site there). Same blast radius as a
            # sync decode-site fault: the attributed victim, else
            # every request the step computed for; quarantining frees
            # their slots, which drops their entries from any younger
            # in-flight records (_free_slot's eager invalidation)
            self._count_transient()
            victim = getattr(e, "slot", -1)
            desc = f"{type(e).__name__}: {e}"
            if 0 <= victim < eng.slots and valid[victim]:
                self._quarantine(self._running[victim], victim, desc)
            else:
                for slot in sorted(rec.uids):
                    if valid[slot]:
                        self._quarantine(self._running[slot], slot,
                                         desc)
            return 0
        self._step_s_ema = dt if self._step_s_ema is None \
            else 0.8 * self._step_s_ema + 0.2 * dt
        with tracing.phase("serve.emit") as ph:
            emitted = discarded = 0
            for slot in sorted(rec.uids):
                if not valid[slot]:
                    discarded += 1
                    continue
                r = self._running[slot]
                if not finite[slot]:
                    # the in-program guard flagged this slot's logits (same
                    # quarantine as the sync beat); any younger in-flight
                    # step for it discards at ITS reconcile by uid mismatch
                    self._quarantine(r, slot, "non-finite decode logits")
                    continue
                token = int(tokens[slot])
                r.output_tokens.append(token)
                self._last_tokens[slot] = token
                emitted += 1
                if self.eos_id is not None and token == self.eos_id:
                    self._finish(r, "eos", slot)
                elif len(r.output_tokens) >= r.max_new_tokens:
                    self._finish(r, "max_new_tokens", slot)
                elif len(r.prompt) + len(r.output_tokens) - 1 \
                        >= eng.max_len:
                    # committed length (prompt + outputs - 1) reached the
                    # cache — the same condition the sync beat reads back
                    # from engine.lengths(), computed host-side here so
                    # reconcile forces nothing beyond the token readback
                    self._finish(r, "max_len", slot)
                elif self.speculative:
                    # outputs settled until the next reconcile: start the
                    # next draft on the worker now, overlapping the device
                    self._presubmit_draft(r)
            ph.note(tokens=emitted)
        if discarded and self.registry is not None:
            self.registry.counter_inc("serving.heartbeat.discarded",
                                      discarded)
        return emitted

    def _reconcile_all(self) -> int:
        """Retire every in-flight step, oldest first (the speculative
        beat's settle point and the endgame drain)."""
        emitted = 0
        while self._pipeline:
            emitted += self._reconcile_oldest()
        return emitted

    def _set_spec_gauge(self, spec_calls: int, spec_emitted: int,
                        decode_steps: int, decode_emitted: int) -> None:
        """The headline speculative gauge: tokens emitted this
        heartbeat per SLOT sequence-step run — a decode step advances
        each participating slot by exactly one (so plain decode pins
        the gauge at 1.0), a verify call is one slot-step that emits
        ``n_accepted + 1``; acceptance is the only thing that pushes
        the reading above 1. Only emitted on speculative runs."""
        del decode_steps            # a slot-step count, not a dispatch count
        if not self.speculative or self.registry is None:
            return
        steps = spec_calls + decode_emitted
        if steps:
            self.registry.gauge_set(
                "serving.spec.tokens_per_step",
                (spec_emitted + decode_emitted) / steps)

    @property
    def pending(self) -> int:
        """Queued + running request count, plus one while any
        dispatched-ahead decode step is still awaiting reconcile (the
        drain target: ``step()`` until 0 leaves nothing behind — not
        even in-flight device work, so the LAST request's EOS cannot
        strand its speculated successors un-discarded)."""
        n = len(self._queue) + sum(r is not None
                                   for r in self._running) \
            + len(self._handoffs)
        if self._pipeline:
            n += 1
        return n

    # ----------------------------------------------------- router seams
    def load_snapshot(self) -> dict:
        """One HOST-ONLY load reading for this scheduler+engine pair —
        the :class:`~apex_tpu.serving.Router`'s least-loaded admission
        signal, taken per routed request. Everything here is host
        bookkeeping (queue/slot walks, the paged allocator's free
        count, the host arena's byte ledger); nothing forces a device
        value, so probing N replicas per submit costs microseconds,
        not syncs. ``host_bytes_free`` is None without a hierarchical-KV
        host tier — when present it is the swap arena's remaining
        headroom, so the router's least-loaded tie-break sees arena
        pressure (a replica about to shed swapped prefixes), not just
        device pages.

        Two SLO-aware fields (both None when ``slo`` is off, so the
        pre-SLO snapshot shape is a strict subset):

        - ``oldest_deadline_s``: seconds until the TIGHTEST live
          deadline (queued or running), negative once blown, None when
          no live request carries one. Reported RELATIVE because
          ``perf_counter`` bases do not cross processes — the fleet
          controller compares urgency, not wall clocks.
        - ``preemptible_pages``: pages held by RUNNING requests whose
          effective priority is strictly below the config's top class
          AND whose committed stream still fits the prefill re-ingest
          window (a decode past ``prefill_len`` is no longer exactly
          resumable, so it is never a victim) — the headroom a
          top-class arrival could reclaim by preemption.
        """
        busy = sum(r is not None for r in self._running)
        tier = getattr(self.engine, "host_tier", None)
        oldest = None
        preemptible = None
        if self.slo is not None:
            now = time.perf_counter()
            live = [r for r in self._running if r is not None]
            live.extend(self._queue)
            for r in live:
                if r.deadline_s is None or r._t_submit is None:
                    continue
                rem = r._t_submit + r.deadline_s - now
                if oldest is None or rem < oldest:
                    oldest = rem
            top = self.slo.top_priority
            preemptible = 0
            for slot, r in enumerate(self._running):
                if r is None or r.status != RequestStatus.RUNNING:
                    continue
                if len(r.prompt) + len(r.output_tokens) \
                        > self.engine.prefill_len:
                    # mirrors _try_preempt: past the re-ingest
                    # window the slot is not exactly resumable
                    continue
                pri = r._eff_priority if r._eff_priority is not None \
                    else self.slo.base_priority(r)
                if pri < top:
                    preemptible += self.engine.slot_pages(slot)
        return {
            "queue_depth": len(self._queue),
            "queue_free": self.max_queue - len(self._queue),
            "slots": self.engine.slots,
            "slots_busy": busy,
            "slots_free": self.engine.slots - busy,
            "inflight_steps": len(self._pipeline),
            "pages_free": self.engine.pages_free,
            "host_bytes_free": None if tier is None
            else tier.capacity_bytes - tier.bytes_used,
            "oldest_deadline_s": oldest,
            "preemptible_pages": preemptible,
            # adapter affinity: the names resident in the device
            # arena (a bind is a hit, not a swap-in), None when LoRA
            # serving is off
            "resident_adapters": self.engine.resident_adapters()
            if getattr(self.engine, "lora", None) is not None else None,
        }

    def drain_requests(self) -> List[Request]:
        """Export every live request — running slots first (admission
        order), then the queue FIFO — rolled back to a servable queued
        state (:meth:`_reset_transient`: outputs cleared, paid-compute
        counters and the original submit clock kept, retry backoff
        cleared so survivors re-admit immediately), with every slot
        freed through the normal quarantine path: pages, reservations
        and prefix pins go back to the pool NOW and any dispatched-
        ahead steps are discarded, so a drained engine audits with
        zero leaked pages. This is the replica-death seam: the router
        calls it on a dead replica and requeues the result on
        survivors — a drain is NOT a fault of the requests, so
        ``retries`` is untouched. The scheduler itself stays
        constructed (its ``completed`` history and telemetry survive);
        pair with :meth:`close` to stop the worker thread."""
        drained: List[Request] = []
        for slot, r in enumerate(self._running):
            if r is None:
                continue
            self._free_slot(slot)   # pages + reservation + prefix pin
            self._reset_transient(r)
            r._not_before = None
            drained.append(r)
        # any in-flight dispatch-ahead steps lost their uids to
        # _free_slot above; drop the empty records (their device work
        # is never reconciled — the dead engine's results are garbage)
        self._pipeline.clear()
        # uncollected handoffs: nobody will ever import them — release
        # each one's cache entry and arena record (complete() tolerates
        # a record discarded mid-flight) and requeue the request
        tier = getattr(self.engine, "host_tier", None)
        for r, key, _keys in self._handoffs:
            if key is not None:
                if self.engine.prefix_cache.drop(key) \
                        and tier is not None:
                    tier.discard(key)
            self._reset_transient(r)
            r._not_before = None
            drained.append(r)
        self._handoffs = []
        # decode-side mirror: noted-but-not-yet-admitted imports also
        # orphan their entry + record when this replica drains (the
        # router re-routes the request through a fresh prefill)
        for key in self._handoff_uids.values():
            if self.engine.prefix_cache.drop(key) \
                    and tier is not None:
                tier.discard(key)
        self._handoff_uids.clear()
        while self._queue:
            r = self._queue.popleft()
            self._reset_transient(r)
            r._not_before = None
            drained.append(r)
        for r in drained:
            # the router re-routes (and re-probes) on a survivor: this
            # scheduler's stashed hash keys are dead weight
            self._presubmitted_keys.pop(r.uid, None)
        return drained

    def close(self) -> None:
        """Stop the scheduler's :class:`~apex_tpu.serving.DraftWorker`
        thread (no-op at ``pipeline_depth=0``; idempotent — the
        weakref finalizer registered at construction runs the same
        stop)."""
        if self._worker is not None:
            self._worker.stop()

    def _sleep_toward_backoff(self) -> None:
        """When nothing occupies a slot and everything queued is inside
        a retry-backoff window, sleep toward the earliest horizon
        (capped at 50 ms per wait) instead of burning CPU — and the
        caller's step budget — on no-op heartbeats."""
        if any(r is not None for r in self._running):
            return
        now = time.perf_counter()
        horizon = min((r._not_before for r in self._queue
                       if r._not_before is not None
                       and r._not_before > now), default=None)
        if horizon is not None:
            time.sleep(min(horizon - now, 0.05))

    # ---------------------------------------------------------------- runs
    def run(self, requests: Sequence[Request] = (),
            max_steps: int = 100000) -> List[Request]:
        """Submit ``requests`` (stepping through :class:`QueueFull`
        backpressure rather than surfacing it) and drain until every
        request finishes. Returns them in completion order and records
        the run's ``serving.tokens_per_s`` gauge."""
        t0 = time.perf_counter()
        tok0 = self.engine.tokens_generated
        done0 = len(self.completed)
        for r in requests:
            while True:
                try:
                    self.submit(r)
                    break
                except QueueFull:
                    # a step admits queued work into slots (and decodes),
                    # freeing queue capacity — backpressure absorbed here
                    if not self.step():
                        if not self._queue:
                            raise    # nothing active yet queue full
                        self._sleep_toward_backoff()
        steps = 0
        while self.pending and steps < max_steps:
            if not self.step():
                self._sleep_toward_backoff()
            steps += 1
        dt = time.perf_counter() - t0
        toks = self.engine.tokens_generated - tok0
        if self.registry is not None and dt > 0:
            self.registry.gauge_set("serving.tokens_per_s", toks / dt)
        _logger.info("served %d request(s): %d tokens in %.3fs "
                     "(%.1f tok/s)", len(self.completed) - done0, toks,
                     dt, toks / dt if dt > 0 else float("inf"))
        return self.completed[done0:]
