"""apex_tpu.serving — compiled KV-cache inference with continuous batching.

The training stack (amp cast policies, Pallas attention, telemetry)
stops at the optimizer step; this subsystem opens the inference
workload the north star calls for — serving a stream of variable-length
generation requests from a fixed set of compiled programs:

- :class:`PagedKVCache` + :class:`PagePool` (:mod:`.kv_cache`) — the
  cache layout: a dense ``[layers, num_pages, heads, head_dim,
  page_len]`` page pool plus a host-side allocator (free list, page
  refcounts, admission reservations). Requests own page lists, not
  rows: short prompts stop paying ``max_len`` HBM, freed pages return
  to the pool immediately, and prefix hits are copy-on-write page
  shares (refcount bump — zero data movement).
- :class:`Engine` (:mod:`.engine`) — exactly TWO XLA executables
  (jitted chunk-prefill + decode step, each gathering K/V through a
  ``[slots, max_pages]`` page-table operand; traced offset/length/
  temperature scalars); greedy / temperature /
  top-k sampling compiled in; attention through the ``decode.*``-tuned
  ``paged_*`` kernels of :mod:`apex_tpu.kernels.decode_attention` /
  :mod:`apex_tpu.kernels.prefill_attention`.
- :class:`PrefixCache` (:mod:`.prefix_cache`) — content-addressed
  prompt-prefix reuse: retained prefixes keyed by a rolling hash over
  ``chunk_len``-aligned token blocks. Entries record the page
  ids already holding the prefix (registration and hits are refcount
  bumps; LRU eviction under pool pressure only). A hit skips
  ``matched_len / chunk_len`` chunks of prefill compute, token-exact
  vs. the cold path.
- :class:`Scheduler` (:mod:`.scheduler`) — continuous batching with
  chunked prefill fused into the decode heartbeat: admit-into-free-slots,
  at most ``chunk_budget`` compiled chunk-prefill steps per tick (so
  in-flight decodes never wait more than one chunk for a new admit),
  EOS/max-token/timeout eviction, bounded-queue :class:`QueueFull`
  backpressure, opt-in prefix retention (``retain_prefixes=True``:
  consult-on-admit, register-on-prefill-completion), and slot-occupancy
  / padding-waste / decomposed-TTFT / chunks-per-prompt /
  ``serving.prefix.*`` / tokens-per-sec telemetry through the shared
  :class:`~apex_tpu.telemetry.MetricsRegistry`.

- :class:`SpecConfig` / :func:`draft_tokens` (:mod:`.speculative`) —
  speculative decoding fused into the heartbeat: a host-side
  prompt-lookup / n-gram drafter proposes up to K next tokens per
  greedy slot, ONE compiled ``[slots, K+1]`` BATCHED verify program
  (:meth:`Engine.verify_batch` — the chunk-append machinery at the
  draft shape; every verify-eligible slot shares one invocation per
  heartbeat) scores them all in a single step, and in-program
  accept-longest-prefix keeps greedy output bitwise identical to
  plain decode while lifting tokens-per-step above 1
  (``Scheduler(speculative=True)``; rejected-tail K/V never becomes
  visible — rollback is a host/length decrement).

- :mod:`.sharding` — tensor-parallel serving (``Engine(mesh=...)``):
  a ``match_partition_rules``-style rule table over the
  TransformerLM pytree plus shard_map-wrapped engine programs. The KV
  pool shards along the heads axis so attention never crosses ICI;
  the only collectives are two psums per transformer block plus one
  all-gather of the sampled logits rows (the tied head runs
  vocab-parallel). ``mesh=None`` stays the verbatim single-chip
  baseline, pinned bitwise against a ``tp=1`` mesh.

- :class:`KVQuantConfig` (:mod:`.kv_quant`) / :class:`WeightQuantConfig`
  (:mod:`.weight_quant`) — the int8 storage tiers over the two dominant
  HBM-resident populations, sharing one symmetric-quant core
  (:mod:`.quant_common`): the KV pool stores int8 with per-``[layer,
  head]`` scales dequantized inside the attention kernels (~2x
  concurrency at the same pool bytes), and the serving weights store
  int8 with per-output-channel scales dequantized in each GEMM's
  epilogue (~2x model-size headroom vs bf16). Both are params/cache
  properties, not programs — zero new executables, token-match-rate
  contracts vs the bf16 oracle, and the ``None`` defaults stay the
  bitwise baselines.

- :class:`FaultPlan` / :class:`FaultPolicy` / :class:`PoolAuditor`
  (:mod:`.faults`) — fault isolation: a seeded deterministic
  chaos-injection harness (non-finite logits into chosen decode slots,
  transient call-boundary exceptions, heartbeat stalls, replica deaths
  at the router tier, debug-copy page-table corruption), the
  scheduler's always-on containment policy (per-slot non-finite
  quarantine, requeue with capped exponential backoff → typed
  ``FAILED``, heartbeat watchdog), and an O(pages) page-pool invariant
  auditor that raises loudly on leaked or double-freed pages.
  Un-faulted greedy requests stay bitwise identical to a fault-free
  run; containment adds ZERO compiled programs.

- :class:`HostTier` / :class:`SwapWorker` (:mod:`.host_tier`) —
  hierarchical KV (``Engine(host_tier=<bytes>)``,
  ``prefix_pool > 0``; composes with ``mesh=``): a bounded host-DRAM
  arena behind the page pool. A prefix entry evicted under pool
  pressure has its page bytes migrated device→host (int8 under
  ``kv_quant`` — half the transfer) instead of being destroyed — by
  default ASYNCHRONOUSLY: the admission path only dispatches a
  fixed-shape compiled gather (the snapshot rides program order) and
  a worker thread forces/checksums/stores the bytes off the hot path,
  the entry staying matchable in the *swapping* → *swapped* states (a
  hit racing its own swap JOINS the copy; ``sync_swap=True`` is the
  measurable inline baseline). A later hit migrates the bytes back
  through the other fixed-shape compiled program (a page-block
  scatter) before copy-on-write sharing as usual; under a mesh both
  swap programs shard over the pool's heads axis with ZERO
  collectives and arena records carry per-shard CRCs. CRC-verified:
  a corrupt/missing swap-in degrades to a verified miss (re-prefill),
  never a wrong token — hit-after-swap greedy streams are bitwise
  identical to never-swapped ones, async or sync, and prefix capacity
  is bounded by host RAM, not HBM.

- :class:`Router` (:mod:`.router`) — replica-parallel serving (tp × dp
  scale-out): N ``Scheduler``+``Engine`` replicas behind one
  host-side ``submit()`` that routes by PREFIX AFFINITY (one set of
  rolling block hashes probes every replica's cache read-only; the
  request lands where its K/V already lives) with least-loaded
  admission as the fallback (free slots / queue depth / free pool
  pages from :meth:`Scheduler.load_snapshot`), cross-replica
  backpressure (a full replica is a spill to the next-best; QueueFull
  only when the whole fleet is saturated, ``retry_after_s`` = max of
  replica hints), and replica-death containment: a dead replica's
  requests drain (:meth:`Scheduler.drain_requests`) and re-route onto
  survivors with zero leaked pages — un-faulted requests stay bitwise.
  Zero compiled programs added; ``serving.router.*`` telemetry.

- :class:`FleetController` (:mod:`.fleet` / :mod:`.fleet_worker`) —
  the Router's fleet, OUT-OF-PROCESS: each replica is a separate OS
  process (``python -m apex_tpu.serving.fleet_worker``) owning its
  own JAX runtime, engine and telemetry registry, behind a
  length-prefixed stdlib AF_UNIX transport. The controller reuses the
  Router's exact decision core (:mod:`.routing_policy` — shared pure
  functions, so in-process and process fleets route identically and
  the parity pin is bitwise) over serialized probes and
  :func:`snapshot_to_wire` load snapshots; requests and disagg arena
  records cross as versioned wire forms (:func:`request_to_wire`,
  :func:`record_to_wire` — handoffs travel BY VALUE and re-verify by
  CRC on the importing arena). Health heartbeats with a missed-beat
  death detector (the ``worker_hang`` fault kind), ROLLING restart
  (drain → respawn → rejoin warm), and elastic
  ``add_replica``/``remove_replica``/``set_role`` under live traffic.
  ``serving.fleet.*`` telemetry; per-worker registries merge into one
  fleet view.

- :class:`LoRAConfig` / :class:`LoRAManager` (:mod:`.lora`) —
  multi-tenant LoRA serving (``Engine(lora=LoRAConfig(...))``):
  thousands of fine-tunes batched on ONE base engine. Each adapter is
  a per-site low-rank pair folded into the four serving GEMMs as an
  epilogue term (``acc + (x @ A) @ B · α``) gathered from a stacked
  device arena by a TRACED per-slot adapter-index operand — adapter
  identity is data, not a trace key, so a heterogeneous-adapter batch
  decodes in one compiled invocation and the program-count pins do
  not move. Adapters hot-load/evict through a bounded HostTier-style
  host store (LRU, refcount pinning while any slot is bound, CRC
  verification on swap-in — a corrupt record fails LOUDLY, never
  decodes wrong tokens); ``Request.adapter`` routes with
  resident-adapter affinity next to prefix affinity on both routing
  fronts; under a mesh the arena shards on the PR-9 rule table's
  axes (A column-split, B row-split) so the existing per-block psums
  restore the sum — zero new collectives. ``lora=None`` (and a
  LoRA engine with no adapter bound) stays the BITWISE base engine
  on the same executables.

- :class:`SLOConfig` / :class:`TenantLedger` (:mod:`.slo`) —
  SLO-aware preemptive scheduling (``Scheduler(slo=SLOConfig(...))``):
  priority classes (``Request.slo_class`` / ``priority``), preempt-
  lowest under admission pressure — the victim's committed pages
  migrate device→host through the existing async swap path (or stay
  resident as a retained prefix) and the request resumes later via
  swap-in + COW prefix-share at the committed offset, BITWISE
  identical to its uninterrupted greedy run; queue-aging starvation
  bounds, per-tenant slot quotas + weighted-fair token accounting
  (one shared ledger across the in-process Router's replicas),
  deadline-aware admission (:class:`DeadlineUnmeetable` with an
  honest EMA-derived ``retry_after_s``), and SLO-aware fleet routing
  (``preemptible_pages`` headroom in :mod:`.routing_policy`, ranked
  identically by Router and FleetController). ``slo=None`` stays the
  verbatim FIFO baseline — zero new compiled programs either way.

Quick start::

    from apex_tpu import serving
    from apex_tpu.models.transformer_lm import create_lm

    model = create_lm("small", vocab_size=32768, max_seq_len=512)
    engine = serving.Engine(model, params, slots=8, max_len=512,
                            prefill_len=128)
    sched = serving.Scheduler(engine, eos_id=0)
    done = sched.run([serving.Request(prompt=[17, 23, 5],
                                      max_new_tokens=64)])
    generated = done[0].output_tokens

Exercised end-to-end by ``benchmarks/run.py`` (the serving cells of
``BENCHMARK.json``) and ``examples/lm/main_amp.py --generate``.
"""

from . import routing_policy, sharding
from .engine import Engine, PendingDecode, sample_tokens
from .faults import (FaultPlan, FaultPolicy, FaultSpec, InjectedFault,
                     PoolAuditor, PoolInvariantError, fault_kind)
from .fleet import FleetController, WorkerDied
from .host_tier import (HostTier, SwapWorker, record_from_wire,
                        record_to_wire)
from .kv_cache import (CacheSpec, PagedKVCache, PagePool, SlotAddr,
                       SlotState, StateBlock)
from .kv_quant import KVQuantConfig
from .lora import LoRAConfig, LoRAManager
from .prefix_cache import PrefixCache, PrefixMatch
from .router import Router
from .scheduler import (DeadlineUnmeetable, QueueFull, Request,
                        RequestStatus, Scheduler,
                        request_from_wire, request_to_wire,
                        snapshot_from_wire, snapshot_to_wire)
from .slo import SLOConfig, TenantLedger
from .speculative import DraftWorker, SpecConfig, draft_tokens
from .weight_quant import WeightQuantConfig

__all__ = ["DeadlineUnmeetable", "DraftWorker", "Engine", "FaultPlan",
           "FaultPolicy",
           "FaultSpec", "FleetController", "HostTier", "InjectedFault",
           "KVQuantConfig", "LoRAConfig", "LoRAManager",
           "PagedKVCache", "PagePool", "SlotState", "SlotAddr", "CacheSpec",
           "StateBlock",
           "PendingDecode", "PoolAuditor", "PoolInvariantError",
           "PrefixCache", "PrefixMatch", "QueueFull", "Request",
           "RequestStatus", "Router", "SLOConfig", "Scheduler",
           "SpecConfig",
           "SwapWorker", "TenantLedger", "WeightQuantConfig",
           "WorkerDied",
           "draft_tokens", "fault_kind", "record_from_wire",
           "record_to_wire", "request_from_wire", "request_to_wire",
           "routing_policy", "sample_tokens", "sharding",
           "snapshot_from_wire", "snapshot_to_wire"]
