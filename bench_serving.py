"""Serving benchmark: continuous-batching decode throughput (tokens/s).

Exercises the full ``apex_tpu.serving`` stack — compiled chunk-prefill +
decode-step programs over a bf16 slot KV cache, continuous-batching
scheduler — on a stream of synthetic variable-length requests, and
prints ONE final JSON line::

  {"metric": "serving_decode_tokens_per_sec", "value": N,
   "unit": "tokens/s", ...}

Methodology matches bench.py: a warmup window (compiles the programs;
discarded), then >= BENCH_SERVING_WINDOWS measured windows reported as
median + min + spread so one line carries its own noise bars. The line
also carries the latency layer: time-to-first-token p50/p95/p99 — now
decomposed into queue-wait and prefill-chunk compute — and per-decode-
step p50/p95/p99 from the telemetry registry's streaming histograms,
plus mean slot occupancy / padding waste.

``--mixed-prompts`` runs the head-of-line-blocking leg the chunked
prefill exists for: an interleaved short/long prompt stream served
twice — chunked (the default scheduler) vs monolithic
(``chunked=False``, the PR 3 baseline) — emitting one row JSON line per
mode and a final line whose payoff fields are per-class TTFT p50/p99
(``ttft_short_p99_ms`` chunked vs monolithic) and aggregate tokens/s.
Both modes serve greedy streams, so the leg also asserts token-identical
outputs — the chunked path must win on latency without moving a single
token.

Regime note: the chunked win presumes silicon's cost model, where a
``[slots, 1]`` decode step is far cheaper than a monolithic
``[1, prefill_len]`` prefill — then interleaving bounds the stall at
one chunk for near-free throughput. On the CPU fallback the reference
decode path attends the FULL cache per slot, inverting the ratio
(decode is the priciest program), so the staggered admission's extra
partial-occupancy decode steps read as a throughput loss there: CPU
rows of this leg are a correctness/plumbing signal, the perf claim is
the TPU rows'. ``BENCH_SERVING_CHUNK_BUDGET`` (default 1) trades the
per-tick stall bound against admission throughput (Sarathi's
token-budget knob).

``--shared-prefix`` runs the prefix-caching leg: a repeated-system-
prompt stream (every request opens with the same
``BENCH_SERVING_SHARED_PREFIX``-token prefix — the shape of real
templated traffic) served twice on identical engine geometry — cold
(``retain_prefixes=False``) vs cached (``retain_prefixes=True``,
``BENCH_SERVING_PREFIX_POOL`` pool rows) — emitting one row per mode
and a final line whose payoff fields are ``prefix_hit_rate``,
``prefill_chunks_skipped_pct`` (telemetry-counted chunk-prefill steps
that never executed — a compute count, honest on the CPU fallback,
unlike the decode-regime claims), TTFT p50/p99 both modes, and
``token_mismatched_requests`` (both modes are greedy, and the reused
prefix K/V is byte-identical to freshly prefilled K/V, so the expected
reading is 0 — bitwise, not approximately).

**Shared-prefix presets**: with no ``BENCH_SERVING_*`` env set the leg
runs the SMOKE geometry (8 requests x 16 new tokens x 2 windows —
minutes, not half-hours, on this box's CPU); the full geometry the PR 5
rows were measured at is one export away::

  # full (the historical default; >25 min on CPU, sized for TPU)
  BENCH_SERVING_REQUESTS=24 BENCH_SERVING_NEW_TOKENS=64 \
  BENCH_SERVING_WINDOWS=3 python bench_serving.py --shared-prefix

``--paged-pool`` runs the block-table capacity leg: the SAME
short-prompt stream served by the contiguous engine (``paged=False``,
``BENCH_SERVING_SLOTS`` slots, the pool bytes of ``slots`` full
``max_len`` rows) and by the paged engine given the SAME physical pool
bytes but ``BENCH_SERVING_PAGED_SLOTS`` (default ``4 x slots``) decode
slots — possible only because requests hold pages, not rows. One row
per mode plus a final line whose payoff fields are
``max_concurrent_requests`` (must exceed the contiguous ``slots`` —
the logical-concurrency unlock), ``hbm_bytes_per_request`` both modes
and the reduction pct (worst-case reservation bytes — an accounting
claim, honest on CPU), peak ``pages_in_use``, and
``token_mismatched_requests`` vs the contiguous baseline (greedy; the
expected reading is 0). Throughput regime note: the paged engine's
wider decode batch costs MORE per step on the CPU fallback (the
reference decode attends every slot) — judge tokens/s on TPU rows; the
capacity and bytes columns are the leg's claim.

``--speculative`` runs the draft-and-verify leg: TWO drafter-friendly
greedy streams — shared-prefix (the ``--shared-prefix`` shape: every
prompt opens with the same system prefix) and multi-turn (a shared
conversation history plus a repeated per-request tail, the
prompt-lookup drafter's best case) — each served twice on one engine
built with ``spec=SpecConfig(draft_len=BENCH_SERVING_SPEC_K)``:
``speculative=False`` (plain decode, the measurable baseline) then
``speculative=True``. One row per (stream, mode) plus a final line
whose payoff fields are ``acceptance_rate`` (accepted/drafted, with
per-verify-call p50/p99 from the ``serving.spec.acceptance_rate``
histogram), ``tokens_per_step`` (tokens emitted per compiled
sequence-step — plain decode pins 1.0, acceptance pushes it above),
and ``token_mismatched_requests`` — spec vs plain, expected **0
bitwise on every backend** (accept-longest-prefix emits only the
verify program's own greedy targets). Throughput regime note: on the
CPU fallback a ``[1, K+1]`` verify costs ~K+1 decode steps of real
compute (the reference kernels do the full math), so spec tokens/s
reads flat-to-worse here even at high acceptance — CPU rows prove
exactness + acceptance; tokens/s is the TPU rows' claim (one verify
dispatch replaces up to K+1 decode dispatches). Defaults to a smoke
geometry; env knobs resize it (env-beats-smoke).

``--chaos`` runs the fault-isolation leg: the IDENTICAL greedy request
stream served twice on one engine — fault rate 0, then
``BENCH_SERVING_FAULT_PCT``% per-tick injection (seeded
``FaultPlan.random``: non-finite logits at the rate, transient
chunk/decode exceptions at half of it) under the standard containment
policy (requeue ×2 then typed FAILED, auditor every event) — one row
per mode plus a final line whose payoff fields are **goodput**
(clean-request tokens/s), ``goodput_retention_pct`` vs the rate-0 row
(the price of containment: requeued prefills re-run, failed requests
waste partial compute), failed/requeued/injected counts,
``pages_in_use_at_drain`` (the auditor ran and the pool drained), and
``token_mismatched_requests`` — clean chaos-run requests vs the rate-0
run, expected 0 **bitwise** on every backend (the containment
guarantee, not a numerics regime claim). Defaults to a smoke geometry
(8 requests × 12 tokens); the env knobs resize it.

``--tensor-parallel`` runs the mesh leg on CPU DEVICE EMULATION (the
leg forces ``JAX_PLATFORMS=cpu`` + ``--xla_force_host_platform_
device_count`` before any backend initializes — it is an exactness +
capacity-accounting measurement by definition; tokens/s over emulated
devices carries no silicon signal): the SAME greedy stream served by
the verbatim single-chip engine (``mesh=None`` — the honest tp=1) and
by ``Engine(mesh=<BENCH_SERVING_TP shards>)``. One row per mode plus a
final line whose payoff fields are tokens/s both modes,
``hbm_bytes_per_shard`` (the pool's heads-axis split: per-chip KV HBM
is ``1/tp`` of the single-chip engine's — the claim that lets a model
of real size serve at all), the per-program collective inventory
(``psums_per_program`` = 2/block, ``all_gathers_per_program`` = 1 —
the HLO-pinned numbers), and ``token_mismatched_requests`` (greedy;
the expected reading is **0** — tp=1 is pinned bitwise and tp>1
token-exact by tests/L0/test_sharding.py). Defaults to a smoke
geometry; env knobs resize it (env-beats-smoke), ``BENCH_SERVING_TP``
sets the shard count (default 2).

``--quantized-kv`` runs the int8-capacity leg: the shared-prefix greedy
stream served twice — the bf16 engine (``kv_quant=None``, the bitwise
oracle) and the int8 engine (``KVQuantConfig`` calibrated on the shared
prefix) given the SAME physical pool bytes but
``BENCH_SERVING_QUANT_SLOTS`` (default 2x) decode slots, possible
because int8 halves bytes-per-position. One row per mode plus a final
line whose payoff fields are ``kv_bytes_per_token_reduction_pct`` (50
by construction — the >= 45% acceptance bar), ``hbm_bytes_per_request``
both modes, ``max_concurrent_requests`` both modes,
``quant_scale_absmax``, and ``token_match_rate`` — positionwise greedy
agreement vs the bf16 oracle (the TOLERANCE contract the quantized
tier trades bitwise parity for; the bf16 default itself stays
bitwise). Throughput regime note: the int8 engine's wider decode batch
costs MORE per step on the CPU fallback (reference kernels dequantize
by materialising; decode attends every slot), so quantized tokens/s
reads flat-to-worse here — capacity, bytes and match-rate are the
CPU-honest columns, tokens/s is the TPU rows' claim (half the cache
DMA per attended token). Defaults to a smoke geometry; env knobs
resize it (env-beats-smoke).

``--quantized-weights`` runs the int8-weights leg: the shared-prefix
greedy stream served THREE ways at IDENTICAL engine geometry — bf16
weights (``weight_quant=None``, the bitwise oracle), int8 weights
(``WeightQuantConfig()``: per-output-channel fp32 scales, dequant
folded into the GEMM epilogues — zero new compiled programs), and
int8 weights + int8 KV (the combined tier; ``kv_quant`` calibrated on
the shared prefix). One row per mode plus a final line whose payoff
fields are ``weight_bytes_reduction_pct`` (the >= 45% acceptance bar;
~49% at the ``small`` shape), ``bytes_per_param`` both modes (scale
overhead charged in), ``hbm_bytes_per_request`` bf16 vs combined (the
int8 cache halves it again on top of the weight cut),
``quant_scale_absmax`` (the grid's representable range — a provenance
number for weights), and ``token_match_rate`` /
``combined_token_match_rate`` — positionwise greedy agreement vs the
bf16 oracle (the TOLERANCE contract; ``weight_quant=None`` stays
bitwise). Throughput regime note: the reference-path GEMMs dequantize
by materialising on the CPU fallback, so quantized tokens/s reads
flat here — weight bytes, per-request bytes and match-rate are the
CPU-honest columns, tokens/s is the TPU rows' claim (half the weight
DMA per GEMM, int8 MXU issue where hardware has it). Defaults to a
smoke geometry; env knobs resize it (env-beats-smoke).

``--async-heartbeat`` runs the dispatch-ahead leg: the SAME seeded
greedy stream served twice on one engine — synchronously
(``pipeline_depth=0``, the bitwise oracle) and pipelined
(``pipeline_depth=BENCH_SERVING_ASYNC_DEPTH``, default 2: decode t+1
dispatches against the speculated schedule before step t's tokens are
read back, one batched readback per reconcile, drafting/hashing on a
worker thread). One row per mode plus a final line whose payoff
fields are **heartbeat wall per emitted token** both modes +
improvement pct (the latency the refactor attacks — host think-time
overlaps device execution instead of serializing with it), the
**duty cycle** (device-wait fraction of beat wall) and host-seconds
fraction behind it, ``discarded_inflight_tokens`` (speculated steps
rolled back at EOS — the price of dispatching ahead), and
``token_mismatched_requests`` — expected 0 **bitwise** on every
backend (same compiled programs, same bytes, deferred readback only).
CPU regime note: this box's CPU backend executes DONATED-buffer
programs synchronously inside the dispatch call (measured: the
engine's donated-cache decode blocks ~the full step at dispatch,
while an undonated jit returns in ~0.1 ms), so dispatch-ahead overlap
is STRUCTURALLY zero here and the pipelined row reads a small
per-beat-overhead LOSS — the same CPU-regime shape as chunked
prefill (PR 4) and speculative verify (PR 8). The CPU-honest columns
are exactness, the host/duty-cycle split, and the overhead bound;
wall-per-token improvement is the silicon claim (real accelerators
dispatch asynchronously — the premise the refactor is built on).
Defaults to a smoke geometry; env knobs resize it (env-beats-smoke).

``--host-tier`` runs the hierarchical-KV leg: a grouped shared-prefix
greedy stream (``BENCH_SERVING_HOST_GROUPS`` distinct
``BENCH_SERVING_SHARED_PREFIX``-token templates, requests cycling
through them) whose prefix WORKING SET deliberately exceeds the
device pool (sized for ~half the groups), served THREE times on
identical pool geometry — tier off (eviction destroys, the pre-tier
baseline), tier on with ``sync_swap=True`` (eviction copies page
bytes to the host arena INLINE on the admission path — the stall
baseline), and tier on async (the default: eviction dispatches the
compiled snapshot gather and a ``SwapWorker`` thread migrates the
bytes off the hot path; revisits swap back in, joining any in-flight
copy). One row per mode plus a final line whose payoff fields are the
**prefix hit rate** per mode (tier-on ≫ tier-off, sync == async),
``prefill_chunks_skipped``, TTFT p50/p99, the **admission-stall
p50/p99 sync vs async** read from the ``serving.swap.admit_stall_s``
telemetry histogram (the async tentpole's claim — and the one async
serving win that is honestly CPU-measurable: the swap "transfer" is
a real memcpy here, and the async dispatch is an undonated ~0.1 ms
enqueue), the swap traffic counters (``hit_after_swap`` /
``swapped_out_pages`` / ``swapped_in_pages`` / ``swap_join_waits`` /
``verify_failed`` — the last expected 0 outside chaos), the
working-set-vs-pool honesty row, ``token_mismatched_requests``
across ALL modes vs tier-off (expected **0 bitwise** on every
backend — the worker changes WHEN bytes move, never what any program
computes), and a nested ``mesh`` sub-leg
(``BENCH_SERVING_HOST_TIER_TP`` shards, CPU device emulation —
auto-skipped with the reason when the backend initialized first):
the same stream on a mesh-sharded host-tier engine, token-exact vs
unsharded with per-shard arena records (``shards == tp``, one CRC
per shard) verified. CPU regime note: swap BANDWIDTH is still the
silicon claim (real device↔host DMA vs this box's memcpy); hit rate,
chunks skipped, TTFT, ADMISSION-STALL REMOVAL and bitwise parity are
the CPU-honest columns. Defaults to a smoke geometry; env knobs
resize it (env-beats-smoke), ``BENCH_SERVING_HOST_TIER_MIB`` bounds
the arena.

``--replica-router`` runs the replica-parallel leg: a multi-turn
session stream (``BENCH_SERVING_REQUESTS`` sessions of 2 turns per
window; turn 2's prompt EXTENDS turn 1's, so its block-aligned prefix
lives exactly where turn 1 was served) routed through
``serving.Router`` three ways — ONE replica (the baseline),
``BENCH_SERVING_REPLICAS`` replicas with prefix-affinity routing, and
the same fleet with seeded RANDOM routing (the control row: what
scale-out looks like when nobody cares where the K/V lives). One row
per mode plus a final line whose payoff fields are aggregate tokens/s
at 1 vs N (+ ``scaling_x``), p99 TTFT both, the **prefix hit rate**
affinity vs random (measured from per-replica
``PrefixCache.stats_since`` deltas over the measured windows — the
delta lens is what makes the reading immune to the counters'
cumulative-across-reset semantics), reused-tokens-per-request both,
``affinity_beats_random`` (the routing claim), and
``token_mismatched_requests`` vs the 1-replica run — expected 0
**bitwise** under every policy (identically-built replicas: routing
changes WHERE a request decodes, never what). CPU regime note:
replicas share this box's CPU cores, so N-replica tokens/s is NOT a
scaling measurement here — affinity hit rate vs the control, bitwise
parity and the leak-free drain are the CPU-honest columns; aggregate
scaling vs replica count is the silicon claim. Defaults to a smoke
geometry; env knobs resize it (env-beats-smoke).

``--disaggregated`` runs the prefill/decode role-split leg: one fleet
of ``BENCH_SERVING_REPLICAS + 1`` identically-built engines over ONE
shared ``HostTier(shared=True)`` arena serves the SAME interleaved
stream twice — every third request a heavyweight (a
``BENCH_SERVING_PREFILL``-token prompt, a few new tokens: pure
ingestion pressure), the rest SHORT bystanders (a one-chunk prompt,
``BENCH_SERVING_NEW_TOKENS`` decode budget) — first colocated (all
roles ``"both"``: every replica interleaves heavyweight chunk
prefills with bystander decodes), then role-split
(``Router(roles=["prefill", "decode", ...])``: heavyweights ingest on
the prefill replica and the CRC'd aligned handoff moves the prefix
through the arena to a decode replica, zero re-prefill on the happy
path). One row per mode plus a final line whose payoff fields are
**bystander TTFT p50/p99** colocated vs split (the head-of-line
claim one fleet-tier up from ``--mixed-prompts``), the
**decode-replica heartbeat** ``serving.heartbeat.host_s`` p50/p99
both modes (read from PER-REPLICA scheduler registries so the
prefill replica's chunky beats cannot pollute the decode reading —
the isolation delta), ``decode_isolation`` (the fraction of
decode-capable replicas' beats that carried NO chunk-prefill work,
from the same scheduler beat counters behind the
``serving.disagg.decode_isolation`` gauge), the handoff traffic
columns (``handoffs`` / ``handoff_bytes`` / ``reprefills`` — the
last expected 0 outside chaos — and handoff export/import p50/p99
from the ``serving.swap.out_s``/``in_s`` histograms),
``arena_bytes_after_drain`` (expected 0 — no leaked handoff
records), and ``token_mismatched_requests`` vs the colocated run
(greedy; expected **0 bitwise** on every backend — the role split
changes WHERE a prompt ingests, never what any program computes).
CPU regime note: both modes share this box's cores, so split-fleet
tokens/s is NOT a throughput claim here — bystander TTFT, the
decode-beat isolation columns, bitwise parity and the leak-free
drain are the CPU-honest columns; aggregate disaggregated throughput
is the silicon claim. Defaults to a smoke geometry; env knobs resize
it (env-beats-smoke), and ``BENCH_SERVING_TRACE`` attaches request
tracing to the split leg (handoff export/import spans included).

``--process-fleet`` runs the out-of-process fleet leg: the SAME
multi-turn session-wave stream as ``--replica-router``, served through
``serving.FleetController`` twice — a ONE-worker fleet (the baseline:
transport cost included, so the scaling ratio is fleet-vs-fleet, not
fleet-vs-thread) and a ``BENCH_SERVING_REPLICAS``-worker fleet with
prefix-affinity routing, every worker a separate OS process
(``python -m apex_tpu.serving.fleet_worker``) owning its own
interpreter, JAX runtime and engine built deterministically from a
shipped spec. One row per mode plus a final line whose payoff fields
are aggregate tokens/s 1 vs N and ``scaling_x`` — on this CPU box an
HONEST column for the first time in the serving bench (the thread
fleets above share one GIL and one runtime; these workers do not, so
"add a worker" is allowed to mean "go faster" here), p99 TTFT both,
prefix hit rate + reused tokens (``prefix_stats`` RPC deltas over the
measured windows), the fleet health counters (``worker_deaths`` and
``hangs_detected``, both expected 0 outside chaos), the
rolling-restart columns (total wall time plus per-worker
``serving.fleet.restart_s`` p50/max for a drain → close → respawn →
rejoin pass over the live fleet, with a post-restart wave set proving
the respawned workers serve), and ``token_mismatched_requests`` vs
the 1-worker run — expected 0 **bitwise** (identically-spec'd
workers: the process boundary changes WHERE a request decodes, never
what). The fleet spawns ONCE per mode — a worker spawn pays
interpreter + jax import + compile, so windows after the warmup serve
warm; greedy outputs are reuse-invariant by the verified-prefix
contract, so warm serving moves no token. Transport overhead note:
every routed request pays an N-probe fan-out and every token batch a
step RPC (microseconds each on AF_UNIX); ``worker<i>/...``-namespaced
histograms in the merged snapshot carry the per-process view.
Defaults to the router leg's smoke geometry; env knobs resize it
(env-beats-smoke).

``--lora`` runs the multi-tenant adapter leg: a seeded stream cycling
through ``BENCH_SERVING_LORA_ADAPTERS`` registered LoRA adapters plus
the base model, served twice at IDENTICAL engine geometry — **mixed**
(one ``Engine(lora=LoRAConfig(...))`` scheduler run, every slot
wearing its own adapter inside one heterogeneous batch) and
**sequential** (the naive baseline: the SAME request set partitioned
by adapter and each group drained alone — what an
engine-per-adapter deployment degenerates to at batch level). One row
per mode plus a final line whose payoff fields are mixed vs
sequential tokens/s (+ ``speedup_x`` — batch-level parallelism the
sequential baseline forfeits), the ``serving.lora.*`` churn columns
(``lora_hits`` / ``lora_loads`` / ``lora_evictions`` over the
measured windows and ``warm_bind_rate`` — the adapter-affinity
payoff reading), ``arena_bytes`` / ``active_adapters`` (the host
store and device arena occupancy), ``recompiles_after_warmup``
(expected **0**: admitting N adapters compiles NOTHING — the traced
adapter-index operand is the whole point), and
``token_mismatched_requests`` mixed vs sequential — expected 0
**bitwise** (per-slot adapter isolation: a slot's tokens depend only
on ITS adapter row, never on its batch neighbours'). CPU regime
note: the skinny epilogue GEMMs cost relatively more here than their
``rank/hidden`` silicon share, so judge tokens/s deltas on TPU rows
— the compile-count, churn and bitwise columns are the CPU-honest
claims. Defaults to a smoke geometry; env knobs resize it
(env-beats-smoke).

Wrapped in ``guard_bench_main`` — EVERY outcome (backend init failure,
OOM, bad env) still ends in a parseable JSON line.
"""

from __future__ import annotations

import json
import os
import re
import sys
import time

import numpy as np

METRIC = "serving_decode_tokens_per_sec"
MIXED_METRIC = "serving_mixed_prompts_tokens_per_sec"
SHARED_METRIC = "serving_shared_prefix_tokens_per_sec"
PAGED_METRIC = "serving_paged_pool_tokens_per_sec"
CHAOS_METRIC = "serving_chaos_goodput_tokens_per_sec"
SPEC_METRIC = "serving_speculative_tokens_per_sec"
TP_METRIC = "serving_tensor_parallel_tokens_per_sec"
QUANT_METRIC = "serving_quantized_kv_tokens_per_sec"
WQUANT_METRIC = "serving_quantized_weights_tokens_per_sec"
ASYNC_METRIC = "serving_async_heartbeat_tokens_per_sec"
ROUTER_METRIC = "serving_replica_router_tokens_per_sec"
HOST_METRIC = "serving_host_tier_tokens_per_sec"
DISAGG_METRIC = "serving_disagg_tokens_per_sec"
FLEET_METRIC = "serving_process_fleet_tokens_per_sec"
OVERLOAD_METRIC = "serving_overload_goodput_tokens_per_sec"
LORA_METRIC = "serving_multi_tenant_lora_tokens_per_sec"

# Literal defaults at import time; the BENCH_SERVING_* env overrides are
# parsed by _load_env() INSIDE each guarded main, so a malformed value
# becomes guard_bench_main's parseable failure line, not an import-time
# traceback (the same contract bench.py holds).
SIZE = "small"
VOCAB = 32768
SLOTS = 8
MAX_LEN = 512
PREFILL_LEN = 128
CHUNK_LEN = 0                   # 0 = engine default
REQUESTS = 24
NEW_TOKENS = 64
WINDOWS = 3
TOP_K = 0
SHORT_LEN = 16
CHUNK_BUDGET = 1
# --shared-prefix leg: shared-system-prompt length (block-aligned reuse
# wants it a multiple of the chunk), prefix-pool rows, and a chunk_len
# small enough that one prompt spans several chunks (reuse is counted
# in whole chunks; the leg defaults chunk to PREFILL/4 when unset)
SHARED_PREFIX = 96
PREFIX_POOL = 4
# --shared-prefix SMOKE preset (applied only to knobs the env leaves
# unset — the full geometry is one export away, see module docstring):
# the historical 24-req/64-token/3-window default needs >25 min on this
# box's CPU, far too long for a smoke signal
SHARED_SMOKE = {"REQUESTS": 8, "NEW_TOKENS": 16, "WINDOWS": 2}
# --paged-pool leg: paged decode width over the same pool bytes as the
# contiguous baseline's SLOTS rows (0 -> 4x), and the short-prompt
# stream's max length (short prompts are where row-granularity HBM
# waste is worst)
PAGED_SLOTS = 0
PAGED_PROMPT = 32
# --chaos leg: per-tick injection percentage (non-finite at this rate,
# transient exceptions at half of it) and its smoke preset — the leg
# serves the SAME stream twice (rate 0, then FAULT_PCT), so halve the
# geometry you would give one mode
FAULT_PCT = 10
CHAOS_SMOKE = {"REQUESTS": 8, "NEW_TOKENS": 12, "WINDOWS": 1}
# --speculative leg: drafts per verify step (the engine's [1, K+1]
# verify shape; on silicon keep K+1 a multiple of 8 for the Pallas
# path) and its smoke preset — the leg serves TWO streams twice each
SPEC_K = 4
SPEC_SMOKE = {"REQUESTS": 6, "NEW_TOKENS": 16, "WINDOWS": 1}
# --tensor-parallel leg: shards (heads/vocab/MLP-inner must divide —
# the engine rejects ragged geometry loudly) and its smoke preset: the
# leg serves the stream TWICE (mesh=None then the mesh) and CPU
# emulation pays tp x the per-step dispatch, so it is sized small
TP = 2
TP_SMOKE = {"SIZE": "tiny", "VOCAB": 512, "SLOTS": 4, "MAX_LEN": 128,
            "PREFILL_LEN": 32, "REQUESTS": 6, "NEW_TOKENS": 12,
            "WINDOWS": 1}
# --quantized-kv leg: int8 decode width over the SAME pool bytes as
# the bf16 baseline's SLOTS (0 -> 2x: int8 halves bytes-per-position,
# so identical bytes hold twice the pages) and its smoke preset — the
# leg serves the shared-prefix stream twice (bf16 oracle, then int8)
QUANT_SLOTS = 0
QUANT_SMOKE = {"SIZE": "tiny", "VOCAB": 512, "SLOTS": 4, "MAX_LEN": 128,
               "PREFILL_LEN": 32, "REQUESTS": 8, "NEW_TOKENS": 12,
               "WINDOWS": 1}
# --quantized-weights leg: the shared-prefix stream at IDENTICAL
# geometry three times (bf16 oracle, int8 weights, int8 weights + int8
# KV) — weight quantization changes param bytes, not pool geometry, so
# unlike --quantized-kv nothing resizes; the smoke preset matches its
# sibling's
WQUANT_SMOKE = {"SIZE": "tiny", "VOCAB": 512, "SLOTS": 4,
                "MAX_LEN": 128, "PREFILL_LEN": 32, "REQUESTS": 8,
                "NEW_TOKENS": 12, "WINDOWS": 1}
# --async-heartbeat leg: in-flight decode steps (pipeline_depth for the
# pipelined mode; the sync mode is always depth 0) and its smoke
# preset — the leg serves the SAME stream in both modes on one engine,
# so halve the geometry you would give one mode
ASYNC_DEPTH = 2
ASYNC_SMOKE = {"SIZE": "tiny", "VOCAB": 512, "SLOTS": 4,
               "MAX_LEN": 128, "PREFILL_LEN": 32, "REQUESTS": 8,
               "NEW_TOKENS": 16, "WINDOWS": 2}
# --replica-router leg: engine replicas behind the prefix-aware router
# (the leg serves its session stream THREE ways — 1 replica, N with
# affinity, N with random routing — so it is sized small) and its
# smoke preset. REQUESTS is SESSIONS per window here (2 turns each);
# CHUNK_LEN stays small so a turn's history spans several blocks and
# reuse is visible at block granularity.
REPLICAS = 2
ROUTER_SMOKE = {"SIZE": "tiny", "VOCAB": 512, "SLOTS": 2,
                "MAX_LEN": 128, "PREFILL_LEN": 48, "CHUNK_LEN": 8,
                "REQUESTS": 6, "NEW_TOKENS": 8, "WINDOWS": 1,
                "PREFIX_POOL": 4}
# --disaggregated leg: the SAME interleaved bystander/heavyweight
# stream served by one fleet of REPLICAS+1 engines over one shared
# host arena, colocated (all "both") then role-split (1 prefill +
# REPLICAS decode with KV handoff) — two serves per window, so it is
# sized small. SHORT_LEN bounds the bystander prompts (they must fit
# one chunk so a bystander's cost is pure decode); PREFILL_LEN is the
# heavyweight prompt (several chunks, so its ingestion visibly hogs a
# colocated replica's beats); HOST_TIER_MIB bounds the handoff arena.
DISAGG_SMOKE = {"SIZE": "tiny", "VOCAB": 512, "SLOTS": 2,
                "MAX_LEN": 128, "PREFILL_LEN": 48, "CHUNK_LEN": 8,
                "SHORT_LEN": 6, "REQUESTS": 9, "NEW_TOKENS": 10,
                "WINDOWS": 1, "PREFIX_POOL": 4}
# --process-fleet leg: the router leg's session-wave geometry over
# OUT-OF-PROCESS workers (each spawn pays interpreter + jax import +
# compile, and the leg serves two fleets — 1 worker then REPLICAS —
# so it is sized small; the stream itself matches ROUTER_SMOKE so the
# two legs' rows are comparable)
FLEET_SMOKE = {"SIZE": "tiny", "VOCAB": 512, "SLOTS": 2,
               "MAX_LEN": 128, "PREFILL_LEN": 48, "CHUNK_LEN": 8,
               "REQUESTS": 6, "NEW_TOKENS": 8, "WINDOWS": 1,
               "PREFIX_POOL": 4}
# --overload leg: a seeded mixed-class stream at >1x slot capacity
# (REQUESTS >> SLOTS; batch-heavy with interactive arrivals landing
# BEHIND running batch work — the FIFO worst case) served twice on one
# engine: FIFO (slo=None, the verbatim baseline) then SLO-aware
# (priority classes + preempt-to-host). Interactive deadlines are
# calibrated from the measured FIFO window wall
# (OVERLOAD_DEADLINE_PCT % of it) and judged IDENTICALLY in both
# modes, so the per-class miss-rate column compares policy, not
# threshold. Deadline-aware ADMISSION stays off here (both modes must
# serve the identical request set for the bitwise
# token_mismatched_requests==0 column); its reject path is unit-tested
# in tests/L0/test_slo.py.
OVERLOAD_DEADLINE_PCT = 50
OVERLOAD_SMOKE = {"SIZE": "tiny", "VOCAB": 512, "SLOTS": 2,
                  "MAX_LEN": 128, "PREFILL_LEN": 48, "CHUNK_LEN": 8,
                  "SHORT_LEN": 6, "REQUESTS": 12, "NEW_TOKENS": 10,
                  "WINDOWS": 1, "PREFIX_POOL": 4}
# --host-tier leg: distinct shared-prefix templates the stream cycles
# through (the pool is sized for ~half of them, so revisits land on
# evicted — with the tier, SWAPPED — prefixes), the host arena bound
# in MiB, the tp width of the mesh-composition sub-leg (0 disables;
# needs emulated CPU devices, so it auto-skips when the backend
# initialized too early — run the leg standalone, or via bench.py's
# subprocess embedding), and the smoke preset (the leg serves the
# stream THREE times — tier off + tier on sync + tier on async — so
# it is sized small; REQUESTS per window should be >= 2x HOST_GROUPS
# so every group is revisited)
HOST_GROUPS = 6
HOST_TIER_MIB = 64
HOST_TIER_TP = 2
# the smoke's swap entries are sized so the deferred half of a
# swap-out (gather execution + force + CRC + defensive copy) clearly
# dominates the ~0.7 ms dispatch floor both modes share — the padded
# gather moves a max_pages-sized block, so MAX_LEN is the byte lever:
# at 128 a tiny-model block is ~128 KiB and admission-stall
# sync-vs-async drowns in this 2-core box's scheduling noise; at 512
# the block is ~2 MiB and the sync stall reads 3-6x the async one
# (measured across phases). WINDOWS 3 gives the p99 estimator ~39
# stall samples instead of max-of-13.
HOST_SMOKE = {"SIZE": "tiny", "VOCAB": 512, "SLOTS": 2, "MAX_LEN": 512,
              "PREFILL_LEN": 104, "CHUNK_LEN": 8, "REQUESTS": 12,
              "NEW_TOKENS": 6, "WINDOWS": 3, "SHARED_PREFIX": 96,
              "PREFIX_POOL": 4}

# --lora leg: distinct registered adapters the stream cycles through
# (every (N+1)th request serves the BASE model — row 0, the zero
# adapter), the adapter rank, and the device-arena rows (0 -> one row
# per adapter: the warm-arena reading; set it BELOW the adapter count
# to measure eviction churn instead). The leg serves the SAME seeded
# stream twice on identically-built engines — mixed (one
# heterogeneous batch) then sequential (per-adapter groups drained
# alone) — so it is sized small.
LORA_ADAPTERS = 3
LORA_RANK = 4
LORA_ARENA = 0
LORA_SMOKE = {"SIZE": "tiny", "VOCAB": 512, "SLOTS": 4, "MAX_LEN": 128,
              "PREFILL_LEN": 32, "REQUESTS": 8, "NEW_TOKENS": 12,
              "WINDOWS": 1}

_ENV_KNOBS = {
    "VOCAB": "BENCH_SERVING_VOCAB", "SLOTS": "BENCH_SERVING_SLOTS",
    "MAX_LEN": "BENCH_SERVING_MAX_LEN",
    "PREFILL_LEN": "BENCH_SERVING_PREFILL",
    "CHUNK_LEN": "BENCH_SERVING_CHUNK",
    "REQUESTS": "BENCH_SERVING_REQUESTS",
    "NEW_TOKENS": "BENCH_SERVING_NEW_TOKENS",
    "WINDOWS": "BENCH_SERVING_WINDOWS", "TOP_K": "BENCH_SERVING_TOP_K",
    "SHORT_LEN": "BENCH_SERVING_SHORT",
    "CHUNK_BUDGET": "BENCH_SERVING_CHUNK_BUDGET",
    "SHARED_PREFIX": "BENCH_SERVING_SHARED_PREFIX",
    "PREFIX_POOL": "BENCH_SERVING_PREFIX_POOL",
    "PAGED_SLOTS": "BENCH_SERVING_PAGED_SLOTS",
    "PAGED_PROMPT": "BENCH_SERVING_PAGED_PROMPT",
    "FAULT_PCT": "BENCH_SERVING_FAULT_PCT",
    "SPEC_K": "BENCH_SERVING_SPEC_K",
    "TP": "BENCH_SERVING_TP",
    "QUANT_SLOTS": "BENCH_SERVING_QUANT_SLOTS",
    "ASYNC_DEPTH": "BENCH_SERVING_ASYNC_DEPTH",
    "REPLICAS": "BENCH_SERVING_REPLICAS",
    "HOST_GROUPS": "BENCH_SERVING_HOST_GROUPS",
    "HOST_TIER_MIB": "BENCH_SERVING_HOST_TIER_MIB",
    "HOST_TIER_TP": "BENCH_SERVING_HOST_TIER_TP",
    "OVERLOAD_DEADLINE_PCT": "BENCH_SERVING_OVERLOAD_DL_PCT",
    "LORA_ADAPTERS": "BENCH_SERVING_LORA_ADAPTERS",
    "LORA_RANK": "BENCH_SERVING_LORA_RANK",
    "LORA_ARENA": "BENCH_SERVING_LORA_ARENA",
}


def _load_env(smoke: dict = None):
    """Apply BENCH_SERVING_* overrides (first statement of every guarded
    main): malformed values die as a clean SystemExit the guard turns
    into its failure JSON line. ``smoke`` maps knob names to the
    calling leg's smoke-preset values, applied ONLY where the env is
    silent — an exported knob always wins, so the full geometry stays
    one export away."""
    g = globals()
    for name, value in (smoke or {}).items():
        g[name] = value
    g["SIZE"] = os.environ.get("BENCH_SERVING_SIZE", g["SIZE"])
    for name, var in _ENV_KNOBS.items():
        raw = os.environ.get(var)
        if raw is None or not raw.strip():
            continue
        try:
            g[name] = int(raw)
        except ValueError:
            raise SystemExit(f"{var}={raw!r} is not an integer")


def _median(xs):
    xs = sorted(xs)
    mid = len(xs) // 2
    return xs[mid] if len(xs) % 2 else 0.5 * (xs[mid - 1] + xs[mid])


def _requests(rng):
    from apex_tpu.serving import Request

    reqs = []
    for _ in range(REQUESTS):
        n = int(rng.integers(1, PREFILL_LEN + 1))
        budget = max(1, min(NEW_TOKENS, MAX_LEN - n))
        reqs.append(Request(
            prompt=rng.integers(1, VOCAB, size=n).tolist(),
            max_new_tokens=budget))
    return reqs


def _mixed_requests(rng):
    """Interleaved short/long arrivals — the stream where monolithic
    prefill's head-of-line blocking shows: every short prompt queued
    behind a long one pays the long one's full prefill."""
    from apex_tpu.serving import Request

    reqs = []
    for i in range(REQUESTS):
        if i % 2 == 0:
            n = int(rng.integers(1, max(2, SHORT_LEN + 1)))
        else:
            n = int(rng.integers(max(1, PREFILL_LEN // 2),
                                 PREFILL_LEN + 1))
        budget = max(1, min(NEW_TOKENS, MAX_LEN - n))
        reqs.append(Request(
            prompt=rng.integers(1, VOCAB, size=n).tolist(),
            max_new_tokens=budget))
    return reqs


def _build_engine(registry=None, prefix_pool=0, chunk_len=None,
                  slots=None, **engine_kw):
    import jax
    import jax.numpy as jnp

    from apex_tpu import serving
    from apex_tpu.models.transformer_lm import create_lm

    model = create_lm(SIZE, vocab_size=VOCAB, max_seq_len=MAX_LEN)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32),
                        train=False)["params"]
    return serving.Engine(model, params,
                          slots=slots if slots is not None else SLOTS,
                          max_len=MAX_LEN, prefill_len=PREFILL_LEN,
                          chunk_len=chunk_len if chunk_len is not None
                          else (CHUNK_LEN or None),
                          prefix_pool=prefix_pool, top_k=TOP_K,
                          registry=registry, **engine_kw)


def main():
    import jax

    _load_env()

    from apex_tpu import serving, telemetry

    tele = telemetry.from_env()     # APEX_TPU_TELEMETRY streams per-run
    reg = tele if tele is not None else telemetry.MetricsRegistry()

    engine = _build_engine()

    rng = np.random.default_rng(0)
    rates = []
    for w in range(WINDOWS + 1):          # window 0 = compile warmup
        engine.reset()
        if w == 1:
            # attach telemetry only after warmup: first-trace compile
            # latency must not poison the TTFT/step histograms
            engine.set_registry(reg)
        sched = serving.Scheduler(engine, max_queue=max(REQUESTS, 1),
                                  registry=reg if w else None)
        t0 = time.perf_counter()
        tok0 = engine.tokens_generated
        done = sched.run(_requests(rng))
        dt = time.perf_counter() - t0
        toks = engine.tokens_generated - tok0
        assert len(done) == REQUESTS
        if w > 0:
            rates.append(toks / dt)

    snap = reg.snapshot()
    ttft = snap["histograms"].get("serving.ttft_s", {})
    qwait = snap["histograms"].get("serving.queue_wait_s", {})
    chunk = snap["histograms"].get("serving.prefill_chunk_s", {})
    step = snap["histograms"].get("serving.decode.step_s", {})
    occ = snap["histograms"].get("serving.slot_occupancy", {})
    value = _median(rates)
    spread = (max(rates) - min(rates)) / value * 100.0 if value else 0.0
    print(json.dumps({
        "metric": METRIC,
        "value": round(value, 2),
        "unit": "tokens/s",
        "min": round(min(rates), 2),
        "spread_pct": round(spread, 1),
        "windows": WINDOWS,
        "compiled_programs": engine.compiled_programs,
        "model": SIZE,
        "slots": SLOTS,
        "max_len": MAX_LEN,
        "prefill_len": PREFILL_LEN,
        "chunk_len": engine.chunk_len,
        "requests_per_window": REQUESTS,
        "cache_dtype": np.dtype(engine.cache.dtype).name,
        "cache_mib": round(engine.cache.nbytes() / 2**20, 2),
        "ttft_p50_ms": round(ttft.get("p50", 0.0) * 1e3, 3),
        "ttft_p95_ms": round(ttft.get("p95", 0.0) * 1e3, 3),
        "ttft_p99_ms": round(ttft.get("p99", 0.0) * 1e3, 3),
        "queue_wait_p99_ms": round(qwait.get("p99", 0.0) * 1e3, 3),
        "prefill_chunk_p50_ms": round(chunk.get("p50", 0.0) * 1e3, 3),
        "prefill_chunk_p99_ms": round(chunk.get("p99", 0.0) * 1e3, 3),
        "decode_step_p50_ms": round(step.get("p50", 0.0) * 1e3, 3),
        "decode_step_p95_ms": round(step.get("p95", 0.0) * 1e3, 3),
        "decode_step_p99_ms": round(step.get("p99", 0.0) * 1e3, 3),
        "slot_occupancy_mean": round(occ.get("mean", 0.0), 3),
        "padding_waste_mean": round(1.0 - occ.get("mean", 0.0), 3),
        "backend": jax.default_backend(),
    }))
    if tele is not None:
        tele.emit_snapshot()
        tele.close()


def _serve_mixed(chunked: bool):
    """Serve WINDOWS measured windows (plus compile warmup) of the mixed
    stream in one mode; returns (median tokens/s, per-request rows)."""
    from apex_tpu import serving, telemetry

    reg = telemetry.MetricsRegistry()
    engine = _build_engine()
    rng = np.random.default_rng(1)
    rates, all_reqs = [], []
    for w in range(WINDOWS + 1):
        engine.reset()
        if w == 1:
            engine.set_registry(reg)
        sched = serving.Scheduler(engine, max_queue=max(REQUESTS, 1),
                                  registry=reg if w else None,
                                  chunked=chunked,
                                  chunk_budget=CHUNK_BUDGET)
        reqs = _mixed_requests(rng)
        t0 = time.perf_counter()
        tok0 = engine.tokens_generated
        done = sched.run(reqs)
        dt = time.perf_counter() - t0
        toks = engine.tokens_generated - tok0
        assert len(done) == REQUESTS
        if w > 0:
            rates.append(toks / dt)
            all_reqs.extend(reqs)
    return _median(rates), all_reqs, engine


def _ttft_percentiles(reqs, short: bool):
    sel = [r.ttft_s for r in reqs
           if (len(r.prompt) <= SHORT_LEN) == short and r.ttft_s]
    if not sel:
        return 0.0, 0.0
    return (float(np.percentile(sel, 50)) * 1e3,
            float(np.percentile(sel, 99)) * 1e3)


def main_mixed():
    import jax

    _load_env()

    rows = {}
    outputs = {}
    for mode, chunked in (("monolithic", False), ("chunked", True)):
        rate, reqs, engine = _serve_mixed(chunked)
        s50, s99 = _ttft_percentiles(reqs, short=True)
        l50, l99 = _ttft_percentiles(reqs, short=False)
        chunks = [r.chunks for r in reqs]
        rows[mode] = {
            "metric": f"{MIXED_METRIC}.{mode}",
            "value": round(rate, 2),
            "unit": "tokens/s",
            "ttft_short_p50_ms": round(s50, 3),
            "ttft_short_p99_ms": round(s99, 3),
            "ttft_long_p50_ms": round(l50, 3),
            "ttft_long_p99_ms": round(l99, 3),
            "chunks_per_prompt_mean": round(float(np.mean(chunks)), 2),
            "chunks_per_prompt_max": int(np.max(chunks)),
            "compiled_programs": engine.compiled_programs,
            "chunk_len": engine.chunk_len,
            "chunk_budget": CHUNK_BUDGET,
        }
        print(json.dumps(rows[mode]))
        # all-greedy stream: per-window request order is deterministic,
        # so both modes should emit identical token streams
        outputs[mode] = [list(r.output_tokens) for r in reqs]
    # reported, not asserted: at the default bf16 policy the two modes'
    # first tokens come from two separately-fused programs, so a
    # near-tie argmax can legitimately flip a low bit — that is a
    # numerics observation, not a broken serving stack (the O0 bitwise
    # pin lives in tests/L0/test_serving.py). Zero is the expected
    # reading on every backend we have measured.
    mismatches = sum(a != b for a, b in zip(outputs["chunked"],
                                            outputs["monolithic"]))
    mono, chk = rows["monolithic"], rows["chunked"]
    imp = (mono["ttft_short_p99_ms"] - chk["ttft_short_p99_ms"]) \
        / mono["ttft_short_p99_ms"] * 100.0 if mono["ttft_short_p99_ms"] \
        else 0.0
    print(json.dumps({
        "metric": MIXED_METRIC,
        "value": chk["value"],
        "unit": "tokens/s",
        "baseline_tokens_per_s": mono["value"],
        "throughput_vs_monolithic_pct": round(
            (chk["value"] - mono["value"]) / mono["value"] * 100.0, 1)
        if mono["value"] else 0.0,
        "ttft_short_p99_ms": chk["ttft_short_p99_ms"],
        "ttft_short_p99_ms_monolithic": mono["ttft_short_p99_ms"],
        "ttft_short_p99_improvement_pct": round(imp, 1),
        "ttft_long_p99_ms": chk["ttft_long_p99_ms"],
        "ttft_long_p99_ms_monolithic": mono["ttft_long_p99_ms"],
        "token_exact_vs_monolithic": mismatches == 0,
        "token_mismatched_requests": mismatches,
        "windows": WINDOWS,
        "requests_per_window": REQUESTS,
        "short_len_max": SHORT_LEN,
        "prefill_len": PREFILL_LEN,
        "chunk_len": chk["chunk_len"],
        "slots": SLOTS,
        "model": SIZE,
        "backend": jax.default_backend(),
    }))


def _shared_prefix_requests(rng, shared=None):
    """Repeated-system-prompt arrivals: every prompt opens with THE SAME
    shared prefix (drawn once per leg from the mode-independent seed;
    ``shared`` overrides the module global for legs that carry their
    own prefix, e.g. --quantized-kv) followed by a short unique tail —
    the traffic shape where content-addressed prefix reuse pays."""
    from apex_tpu.serving import Request

    if shared is None:
        shared = _SHARED_TOKENS
    reqs = []
    for _ in range(REQUESTS):
        tail = max(1, PREFILL_LEN - len(shared))
        n = int(rng.integers(1, tail + 1))
        prompt = shared + rng.integers(1, VOCAB, size=n).tolist()
        budget = max(1, min(NEW_TOKENS, MAX_LEN - len(prompt)))
        reqs.append(Request(prompt=prompt, max_new_tokens=budget))
    return reqs


_SHARED_TOKENS: list = []


def _serve_shared(retain: bool, chunk_len: int):
    """WINDOWS measured windows (plus compile warmup) of the shared-
    prefix stream; IDENTICAL engine geometry in both modes (the pool is
    allocated either way) so cold vs cached compare the same compiled
    programs — only the scheduler's retain_prefixes flag differs."""
    from apex_tpu import serving, telemetry

    reg = telemetry.MetricsRegistry()
    engine = _build_engine(prefix_pool=PREFIX_POOL, chunk_len=chunk_len)
    rng = np.random.default_rng(2)
    rates, all_reqs, warm_stats = [], [], {}
    for w in range(WINDOWS + 1):
        engine.reset()          # retained prefixes survive (warm cache)
        if w == 1:
            engine.set_registry(reg)
            # measured-window accounting starts here: the compile-warmup
            # window populated the cache (its misses/registrations are
            # cache construction, not serving behaviour), so the
            # reported prefix stats are deltas past this snapshot
            warm_stats = dict(engine.prefix_cache.stats())
        sched = serving.Scheduler(engine, max_queue=max(REQUESTS, 1),
                                  registry=reg if w else None,
                                  chunk_budget=CHUNK_BUDGET,
                                  retain_prefixes=retain)
        reqs = _shared_prefix_requests(rng)
        t0 = time.perf_counter()
        tok0 = engine.tokens_generated
        done = sched.run(reqs)
        dt = time.perf_counter() - t0
        toks = engine.tokens_generated - tok0
        assert len(done) == REQUESTS
        if w > 0:
            rates.append(toks / dt)
            all_reqs.extend(reqs)
    end = engine.prefix_cache.stats()
    delta = {k: end[k] - warm_stats.get(k, 0)
             for k in ("hits", "misses", "tokens_reused", "evictions",
                       "pool_full", "registrations")}
    consulted = delta["hits"] + delta["misses"]
    delta["hit_rate"] = delta["hits"] / consulted if consulted else 0.0
    return _median(rates), all_reqs, engine, delta


def main_shared():
    import jax

    _load_env(smoke=SHARED_SMOKE)

    global _SHARED_TOKENS
    chunk_len = CHUNK_LEN or max(1, PREFILL_LEN // 4)
    rng0 = np.random.default_rng(7)
    # every prompt = shared prefix + >=1 unique token, so the prefix
    # must leave tail room inside the fixed prefill window
    shared_len = min(SHARED_PREFIX, PREFILL_LEN - 1)
    _SHARED_TOKENS = rng0.integers(1, VOCAB, size=shared_len).tolist()
    rows, outputs = {}, {}
    for mode, retain in (("cold", False), ("cached", True)):
        rate, reqs, engine, stats = _serve_shared(retain, chunk_len)
        ttfts = [r.ttft_s for r in reqs if r.ttft_s]
        # every field in this row measures the SAME window set (warmup
        # excluded): chunks/reused summed over measured requests,
        # hit/miss/eviction stats as deltas past the warmup snapshot —
        # so tokens_reused == prefill_chunks_skipped * chunk_len holds
        # by construction (reuse is block-aligned)
        chunks_run = sum(r.chunks for r in reqs)
        reused = sum(r.reused_tokens for r in reqs)
        skipped = reused // engine.chunk_len
        rows[mode] = {
            "metric": f"{SHARED_METRIC}.{mode}",
            "value": round(rate, 2),
            "unit": "tokens/s",
            "ttft_p50_ms": round(
                float(np.percentile(ttfts, 50)) * 1e3, 3) if ttfts else 0.0,
            "ttft_p99_ms": round(
                float(np.percentile(ttfts, 99)) * 1e3, 3) if ttfts else 0.0,
            "prefill_chunks_run": chunks_run,
            "prefill_chunks_skipped": skipped,
            "prefix_hit_rate": round(stats["hit_rate"], 4),
            "tokens_reused": stats["tokens_reused"],
            "evictions": stats["evictions"],
            "pool_full": stats["pool_full"],
            "compiled_programs": engine.compiled_programs,
            "chunk_len": engine.chunk_len,
            "prefix_pool": PREFIX_POOL,
        }
        print(json.dumps(rows[mode]))
        # all-greedy stream from a mode-independent seed: the cached
        # run restores byte-identical K/V through the same compiled
        # programs, so outputs must match the cold run token-for-token
        outputs[mode] = [list(r.output_tokens) for r in reqs]
    mismatches = sum(a != b for a, b in zip(outputs["cached"],
                                            outputs["cold"]))
    cold, cached = rows["cold"], rows["cached"]
    total = cached["prefill_chunks_run"] + cached["prefill_chunks_skipped"]
    print(json.dumps({
        "metric": SHARED_METRIC,
        "value": cached["value"],
        "unit": "tokens/s",
        "baseline_tokens_per_s": cold["value"],
        "prefix_hit_rate": cached["prefix_hit_rate"],
        "prefill_chunks_skipped_pct": round(
            100.0 * cached["prefill_chunks_skipped"] / total, 1)
        if total else 0.0,
        "tokens_reused": cached["tokens_reused"],
        "ttft_p50_ms": cached["ttft_p50_ms"],
        "ttft_p99_ms": cached["ttft_p99_ms"],
        "ttft_p50_ms_cold": cold["ttft_p50_ms"],
        "ttft_p99_ms_cold": cold["ttft_p99_ms"],
        "token_exact_vs_cold": mismatches == 0,
        "token_mismatched_requests": mismatches,
        "windows": WINDOWS,
        "requests_per_window": REQUESTS,
        "shared_prefix_len": shared_len,
        "prefill_len": PREFILL_LEN,
        "chunk_len": cached["chunk_len"],
        "prefix_pool": PREFIX_POOL,
        "slots": SLOTS,
        "model": SIZE,
        "backend": jax.default_backend(),
    }))


def _short_requests(rng):
    """Short-prompt arrivals — the stream where row-granularity HBM
    waste is worst: a 512-position contiguous row holds a <= 32-token
    prompt plus a small budget, >90% of the row dead."""
    from apex_tpu.serving import Request

    reqs = []
    for _ in range(REQUESTS):
        n = int(rng.integers(1, min(PAGED_PROMPT, PREFILL_LEN) + 1))
        budget = max(1, min(NEW_TOKENS, MAX_LEN - n))
        reqs.append(Request(
            prompt=rng.integers(1, VOCAB, size=n).tolist(),
            max_new_tokens=budget))
    return reqs


def _serve_paged_leg(paged: bool, slots: int, num_pages=None, *,
                     requests_fn=_short_requests, seed: int = 3,
                     retain_prefixes: bool = False, **engine_kw):
    """One mode of the --paged-pool (and, parameterized, --quantized-kv)
    leg: WINDOWS measured windows (plus compile warmup) of the
    ``requests_fn`` stream, tracking the peak number of in-flight
    (prefilling + running) requests per window and, on the paged
    engine, peak pages_in_use. ``retain_prefixes`` serves with prefix
    retention on and clears the prefix pool between windows (identical
    cold start per mode — cross-mode comparisons stay
    window-for-window honest); extra kwargs reach the Engine."""
    from apex_tpu import serving, telemetry

    reg = telemetry.MetricsRegistry()
    kw = {"paged": paged, **engine_kw}
    if paged and num_pages is not None:
        kw["num_pages"] = num_pages
    engine = _build_engine(slots=slots, **kw)
    rng = np.random.default_rng(seed)
    rates, all_reqs = [], []
    peak_inflight = peak_pages = 0
    for w in range(WINDOWS + 1):
        engine.reset(clear_prefixes=retain_prefixes)
        if w == 1:
            engine.set_registry(reg)
        sched = serving.Scheduler(engine, max_queue=max(REQUESTS, 1),
                                  registry=reg if w else None,
                                  chunk_budget=CHUNK_BUDGET,
                                  retain_prefixes=retain_prefixes)
        reqs = requests_fn(rng)
        t0 = time.perf_counter()
        tok0 = engine.tokens_generated
        for r in reqs:
            sched.submit(r)
        while sched.pending:
            sched.step()
            if w > 0:
                inflight = sum(r.status in ("prefilling", "running")
                               for r in reqs)
                peak_inflight = max(peak_inflight, inflight)
                if paged:
                    peak_pages = max(peak_pages,
                                     engine.pool_stats()["pages_in_use"])
        dt = time.perf_counter() - t0
        toks = engine.tokens_generated - tok0
        assert len(sched.completed) >= len(reqs)
        if w > 0:
            rates.append(toks / dt)
            all_reqs.extend(reqs)
    return _median(rates), all_reqs, engine, peak_inflight, peak_pages


def paged_capacity_stats():
    """The --paged-pool measurement, reusable by bench.py's serving
    trajectory leg: serve the short-prompt stream on the contiguous
    engine (SLOTS rows) and on the paged engine given the SAME physical
    pool bytes but 4x the decode slots; return the two rows plus the
    headline comparison dict. Token streams are greedy and compared
    request-for-request."""
    from apex_tpu.serving.engine import resolve_page_len

    # replicate the Engine's chunk_len default EXACTLY (incl. the
    # spill-to-single-chunk degrade) so the page size below is the one
    # the constructed engine will actually use
    chunk = CHUNK_LEN or min(PREFILL_LEN, 256)
    if not CHUNK_LEN and -(-PREFILL_LEN // chunk) * chunk > MAX_LEN:
        chunk = PREFILL_LEN
    paged_slots = PAGED_SLOTS or SLOTS * 4
    # identical pool bytes: the paged pool spends the contiguous
    # layout's slots * max_len positions, sentinel INCLUDED in the
    # count (the paged engine measurably holds one page less).
    # resolve_page_len is the Engine's own resolution (tuned
    # decode.page_len key included) — sizing with anything else would
    # silently hand the paged engine a different byte budget
    page_len = resolve_page_len(chunk)
    num_pages = SLOTS * MAX_LEN // page_len
    rows, outputs = {}, {}
    for mode, paged in (("contiguous", False), ("paged", True)):
        rate, reqs, engine, peak_inflight, peak_pages = _serve_paged_leg(
            paged, paged_slots if paged else SLOTS,
            num_pages if paged else None)
        if paged:
            # worst-case reservation per request (what admission holds)
            # -> HBM bytes the request can ever touch
            per_pos = engine.cache.nbytes() \
                / (engine.num_pages * engine.page_len)
            demands = [engine.pages_required(len(r.prompt),
                                             r.max_new_tokens)
                       * engine.page_len for r in reqs]
            bytes_per_req = float(np.mean(demands)) * per_pos
        else:
            per_pos = engine.cache.nbytes() \
                / ((engine.slots + engine.prefix_pool) * engine.max_len)
            bytes_per_req = engine.max_len * per_pos   # a whole row
        rows[mode] = {
            "metric": f"{PAGED_METRIC}.{mode}",
            "value": round(rate, 2),
            "unit": "tokens/s",
            "slots": engine.slots,
            "max_concurrent_requests": peak_inflight,
            "hbm_bytes_per_request": round(bytes_per_req),
            "pool_mib": round(engine.cache.nbytes() / 2**20, 2),
            "compiled_programs": engine.compiled_programs,
        }
        if paged:
            rows[mode]["page_len"] = engine.page_len
            rows[mode]["num_pages"] = engine.num_pages
            rows[mode]["peak_pages_in_use"] = peak_pages
            rows[mode]["copy_programs"] = engine.copy_traces
        outputs[mode] = [list(r.output_tokens) for r in reqs]
    mismatches = sum(a != b for a, b in zip(outputs["paged"],
                                            outputs["contiguous"]))
    con, pag = rows["contiguous"], rows["paged"]
    reduction = (1.0 - pag["hbm_bytes_per_request"]
                 / con["hbm_bytes_per_request"]) * 100.0 \
        if con["hbm_bytes_per_request"] else 0.0
    summary = {
        "metric": PAGED_METRIC,
        "value": pag["value"],
        "unit": "tokens/s",
        "baseline_tokens_per_s": con["value"],
        "max_concurrent_requests": pag["max_concurrent_requests"],
        "max_concurrent_requests_contiguous":
            con["max_concurrent_requests"],
        "contiguous_slots": con["slots"],
        "logical_concurrency_exceeds_rows":
            pag["max_concurrent_requests"] > con["slots"],
        "hbm_bytes_per_request": pag["hbm_bytes_per_request"],
        "hbm_bytes_per_request_contiguous":
            con["hbm_bytes_per_request"],
        "hbm_bytes_per_request_reduction_pct": round(reduction, 1),
        "pool_mib": pag["pool_mib"],
        "pool_mib_contiguous": con["pool_mib"],
        "peak_pages_in_use": pag["peak_pages_in_use"],
        "token_exact_vs_contiguous": mismatches == 0,
        "token_mismatched_requests": mismatches,
        "windows": WINDOWS,
        "requests_per_window": REQUESTS,
        "short_prompt_max": min(PAGED_PROMPT, PREFILL_LEN),
        "model": SIZE,
    }
    return rows, summary


def main_paged():
    import jax

    _load_env()

    rows, summary = paged_capacity_stats()
    for mode in ("contiguous", "paged"):
        print(json.dumps(rows[mode]))
    summary["backend"] = jax.default_backend()
    print(json.dumps(summary))


def _chaos_requests():
    """A deterministic greedy stream (mode-independent seed): identical
    prompts/budgets served at fault rate 0 and at FAULT_PCT, so the
    two modes' outputs compare request-for-request."""
    from apex_tpu.serving import Request

    rng = np.random.default_rng(5)
    reqs = []
    for _ in range(REQUESTS):
        n = int(rng.integers(1, PREFILL_LEN + 1))
        budget = max(1, min(NEW_TOKENS, MAX_LEN - n))
        reqs.append(Request(
            prompt=rng.integers(1, VOCAB, size=n).tolist(),
            max_new_tokens=budget))
    return reqs


def _serve_chaos(engine, plan):
    """One mode of the --chaos leg: serve the deterministic stream with
    (or without) an injection plan under the standard containment
    policy; returns (requests, wall seconds, scheduler)."""
    from apex_tpu import serving

    policy = serving.FaultPolicy(max_retries=2, backoff_base_s=0.0,
                                 audit_every_n=1)
    sched = serving.Scheduler(engine, max_queue=max(REQUESTS, 1),
                              chunk_budget=CHUNK_BUDGET,
                              fault_policy=policy, fault_plan=plan)
    reqs = _chaos_requests()
    t0 = time.perf_counter()
    done = sched.run(reqs, max_steps=REQUESTS * (NEW_TOKENS + 64))
    dt = time.perf_counter() - t0
    assert len(done) == REQUESTS
    return reqs, dt, sched


def chaos_stats():
    """The --chaos measurement, reusable by bench.py's serving
    trajectory leg: the identical greedy stream at fault rate 0 vs
    FAULT_PCT% per-tick injection (seeded, deterministic). Headline
    fields: goodput (clean-request tokens/s — requests that never
    faulted), failed/requeued/injected counts, and
    token_mismatched_requests (clean chaos-run requests vs the rate-0
    run; the containment guarantee says 0, bitwise). A discarded
    warmup pass compiles the programs first, so the rate-0 goodput row
    is not poisoned by trace latency."""
    from apex_tpu import serving

    engine = _build_engine()
    _serve_chaos(engine, None)      # compile warmup, discarded
    rows = {}
    outputs = {}
    # ticks upper bound for the plan: every request's decode budget
    # plus generous prefill/requeue slack — the plan just needs to
    # cover the run, extra scheduled ticks never fire
    ticks = REQUESTS * (NEW_TOKENS + 64)
    for mode in ("rate0", "chaos"):
        engine.reset()
        if mode == "rate0":
            plan = None
        else:
            plan = serving.FaultPlan.random(
                9, ticks, slots=SLOTS,
                nonfinite_rate=FAULT_PCT / 100.0,
                exception_rate=FAULT_PCT / 200.0)
        reqs, dt, sched = _serve_chaos(engine, plan)
        clean = [r for r in reqs if r.retries == 0
                 and r.status == "finished"]
        goodput = sum(len(r.output_tokens) for r in clean) / dt \
            if dt > 0 else 0.0
        audit = sched.auditor.audit(engine) if sched.auditor else {}
        rows[mode] = {
            "metric": f"{CHAOS_METRIC}.{mode}",
            "value": round(goodput, 2),
            "unit": "tokens/s",
            "clean_requests": len(clean),
            "failed_requests": sum(r.status == "failed" for r in reqs),
            "requeued_retries": sum(r.retries for r in reqs),
            "injected": plan.stats() if plan is not None else {},
            "pages_in_use_at_drain": audit.get("pages_in_use", 0),
            "compiled_programs": engine.compiled_programs,
        }
        outputs[mode] = {i: list(r.output_tokens)
                         for i, r in enumerate(reqs)
                         if r.retries == 0 and r.status == "finished"}
    # a clean chaos-run request must match the rate-0 run bitwise —
    # requests the plan faulted (retried or failed) are excluded, the
    # containment guarantee is about everyone else
    mismatches = sum(outputs["chaos"][i] != outputs["rate0"].get(i)
                     for i in outputs["chaos"])
    r0, rc = rows["rate0"], rows["chaos"]
    summary = {
        "metric": CHAOS_METRIC,
        "value": rc["value"],
        "unit": "tokens/s",
        "goodput_rate0_tokens_per_s": r0["value"],
        "goodput_retention_pct": round(
            100.0 * rc["value"] / r0["value"], 1)
        if r0["value"] else 0.0,
        "fault_pct": FAULT_PCT,
        "clean_requests": rc["clean_requests"],
        "failed_requests": rc["failed_requests"],
        "requeued_retries": rc["requeued_retries"],
        "injected": rc["injected"],
        "token_mismatched_requests": mismatches,
        "token_exact_clean_vs_rate0": mismatches == 0,
        "pages_in_use_at_drain": rc["pages_in_use_at_drain"],
        "requests_per_window": REQUESTS,
        "model": SIZE,
    }
    return rows, summary


def main_chaos():
    import jax

    _load_env(smoke=CHAOS_SMOKE)

    rows, summary = chaos_stats()
    for mode in ("rate0", "chaos"):
        print(json.dumps(rows[mode]))
    summary["backend"] = jax.default_backend()
    print(json.dumps(summary))


def _spec_streams():
    """The two drafter-friendly stream factories, seeded independently
    of mode so plain and speculative serve IDENTICAL prompts:
    shared-prefix (every prompt opens with one system prefix) and
    multi-turn (a shared conversation history + a per-request tail
    repeated twice — the trailing n-gram matches its own first copy, so
    the drafter fires from the very first decode step)."""
    rng0 = np.random.default_rng(7)
    shared_len = min(SHARED_PREFIX, PREFILL_LEN - 1)
    shared = rng0.integers(1, VOCAB, size=shared_len).tolist()
    history_len = min(SHARED_PREFIX, max(1, PREFILL_LEN - 8))
    history = rng0.integers(1, VOCAB, size=history_len).tolist()

    from apex_tpu.serving import Request

    def shared_prefix(rng):
        reqs = []
        for _ in range(REQUESTS):
            tail = max(1, PREFILL_LEN - len(shared))
            n = int(rng.integers(1, tail + 1))
            prompt = shared + rng.integers(1, VOCAB, size=n).tolist()
            budget = max(1, min(NEW_TOKENS, MAX_LEN - len(prompt)))
            reqs.append(Request(prompt=prompt, max_new_tokens=budget))
        return reqs

    def multi_turn(rng):
        reqs = []
        for _ in range(REQUESTS):
            room = max(2, PREFILL_LEN - len(history))
            u = int(rng.integers(1, max(2, room // 2 + 1)))
            tail = rng.integers(1, VOCAB, size=u).tolist()
            prompt = (history + tail + tail)[:PREFILL_LEN]
            budget = max(1, min(NEW_TOKENS, MAX_LEN - len(prompt)))
            reqs.append(Request(prompt=prompt, max_new_tokens=budget))
        return reqs

    return {"shared_prefix": shared_prefix, "multi_turn": multi_turn}


def _serve_spec(engine, factory, seed, speculative):
    """WINDOWS measured windows (plus compile warmup) of one stream in
    one mode; per-mode registry so the acceptance stats are the
    measured windows' own."""
    from apex_tpu import serving, telemetry

    reg = telemetry.MetricsRegistry()
    rng = np.random.default_rng(seed)
    rates, all_reqs = [], []
    tok0 = step0 = ver0 = 0
    for w in range(WINDOWS + 1):
        engine.reset()
        engine.set_registry(reg if w else None)
        sched = serving.Scheduler(engine, max_queue=max(REQUESTS, 1),
                                  registry=reg if w else None,
                                  chunk_budget=CHUNK_BUDGET,
                                  speculative=speculative)
        reqs = factory(rng)
        t0 = time.perf_counter()
        tokw = engine.tokens_generated
        done = sched.run(reqs)
        dt = time.perf_counter() - t0
        assert len(done) == REQUESTS
        if w > 0:
            rates.append((engine.tokens_generated - tokw) / dt)
            all_reqs.extend(reqs)
    engine.set_registry(None)
    snap = reg.snapshot()
    return _median(rates), all_reqs, snap


def spec_stats():
    """The --speculative measurement, reusable by bench.py's serving
    trajectory leg: both drafter-friendly streams served plain vs
    speculative on ONE spec-enabled engine (same compiled programs —
    the verify program only ever traces once), with per-mode
    acceptance stats and a bitwise token comparison. A discarded
    warmup window per (stream, mode) keeps trace latency out of the
    rates."""
    from apex_tpu.serving import SpecConfig

    engine = _build_engine(spec=SpecConfig(draft_len=SPEC_K, ngram=3))
    rows, summaries = {}, {}
    for stream, factory in _spec_streams().items():
        outputs = {}
        for mode, speculative in (("plain", False), ("spec", True)):
            rate, reqs, snap = _serve_spec(engine, factory,
                                           seed=11, speculative=speculative)
            drafted = snap["counters"].get("serving.spec.drafted", 0)
            accepted = snap["counters"].get("serving.spec.accepted", 0)
            acc_hist = snap["histograms"].get(
                "serving.spec.acceptance_rate", {})
            # batched verify: serving.spec.verify_s counts DISPATCHES
            # (one [slots, K+1] call per heartbeat with >=1 eligible
            # slot); the per-SLOT sequence-step arithmetic below wants
            # slot-steps, which the engine counts separately
            verify_dispatches = snap["histograms"].get(
                "serving.spec.verify_s", {}).get("count", 0)
            verify_slots = snap["counters"].get(
                "serving.spec.verify_slots", 0)
            decode_steps = snap["counters"].get("serving.decode.steps",
                                                0)
            emitted = sum(len(r.output_tokens) for r in reqs)
            # per-SLOT sequence steps: each decode-emitted token is one
            # slot advancing one step (batch width is not speculation —
            # plain decode must read exactly 1.0), each verified slot is
            # one slot-step emitting n_accepted + 1 tokens
            spec_emitted = int(accepted) + int(verify_slots)
            decode_emitted = emitted - len(reqs) - spec_emitted
            seq_steps = verify_slots + decode_emitted
            row = {
                "metric": f"{SPEC_METRIC}.{stream}.{mode}",
                "value": round(rate, 2),
                "unit": "tokens/s",
                "drafted": int(drafted),
                "accepted": int(accepted),
                "acceptance_rate": round(accepted / drafted, 4)
                if drafted else 0.0,
                "acceptance_p50": round(acc_hist.get("p50", 0.0), 4),
                "acceptance_p99": round(acc_hist.get("p99", 0.0), 4),
                "verify_calls": int(verify_dispatches),
                "verify_slot_steps": int(verify_slots),
                "decode_steps": int(decode_steps),
                # the per-request prefill token is excluded from the
                # numerator: it rides the chunk program either way
                "tokens_per_step": round(
                    (emitted - len(reqs)) / seq_steps, 3)
                if seq_steps else 0.0,
                "spec_accepted_per_request": round(
                    float(np.mean([r.spec_accepted for r in reqs])), 2),
                "compiled_programs": engine.compiled_programs,
            }
            rows[f"{stream}.{mode}"] = row
            outputs[mode] = [list(r.output_tokens) for r in reqs]
        summaries[stream] = {
            "mismatches": sum(a != b for a, b in zip(outputs["spec"],
                                                     outputs["plain"])),
        }
    sp = rows["shared_prefix.spec"]
    mt = rows["multi_turn.spec"]
    mism = (summaries["shared_prefix"]["mismatches"]
            + summaries["multi_turn"]["mismatches"])
    summary = {
        "metric": SPEC_METRIC,
        "value": sp["value"],
        "unit": "tokens/s",
        "baseline_tokens_per_s": rows["shared_prefix.plain"]["value"],
        "acceptance_rate": sp["acceptance_rate"],
        "acceptance_p50": sp["acceptance_p50"],
        "acceptance_p99": sp["acceptance_p99"],
        "tokens_per_step": sp["tokens_per_step"],
        "tokens_per_step_plain": rows["shared_prefix.plain"][
            "tokens_per_step"],
        "multi_turn_tokens_per_s": mt["value"],
        "multi_turn_acceptance_rate": mt["acceptance_rate"],
        "multi_turn_tokens_per_step": mt["tokens_per_step"],
        "token_exact_vs_plain": mism == 0,
        "token_mismatched_requests": mism,
        "spec_k": SPEC_K,
        "windows": WINDOWS,
        "requests_per_window": REQUESTS,
        "compiled_programs": engine.compiled_programs,
        "verify_traces": engine.verify_traces,
        "model": SIZE,
    }
    return rows, summary


def main_spec():
    import jax

    _load_env(smoke=SPEC_SMOKE)

    rows, summary = spec_stats()
    for row in rows.values():
        print(json.dumps(row))
    summary["backend"] = jax.default_backend()
    print(json.dumps(summary))


def quantized_kv_stats():
    """The --quantized-kv measurement, reusable by bench.py's serving
    trajectory leg: the shared-prefix greedy stream served by the bf16
    engine (``kv_quant=None`` — the bitwise oracle) and by the int8
    engine (``KVQuantConfig`` calibrated on the shared prefix) given
    the SAME physical pool bytes but ~2x the decode slots — possible
    because int8 halves bytes-per-position. Headline fields:
    ``kv_bytes_per_token`` both modes + reduction pct (the >= 45%
    acceptance bar; 50% by construction), ``hbm_bytes_per_request``
    both modes, ``max_concurrent_requests`` both modes, and
    ``token_match_rate`` — positionwise greedy agreement vs the bf16
    oracle (the tolerance contract; ``kv_quant=None`` stays bitwise).
    CPU-regime caveat: the int8 engine's wider decode batch costs MORE
    per step on the CPU fallback, so judge tokens/s on TPU rows —
    capacity, bytes and match-rate are the leg's claim."""
    from apex_tpu import telemetry
    from apex_tpu.serving import KVQuantConfig
    from apex_tpu.serving.engine import resolve_page_len

    # replicate the Engine's chunk_len default EXACTLY (incl. the
    # spill-to-single-chunk degrade) — same discipline as the paged leg
    chunk = CHUNK_LEN or min(PREFILL_LEN, 256)
    if not CHUNK_LEN and -(-PREFILL_LEN // chunk) * chunk > MAX_LEN:
        chunk = PREFILL_LEN
    page_len = resolve_page_len(chunk)
    num_pages = SLOTS * MAX_LEN // page_len
    quant_slots = QUANT_SLOTS or SLOTS * 2
    rng0 = np.random.default_rng(7)
    shared_len = min(SHARED_PREFIX, PREFILL_LEN - 1)
    shared = rng0.integers(1, VOCAB, size=shared_len).tolist()
    # calibrate on the stream's own shared prefix — representative
    # traffic beats the seeded random fallback, exactly the guidance
    # docs/serving.md gives operators
    cfg = KVQuantConfig(calibration_tokens=list(shared))
    rows, outputs = {}, {}
    for mode in ("bf16", "int8"):
        quant = mode == "int8"
        rate, reqs, engine, peak_inflight, peak_pages = _serve_paged_leg(
            True, quant_slots if quant else SLOTS,
            # identical pool BYTES: int8 positions cost half a bf16
            # position, so the same budget holds 2x the pages
            num_pages * 2 if quant else num_pages,
            requests_fn=lambda r: _shared_prefix_requests(r, shared),
            seed=6, retain_prefixes=True, prefix_pool=PREFIX_POOL,
            kv_quant=cfg if quant else None)
        # the serving.kv.* gauges ARE the capacity-claim numbers — read
        # them from the engine's own emitter rather than re-deriving
        # the formulas here
        reg = telemetry.MetricsRegistry()
        engine.set_registry(reg)
        gauges = reg.snapshot()["gauges"]
        per_pos = engine.cache.nbytes() \
            / (engine.num_pages * engine.page_len)
        demands = [engine.pages_required(len(r.prompt),
                                         r.max_new_tokens)
                   * engine.page_len for r in reqs]
        rows[mode] = {
            "metric": f"{QUANT_METRIC}.{mode}",
            "value": round(rate, 2),
            "unit": "tokens/s",
            "slots": engine.slots,
            "cache_dtype": np.dtype(engine.cache.dtype).name,
            "kv_bytes_per_token":
                int(gauges["serving.kv.bytes_per_token"]),
            "hbm_bytes_per_request": round(float(np.mean(demands))
                                           * per_pos),
            "pool_mib": round(engine.cache.nbytes() / 2**20, 2),
            "num_pages": engine.num_pages,
            "max_concurrent_requests": peak_inflight,
            "peak_pages_in_use": peak_pages,
            "compiled_programs": engine.compiled_programs,
        }
        if quant:
            rows[mode]["quant_scale_absmax"] = round(
                gauges["serving.kv.quant_scale_absmax"], 4)
        outputs[mode] = [list(r.output_tokens) for r in reqs]
    tot = hit = mismatched = 0
    for a, b in zip(outputs["bf16"], outputs["int8"]):
        tot += max(len(a), len(b))
        hit += sum(int(x == y) for x, y in zip(a, b))
        mismatched += int(a != b)
    bf, q8 = rows["bf16"], rows["int8"]
    summary = {
        "metric": QUANT_METRIC,
        "value": q8["value"],
        "unit": "tokens/s",
        "baseline_tokens_per_s": bf["value"],
        "token_match_rate": round(hit / tot, 4) if tot else 1.0,
        "token_mismatched_requests": mismatched,
        "kv_bytes_per_token": q8["kv_bytes_per_token"],
        "kv_bytes_per_token_bf16": bf["kv_bytes_per_token"],
        "kv_bytes_per_token_reduction_pct": round(
            (1.0 - q8["kv_bytes_per_token"]
             / bf["kv_bytes_per_token"]) * 100.0, 1)
        if bf["kv_bytes_per_token"] else 0.0,
        "hbm_bytes_per_request": q8["hbm_bytes_per_request"],
        "hbm_bytes_per_request_bf16": bf["hbm_bytes_per_request"],
        "hbm_bytes_per_request_reduction_pct": round(
            (1.0 - q8["hbm_bytes_per_request"]
             / bf["hbm_bytes_per_request"]) * 100.0, 1)
        if bf["hbm_bytes_per_request"] else 0.0,
        "max_concurrent_requests": q8["max_concurrent_requests"],
        "max_concurrent_requests_bf16": bf["max_concurrent_requests"],
        "slots": q8["slots"],
        "slots_bf16": bf["slots"],
        "pool_mib": q8["pool_mib"],
        "pool_mib_bf16": bf["pool_mib"],
        "quant_scale_absmax": q8["quant_scale_absmax"],
        "windows": WINDOWS,
        "requests_per_window": REQUESTS,
        "shared_prefix_len": shared_len,
        "model": SIZE,
    }
    return rows, summary


def main_quant():
    import jax

    _load_env(smoke=dict(QUANT_SMOKE))

    rows, summary = quantized_kv_stats()
    for mode in ("bf16", "int8"):
        print(json.dumps(rows[mode]))
    summary["backend"] = jax.default_backend()
    print(json.dumps(summary))


def quantized_weights_stats():
    """The --quantized-weights measurement, reusable by bench.py's
    serving trajectory leg: the shared-prefix greedy stream served
    THREE ways at IDENTICAL engine geometry — bf16 weights
    (``weight_quant=None``, the bitwise oracle), int8 weights
    (``WeightQuantConfig()``: per-output-channel scales, dequant in
    the GEMM epilogues), and int8 weights + int8 KV (the combined
    tier, ``kv_quant`` calibrated on the shared prefix). Headline
    fields: ``weight_bytes_reduction_pct`` (the >= 45% acceptance
    bar), ``bytes_per_param`` both modes (scale overhead charged in),
    ``hbm_bytes_per_request`` bf16 vs combined (the KV half of the
    combined claim), and ``token_match_rate`` /
    ``combined_token_match_rate`` — positionwise greedy agreement vs
    the bf16 oracle (the tolerance contract; ``weight_quant=None``
    stays bitwise). CPU-regime caveat: the reference-path GEMMs
    dequantize by materialising, so quantized tokens/s reads flat
    here — weight bytes, per-request bytes and match-rate are the
    leg's claim; tokens/s is the TPU rows' (half the weight DMA per
    GEMM)."""
    from apex_tpu import telemetry
    from apex_tpu.serving import KVQuantConfig, WeightQuantConfig
    from apex_tpu.serving.weight_quant import (param_bytes, param_count,
                                               quant_scale_absmax)

    rng0 = np.random.default_rng(7)
    shared_len = min(SHARED_PREFIX, PREFILL_LEN - 1)
    shared = rng0.integers(1, VOCAB, size=shared_len).tolist()
    kv_cfg = KVQuantConfig(calibration_tokens=list(shared))
    modes = {
        "bf16": {},
        "int8w": {"weight_quant": WeightQuantConfig()},
        "int8w_int8kv": {"weight_quant": WeightQuantConfig(),
                         "kv_quant": kv_cfg},
    }
    rows, outputs = {}, {}
    for mode, kw in modes.items():
        rate, reqs, engine, peak_inflight, _pages = _serve_paged_leg(
            True, SLOTS, None,
            requests_fn=lambda r: _shared_prefix_requests(r, shared),
            seed=6, retain_prefixes=True, prefix_pool=PREFIX_POOL, **kw)
        reg = telemetry.MetricsRegistry()
        engine.set_registry(reg)
        gauges = reg.snapshot()["gauges"]
        per_pos = engine.cache.nbytes() \
            / (engine.num_pages * engine.page_len)
        demands = [engine.pages_required(len(r.prompt),
                                         r.max_new_tokens)
                   * engine.page_len for r in reqs]
        w_bytes = param_bytes(engine.params)
        rows[mode] = {
            "metric": f"{WQUANT_METRIC}.{mode}",
            "value": round(rate, 2),
            "unit": "tokens/s",
            "slots": engine.slots,
            "weight_mib": round(w_bytes / 2**20, 3),
            "bytes_per_param": round(
                w_bytes / param_count(engine.params), 3),
            "cache_dtype": np.dtype(engine.cache.dtype).name,
            "kv_bytes_per_token":
                int(gauges["serving.kv.bytes_per_token"]),
            "hbm_bytes_per_request": round(float(np.mean(demands))
                                           * per_pos),
            "max_concurrent_requests": peak_inflight,
            "compiled_programs": engine.compiled_programs,
        }
        if "weight_quant" in kw:
            rows[mode]["quant_scale_absmax"] = round(
                quant_scale_absmax(engine.params), 4)
        outputs[mode] = [list(r.output_tokens) for r in reqs]

    def _match(mode):
        tot = hit = mismatched = 0
        for a, b in zip(outputs["bf16"], outputs[mode]):
            tot += max(len(a), len(b))
            hit += sum(int(x == y) for x, y in zip(a, b))
            mismatched += int(a != b)
        return (hit / tot if tot else 1.0), mismatched

    rate_w, mism_w = _match("int8w")
    rate_c, mism_c = _match("int8w_int8kv")
    bf, w8, c8 = rows["bf16"], rows["int8w"], rows["int8w_int8kv"]
    summary = {
        "metric": WQUANT_METRIC,
        "value": w8["value"],
        "unit": "tokens/s",
        "baseline_tokens_per_s": bf["value"],
        "combined_tokens_per_s": c8["value"],
        "token_match_rate": round(rate_w, 4),
        "token_mismatched_requests": mism_w,
        "combined_token_match_rate": round(rate_c, 4),
        "combined_token_mismatched_requests": mism_c,
        "weight_mib": w8["weight_mib"],
        "weight_mib_bf16": bf["weight_mib"],
        "weight_bytes_reduction_pct": round(
            (1.0 - w8["weight_mib"] / bf["weight_mib"]) * 100.0, 1)
        if bf["weight_mib"] else 0.0,
        "bytes_per_param": w8["bytes_per_param"],
        "bytes_per_param_bf16": bf["bytes_per_param"],
        "hbm_bytes_per_request": c8["hbm_bytes_per_request"],
        "hbm_bytes_per_request_bf16": bf["hbm_bytes_per_request"],
        "hbm_bytes_per_request_reduction_pct": round(
            (1.0 - c8["hbm_bytes_per_request"]
             / bf["hbm_bytes_per_request"]) * 100.0, 1)
        if bf["hbm_bytes_per_request"] else 0.0,
        "quant_scale_absmax": w8["quant_scale_absmax"],
        "slots": w8["slots"],
        "windows": WINDOWS,
        "requests_per_window": REQUESTS,
        "shared_prefix_len": shared_len,
        "model": SIZE,
    }
    return rows, summary


def main_wquant():
    import jax

    _load_env(smoke=dict(WQUANT_SMOKE))

    rows, summary = quantized_weights_stats()
    for mode in ("bf16", "int8w", "int8w_int8kv"):
        print(json.dumps(rows[mode]))
    summary["backend"] = jax.default_backend()
    print(json.dumps(summary))


def _ensure_cpu_devices(n: int) -> None:
    """Force the CPU backend with >= ``n`` emulated devices BEFORE the
    first backend initialization (XLA reads ``XLA_FLAGS`` when a client
    is created, so this works even though jax was imported by the
    guard). The TP leg is CPU device emulation by definition — its
    claims are exactness and per-shard HBM accounting, never emulated
    tokens/s. A backend that initialized too early fails loudly: run
    the leg standalone (or via bench.py's subprocess embedding)."""
    import jax

    want = max(int(n), 1)
    flags = os.environ.get("XLA_FLAGS", "")
    pat = r"--xla_force_host_platform_device_count=(\d+)"
    m = re.search(pat, flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (
            flags
            + f" --xla_force_host_platform_device_count={want}").strip()
    elif int(m.group(1)) < want:
        # a pre-existing smaller count would starve the mesh — raise
        # it (harmless if the backend is already live: the loud check
        # below still catches that case)
        os.environ["XLA_FLAGS"] = re.sub(
            pat, f"--xla_force_host_platform_device_count={want}",
            flags)
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
    have = len(jax.devices())
    if have < n:
        raise SystemExit(
            f"mesh leg needs {n} CPU devices, got {have}: the jax "
            "backend initialized before XLA_FLAGS could take effect — "
            "run the leg standalone (bench.py embeds the mesh legs as "
            "subprocesses for this reason)")


def _serve_tp(engine, seed: int):
    """WINDOWS measured windows (plus compile warmup) of the standard
    variable-length greedy stream on one engine; identical seed per
    mode so the two modes' outputs compare request-for-request."""
    from apex_tpu import serving, telemetry

    reg = telemetry.MetricsRegistry()
    rng = np.random.default_rng(seed)
    rates, all_reqs = [], []
    for w in range(WINDOWS + 1):
        engine.reset()
        engine.set_registry(reg if w else None)
        sched = serving.Scheduler(engine, max_queue=max(REQUESTS, 1),
                                  registry=reg if w else None,
                                  chunk_budget=CHUNK_BUDGET)
        reqs = _requests(rng)
        t0 = time.perf_counter()
        tok0 = engine.tokens_generated
        done = sched.run(reqs)
        dt = time.perf_counter() - t0
        assert len(done) == REQUESTS
        if w > 0:
            rates.append((engine.tokens_generated - tok0) / dt)
            all_reqs.extend(reqs)
    engine.set_registry(None)
    return _median(rates), all_reqs, reg.snapshot()


def tp_stats():
    """The --tensor-parallel measurement, reusable by bench.py's
    serving trajectory leg (via subprocess — the parent's backend is
    already initialized): the SAME greedy stream on the verbatim
    single-chip engine (mesh=None, the honest tp=1 baseline) and on
    ``Engine(mesh=<TP shards>)``. Headline fields: tokens/s both modes
    (CPU emulation — a plumbing/capacity signal, judge throughput on
    silicon), per-shard KV HBM bytes (the heads-axis split's 1/tp
    claim), the per-program collective inventory, and
    token_mismatched_requests (expected 0: tp=1 is bitwise-pinned,
    tp>1 token-exact)."""
    import jax
    from jax.sharding import Mesh

    from apex_tpu.serving import sharding

    _ensure_cpu_devices(TP)
    rows, outputs = {}, {}
    for mode in ("tp1", "sharded"):
        mesh = None if mode == "tp1" else \
            Mesh(np.array(jax.devices()[:TP]), ("tp",))
        engine = _build_engine(mesh=mesh)
        rate, reqs, snap = _serve_tp(engine, seed=13)
        rows[mode] = {
            "metric": f"{TP_METRIC}.{mode}",
            "value": round(rate, 2),
            "unit": "tokens/s",
            "tp": engine.tp,
            "hbm_bytes_per_shard": engine.cache.nbytes() // engine.tp,
            "pool_mib": round(engine.cache.nbytes() / 2**20, 2),
            "compiled_programs": engine.compiled_programs,
            "decode_step_p50_ms": round(
                snap["histograms"].get("serving.decode.step_s",
                                       {}).get("p50", 0.0) * 1e3, 3),
        }
        if mesh is not None:
            coll = sharding.expected_collectives(
                int(engine.cache.layers))
            rows[mode]["psums_per_program"] = coll["all_reduce"]
            rows[mode]["all_gathers_per_program"] = coll["all_gather"]
            rows[mode]["tp_gauges"] = {
                k: v for k, v in snap["gauges"].items()
                if k.startswith("serving.tp.")}
        outputs[mode] = [list(r.output_tokens) for r in reqs]
    mismatches = sum(a != b for a, b in zip(outputs["sharded"],
                                            outputs["tp1"]))
    t1, sh = rows["tp1"], rows["sharded"]
    summary = {
        "metric": TP_METRIC,
        "value": sh["value"],
        "unit": "tokens/s",
        "baseline_tokens_per_s": t1["value"],
        "tp": sh["tp"],
        "hbm_bytes_per_shard": sh["hbm_bytes_per_shard"],
        "hbm_bytes_per_shard_tp1": t1["hbm_bytes_per_shard"],
        "hbm_bytes_per_shard_reduction_pct": round(
            (1.0 - sh["hbm_bytes_per_shard"]
             / t1["hbm_bytes_per_shard"]) * 100.0, 1)
        if t1["hbm_bytes_per_shard"] else 0.0,
        "psums_per_program": sh["psums_per_program"],
        "all_gathers_per_program": sh["all_gathers_per_program"],
        "token_exact_vs_tp1": mismatches == 0,
        "token_mismatched_requests": mismatches,
        "windows": WINDOWS,
        "requests_per_window": REQUESTS,
        "model": SIZE,
        "emulated_devices": True,
    }
    return rows, summary


def main_tp():
    import jax

    _load_env(smoke=dict(TP_SMOKE))

    rows, summary = tp_stats()
    for mode in ("tp1", "sharded"):
        print(json.dumps(rows[mode]))
    summary["backend"] = jax.default_backend()
    print(json.dumps(summary))


def _serve_async(engine, depth, seed):
    """WINDOWS measured windows (plus a discarded compile warmup) of
    the seeded stream at one pipeline depth; per-mode registry so the
    heartbeat split is the measured windows' own."""
    from apex_tpu import serving, telemetry

    reg = telemetry.MetricsRegistry()
    rng = np.random.default_rng(seed)
    rates, all_reqs = [], []
    for w in range(WINDOWS + 1):
        engine.reset()
        engine.set_registry(reg if w else None)
        sched = serving.Scheduler(engine, max_queue=max(REQUESTS, 1),
                                  registry=reg if w else None,
                                  chunk_budget=CHUNK_BUDGET,
                                  pipeline_depth=depth)
        reqs = _requests(rng)
        t0 = time.perf_counter()
        tokw = engine.tokens_generated
        done = sched.run(reqs)
        dt = time.perf_counter() - t0
        assert len(done) == REQUESTS
        if w > 0:
            rates.append((engine.tokens_generated - tokw) / dt)
            all_reqs.extend(reqs)
    engine.set_registry(None)
    return _median(rates), all_reqs, reg.snapshot()


def async_stats():
    """The --async-heartbeat measurement, reusable by bench.py's
    serving trajectory leg: the SAME seeded greedy stream served by one
    engine synchronously (pipeline_depth=0, the bitwise oracle) and
    dispatch-ahead (pipeline_depth=ASYNC_DEPTH), one warmup window per
    mode discarded. Headline fields per mode: tokens/s, **heartbeat
    wall per emitted token** (total beat wall / tokens — the latency
    the refactor attacks), the **duty cycle** (device-wait fraction of
    beat wall: host think-time leaves this denominator when it overlaps
    device execution), and the host/device second totals behind both.
    ``token_mismatched_requests`` is the exactness pin (must be 0 —
    same programs, same bytes, deferred readback only). CPU-regime
    note: the CPU backend executes donated-buffer programs
    synchronously inside the dispatch call, so overlap is structurally
    zero here and the pipelined row reads a small per-beat-overhead
    loss — exactness, the host/duty-cycle split and the overhead
    bound are the CPU-honest columns; the improvement is the silicon
    claim (see the module docstring)."""
    engine = _build_engine()
    rows, outputs = {}, {}
    for mode, depth in (("sync", 0), ("pipelined", ASYNC_DEPTH)):
        rate, reqs, snap = _serve_async(engine, depth, seed=13)
        h = snap["histograms"]
        host = h.get("serving.heartbeat.host_s", {})
        dwait = h.get("serving.heartbeat.device_wait_s", {})
        host_total = host.get("mean", 0.0) * host.get("count", 0)
        dwait_total = dwait.get("mean", 0.0) * dwait.get("count", 0)
        wall_total = host_total + dwait_total
        emitted = sum(len(r.output_tokens) for r in reqs)
        row = {
            "metric": f"{ASYNC_METRIC}.{mode}",
            "value": round(rate, 2),
            "unit": "tokens/s",
            "pipeline_depth": depth,
            "beats": host.get("count", 0),
            "heartbeat_wall_s": round(wall_total, 4),
            "heartbeat_wall_per_token_ms": round(
                1000.0 * wall_total / emitted, 4) if emitted else 0.0,
            "host_s": round(host_total, 4),
            "device_wait_s": round(dwait_total, 4),
            "duty_cycle": round(dwait_total / wall_total, 4)
            if wall_total else 0.0,
            "discarded_inflight_tokens": int(snap["counters"].get(
                "serving.heartbeat.discarded", 0)),
            "decode_step_p50_s": round(
                h.get("serving.decode.step_s", {}).get("p50", 0.0), 6),
            "compiled_programs": engine.compiled_programs,
        }
        rows[mode] = row
        outputs[mode] = [list(r.output_tokens) for r in reqs]
    mismatches = sum(a != b for a, b in zip(outputs["pipelined"],
                                            outputs["sync"]))
    sy, pi = rows["sync"], rows["pipelined"]
    summary = {
        "metric": ASYNC_METRIC,
        "value": pi["value"],
        "unit": "tokens/s",
        "baseline_tokens_per_s": sy["value"],
        "pipeline_depth": ASYNC_DEPTH,
        "heartbeat_wall_per_token_ms": pi["heartbeat_wall_per_token_ms"],
        "heartbeat_wall_per_token_ms_sync": sy[
            "heartbeat_wall_per_token_ms"],
        "heartbeat_wall_per_token_improvement_pct": round(
            (1.0 - pi["heartbeat_wall_per_token_ms"]
             / sy["heartbeat_wall_per_token_ms"]) * 100.0, 1)
        if sy["heartbeat_wall_per_token_ms"] else 0.0,
        "duty_cycle": pi["duty_cycle"],
        "duty_cycle_sync": sy["duty_cycle"],
        "host_s_fraction": round(1.0 - pi["duty_cycle"], 4),
        "discarded_inflight_tokens": pi["discarded_inflight_tokens"],
        "token_exact_vs_sync": mismatches == 0,
        "token_mismatched_requests": mismatches,
        "windows": WINDOWS,
        "requests_per_window": REQUESTS,
        "compiled_programs": engine.compiled_programs,
        "model": SIZE,
    }
    return rows, summary


def main_async():
    import jax

    _load_env(smoke=dict(ASYNC_SMOKE))

    rows, summary = async_stats()
    for mode in ("sync", "pipelined"):
        print(json.dumps(rows[mode]))
    summary["backend"] = jax.default_backend()
    print(json.dumps(summary))


def _host_tier_requests(rng, groups):
    """REQUESTS arrivals cycling through the ``groups`` templates in
    order (request i opens with template ``i % G`` plus a short unique
    tail) — by the time a template is revisited, the pool pressure of
    the templates in between has evicted it, which is exactly the
    traffic the host tier exists for."""
    from apex_tpu.serving import Request

    reqs = []
    for i in range(REQUESTS):
        shared = groups[i % len(groups)]
        tail = max(1, min(8, PREFILL_LEN - len(shared)))
        n = int(rng.integers(1, tail + 1))
        prompt = shared + rng.integers(1, VOCAB, size=n).tolist()
        budget = max(1, min(NEW_TOKENS, MAX_LEN - len(prompt)))
        reqs.append(Request(prompt=prompt, max_new_tokens=budget))
    return reqs


def _host_tier_geometry(chunk):
    """(num_pages, prefix_pages, demand): a pool sized for the serving
    slots' worst-case reservations plus a resident-prefix budget of
    roughly HALF the template working set — so the leg's eviction
    churn is by construction, not by luck."""
    from apex_tpu.serving.engine import resolve_page_len

    page_len = resolve_page_len(chunk)
    shared_len = (min(SHARED_PREFIX, PREFILL_LEN - 1) // chunk) * chunk
    prefix_pages = max(1, shared_len // page_len)
    prefill_extent = -(-PREFILL_LEN // chunk) * chunk
    occupied = min(PREFILL_LEN + NEW_TOKENS, MAX_LEN)
    demand = -(-max(prefill_extent, occupied) // page_len)
    budget = max(prefix_pages, (HOST_GROUPS // 2) * prefix_pages)
    return 1 + SLOTS * demand + budget, prefix_pages, demand


def _serve_host_tier(mode: str, chunk: int, groups, num_pages,
                     mesh=None, policy=None):
    """WINDOWS measured windows (plus a discarded compile warmup) of
    the grouped template stream on one mode's engine — ``"tier_off"``
    (eviction destroys), ``"tier_on_sync"`` (the inline admission-
    stall baseline) or ``"tier_on"`` (async swap-out, the default) —
    IDENTICAL pool geometry throughout; only the tier mode differs.
    Prefix stats are deltas past the warmup snapshot (the cache
    counters are run-scoped); swap counters and the
    ``serving.swap.admit_stall_s`` stall histogram are engine-emitted
    into the measured windows' registry only."""
    from apex_tpu import serving, telemetry

    reg = telemetry.MetricsRegistry()
    kw = {} if policy is None else {"policy": policy}
    engine = _build_engine(
        prefix_pool=PREFIX_POOL, chunk_len=chunk, num_pages=num_pages,
        mesh=mesh,
        host_tier=None if mode == "tier_off" else (HOST_TIER_MIB << 20),
        sync_swap=mode == "tier_on_sync", **kw)
    rng = np.random.default_rng(5)
    rates, all_reqs, warm_stats = [], [], {}
    for w in range(WINDOWS + 1):
        engine.reset()      # retained AND swapped prefixes stay warm
        if w == 1:
            engine.set_registry(reg)
            warm_stats = dict(engine.prefix_cache.stats())
        sched = serving.Scheduler(engine, max_queue=max(REQUESTS, 1),
                                  registry=reg if w else None,
                                  chunk_budget=CHUNK_BUDGET,
                                  retain_prefixes=True)
        reqs = _host_tier_requests(rng, groups)
        t0 = time.perf_counter()
        tok0 = engine.tokens_generated
        done = sched.run(reqs)
        dt = time.perf_counter() - t0
        toks = engine.tokens_generated - tok0
        assert len(done) == REQUESTS
        if w > 0:
            rates.append(toks / dt)
            all_reqs.extend(reqs)
    engine.set_registry(None)
    engine.close()          # drain + stop the SwapWorker (async mode)
    delta = engine.prefix_cache.stats_since(warm_stats)
    return _median(rates), all_reqs, engine, delta, reg.snapshot()


def _stall_ms(snap, pct):
    """A percentile of the ``serving.swap.admit_stall_s`` histogram in
    ms — the telemetry-wired admission-stall reading (NOT bench-local
    timing: the claim is pinned on the same histogram a production
    dashboard reads)."""
    h = snap["histograms"].get("serving.swap.admit_stall_s", {})
    return round(h.get(pct, 0.0) * 1e3, 4)


def host_tier_stats():
    """The --host-tier measurement, reusable by bench.py's serving
    trajectory leg: a template working set deliberately larger than
    the device pool, served tier-off (evictions destroy — revisits
    re-prefill), tier-on with ``sync_swap=True`` (evictions swap
    INLINE on the admission path — the stall baseline), and tier-on
    async (the default: evictions dispatch, a SwapWorker migrates off
    the hot path). Headline fields: prefix hit rate and prefill
    chunks skipped per mode, TTFT p50/p99 per mode, **admission-stall
    p50/p99 sync vs async** (from the ``serving.swap.admit_stall_s``
    histogram — the async tentpole's honestly-CPU-measurable claim),
    the swap traffic counters, and ``token_mismatched_requests``
    across all modes (greedy, expected 0 — the worker changes WHEN
    bytes move, never what any program computes)."""
    chunk = CHUNK_LEN or 8
    num_pages, prefix_pages, demand = _host_tier_geometry(chunk)
    rng0 = np.random.default_rng(29)
    shared_len = (min(SHARED_PREFIX, PREFILL_LEN - 1) // chunk) * chunk
    groups = [rng0.integers(1, VOCAB, size=shared_len).tolist()
              for _ in range(max(1, HOST_GROUPS))]
    rows, outputs = {}, {}
    for mode in ("tier_off", "tier_on_sync", "tier_on"):
        rate, reqs, engine, stats, snap = _serve_host_tier(
            mode, chunk, groups, num_pages)
        ttfts = [r.ttft_s for r in reqs if r.ttft_s]
        counters = snap["counters"]
        gauges = snap["gauges"]
        reused = sum(r.reused_tokens for r in reqs)
        rows[mode] = {
            "metric": f"{HOST_METRIC}.{mode}",
            "value": round(rate, 2),
            "unit": "tokens/s",
            "prefix_hit_rate": round(stats["hit_rate"], 4),
            "tokens_reused": stats["tokens_reused"],
            "prefill_chunks_run": sum(r.chunks for r in reqs),
            "prefill_chunks_skipped": reused // engine.chunk_len,
            "evictions": stats["evictions"],
            "swap_outs": stats["swap_outs"],
            "swap_ins": stats["swap_ins"],
            "ttft_p50_ms": round(float(np.percentile(ttfts, 50)) * 1e3,
                                 3) if ttfts else 0.0,
            "ttft_p99_ms": round(float(np.percentile(ttfts, 99)) * 1e3,
                                 3) if ttfts else 0.0,
            "admit_stall_p50_ms": _stall_ms(snap, "p50"),
            "admit_stall_p99_ms": _stall_ms(snap, "p99"),
            "swap_join_waits": int(counters.get(
                "serving.swap.swap_join_waits", 0)),
            "hit_after_swap": int(counters.get(
                "serving.swap.hit_after_swap", 0)),
            "swapped_out_pages": int(counters.get(
                "serving.swap.swapped_out_pages", 0)),
            "swapped_in_pages": int(counters.get(
                "serving.swap.swapped_in_pages", 0)),
            "swap_verify_failed": int(counters.get(
                "serving.swap.verify_failed", 0)),
            "host_bytes": int(gauges.get("serving.swap.host_bytes", 0)),
            "compiled_programs": engine.compiled_programs,
        }
        outputs[mode] = [list(r.output_tokens) for r in reqs]
    mismatches = max(
        sum(a != b for a, b in zip(outputs[m], outputs["tier_off"]))
        for m in ("tier_on", "tier_on_sync"))
    off, on = rows["tier_off"], rows["tier_on"]
    sync = rows["tier_on_sync"]
    total = on["prefill_chunks_run"] + on["prefill_chunks_skipped"]
    stall_sync, stall_async = sync["admit_stall_p99_ms"], \
        on["admit_stall_p99_ms"]
    summary = {
        "metric": HOST_METRIC,
        "value": on["value"],
        "unit": "tokens/s",
        "baseline_tokens_per_s": off["value"],
        "sync_swap_tokens_per_s": sync["value"],
        "prefix_hit_rate": on["prefix_hit_rate"],
        "prefix_hit_rate_tier_off": off["prefix_hit_rate"],
        "hit_rate_improved": on["prefix_hit_rate"]
        > off["prefix_hit_rate"],
        # async must not trade hit rate for stall: sync and async see
        # the identical swap state (reservations are synchronous)
        "hit_rate_unchanged_vs_sync": on["prefix_hit_rate"]
        == sync["prefix_hit_rate"],
        "prefill_chunks_skipped": on["prefill_chunks_skipped"],
        "prefill_chunks_skipped_tier_off": off["prefill_chunks_skipped"],
        "prefill_chunks_skipped_pct": round(
            100.0 * on["prefill_chunks_skipped"] / total, 1)
        if total else 0.0,
        "ttft_p50_ms": on["ttft_p50_ms"],
        "ttft_p99_ms": on["ttft_p99_ms"],
        "ttft_p50_ms_tier_off": off["ttft_p50_ms"],
        "ttft_p99_ms_tier_off": off["ttft_p99_ms"],
        "ttft_improved": on["ttft_p50_ms"] < off["ttft_p50_ms"],
        # THE async tentpole's claim, wired through telemetry: the
        # admission path pays a dispatch, not the migration
        "admit_stall_p50_ms_sync": sync["admit_stall_p50_ms"],
        "admit_stall_p99_ms_sync": stall_sync,
        "admit_stall_p50_ms_async": on["admit_stall_p50_ms"],
        "admit_stall_p99_ms_async": stall_async,
        "admit_stall_p99_reduction_pct": round(
            100.0 * (1.0 - stall_async / stall_sync), 1)
        if stall_sync > 0 else 0.0,
        # the p50 companion is the ROBUST estimator on this box: the
        # p99 of ~40 samples is tail-dominated, and a 2-core machine
        # lands rare ~10 ms scheduler spikes on either mode — judge a
        # single run by p50, the p99 trend across runs
        "admit_stall_p50_reduction_pct": round(
            100.0 * (1.0 - on["admit_stall_p50_ms"]
                     / sync["admit_stall_p50_ms"]), 1)
        if sync["admit_stall_p50_ms"] > 0 else 0.0,
        "admit_stall_reduced": 0 < stall_async < stall_sync
        or (stall_async == 0 and stall_sync > 0),
        "admit_stall_p50_reduced":
        on["admit_stall_p50_ms"] < sync["admit_stall_p50_ms"],
        "swap_join_waits": on["swap_join_waits"],
        "hit_after_swap": on["hit_after_swap"],
        "swapped_out_pages": on["swapped_out_pages"],
        "swapped_in_pages": on["swapped_in_pages"],
        "swap_verify_failed": on["swap_verify_failed"],
        "host_bytes": on["host_bytes"],
        "host_tier_mib": HOST_TIER_MIB,
        "token_exact_vs_tier_off": mismatches == 0,
        "token_mismatched_requests": mismatches,
        # the honesty row: the template working set must EXCEED the
        # pool's resident-prefix headroom or the leg measured nothing
        "prefix_working_set_pages": len(groups) * prefix_pages,
        "pool_pages": num_pages,
        "slot_reservation_pages": SLOTS * demand,
        "groups": len(groups),
        "windows": WINDOWS,
        "requests_per_window": REQUESTS,
        "shared_prefix_len": shared_len,
        "chunk_len": chunk,
        "model": SIZE,
    }
    summary["mesh"] = _host_tier_tp_leg(chunk, groups, num_pages)
    return rows, summary


def _host_tier_tp_leg(chunk, groups, num_pages):
    """The mesh-composition sub-leg (``HOST_TIER_TP`` shards, CPU
    device emulation): the SAME grouped stream on a mesh-sharded
    host-tier engine must be token-exact vs an unsharded host-tier
    run, with PER-SHARD arena records (``shards == tp``, one CRC per
    shard). Both runs use policy O0 (exact fp32) — the comparison
    must isolate the SWAP layer, and at bf16 the tp row-parallel
    psum's ~1-ulp rounding can flip near-tie argmaxes on its own (the
    PR 14 finding; the tp tests pin at O0 for the same reason). Skips
    — with the reason in the row — when tp < 2 or the backend
    initialized before emulated devices could be forced (run the leg
    standalone, or via bench.py's subprocess embedding). Exactness +
    per-shard accounting are the claims; emulated-CPU tokens/s is not
    one."""
    if HOST_TIER_TP < 2:
        return {"skipped": f"HOST_TIER_TP={HOST_TIER_TP}"}
    try:
        _ensure_cpu_devices(HOST_TIER_TP)
    except (SystemExit, RuntimeError) as e:
        return {"skipped": str(e)}
    import jax
    from jax.sharding import Mesh

    from apex_tpu.amp.policy import resolve_policy

    policy = resolve_policy("O0", verbose=False)
    mesh = Mesh(np.array(jax.devices()[:HOST_TIER_TP]), ("tp",))
    _, reqs0, e0, _s0, _ = _serve_host_tier(
        "tier_on", chunk, groups, num_pages, policy=policy)
    unsharded_outputs = [list(r.output_tokens) for r in reqs0]
    _, reqs, engine, stats, _snap = _serve_host_tier(
        "tier_on", chunk, groups, num_pages, mesh=mesh, policy=policy)
    sharded = [list(r.output_tokens) for r in reqs]
    mismatches = sum(a != b for a, b in zip(sharded,
                                            unsharded_outputs))
    # per-shard arena byte accounting: force one more swap-out and
    # inspect the resident record (the serve above drained its arena
    # by swapping everything back in on revisit)
    rec_row = {}
    if engine.prefix_cache.evict_lru():
        if engine._swap_worker is not None:
            engine._swap_worker.drain()
        keys = engine.host_tier.keys()
        if keys:
            rec = engine.host_tier._entries[keys[0]]
            rec_row = {
                "record_shards": rec.shards,
                "record_crcs": len(rec.crc),
                "record_nbytes": rec.nbytes,
                "per_shard_records_verified":
                    rec.shards == HOST_TIER_TP
                    and len(rec.crc) == HOST_TIER_TP,
            }
    engine.close()
    return {
        "tp": HOST_TIER_TP,
        "token_mismatched_requests": mismatches,
        "token_exact_vs_unsharded": mismatches == 0,
        "swap_outs": stats["swap_outs"],
        "swap_ins": stats["swap_ins"],
        "emulated_devices": len(jax.devices()),
        **rec_row,
    }


def main_host_tier():
    import jax

    _load_env(smoke=dict(HOST_SMOKE))
    if HOST_TIER_TP >= 2:
        # the mesh-composition sub-leg needs emulated devices BEFORE
        # the first backend init; a too-late call degrades the sub-leg
        # to a reasoned skip, never the whole row (the main modes run
        # mesh=None and are indifferent to the device count)
        try:
            _ensure_cpu_devices(HOST_TIER_TP)
        except (SystemExit, RuntimeError):
            pass

    rows, summary = host_tier_stats()
    for mode in ("tier_off", "tier_on_sync", "tier_on"):
        print(json.dumps(rows[mode]))
    summary["backend"] = jax.default_backend()
    print(json.dumps(summary))


def _router_waves(rng):
    """REQUESTS multi-turn sessions, 2 turns each, served as
    sequential WAVES (a turn arrives only after the previous response
    — real multi-turn traffic). Turn 2's prompt EXTENDS turn 1's, so
    its block-aligned prefix is resident exactly on the replica that
    served turn 1: affinity routing hits it, random routing hits only
    when luck lands the turn home — which is what makes the hit-rate
    gap the routing claim."""
    from apex_tpu.serving import Request

    chunk = CHUNK_LEN or 8
    waves = [[], []]
    for _ in range(REQUESTS):
        # session histories are DISJOINT on purpose: the only possible
        # hit is a turn-2 request finding its own turn-1 K/V, so the
        # hit rate reads routing quality cleanly (a shared system
        # prompt would let any replica serve a shallow hit and blur
        # the affinity-vs-random gap the leg exists to measure)
        p = rng.integers(1, VOCAB, size=2 * chunk).tolist()
        for t in range(2):
            prompt = list(p)[:PREFILL_LEN]
            budget = max(1, min(NEW_TOKENS, MAX_LEN - len(prompt)))
            waves[t].append(Request(prompt=prompt,
                                    max_new_tokens=budget))
            if len(p) + chunk <= PREFILL_LEN:
                p = p + rng.integers(1, VOCAB, size=chunk).tolist()
    return waves


def _serve_router(engines, policy, seed, tracer=None):
    """WINDOWS measured windows (plus a discarded compile warmup) of
    the session-wave stream through one Router mode. Per-replica
    prefix accounting reads ``stats_since`` DELTAS over the measured
    windows — the cache counters survive the warm resets between
    windows on purpose, so only a delta isolates the window.
    ``tracer`` (the ``BENCH_SERVING_TRACE`` knob) attaches request
    tracing to every window's router — token-bitwise invisible by the
    tracer contract, so the measured stream is unchanged."""
    from apex_tpu import serving, telemetry

    reg = telemetry.MetricsRegistry()
    rng = np.random.default_rng(seed)
    rates, all_reqs, ttfts = [], [], []
    hits = misses = reused = 0
    for w in range(WINDOWS + 1):
        for e in engines:
            e.reset(clear_prefixes=True)
            e.set_registry(reg if w else None)
        router = serving.Router(engines, registry=reg if w else None,
                                route_policy=policy, seed=seed,
                                max_queue=max(REQUESTS, 1),
                                chunk_budget=CHUNK_BUDGET,
                                retain_prefixes=True, tracer=tracer)
        waves = _router_waves(rng)
        base = [e.prefix_cache.stats() for e in engines]
        t0 = time.perf_counter()
        tokw = sum(e.tokens_generated for e in engines)
        for wave in waves:
            router.run(wave)
        dt = time.perf_counter() - t0
        router.close()
        reqs = [r for wave in waves for r in wave]
        assert all(r.status == "finished" for r in reqs)
        if w > 0:
            rates.append(
                (sum(e.tokens_generated for e in engines) - tokw) / dt)
            for e, b in zip(engines, base):
                d = e.prefix_cache.stats_since(b)
                hits += d["hits"]
                misses += d["misses"]
                reused += d["tokens_reused"]
            all_reqs.extend(reqs)
            ttfts.extend(r.ttft_s for r in reqs
                         if r.ttft_s is not None)
    for e in engines:
        e.set_registry(None)
    consulted = hits + misses
    return {
        "rate": _median(rates),
        "hit_rate": hits / consulted if consulted else 0.0,
        "reused_per_request": reused / len(all_reqs) if all_reqs
        else 0.0,
        "ttft_p99_ms": float(np.percentile(ttfts, 99) * 1e3)
        if ttfts else 0.0,
        "reqs": all_reqs,
        "snap": reg.snapshot(),
    }


def replica_router_stats():
    """The --replica-router measurement, reusable by bench.py's
    serving trajectory leg: the SAME seeded session-wave stream served
    through Router(1 replica) — the baseline — then
    Router(REPLICAS) with affinity routing and with seeded random
    routing (the control). Headline fields: aggregate tokens/s 1 vs N
    (CPU caveat: replicas share cores here — scaling is the silicon
    claim), p99 TTFT, prefix hit rate affinity vs random (the
    CPU-honest routing claim: ``affinity_beats_random`` compares hit
    rate, depth-tie-broken by reused tokens), and
    ``token_mismatched_requests`` vs the 1-replica run (expected 0,
    bitwise, under every policy)."""
    n = max(1, REPLICAS)
    engines = [_build_engine(prefix_pool=PREFIX_POOL)
               for _ in range(n)]
    modes = {
        "one_replica": (engines[:1], "affinity"),
        "affinity": (engines, "affinity"),
        "random": (engines, "random"),
    }
    # BENCH_SERVING_TRACE=path (off by default): attach a request
    # tracer to the affinity leg and write a Chrome-trace artifact
    # (load at https://ui.perfetto.dev) — every request's life across
    # router, replicas and worker threads, riding the measured stream
    # (token-bitwise invisible by the tracer contract)
    trace_path = os.environ.get("BENCH_SERVING_TRACE")
    trace_spans = None
    rows, results = {}, {}
    for mode, (engs, policy) in modes.items():
        tracer = None
        if trace_path and mode == "affinity":
            from apex_tpu.telemetry import Tracer

            tracer = Tracer(max_traces=8192)
        res = _serve_router(engs, policy, seed=17, tracer=tracer)
        if tracer is not None:
            trace_spans = tracer.export_chrome_trace(trace_path)
        results[mode] = res
        counters = res["snap"]["counters"]
        rows[mode] = {
            "metric": f"{ROUTER_METRIC}.{mode}",
            "value": round(res["rate"], 2),
            "unit": "tokens/s",
            "replicas": len(engs),
            "route_policy": policy,
            "prefix_hit_rate": round(res["hit_rate"], 4),
            "reused_tokens_per_request": round(
                res["reused_per_request"], 2),
            "ttft_p99_ms": round(res["ttft_p99_ms"], 3),
            "routed": int(counters.get("serving.router.routed", 0)),
            "affinity_hits": int(counters.get(
                "serving.router.affinity_hits", 0)),
            "spills": int(counters.get("serving.router.spills", 0)),
            "compiled_programs": [e.compiled_programs for e in engs],
        }
    ref = [list(r.output_tokens) for r in results["one_replica"]["reqs"]]
    mism = sum(
        sum(a != b for a, b in
            zip([list(r.output_tokens) for r in results[m]["reqs"]],
                ref))
        for m in ("affinity", "random"))
    aff, rnd, one = rows["affinity"], rows["random"], rows["one_replica"]
    summary = {
        "metric": ROUTER_METRIC,
        "value": aff["value"],
        "unit": "tokens/s",
        "replicas": n,
        "baseline_tokens_per_s": one["value"],
        "scaling_x": round(aff["value"] / one["value"], 3)
        if one["value"] else 0.0,
        "ttft_p99_ms": aff["ttft_p99_ms"],
        "ttft_p99_ms_one_replica": one["ttft_p99_ms"],
        "prefix_hit_rate": aff["prefix_hit_rate"],
        "prefix_hit_rate_random": rnd["prefix_hit_rate"],
        "reused_tokens_per_request": aff["reused_tokens_per_request"],
        "reused_tokens_per_request_random": rnd[
            "reused_tokens_per_request"],
        "affinity_beats_random": (
            aff["prefix_hit_rate"], aff["reused_tokens_per_request"])
        > (rnd["prefix_hit_rate"], rnd["reused_tokens_per_request"]),
        "affinity_hits": aff["affinity_hits"],
        "spills": aff["spills"],
        "token_exact_vs_one_replica": mism == 0,
        "token_mismatched_requests": mism,
        "windows": WINDOWS,
        "sessions_per_window": REQUESTS,
        "turns": 2,
        "compiled_programs": [e.compiled_programs for e in engines],
        "model": SIZE,
    }
    if trace_path:
        summary["trace_path"] = trace_path
        summary["trace_spans"] = trace_spans
    return rows, summary


def main_router():
    import jax

    _load_env(smoke=dict(ROUTER_SMOKE))

    rows, summary = replica_router_stats()
    for mode in ("one_replica", "affinity", "random"):
        print(json.dumps(rows[mode]))
    summary["backend"] = jax.default_backend()
    print(json.dumps(summary))


def _fleet_spec():
    """One worker's plain-dict engine spec — the only engine
    description that can cross a process boundary
    (``fleet_worker.build_engine_from_spec`` rebuilds it
    deterministically inside each worker, so every worker holds
    bitwise-identical weights)."""
    engine = {"slots": SLOTS, "max_len": MAX_LEN,
              "prefill_len": PREFILL_LEN, "prefix_pool": PREFIX_POOL,
              "top_k": TOP_K}
    if CHUNK_LEN:
        engine["chunk_len"] = CHUNK_LEN
    return {"model": {"preset": SIZE, "vocab_size": VOCAB,
                      "max_seq_len": MAX_LEN},
            "init_seed": 0,
            "engine": engine}


def _serve_fleet(n, seed):
    """WINDOWS measured windows (plus a spawn/compile warmup window)
    of the session-wave stream through one ``FleetController`` of
    ``n`` worker PROCESSES, then (fleets of 2+) a rolling restart
    with a post-restart wave set. The fleet spawns ONCE — a worker
    spawn pays interpreter + jax import + compile, far too much per
    window — so post-warmup windows serve warm caches; that moves no
    token (greedy outputs are reuse-invariant by the verified-prefix
    contract) and the per-window hit accounting stays a
    ``prefix_stats`` delta, immune to the warmth."""
    from apex_tpu import serving, telemetry

    reg = telemetry.MetricsRegistry()
    rng = np.random.default_rng(seed)
    fc = serving.FleetController(
        [_fleet_spec() for _ in range(n)], registry=reg,
        route_policy="affinity", seed=seed,
        max_queue=max(REQUESTS, 1), chunk_budget=CHUNK_BUDGET,
        retain_prefixes=True)
    rates, all_reqs, ttfts = [], [], []
    hits = misses = reused = 0
    restart_wall_s = None
    try:
        for w in range(WINDOWS + 1):
            waves = _router_waves(rng)
            base = [fc.prefix_stats(i) for i in range(n)]
            t0 = time.perf_counter()
            for wave in waves:
                fc.run(wave)
            dt = time.perf_counter() - t0
            reqs = [r for wave in waves for r in wave]
            assert all(r.status == "finished" for r in reqs)
            if w > 0:
                rates.append(
                    sum(len(r.output_tokens) for r in reqs) / dt)
                for i, b in enumerate(base):
                    s = fc.prefix_stats(i)
                    hits += s["hits"] - b["hits"]
                    misses += s["misses"] - b["misses"]
                    reused += s["tokens_reused"] - b["tokens_reused"]
                all_reqs.extend(reqs)
                ttfts.extend(r.ttft_s for r in reqs
                             if r.ttft_s is not None)
        if n > 1:
            # drain -> close -> respawn -> rejoin, one live worker at
            # a time; the post-restart wave set proves the respawned
            # workers serve (and re-warm as re-routed traffic lands)
            t0 = time.perf_counter()
            fc.rolling_restart()
            restart_wall_s = time.perf_counter() - t0
            for wave in _router_waves(rng):
                fc.run(wave)
                assert all(r.status == "finished" for r in wave)
        snap = fc.metrics_snapshot()
    finally:
        fc.close()
    consulted = hits + misses
    return {
        "rate": _median(rates),
        "hit_rate": hits / consulted if consulted else 0.0,
        "reused_per_request": reused / len(all_reqs) if all_reqs
        else 0.0,
        "ttft_p99_ms": float(np.percentile(ttfts, 99) * 1e3)
        if ttfts else 0.0,
        "reqs": all_reqs,
        "restart_wall_s": restart_wall_s,
        "snap": snap,
    }


def process_fleet_stats():
    """The --process-fleet measurement, reusable by bench.py's serving
    leg: the SAME seeded session-wave stream served through a
    1-worker process fleet (the baseline — transport cost included,
    so ``scaling_x`` is fleet-vs-fleet) and a REPLICAS-worker fleet
    with affinity routing. Headline fields: aggregate tokens/s 1 vs N
    and ``scaling_x`` (an honest CPU-box column — no shared GIL or
    runtime across workers), p99 TTFT both, prefix hit rate, the
    rolling-restart columns, the health counters (expected 0 outside
    chaos), and ``token_mismatched_requests`` vs the 1-worker run
    (expected 0, bitwise)."""
    n = max(1, REPLICAS)
    rows, results = {}, {}
    for mode, k in (("one_worker", 1), ("fleet", n)):
        res = _serve_fleet(k, seed=17)
        results[mode] = res
        counters = res["snap"]["counters"]
        rows[mode] = {
            "metric": f"{FLEET_METRIC}.{mode}",
            "value": round(res["rate"], 2),
            "unit": "tokens/s",
            "workers": k,
            "route_policy": "affinity",
            "prefix_hit_rate": round(res["hit_rate"], 4),
            "reused_tokens_per_request": round(
                res["reused_per_request"], 2),
            "ttft_p99_ms": round(res["ttft_p99_ms"], 3),
            "routed": int(counters.get("serving.fleet.routed", 0)),
            "affinity_hits": int(counters.get(
                "serving.fleet.affinity_hits", 0)),
            "spills": int(counters.get("serving.fleet.spills", 0)),
        }
    ref = [list(r.output_tokens)
           for r in results["one_worker"]["reqs"]]
    mism = sum(a != b for a, b in
               zip([list(r.output_tokens)
                    for r in results["fleet"]["reqs"]], ref))
    fleet, one = rows["fleet"], rows["one_worker"]
    snap = results["fleet"]["snap"]
    restart_h = snap["histograms"].get("serving.fleet.restart_s", {})
    summary = {
        "metric": FLEET_METRIC,
        "value": fleet["value"],
        "unit": "tokens/s",
        "workers": n,
        "baseline_tokens_per_s": one["value"],
        "scaling_x": round(fleet["value"] / one["value"], 3)
        if one["value"] else 0.0,
        # out-of-process workers share no GIL and no runtime: unlike
        # every thread-fleet leg above, this ratio is a real CPU-box
        # measurement, not a silicon-only claim
        "scaling_honest_on_cpu": True,
        "ttft_p99_ms": fleet["ttft_p99_ms"],
        "ttft_p99_ms_one_worker": one["ttft_p99_ms"],
        "prefix_hit_rate": fleet["prefix_hit_rate"],
        "reused_tokens_per_request": fleet[
            "reused_tokens_per_request"],
        "affinity_hits": fleet["affinity_hits"],
        "spills": fleet["spills"],
        "worker_deaths": int(snap["counters"].get(
            "serving.fleet.worker_deaths", 0)),
        "hangs_detected": int(snap["counters"].get(
            "serving.fleet.hangs_detected", 0)),
        "restarts": int(snap["counters"].get(
            "serving.fleet.restarts", 0)),
        "restart_wall_s": round(
            results["fleet"]["restart_wall_s"], 3)
        if results["fleet"]["restart_wall_s"] is not None else None,
        "restart_p50_s": round(restart_h.get("p50", 0.0), 3),
        "restart_max_s": round(restart_h.get("max", 0.0), 3),
        "token_exact_vs_one_worker": mism == 0,
        "token_mismatched_requests": mism,
        "windows": WINDOWS,
        "sessions_per_window": REQUESTS,
        "turns": 2,
        "model": SIZE,
    }
    return rows, summary


def main_fleet():
    import jax

    _load_env(smoke=dict(FLEET_SMOKE))

    rows, summary = process_fleet_stats()
    for mode in ("one_worker", "fleet"):
        print(json.dumps(rows[mode]))
    summary["backend"] = jax.default_backend()
    print(json.dumps(summary))


def _disagg_requests(rng):
    """REQUESTS arrivals, bystanders interleaved with heavyweights:
    every THIRD request is a heavyweight (a near-PREFILL_LEN prompt,
    a few new tokens — pure ingestion pressure), the rest are SHORT
    bystanders (a one-chunk prompt, the full NEW_TOKENS decode
    budget). Returns ``(requests, bystander_mask)`` — the mask is
    what splits the TTFT histograms by class."""
    from apex_tpu.serving import Request

    chunk = CHUNK_LEN or 8
    reqs, bystander = [], []
    for i in range(REQUESTS):
        heavy = i % 3 == 2
        if heavy:
            lo = max(chunk + 1, PREFILL_LEN - chunk)
            n = int(rng.integers(lo, PREFILL_LEN + 1))
            budget = max(1, NEW_TOKENS // 4)
        else:
            n = int(rng.integers(1, max(2, min(SHORT_LEN, chunk)) + 1))
            budget = NEW_TOKENS
        reqs.append(Request(
            prompt=rng.integers(1, VOCAB, size=n).tolist(),
            max_new_tokens=max(1, min(budget, MAX_LEN - n))))
        bystander.append(not heavy)
    return reqs, bystander


def _host_beat_ms(rep_reg, pct):
    """A percentile of ONE replica's ``serving.heartbeat.host_s``
    histogram in ms — the per-replica registry is what keeps the
    prefill replica's chunky beats out of a decode replica's
    reading."""
    snap = rep_reg.snapshot()
    h = snap["histograms"].get("serving.heartbeat.host_s", {})
    return h.get(pct, 0.0) * 1e3


def _serve_disagg(engines, roles, seed, tier, tracer=None):
    """WINDOWS measured windows (plus a discarded compile warmup) of
    the bystander/heavyweight stream through one Router role layout
    over the SHARED arena ``tier``. Fleet-level metrics (router
    counters, engine-side swap histograms, the disagg gauges) land in
    one shared registry; each measured window ALSO re-points the
    SCHEDULER-side registry per replica — heartbeat host_s and the
    scheduler-emitted disagg counters split by replica, which is the
    only honest way to read a decode replica's beat profile out of a
    mixed fleet (the fleet histogram would pool the prefill replica's
    chunk-prefill beats into it)."""
    from apex_tpu import serving, telemetry

    reg = telemetry.MetricsRegistry()
    rep_regs = [telemetry.MetricsRegistry() for _ in engines]
    decode_idx = [i for i, role in enumerate(roles)
                  if role != "prefill"]
    rng = np.random.default_rng(seed)
    rates, all_reqs, by_ttfts, heavy_ttfts = [], [], [], []
    beats_total = beats_prefill = 0
    for w in range(WINDOWS + 1):
        for e in engines:
            e.reset(clear_prefixes=True)
            e.set_registry(reg if w else None)
        assert tier.bytes_used == 0     # windows start arena-clean
        router = serving.Router(engines, registry=reg if w else None,
                                roles=list(roles), seed=seed,
                                max_queue=max(REQUESTS, 1),
                                chunk_budget=CHUNK_BUDGET,
                                retain_prefixes=True, tracer=tracer)
        if w:
            for s, rr in zip(router.replicas, rep_regs):
                s.registry = rr
        reqs, bystander = _disagg_requests(rng)
        t0 = time.perf_counter()
        tok0 = sum(e.tokens_generated for e in engines)
        router.run(reqs)
        dt = time.perf_counter() - t0
        router.close()
        assert all(r.status == "finished" for r in reqs)
        if w > 0:
            rates.append(
                (sum(e.tokens_generated for e in engines) - tok0) / dt)
            all_reqs.extend(reqs)
            for r, is_by in zip(reqs, bystander):
                if r.ttft_s is None:
                    continue
                (by_ttfts if is_by else heavy_ttfts).append(r.ttft_s)
            for i in decode_idx:
                beats_total += router.replicas[i].beats_total
                beats_prefill += router.replicas[i].beats_with_prefill
    for e in engines:
        e.set_registry(None)
    return {
        "rate": _median(rates),
        "reqs": all_reqs,
        "bystander_ttfts": by_ttfts,
        "heavy_ttfts": heavy_ttfts,
        "beats_total": beats_total,
        "beats_with_prefill": beats_prefill,
        "decode_idx": decode_idx,
        "snap": reg.snapshot(),
        "rep_regs": rep_regs,
    }


def disagg_stats():
    """The --disaggregated measurement, reusable by bench.py's serving
    trajectory leg: the SAME seeded bystander/heavyweight stream
    served by ONE fleet of REPLICAS+1 engines over one shared
    ``HostTier(shared=True)`` arena, colocated (all ``"both"``) then
    role-split (1 prefill + REPLICAS decode, KV handoff through the
    arena). Headline fields: bystander TTFT p50/p99 both modes (the
    head-of-line claim), decode-replica heartbeat host_s p50/p99 both
    modes from per-replica registries (the isolation delta),
    ``decode_isolation`` both modes, the handoff traffic columns with
    export/import p50/p99 from the swap histograms,
    ``arena_bytes_after_drain`` (expected 0), and
    ``token_mismatched_requests`` vs colocated (expected 0,
    bitwise)."""
    from apex_tpu import serving

    n = max(1, REPLICAS) + 1
    tier = serving.HostTier(HOST_TIER_MIB << 20, shared=True)
    engines = [_build_engine(prefix_pool=PREFIX_POOL, host_tier=tier)
               for _ in range(n)]
    modes = {
        "colocated": ["both"] * n,
        "disaggregated": ["prefill"] + ["decode"] * (n - 1),
    }
    # BENCH_SERVING_TRACE=path (off by default): attach a request
    # tracer to the split leg and write a Chrome-trace artifact — the
    # handoff_export / handoff_import spans ride every hand-over, so
    # the artifact shows a request's life across BOTH role tiers
    trace_path = os.environ.get("BENCH_SERVING_TRACE")
    trace_spans = None
    rows, results = {}, {}
    for mode, roles in modes.items():
        tracer = None
        if trace_path and mode == "disaggregated":
            from apex_tpu.telemetry import Tracer

            tracer = Tracer(max_traces=8192)
        res = _serve_disagg(engines, roles, seed=23, tier=tier,
                            tracer=tracer)
        if tracer is not None:
            trace_spans = tracer.export_chrome_trace(trace_path)
        results[mode] = res
        # leak check: with every request drained and the prefix pools
        # cleared, a nonzero arena is an orphaned handoff record
        for e in engines:
            e.reset(clear_prefixes=True)
        counters = res["snap"]["counters"]
        hist = res["snap"]["histograms"]
        by, heavy = res["bystander_ttfts"], res["heavy_ttfts"]
        bt, bp = res["beats_total"], res["beats_with_prefill"]
        rep = res["rep_regs"]

        def _swap_ms(name, pct):
            return round(hist.get(name, {}).get(pct, 0.0) * 1e3, 4)

        def _sched_counter(name):
            return int(sum(r.snapshot()["counters"].get(name, 0)
                           for r in rep))

        host_p50 = [_host_beat_ms(rep[i], "p50")
                    for i in res["decode_idx"]]
        host_p99 = [_host_beat_ms(rep[i], "p99")
                    for i in res["decode_idx"]]
        rows[mode] = {
            "metric": f"{DISAGG_METRIC}.{mode}",
            "value": round(res["rate"], 2),
            "unit": "tokens/s",
            "roles": list(roles),
            "ttft_bystander_p50_ms": round(float(
                np.percentile(by, 50)) * 1e3, 3) if by else 0.0,
            "ttft_bystander_p99_ms": round(float(
                np.percentile(by, 99)) * 1e3, 3) if by else 0.0,
            "ttft_heavy_p99_ms": round(float(
                np.percentile(heavy, 99)) * 1e3, 3) if heavy else 0.0,
            # decode-capable replicas only, per-replica registries:
            # median-of-p50s / worst p99 across the decode tier
            "decode_heartbeat_host_p50_ms": round(
                _median(host_p50), 4) if host_p50 else 0.0,
            "decode_heartbeat_host_p99_ms": round(
                max(host_p99), 4) if host_p99 else 0.0,
            "decode_isolation": round(1.0 - bp / bt, 4) if bt else 0.0,
            "handoffs": _sched_counter("serving.disagg.handoffs"),
            "reprefills": _sched_counter("serving.disagg.reprefills"),
            "handoff_bytes": int(counters.get(
                "serving.disagg.handoff_bytes", 0)),
            "swap_out_p50_ms": _swap_ms("serving.swap.out_s", "p50"),
            "swap_out_p99_ms": _swap_ms("serving.swap.out_s", "p99"),
            "swap_in_p50_ms": _swap_ms("serving.swap.in_s", "p50"),
            "swap_in_p99_ms": _swap_ms("serving.swap.in_s", "p99"),
            "swap_verify_failed": int(counters.get(
                "serving.swap.verify_failed", 0)),
            "spills": int(counters.get("serving.router.spills", 0)),
            "arena_bytes_after_drain": int(tier.bytes_used),
            "compiled_programs": [e.compiled_programs for e in engines],
        }
    ref = [list(r.output_tokens) for r in results["colocated"]["reqs"]]
    split = [list(r.output_tokens)
             for r in results["disaggregated"]["reqs"]]
    mism = sum(a != b for a, b in zip(split, ref))
    col, dis = rows["colocated"], rows["disaggregated"]
    summary = {
        "metric": DISAGG_METRIC,
        "value": dis["value"],
        "unit": "tokens/s",
        "replicas": n,
        "decode_replicas": n - 1,
        "colocated_tokens_per_s": col["value"],
        "ttft_bystander_p50_ms": dis["ttft_bystander_p50_ms"],
        "ttft_bystander_p50_ms_colocated":
            col["ttft_bystander_p50_ms"],
        "ttft_bystander_p99_ms": dis["ttft_bystander_p99_ms"],
        "ttft_bystander_p99_ms_colocated":
            col["ttft_bystander_p99_ms"],
        "decode_heartbeat_host_p50_ms":
            dis["decode_heartbeat_host_p50_ms"],
        "decode_heartbeat_host_p50_ms_colocated":
            col["decode_heartbeat_host_p50_ms"],
        "decode_heartbeat_host_p99_ms":
            dis["decode_heartbeat_host_p99_ms"],
        "decode_heartbeat_host_p99_ms_colocated":
            col["decode_heartbeat_host_p99_ms"],
        "decode_isolation": dis["decode_isolation"],
        "decode_isolation_colocated": col["decode_isolation"],
        # the structural isolation claim: a decode replica's beat TAIL
        # is heavy-prompt ingestion chunks in the colocated fleet and
        # decode-only work in the split fleet (bystander single-chunk
        # prefills ride the decode tier in BOTH, so the p50s match —
        # the p99 is where the heavyweights were)
        "decode_beat_tail_improved": dis["decode_heartbeat_host_p99_ms"]
        < col["decode_heartbeat_host_p99_ms"],
        "decode_host_p99_isolation_x": round(
            col["decode_heartbeat_host_p99_ms"]
            / dis["decode_heartbeat_host_p99_ms"], 3)
        if dis["decode_heartbeat_host_p99_ms"] else 0.0,
        "handoffs": dis["handoffs"],
        "handoff_bytes": dis["handoff_bytes"],
        "reprefills": dis["reprefills"],
        "zero_reprefills_clean": dis["reprefills"] == 0,
        "handoff_export_p50_ms": dis["swap_out_p50_ms"],
        "handoff_export_p99_ms": dis["swap_out_p99_ms"],
        "handoff_import_p50_ms": dis["swap_in_p50_ms"],
        "handoff_import_p99_ms": dis["swap_in_p99_ms"],
        "swap_verify_failed": dis["swap_verify_failed"],
        "arena_bytes_after_drain": dis["arena_bytes_after_drain"],
        "token_exact_vs_colocated": mism == 0,
        "token_mismatched_requests": mism,
        "windows": WINDOWS,
        "requests_per_window": REQUESTS,
        "compiled_programs": [e.compiled_programs for e in engines],
        "model": SIZE,
    }
    if trace_path:
        summary["trace_path"] = trace_path
        summary["trace_spans"] = trace_spans
    return rows, summary


def main_disagg():
    import jax

    _load_env(smoke=dict(DISAGG_SMOKE))

    rows, summary = disagg_stats()
    for mode in ("colocated", "disaggregated"):
        print(json.dumps(rows[mode]))
    summary["backend"] = jax.default_backend()
    print(json.dumps(summary))


def _overload_requests(rng):
    """REQUESTS arrivals at >1x slot capacity, batch-heavy with every
    THIRD request an interactive-class arrival (a one-chunk prompt,
    the full decode budget) landing BEHIND batch heavyweights
    (near-PREFILL_LEN prompts) — the FIFO worst case: under overload
    every interactive queues behind the batch work that got there
    first. Returns ``(requests, classes)``; the class list is what
    splits the TTFT/deadline columns."""
    from apex_tpu.serving import Request

    chunk = CHUNK_LEN or 8
    reqs, classes = [], []
    for i in range(REQUESTS):
        interactive = i % 3 == 2
        if interactive:
            n = int(rng.integers(1, max(2, min(SHORT_LEN, chunk)) + 1))
        else:
            lo = max(chunk + 1, PREFILL_LEN - 2 * chunk)
            n = int(rng.integers(lo, PREFILL_LEN + 1))
        reqs.append(Request(
            prompt=rng.integers(1, VOCAB, size=n).tolist(),
            max_new_tokens=max(1, min(NEW_TOKENS, MAX_LEN - n)),
            slo_class="interactive" if interactive else "batch"))
        classes.append("interactive" if interactive else "batch")
    return reqs, classes


def _serve_overload(engine, slo, seed, registry,
                    interactive_deadline_s=None):
    """One serve of the seeded overload stream (regenerated from
    ``seed``, so FIFO and SLO modes see byte-identical prompts and
    budgets). ``interactive_deadline_s`` stamps a ``deadline_s`` on
    the interactive class only — the scheduler's deadline ordering
    and miss telemetry see it, but both modes are JUDGED by the
    bench's own post-hoc verdict so the threshold is identical.

    Arrivals are staggered, not batched: the batch class is submitted
    up front (filling every slot and the queue), then one interactive
    request arrives every few scheduler steps — mid-decode, when the
    slots are already full of batch work. That is the shape that makes
    FIFO head-of-line blocking visible AND forces the SLO mode through
    its preempt-to-host path (a same-instant ``run()`` would let
    priority admission alone serve interactive first, preempting
    nothing)."""
    from apex_tpu import serving

    rng = np.random.default_rng(seed)
    reqs, classes = _overload_requests(rng)
    if interactive_deadline_s is not None:
        for r, cls in zip(reqs, classes):
            if cls == "interactive":
                r.deadline_s = float(interactive_deadline_s)
    engine.set_registry(registry)
    sched = serving.Scheduler(engine, max_queue=max(REQUESTS, 1),
                              chunk_budget=CHUNK_BUDGET,
                              retain_prefixes=True, slo=slo,
                              registry=registry)
    arrivals = [r for r, c in zip(reqs, classes) if c == "interactive"]
    t0 = time.perf_counter()
    tok0 = engine.tokens_generated
    for r, cls in zip(reqs, classes):
        if cls == "batch":
            sched.submit(r)
    steps = 0
    while arrivals or not all(r.status.terminal for r in reqs):
        sched.step()
        steps += 1
        if arrivals and steps % 3 == 0:
            sched.submit(arrivals.pop(0))
    dt = time.perf_counter() - t0
    assert all(r.status == "finished" for r in reqs)
    return reqs, classes, dt, engine.tokens_generated - tok0


def overload_stats():
    """The --overload measurement, reusable by bench.py's serving leg:
    the SAME seeded mixed-class stream at >1x capacity served FIFO
    (slo=None — the verbatim baseline path) then SLO-aware (priority
    classes, preempt-to-host migration) on ONE engine at identical
    geometry. Headline fields: interactive TTFT p50/p99 both modes,
    per-class deadline-miss rate both modes (one threshold, calibrated
    at OVERLOAD_DEADLINE_PCT% of the matching FIFO window's wall),
    goodput (tokens/s of met-deadline completions), preempt/resume
    churn, and ``token_mismatched_requests`` vs FIFO (expected 0 —
    a preempted-then-resumed greedy request is bitwise)."""
    from apex_tpu import serving, telemetry

    engine = _build_engine(prefix_pool=PREFIX_POOL,
                           host_tier=HOST_TIER_MIB << 20)
    slo_cfg = serving.SLOConfig(
        classes={"batch": 0, "interactive": 10},
        preempt=True, deadline_admission=False)
    # compile warmup, discarded (FIFO shape; the SLO mode adds zero
    # compiled programs, so one warmup covers both modes)
    engine.reset(clear_prefixes=True)
    _serve_overload(engine, None, seed=31, registry=None)
    regs = {"fifo": telemetry.MetricsRegistry(),
            "slo": telemetry.MetricsRegistry()}
    served = {"fifo": [], "slo": []}
    # FIFO windows first: their walls calibrate the per-window
    # interactive deadline BOTH modes are judged against
    for w in range(WINDOWS):
        engine.reset(clear_prefixes=True)
        served["fifo"].append(_serve_overload(
            engine, None, seed=31 + w, registry=regs["fifo"]))
    deadlines = [OVERLOAD_DEADLINE_PCT / 100.0 * dt
                 for _, _, dt, _ in served["fifo"]]
    for w in range(WINDOWS):
        engine.reset(clear_prefixes=True)
        served["slo"].append(_serve_overload(
            engine, slo_cfg, seed=31 + w, registry=regs["slo"],
            interactive_deadline_s=deadlines[w]))
    engine.set_registry(None)

    rows = {}
    for mode in ("fifo", "slo"):
        ttfts = {"interactive": [], "batch": []}
        missed = {"interactive": 0, "batch": 0}
        count = {"interactive": 0, "batch": 0}
        met_tokens = total_tokens = 0
        wall = 0.0
        for w, (reqs, classes, dt, toks) in enumerate(served[mode]):
            wall += dt
            total_tokens += toks
            for r, cls in zip(reqs, classes):
                count[cls] += 1
                if r.ttft_s is not None:
                    ttfts[cls].append(r.ttft_s)
                miss = (cls == "interactive"
                        and r.latency_s is not None
                        and r.latency_s > deadlines[w])
                missed[cls] += bool(miss)
                if not miss:
                    met_tokens += len(r.output_tokens)
        counters = regs[mode].snapshot()["counters"]
        it = ttfts["interactive"]
        rows[mode] = {
            "metric": f"{OVERLOAD_METRIC}.{mode}",
            "value": round(met_tokens / wall, 2) if wall else 0.0,
            "unit": "tokens/s",
            "tokens_per_s": round(total_tokens / wall, 2)
            if wall else 0.0,
            "ttft_interactive_p50_ms": round(float(
                np.percentile(it, 50)) * 1e3, 3) if it else 0.0,
            "ttft_interactive_p99_ms": round(float(
                np.percentile(it, 99)) * 1e3, 3) if it else 0.0,
            "deadline_miss_rate_interactive": round(
                missed["interactive"] / count["interactive"], 4)
            if count["interactive"] else 0.0,
            "deadline_miss_rate_batch": round(
                missed["batch"] / count["batch"], 4)
            if count["batch"] else 0.0,
            "preemptions": int(counters.get(
                "serving.preempt.preemptions", 0)),
            "resumes": int(counters.get("serving.preempt.resumes", 0)),
            "resume_reprefills": int(counters.get(
                "serving.preempt.resume_reprefills", 0)),
            "deadline_rejected": int(counters.get(
                "serving.slo.deadline_rejected", 0)),
            "compiled_programs": engine.compiled_programs,
        }
    mism = 0
    for (f_reqs, _, _, _), (s_reqs, _, _, _) in zip(served["fifo"],
                                                    served["slo"]):
        mism += sum(list(a.output_tokens) != list(b.output_tokens)
                    for a, b in zip(f_reqs, s_reqs))
    fifo, slo = rows["fifo"], rows["slo"]
    summary = {
        "metric": OVERLOAD_METRIC,
        "value": slo["value"],
        "unit": "tokens/s",
        "goodput_fifo": fifo["value"],
        "tokens_per_s": slo["tokens_per_s"],
        "tokens_per_s_fifo": fifo["tokens_per_s"],
        "ttft_interactive_p50_ms": slo["ttft_interactive_p50_ms"],
        "ttft_interactive_p50_ms_fifo": fifo["ttft_interactive_p50_ms"],
        "ttft_interactive_p99_ms": slo["ttft_interactive_p99_ms"],
        "ttft_interactive_p99_ms_fifo": fifo["ttft_interactive_p99_ms"],
        "deadline_miss_rate_interactive":
            slo["deadline_miss_rate_interactive"],
        "deadline_miss_rate_interactive_fifo":
            fifo["deadline_miss_rate_interactive"],
        "deadline_miss_rate_batch": slo["deadline_miss_rate_batch"],
        "deadline_miss_rate_batch_fifo":
            fifo["deadline_miss_rate_batch"],
        # the tentpole's acceptance pair: under overload the SLO mode
        # must strictly beat FIFO on the interactive tail AND miss
        # rate, at zero token drift
        "ttft_p99_improved": slo["ttft_interactive_p99_ms"]
        < fifo["ttft_interactive_p99_ms"],
        "miss_rate_improved": slo["deadline_miss_rate_interactive"]
        < fifo["deadline_miss_rate_interactive"],
        "preemptions": slo["preemptions"],
        "resumes": slo["resumes"],
        "resume_reprefills": slo["resume_reprefills"],
        "deadline_rejected": slo["deadline_rejected"],
        "token_exact_vs_fifo": mism == 0,
        "token_mismatched_requests": mism,
        "deadline_pct_of_fifo_wall": OVERLOAD_DEADLINE_PCT,
        "windows": WINDOWS,
        "requests_per_window": REQUESTS,
        "slots": SLOTS,
        "overload_factor": round(REQUESTS / max(1, SLOTS), 2),
        "compiled_programs": engine.compiled_programs,
        "model": SIZE,
    }
    return rows, summary


def main_overload():
    import jax

    _load_env(smoke=dict(OVERLOAD_SMOKE))

    rows, summary = overload_stats()
    for mode in ("fifo", "slo"):
        print(json.dumps(rows[mode]))
    summary["backend"] = jax.default_backend()
    print(json.dumps(summary))


def _lora_adapter_sites(seed: int):
    """Seeded per-site stacked A/B matrices matching the leg model's
    projection geometry (the register-time shape contract): A is
    ``[layers, d_in, rank]``, B ``[layers, rank, d_out]`` per GEMM
    site. Scaled small so adapted logits stay near the base model's —
    the realistic fine-tune regime, and the one where a sign error in
    the epilogue would still flip greedy tokens loudly."""
    from apex_tpu.models.transformer_lm import create_lm

    model = create_lm(SIZE, vocab_size=VOCAB, max_seq_len=MAX_LEN)
    h, layers = model.hidden, model.num_layers
    inner = model.mlp_ratio * h
    rng = np.random.default_rng(seed)
    dims = {"qkv": (h, 3 * h), "proj": (h, h),
            "mlp_in": (h, inner), "mlp_out": (inner, h)}
    return {site: (0.05 * rng.standard_normal(
                       (layers, d_in, LORA_RANK)).astype(np.float32),
                   0.05 * rng.standard_normal(
                       (layers, LORA_RANK, d_out)).astype(np.float32))
            for site, (d_in, d_out) in dims.items()}


def _lora_requests(rng, names):
    """The mixed-tenant stream: adapter assignment cycles through the
    base model (``adapter=None``) plus every registered adapter, so a
    full batch is maximally heterogeneous."""
    from apex_tpu.serving import Request

    cycle = [None] + list(names)
    reqs = []
    for i in range(REQUESTS):
        n = int(rng.integers(1, PREFILL_LEN + 1))
        budget = max(1, min(NEW_TOKENS, MAX_LEN - n))
        reqs.append(Request(
            prompt=rng.integers(1, VOCAB, size=n).tolist(),
            max_new_tokens=budget, adapter=cycle[i % len(cycle)]))
    return reqs


def _serve_lora(mixed: bool, names):
    """WINDOWS measured windows (plus compile warmup) of the mixed-
    tenant stream on a fresh LoRA engine. ``mixed`` drains the whole
    window in ONE scheduler run (heterogeneous batches); the baseline
    partitions the SAME request list by adapter and drains each group
    alone — identical requests, identical geometry, only batch
    composition differs. Returns the rate, the measured requests (in
    stream order — the bitwise-compare key), the engine, the
    ``serving.lora.*`` counter deltas past warmup, and the number of
    programs compiled AFTER warmup (the zero-recompile claim)."""
    from apex_tpu import serving
    from apex_tpu.serving import LoRAConfig

    arena = LORA_ARENA or len(names)
    engine = _build_engine(lora=LoRAConfig(
        rank=LORA_RANK, arena_slots=arena, host_bytes=64 << 20))
    for i, name in enumerate(names):
        engine.lora_register(name, _lora_adapter_sites(100 + i),
                             alpha=0.5)
    rng = np.random.default_rng(11)
    rates, all_reqs = [], []
    warm_stats, warm_programs = {}, 0
    for w in range(WINDOWS + 1):
        engine.reset()          # adapter residency survives (warm arena)
        if w == 1:
            warm_stats = dict(engine.lora.stats())
            warm_programs = engine.compiled_programs
        reqs = _lora_requests(rng, names)
        if mixed:
            groups = [reqs]
        else:
            groups = [[r for r in reqs if r.adapter == a]
                      for a in [None] + list(names)]
            groups = [g for g in groups if g]
        t0 = time.perf_counter()
        tok0 = engine.tokens_generated
        for grp in groups:
            sched = serving.Scheduler(engine,
                                      max_queue=max(REQUESTS, 1),
                                      chunk_budget=CHUNK_BUDGET)
            done = sched.run(list(grp))
            assert len(done) == len(grp)
        dt = time.perf_counter() - t0
        toks = engine.tokens_generated - tok0
        if w > 0:
            rates.append(toks / dt)
            all_reqs.extend(reqs)
    end = engine.lora.stats()
    delta = {k: end[k] - warm_stats.get(k, 0)
             for k in ("hits", "loads", "evictions")}
    return (_median(rates), all_reqs, engine, delta,
            engine.compiled_programs - warm_programs)


def lora_stats():
    """The --lora measurement, reusable by bench.py's serving
    trajectory leg: the mixed-tenant stream served heterogeneously
    batched vs per-adapter sequential at identical geometry. Headline
    fields: tokens/s both modes + ``speedup_x``, the adapter churn
    columns (``warm_bind_rate`` is the affinity-routing payoff
    reading), arena/host-store occupancy, ``recompiles_after_warmup``
    (expected 0 — N adapters, zero new programs), and
    ``token_mismatched_requests`` (expected 0 — per-slot isolation is
    bitwise, so batch composition moves no token)."""
    names = [f"tenant-{i}" for i in range(LORA_ADAPTERS)]
    rows, outputs = {}, {}
    for mode in ("mixed", "sequential"):
        rate, reqs, engine, churn, recompiles = _serve_lora(
            mode == "mixed", names)
        ttfts = [r.ttft_s for r in reqs if r.ttft_s]
        binds = churn["hits"] + churn["loads"]
        stats = engine.lora.stats()
        rows[mode] = {
            "metric": f"{LORA_METRIC}.{mode}",
            "value": round(rate, 2),
            "unit": "tokens/s",
            "ttft_p50_ms": round(
                float(np.percentile(ttfts, 50)) * 1e3, 3)
            if ttfts else 0.0,
            "ttft_p99_ms": round(
                float(np.percentile(ttfts, 99)) * 1e3, 3)
            if ttfts else 0.0,
            "lora_hits": churn["hits"],
            "lora_loads": churn["loads"],
            "lora_evictions": churn["evictions"],
            "warm_bind_rate": round(churn["hits"] / binds, 4)
            if binds else 0.0,
            "arena_bytes": stats["bytes_used"],
            "active_adapters": stats["resident"],
            "compiled_programs": engine.compiled_programs,
            "recompiles_after_warmup": recompiles,
        }
        outputs[mode] = [list(r.output_tokens) for r in reqs]
    mismatched = sum(a != b for a, b in zip(outputs["mixed"],
                                            outputs["sequential"]))
    mx, sq = rows["mixed"], rows["sequential"]
    summary = {
        "metric": LORA_METRIC,
        "value": mx["value"],
        "unit": "tokens/s",
        "baseline_tokens_per_s": sq["value"],
        "speedup_x": round(mx["value"] / sq["value"], 3)
        if sq["value"] else 0.0,
        "token_mismatched_requests": mismatched,
        "adapters": LORA_ADAPTERS,
        "rank": LORA_RANK,
        "arena_slots": LORA_ARENA or LORA_ADAPTERS,
        "lora_hits": mx["lora_hits"],
        "lora_loads": mx["lora_loads"],
        "lora_evictions": mx["lora_evictions"],
        "warm_bind_rate": mx["warm_bind_rate"],
        "arena_bytes": mx["arena_bytes"],
        "active_adapters": mx["active_adapters"],
        "compiled_programs": mx["compiled_programs"],
        "recompiles_after_warmup": mx["recompiles_after_warmup"],
        "ttft_p50_ms": mx["ttft_p50_ms"],
        "ttft_p99_ms": mx["ttft_p99_ms"],
        "ttft_p50_ms_sequential": sq["ttft_p50_ms"],
        "ttft_p99_ms_sequential": sq["ttft_p99_ms"],
        "windows": WINDOWS,
        "requests_per_window": REQUESTS,
        "slots": SLOTS,
        "model": SIZE,
    }
    return rows, summary


def main_lora():
    import jax

    _load_env(smoke=dict(LORA_SMOKE))

    rows, summary = lora_stats()
    for mode in ("mixed", "sequential"):
        print(json.dumps(rows[mode]))
    summary["backend"] = jax.default_backend()
    print(json.dumps(summary))


if __name__ == "__main__":
    from apex_tpu.telemetry import guard_bench_main
    from apex_tpu.utils.chip import enable_compile_cache

    enable_compile_cache()
    if "--mixed-prompts" in sys.argv[1:]:
        guard_bench_main(main_mixed, MIXED_METRIC)
    elif "--shared-prefix" in sys.argv[1:]:
        guard_bench_main(main_shared, SHARED_METRIC)
    elif "--paged-pool" in sys.argv[1:]:
        guard_bench_main(main_paged, PAGED_METRIC)
    elif "--chaos" in sys.argv[1:]:
        guard_bench_main(main_chaos, CHAOS_METRIC)
    elif "--speculative" in sys.argv[1:]:
        guard_bench_main(main_spec, SPEC_METRIC)
    elif "--tensor-parallel" in sys.argv[1:]:
        guard_bench_main(main_tp, TP_METRIC)
    elif "--quantized-kv" in sys.argv[1:]:
        guard_bench_main(main_quant, QUANT_METRIC)
    elif "--quantized-weights" in sys.argv[1:]:
        guard_bench_main(main_wquant, WQUANT_METRIC)
    elif "--async-heartbeat" in sys.argv[1:]:
        guard_bench_main(main_async, ASYNC_METRIC)
    elif "--replica-router" in sys.argv[1:]:
        guard_bench_main(main_router, ROUTER_METRIC)
    elif "--disaggregated" in sys.argv[1:]:
        guard_bench_main(main_disagg, DISAGG_METRIC)
    elif "--process-fleet" in sys.argv[1:]:
        guard_bench_main(main_fleet, FLEET_METRIC)
    elif "--host-tier" in sys.argv[1:]:
        guard_bench_main(main_host_tier, HOST_METRIC)
    elif "--overload" in sys.argv[1:]:
        guard_bench_main(main_overload, OVERLOAD_METRIC)
    elif "--lora" in sys.argv[1:]:
        guard_bench_main(main_lora, LORA_METRIC)
    else:
        guard_bench_main(main, METRIC)
