"""Compiled inference engine: chunk prefill and decode over a paged KV pool.

The cache is a dense pool of fixed-size pages
(:class:`~apex_tpu.serving.PagedKVCache`) addressed through per-slot
page tables (:class:`~apex_tpu.serving.PagePool` host allocator);
lengths live host-side. The engine owns the XLA executables a serving
process needs, each traced once at fixed shapes and each taking a
``[.., max_pages]`` int32 page-table operand next to the tokens:

- **chunk prefill** (the one ingestion path): ``[1, chunk_len]``
  tokens (one chunk of a prompt, right-padded on the final partial
  chunk) → the model's chunked-prefill forward against ONE slot's
  pages, K/V written as whole pages at ``[offset, offset +
  chunk_len)``, shifted-causal attention over the slot's existing
  prefix, a token sampled from the last *valid* row (the request's
  first token when the chunk is final; discarded otherwise). Offset,
  valid-count, temperature and the PRNG key are *traced* scalars —
  every chunk of every prompt lands in this one executable, and the
  scheduler runs at most one between decode steps, so in-flight
  decodes never wait more than one chunk for a new admit.
- **decode step**: ``[slots, 1]`` tokens (every slot's latest token) →
  single-token cached forward, one new token per slot. Inactive slots
  compute too (their output is discarded and their length frozen) —
  that padding waste is the price of a fixed-shape program, and the
  scheduler reports it.

Prefix reuse is copy-on-write: a hit SHARES the donor's pages
(refcount bump, zero data movement) and the remaining suffix flows
through the chunk program starting at the matched offset, skipping
``matched_len / chunk_len`` chunks of attention+MLP compute outright —
no program of its own.

Sampling runs inside the compiled programs: greedy when a slot's
temperature is 0, else temperature softmax over logits optionally
truncated to the engine's static ``top_k``. Temperatures are per-slot
traced values; ``top_k`` is static (a different ``top_k`` is a new
engine).

Every sampling program also carries the **non-finite guard**: an
``all(isfinite)`` reduction over the fp32 logits row(s) it samples
from, returned per slot so the host (the scheduler's fault policy) can
quarantine a NaN/Inf slot while its batchmates keep their exact tokens
— fused into the existing executables, zero new programs. The decode
and chunk programs additionally take a ``fault_bias`` logit-offset
operand (all-zero in production — adding +0.0 to an fp32 row is
value-identical — NaN/Inf under a
:class:`~apex_tpu.serving.FaultPlan`, which makes the guard fire on
real non-finite logits). Verdicts land in
:attr:`Engine.last_decode_finite` / :attr:`Engine.last_chunk_finite`
and count ``serving.faults.nonfinite``.

**Speculative verify** (``spec=SpecConfig(...)``): one more compiled
program — a BATCHED ``[slots, K+1]`` draft-and-verify step built on the
chunk-append machinery, the same fixed-shape discipline as decode:
every verify-eligible slot shares ONE program invocation per heartbeat
(instead of B sequential single-slot calls), and slots not verifying
ride along as padding whose cache bytes are provably untouched (their
table-row operand is zeroed so writes land on the sentinel page). The
host drafts K tokens per slot (prompt-lookup n-gram — see
:mod:`apex_tpu.serving.speculative`), the program embeds each row's
``[last_token, d_1 .. d_K]`` at that slot's current offset, writes
their K/V (per-position scatters — ``unaligned_append``), runs
shifted-causal attention, and computes ACCEPT-LONGEST-PREFIX
*in-program* per row:
greedy target ``g_s``, ``n_accepted`` = the longest run with
``d_i == g_{i-1}``. The emitted tokens ``g_0 .. g_m`` are the
program's own greedy targets, so greedy output is token-identical to
plain decode by construction. The rejected tail's K/V is written but
NEVER visible: lengths are what gate attention, and the host sets each
verifying slot's length to ``offset + n_accepted + 1`` —
rollback is a length decrement, no cache mutation to undo; the stale
positions are overwritten write-then-attend before anything can attend
them (the same contract inactive-slot decode writes already live by).
One executable serves every draft/offset/slot combination AND the
single-slot :meth:`Engine.verify_step` wrapper (``verify_traces`` pins
it); a fused per-row isfinite guard + per-slot ``fault_bias`` operand
give chaos the same grip it has on every other program
(:attr:`Engine.last_verify_finite_slots`).

**Async dispatch** (the pipelined heartbeat's engine half): the decode
step is split into :meth:`Engine.decode_dispatch` — enqueue the
compiled call and return a :class:`PendingDecode` whose sampled tokens
stay ON DEVICE — and :meth:`Engine.decode_reconcile` — one batched
readback per step, where emission accounting and the finiteness
verdict land. ``decode_dispatch`` accepts a previous pending step's
un-forced token array as its ``last_tokens``, so decode step t+1
chains onto step t entirely on the device; :meth:`Engine.decode_step`
is the two halves back-to-back (the depth-0 sync oracle — same
program, same operands, same bytes). Every site that blocks on the
runtime — forced reads (token readback, finite flags), the
:meth:`Engine.sync` barrier, and the compiled calls themselves
(:meth:`Engine._runtime_call`: the CPU backend executes
donated-buffer programs synchronously inside dispatch, so the call's
block time IS device execution there) — charges its block time to
:attr:`Engine.device_wait_s`, which the scheduler differences per
heartbeat into the ``serving.heartbeat.*`` host-think / device-wait
split. The same seconds are kept apart where the work happens, as
phases (``apex.engine.upload`` / ``launch`` / ``readback``, see
:func:`apex_tpu.telemetry.tracing.phase`) and as the counters
:attr:`Engine.upload_s`, :attr:`Engine.launch_s` and
:attr:`Engine.readback_s`, which sum to ``device_wait_s``: a program's
operands are built and uploaded (:meth:`Engine._operands`) before the
compiled call that takes them, so that what a launch costs on silicon
is a number and not an assumption.

**Tensor parallelism** (``mesh=...``): the same programs,
shard_map'd over a 1-D tensor-parallel mesh axis
(:mod:`apex_tpu.serving.sharding`). Params split per a
``match_partition_rules`` table (qkv/MLP-up column-parallel, proj/
MLP-down row-parallel, embeddings replicated), the KV pool shards
along the HEADS axis (``[layers, num_pages, heads/tp, head_dim,
page_len]`` per shard) so attention never crosses ICI, and the only
collectives are the two canonical TP all-reduces per block
(post-attention, post-MLP) plus ONE all-gather of the sampled logits
rows (the tied head computes vocab/tp slices per shard) — 2 psums per
block + 1 gather per program, pinned from compiled HLO. ``mesh=None``
(the default) is the verbatim single-chip baseline — none of the
sharding code is on its trace path — and a ``tp=1`` mesh is pinned
bitwise against it on a greedy stream.

Weights are cast ONCE at construction through the amp cast-policy
machinery (default: pure-half O3 — bf16 storage, no fp32 masters, the
cache in the same dtype); pass ``policy=amp.resolve_policy("O0")`` for
an exact-fp32 engine (the decode-parity tests' configuration).

Trace accounting: the python bodies of the programs run only when jax
traces them, so ``chunk_traces``/``decode_traces`` count compiles — the
serving test tier pins the engine to exactly TWO compiled programs
across a multi-request, variable-length, hit/miss/evict run
(copy-on-write sharing is host bookkeeping, not a program).

Host bookkeeping (all numpy, no device work):

- ``page_len`` positions per page (``decode.page_len`` tuned key,
  degraded to divide ``chunk_len`` — chunk writes must cover whole
  pages so shared pages are never written);
- a ``[slots, max_pages]`` page table mirrored to the device as an
  operand of every call; page 0 is the sentinel the fixed-shape decode
  program's inactive-slot writes land on;
- worst-case page **reservation** at admission
  (:meth:`Engine.try_reserve_slot` — the scheduler's admit gate), so an
  admitted request can always grow to its token budget: pool pressure
  queues requests, evicts LRU prefix entries, and ultimately surfaces
  as submit-side ``QueueFull`` — never a mid-decode failure;
- prefix retention/hits as page sharing (:meth:`Engine.retain_prefix` /
  :meth:`Engine.attach_prefix`) with refcounts in the
  :class:`~apex_tpu.serving.PagePool`.
"""

from __future__ import annotations

import dataclasses
import math
import threading
import time
import weakref
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.kernels import vmem
from apex_tpu.log_util import get_logger
from apex_tpu.telemetry import tracing

from .host_tier import HostTier, SwapWorker
from .kv_cache import (CacheSpec, PagedKVCache, PagePool, SlotAddr,
                       SlotState)
from .kv_quant import KVQuantConfig
from .prefix_cache import PrefixCache
from .speculative import SpecConfig
from .weight_quant import WeightQuantConfig

__all__ = ["Engine", "PendingDecode", "resolve_page_len",
           "sample_tokens"]

_logger = get_logger("serving")


def resolve_page_len(chunk_len: int, page_len: Optional[int] = None) -> int:
    """The engine's page-size resolution, exposed so external sizers
    compute pool geometry with the SAME value the constructor will: an
    explicit
    ``page_len`` must divide ``chunk_len`` (chunk writes must cover
    whole pages — the copy-on-write invariant); the default is the
    ``decode.page_len`` tuned key, else ``min(chunk_len, 128)``,
    degraded to the largest common divisor of ``chunk_len``."""
    chunk_len = int(chunk_len)
    if page_len is None:
        page_len = vmem.get_override("decode.page_len", 0) \
            or min(chunk_len, 128)
        if chunk_len % page_len:
            page_len = math.gcd(page_len, chunk_len)
    page_len = int(page_len)
    if page_len < 1 or chunk_len % page_len:
        raise ValueError(
            f"page_len {page_len} must divide chunk_len {chunk_len} "
            f"(chunk writes must cover whole pages — a partially-"
            f"written shared page would break copy-on-write)")
    return page_len


def sample_tokens(logits, temperature, key, top_k: int = 0):
    """Sample one token per row of ``logits`` [N, V] (inside jit).

    ``temperature`` [N]: 0 → greedy (argmax), > 0 → softmax sampling at
    that temperature. ``top_k`` (static): when > 0, logits outside each
    row's top-k are masked before sampling. Greedy rows ignore top_k
    (argmax is already top-1)."""
    logits = jnp.asarray(logits, jnp.float32)
    temperature = jnp.asarray(temperature, jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if top_k and top_k > 0 and top_k < logits.shape[-1]:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    sampled = jax.random.categorical(key, scaled).astype(jnp.int32)
    return jnp.where(temperature > 0, sampled, greedy)


@dataclasses.dataclass
class PendingDecode:
    """One dispatched-but-unread decode step — the handle the async
    pipelined heartbeat holds between :meth:`Engine.decode_dispatch`
    and :meth:`Engine.decode_reconcile`.

    ``tokens`` / ``finite`` are DEVICE arrays: touching them with
    ``int()`` / ``float()`` / ``np.asarray`` forces the host to wait
    for the step — exactly the stall dispatch-ahead execution exists to
    remove — so nothing reads them until reconcile (the scheduler lint
    in ``tests/L0/test_serving_metrics_lint.py`` enforces this on the
    dispatch region). ``active`` is the host-side dispatch mask (who
    the step computed for) and ``t_dispatch`` the dispatch timestamp,
    so reconcile can observe the full dispatch→retire latency as
    ``serving.decode.step_s`` (in sync mode reconcile follows dispatch
    immediately and the reading degenerates to today's measurement).
    ``attended`` is what each decoding row's length was
    in this step, kept for reconcile's ``serving.decode.pages_live`` /
    ``pages_tabled`` / ``pages_written`` counters: dispatch counts
    nothing itself."""

    tokens: Any                 # [slots] int32, ON DEVICE until reconcile
    finite: Any                 # [slots] bool, ON DEVICE until reconcile
    active: np.ndarray          # [slots] bool, host dispatch mask
    t_dispatch: float
    attended: np.ndarray        # [decoding rows] int lengths
    reconciled: bool = False


@dataclasses.dataclass
class PendingPrefill:
    """One dispatched-but-unread chunk-prefill step — the prefill-path
    twin of :class:`PendingDecode`, held by the scheduler between
    :meth:`Engine.prefill_chunk_dispatch` and
    :meth:`Engine.prefill_chunk_reconcile` so chunk ``t+1`` can be
    issued before chunk ``t``'s sampled token is forced to host.

    ``token`` / ``finite`` are DEVICE scalars until reconcile; the
    force-early lint covers the dispatch half by name, exactly like the
    decode region. ``final`` and the timestamps are host bookkeeping so
    reconcile can finish the chunk's counters identically to the
    synchronous path."""

    token: Any                  # scalar int32, ON DEVICE until reconcile
    finite: Any                 # scalar bool, ON DEVICE until reconcile
    slot: int
    final: bool
    t_dispatch: float
    dispatch_s: float
    reconciled: bool = False


class Engine:
    """KV-cache inference engine over a ``TransformerLM``-shaped model.

    Parameters
    ----------
    model:
        A flax module with the cache-threading contract of
        :class:`apex_tpu.models.transformer_lm.TransformerLM`
        (``cache``/``positions`` chunk prefill and decode) and the
        geometry attributes ``num_heads``/``hidden``/``max_seq_len``.
        What it needs held is its ``cache_spec()``
        (:class:`~apex_tpu.serving.kv_cache.CacheSpec`: the layers that
        hold pages, of what K/V heads, and the blocks of per-slot state
        some layers keep; without one: pages of all its heads on each
        of ``num_layers`` layers). A model whose spec has state takes
        ``state``/``addr`` besides and returns the updated blocks and
        its tokens-per-expert counts with the pools.
    params:
        The model's parameter pytree (e.g. a train state's params).
        Cast once through ``policy.cast_params`` — by default to the
        pure-half O3 shape.
    slots:
        Concurrent sequences per decode step (the continuous-batching
        width).
    max_len:
        Cache positions per slot (prompt + generation budget); must not
        exceed the model's ``max_seq_len``.
    prefill_len:
        The longest prompt the chunk program accepts (``<= max_len``).
        Longer prompts are rejected at submit time.
    chunk_len:
        Tokens per chunk-prefill step (default ``min(prefill_len,
        256)``). Smaller chunks bound the stall a prefill imposes on
        in-flight decodes more tightly but pay more per-chunk overhead;
        lane-aligned values (multiples of 128) keep the chunk kernel on
        its Pallas path.
    policy:
        An :class:`apex_tpu.amp.Policy` governing weight/cache storage;
        default ``resolve_policy("O3", verbose=False)`` (pure bf16).
    prefix_pool:
        Full-length requests' worth of pages set aside in the
        ``num_pages`` default for content-addressed prompt-prefix reuse
        (0 = off). When > 0 the engine exposes a
        :class:`~apex_tpu.serving.PrefixCache` as ``prefix_cache``
        (consulted by ``Scheduler(retain_prefixes=True)``); retained
        prefixes share the one pool copy-on-write.
    page_len:
        Positions per page. Default: the ``decode.page_len``
        tuned key, else ``min(chunk_len, 128)``, degraded to the largest
        common divisor of ``chunk_len`` — a page is the unit of sharing
        and must be covered whole by every chunk write. An explicit
        value that does not divide ``chunk_len`` is rejected.
    num_pages:
        Physical pool pages INCLUDING the page-0 sentinel.
        Default: ``(slots + prefix_pool) * ceil(max_len / page_len) + 1``
        — every slot and retained prefix at full length; size it down
        for denser sharing or up for more retained prefixes.
    spec:
        A :class:`~apex_tpu.serving.SpecConfig` enabling the
        speculative-verify program (``draft_len`` fixes its
        ``[slots, K+1]`` compiled shape — one batched invocation serves
        every verify-eligible slot per heartbeat). None (the default)
        compiles nothing extra and leaves today's program set
        untouched; the program itself traces lazily on the first
        :meth:`verify_batch` / :meth:`verify_step`.
    mesh:
        A 1-D :class:`jax.sharding.Mesh` enabling tensor-parallel
        serving: every compiled program runs shard_map'd
        over the mesh axis with params split per the
        :mod:`~apex_tpu.serving.sharding` rule table and the KV pool
        sharded along heads (``heads % tp == 0`` enforced, as are the
        MLP-inner and vocab splits). ``mesh=None`` (the default) is
        the verbatim single-chip engine.
    kv_quant:
        A :class:`~apex_tpu.serving.KVQuantConfig` turning on the
        quantized cache STORAGE tier (composes
        with prefix sharing, speculative verify and ``mesh=``): K/V are
        stored as int8 with per-``[layer, head]`` fp32 scales carried
        in the cache pytree — halving pool HBM, so the same bytes hold
        ~2x the slots/pages — cache writes quantize in-program and the
        attention kernels dequantize in-kernel. Scales are calibrated
        (or given) at construction; degenerate calibration (absmax 0 /
        non-finite) raises HERE. Greedy output becomes a
        token-match-rate claim vs the bf16 oracle; ``kv_quant=None`` (the
        default) is the bitwise bf16 baseline — none of the quant code
        is on its trace path. The program set is unchanged either way
        (dequant is fused, never a new executable).
    weight_quant:
        A :class:`~apex_tpu.serving.WeightQuantConfig` turning on the
        quantized WEIGHT storage tier (composes with
        ``kv_quant``, prefix sharing, speculative verify, the async
        heartbeat, ``host_tier`` and ``mesh=``): the big serving GEMM
        kernels — qkv, proj, MLP in/out, and the tied vocab head — are
        stored int8 with per-output-channel fp32 scales, and dequant
        is the scale multiply folded onto each GEMM's accumulator in
        the epilogue (:mod:`~apex_tpu.serving.weight_quant`). Roughly
        halves weight HBM vs bf16; together with ``kv_quant`` the two
        dominant resident allocations both shrink. A params property,
        not a program — the compiled-program set and every trace-count
        pin are unchanged. Calibration is the per-channel absmax of
        the (policy-cast) weights themselves, resolved HERE with the
        loud degenerate-channel failure; under a mesh the scales shard
        with their kernels per the partition-rule table. Greedy output
        becomes a token-match-rate claim vs the bf16 oracle;
        ``weight_quant=None`` (the default) is the bitwise baseline —
        none of the quant code is on its trace path.
    host_tier:
        Hierarchical-KV host-DRAM prefix tier (requires
        ``prefix_pool > 0``; composes with ``mesh=``): an int capacity
        in BYTES, or a pre-built :class:`~apex_tpu.serving.HostTier`.
        When set, a prefix entry evicted under pool pressure has its
        page bytes migrated device→host into the bounded arena instead
        of being destroyed (int8 under ``kv_quant`` — half the
        transfer bytes). Swap-out is ASYNCHRONOUS by default: the
        admission path only DISPATCHES a fixed-shape compiled gather
        (``swap_out`` — the pool-byte snapshot is taken at dispatch,
        before the freed pages can be reused) and hands the un-forced
        device blocks to a :class:`~apex_tpu.serving.SwapWorker`
        thread, which forces, checksums and stores them off the hot
        path; the entry sits matchable in the *swapping* state
        meanwhile, and a hit racing its own swap-out JOINS the
        in-flight copy (never reads partial bytes). A later hit
        migrates the bytes back through the other compiled program
        (``swap_in``: a fixed-shape page-block scatter, one dispatch
        per swap-in — no attention, no sampling, no PRNG) before
        copy-on-write sharing as usual. Restored pages are byte-exact
        (per-shard CRC-verified; a corrupt/missing swap-in degrades to
        a verified miss and a re-prefill, never a wrong token), so a
        hit-after-swap greedy stream is bitwise identical to a
        never-swapped one — asynchronously or not — and prefix
        capacity is bounded by host RAM instead of device HBM. Under
        a ``mesh`` both swap programs run shard_map'd with the pool's
        heads-axis sharding — each shard gathers/scatters its own
        ``heads/tp`` slice, ZERO collectives (pure data movement) —
        and arena records carry one CRC per shard. ``None`` (default)
        keeps today's destroy-on-evict behaviour and traces nothing
        extra.
    sync_swap:
        Escape hatch (``host_tier`` only): True forces the PRE-ASYNC
        behaviour — swap-out forces the gathered bytes to host and
        stores them inline on the admission path (no worker thread).
        The emitted token streams are bitwise identical either way
        (pinned); the hatch exists for debugging and as the measurable
        baseline (``serving.swap.admit_stall_s`` sync vs async is the
        admission-stall claim).
    top_k:
        Static top-k truncation for sampled (non-greedy) slots; 0 = off.
    registry:
        Optional :class:`apex_tpu.telemetry.MetricsRegistry`; when set,
        the engine observes ``serving.decode.step_s`` and
        ``serving.prefill_chunk_s`` latencies and counts generated
        tokens.
    """

    def __init__(self, model, params, *, slots: int, max_len: int,
                 prefill_len: Optional[int] = None,
                 chunk_len: Optional[int] = None, policy=None,
                 prefix_pool: int = 0, top_k: int = 0, seed: int = 0,
                 registry=None, page_len: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 spec: Optional[SpecConfig] = None, mesh=None,
                 kv_quant: Optional[KVQuantConfig] = None,
                 weight_quant: Optional[WeightQuantConfig] = None,
                 host_tier=None, sync_swap: bool = False, lora=None):
        from apex_tpu.amp.policy import resolve_policy

        if policy is None:
            policy = resolve_policy("O3", verbose=False)
        self.policy = policy
        half = policy.compute_dtype
        max_seq = int(getattr(model, "max_seq_len", max_len))
        if max_len > max_seq:
            raise ValueError(f"max_len {max_len} exceeds the model's "
                             f"max_seq_len {max_seq}")
        if prefill_len is None:
            prefill_len = max_len
        if not 0 < prefill_len <= max_len:
            raise ValueError(f"prefill_len {prefill_len} must be in "
                             f"(0, max_len={max_len}]")
        if slots < 1:
            raise ValueError("slots must be >= 1")
        if chunk_len is None:
            chunk_len = min(int(prefill_len), 256)
            if -(-int(prefill_len) // chunk_len) * chunk_len > max_len:
                # the defaulted geometry must always be servable: when
                # the rounded-up window would spill past the cache
                # (prefill_len just over a chunk multiple with little
                # decode headroom), degrade to single-chunk ingestion
                chunk_len = int(prefill_len)
        if not 0 < chunk_len <= prefill_len:
            raise ValueError(f"chunk_len {chunk_len} must be in "
                             f"(0, prefill_len={prefill_len}]")
        # every chunk writes a full chunk_len-wide K/V slice (the final
        # partial chunk is padded), so the LAST chunk's window must fit
        # the cache: otherwise the model's position clip would silently
        # relocate the write over earlier prompt K/V (cache corruption,
        # not an error). Reject the geometry loudly at construction.
        n_chunks = -(-int(prefill_len) // int(chunk_len))
        if n_chunks * int(chunk_len) > max_len:
            raise ValueError(
                f"chunk_len {chunk_len}: the final chunk window "
                f"[{(n_chunks - 1) * chunk_len}, {n_chunks * chunk_len})"
                f" of a prefill_len={prefill_len} prompt exceeds "
                f"max_len={max_len}; pick a chunk_len with "
                f"ceil(prefill_len/chunk_len)*chunk_len <= max_len")
        if prefix_pool < 0:
            raise ValueError("prefix_pool must be >= 0")
        if spec is not None:
            if not isinstance(spec, SpecConfig):
                raise TypeError(f"spec must be a SpecConfig, got "
                                f"{type(spec).__name__}")
            if spec.draft_len + 1 > max_len:
                raise ValueError(
                    f"spec.draft_len {spec.draft_len}: a verify step "
                    f"writes draft_len + 1 = {spec.draft_len + 1} "
                    f"positions, which cannot fit max_len={max_len}")
        self.spec = spec
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.prefill_len = int(prefill_len)
        self.chunk_len = int(chunk_len)
        self.prefix_pool = int(prefix_pool)
        self.top_k = int(top_k)
        hidden = int(model.hidden)
        heads = int(model.num_heads)
        # the pool's and the state's geometry are the MODEL's, stated per
        # kind of layer (kv_cache.CacheSpec): which of its layers hold
        # pages, of how many K/V heads (fewer than its query heads under
        # grouped-query attention) of what size, and which hold blocks of
        # per-slot state, of what shape and dtype
        self.cache_spec = spec_ = CacheSpec.of(model)
        layers = spec_.page_layers
        kv_heads, head_dim = spec_.kv_heads, spec_.head_dim
        # per-slot state beside the pages (kv_cache.SlotState): what is
        # not built for it is refused by name HERE, never run wrong
        self.slot_state = bool(spec_.state)
        self.model_kind = str(getattr(model, "model_kind",
                                      type(model).__name__))
        if self.slot_state:
            for on, what in (
                    (prefix_pool > 0, "prefix_cache retention "
                     "(prefix_pool > 0)"),
                    (host_tier is not None, "host_tier swap"),
                    (spec is not None, "speculative verify (the "
                     "unaligned window)"),
                    (lora is not None, "LoRA adapters"),
                    (kv_quant is not None, "the int8 KV tier (kv_quant)"),
                    (weight_quant is not None, "the int8 weight tier "
                     "(weight_quant)"),
                    (mesh is not None, "tensor parallelism (mesh=)")):
                if on:
                    raise NotImplementedError(
                        f"serving.Engine: {what} is not built for a model "
                        f"with per-slot state ({self.model_kind!r}: "
                        f"{', '.join(b.name for b in spec_.state)} beside "
                        "its pages); it re-enters or "
                        "re-shapes a request from pages alone, and the "
                        "slot's state would be wrong")
        # quantized-cache storage tier (independent of the COMPUTE half
        # dtype the policy picks): int8 K/V with per-[layer, head] fp32
        # scales, resolved HERE so a degenerate calibration (absmax 0 /
        # non-finite) is a loud construction error, never NaN output.
        # Calibration runs on the caller's uncast model/params — absmax
        # estimation does not need the serving dtype's rounding.
        self.kv_quant = kv_quant
        if kv_quant is not None:
            if not isinstance(kv_quant, KVQuantConfig):
                raise TypeError(f"kv_quant must be a KVQuantConfig, "
                                f"got {type(kv_quant).__name__}")
            k_scale, v_scale = kv_quant.resolve_scales(
                model, params, layers=layers, heads=heads)
            cache_dtype = jnp.dtype(kv_quant.dtype)
        else:
            k_scale = v_scale = None
            cache_dtype = half
        # quantized WEIGHT storage tier (independent of both the
        # compute half dtype and the cache tier): int8 GEMM kernels
        # with per-output-channel fp32 scales, dequantized in the
        # matmul epilogue. A params property, not a program — the
        # compiled-program set and every trace-count pin are unchanged.
        self.weight_quant = weight_quant
        if weight_quant is not None \
                and not isinstance(weight_quant, WeightQuantConfig):
            raise TypeError(f"weight_quant must be a WeightQuantConfig, "
                            f"got {type(weight_quant).__name__}")
        self.mesh = mesh
        if mesh is not None:
            from . import sharding as _sharding

            self._tp_axis = _sharding.tp_axis_of(mesh)
            self.tp = int(np.prod(mesh.devices.shape))
            _sharding.validate_tp_geometry(
                self.tp, num_heads=heads, hidden=hidden,
                mlp_ratio=int(getattr(model, "mlp_ratio", 4)),
                vocab_size=int(model.vocab_size))
        else:
            self._tp_axis = None
            self.tp = 1
        # pin the eval dtype on the module itself so decode GEMMs and
        # the cache agree (pure-half: no fp32 masters anywhere); under a
        # mesh also pin the tensor-parallel shard geometry (the module
        # becomes one Megatron-style shard inside shard_map)
        clone_kw = {"inference_dtype": half}
        if mesh is not None:
            clone_kw.update(tp_axis=self._tp_axis, tp_size=self.tp)
        if weight_quant is not None:
            clone_kw["weight_quant"] = True
        try:
            self._model = model.clone(**clone_kw)
        except TypeError:  # model without the inference_dtype field
            # diagnose by the field actually missing — a tp-capable
            # model lacking only weight_quant (or vice versa) must be
            # told about ITS gap, not the other feature's
            fields = set(getattr(type(model), "__dataclass_fields__",
                                 ()))
            if mesh is not None \
                    and not {"tp_axis", "tp_size"} <= fields:
                raise TypeError(
                    "Engine(mesh=...) needs a model with tp_axis/"
                    "tp_size fields (the TransformerLM tensor-parallel "
                    "contract)")
            if weight_quant is not None \
                    and "weight_quant" not in fields:
                raise TypeError(
                    "Engine(weight_quant=...) needs a model with the "
                    "weight_quant field (the TransformerLM "
                    "quantized-serving contract)")
            if mesh is not None or weight_quant is not None:
                # the fields exist, so the clone failed for some other
                # reason — degrading to the un-cloned model would
                # silently drop the requested tier
                raise
            self._model = model
        self.params = policy.cast_params(params)
        if weight_quant is not None:
            # quantize AFTER the policy cast (the absmax measured is
            # the serving dtype's, so codes reproduce exactly the
            # values the bf16 GEMM would have loaded) and BEFORE the
            # mesh placement, so the scale leaves shard with their
            # kernels under the rule table below
            self.params = weight_quant.quantize_params(self.params)
        if mesh is not None:
            # permute/scale + place per the partition-rule table; the
            # spec tree below is what the shard_map wrappers split by
            self.params = _sharding.shard_params(
                self.params, mesh, num_heads=heads, axis=self._tp_axis)
            self._pspec = _sharding.match_partition_rules(
                _sharding.partition_rules(self._tp_axis), self.params)
        self.page_len = page_len = resolve_page_len(self.chunk_len,
                                                    page_len)
        self.max_pages = -(-self.max_len // page_len)
        if num_pages is None:
            # (slots + prefix_pool) full-length requests plus the
            # sentinel; a short request consumes only its own pages
            num_pages = (self.slots + self.prefix_pool) \
                * self.max_pages + 1
        num_pages = int(num_pages)
        if num_pages < self.max_pages + 1:
            raise ValueError(
                f"num_pages {num_pages} cannot hold even one "
                f"max_len request ({self.max_pages} pages) plus "
                f"the sentinel page")
        self.num_pages = num_pages
        if mesh is None:
            state = None
            if self.slot_state:
                state = SlotState.create(spec_, slots=self.slots,
                                         dtype=half)
            self.cache = PagedKVCache.create(
                layers=layers, num_pages=num_pages, heads=kv_heads,
                page_len=page_len, head_dim=head_dim,
                dtype=cache_dtype, k_scale=k_scale, v_scale=v_scale,
                state=state, value_dim=spec_.value_dim)
        else:
            # heads-axis pool sharding: each shard holds
            # [layers, num_pages, heads/tp, head_dim, page_len] —
            # attention never crosses ICI; page tables, lengths and
            # the allocator stay replicated host state. Allocated
            # DIRECTLY into the sharded layout (zeros_sharded): a
            # pool sized to aggregate HBM — the point of sharding
            # it — must never transit one chip whole. Quantization
            # scales shard ALONG the pool's heads axis
            # ([layers, heads/tp] per shard), so each shard
            # de/quantizes its own heads collective-free.
            shape = (layers, num_pages, kv_heads, head_dim, page_len)
            pspec = _sharding.cache_pspec(self._tp_axis)
            if k_scale is not None:
                sspec = _sharding.scale_pspec(self._tp_axis)
                from jax.sharding import NamedSharding
                k_scale = jax.device_put(
                    k_scale, NamedSharding(mesh, sspec))
                v_scale = jax.device_put(
                    v_scale, NamedSharding(mesh, sspec))
            self.cache = PagedKVCache(
                k=_sharding.zeros_sharded(shape, cache_dtype, mesh,
                                          pspec),
                v=_sharding.zeros_sharded(shape, cache_dtype, mesh,
                                          pspec),
                k_scale=k_scale, v_scale=v_scale)
        self.pool = PagePool(num_pages, page_len)
        self._page_table = np.zeros((self.slots, self.max_pages),
                                    np.int32)
        self._n_pages = np.zeros(self.slots, np.int32)
        self._host_len = np.zeros(self.slots, np.int32)
        self._slot_reserved = np.zeros(self.slots, np.int32)
        # retained prefixes share the one pool; prefix_pool sizes the
        # EXTRA capacity set aside for them in the num_pages default and
        # gates the feature on
        self.prefix_cache = None if self.prefix_pool == 0 else \
            PrefixCache(block_len=self.chunk_len,
                        on_evict=self.pool.release)
        # hierarchical KV: the host-DRAM prefix tier behind the paged
        # pool. Wired AFTER the prefix cache exists — eviction becomes
        # swap-out (a dispatched device→host migration; the entry
        # stays matchable as "swapping" then "swapped"), a swapped hit
        # swaps back in through _jit_swap_in. Both swap programs are
        # mesh-aware: under a tp mesh they run shard_map'd over the
        # pool's heads axis (each shard moves its own heads/tp slice —
        # zero collectives, pinned from compiled HLO).
        self.host_tier: Optional[HostTier] = None
        self.host_tier_shared = False
        self.sync_swap = bool(sync_swap)
        self._swap_worker: Optional[SwapWorker] = None
        self.swap_verify_failed = 0
        if host_tier is not None:
            if self.prefix_cache is None:
                raise ValueError(
                    "Engine(host_tier=...) requires prefix_pool > 0 — "
                    "the tier is a second level behind the prefix "
                    "cache, not a standalone store")
            self.host_tier = host_tier if isinstance(host_tier, HostTier) \
                else HostTier(int(host_tier))
            # externally-owned-arena mode (disaggregated serving): a
            # pre-built HostTier(shared=True) is co-owned by N engines
            # — register as ONE of its eviction listeners instead of
            # claiming the exclusive hook, and scope the cross-tier
            # audit to keys this engine's prefix index owns (the
            # PoolAuditor consults host_tier_shared)
            self.host_tier_shared = bool(
                getattr(self.host_tier, "shared", False))
            if self.host_tier_shared:
                self.host_tier.add_on_evict(self._on_host_tier_evict)
            else:
                self.host_tier.on_evict = self._on_host_tier_evict
            self.prefix_cache.set_swap_hooks(
                swap_out=self._dispatch_swap_out,
                contains=self.host_tier.contains)
            self._jit_swap_in = jax.jit(
                self._wrap_swap(self._swap_in_impl,
                                extra_in=(self._swap_block_pspec(),) * 2
                                + (None,), block_out=0),
                donate_argnums=(0,))
            # the swap-out gather is deliberately UNDONATED: its output
            # is a fresh snapshot buffer (the worker forces it later)
            # and an undonated call dispatches asynchronously even on
            # the CPU backend — which is exactly what takes the
            # device→host migration off the admission path
            self._jit_swap_out = jax.jit(
                self._wrap_swap(self._swap_out_impl, extra_in=(None,),
                                block_out=2))
            if not self.sync_swap:
                self._swap_worker = SwapWorker()
                # stop the thread when the engine is collected (the
                # finalizer closes over the WORKER, not self — no cycle)
                weakref.finalize(self, self._swap_worker.stop)
        # multi-tenant LoRA tier (:mod:`apex_tpu.serving.lora`): a
        # stacked per-site adapter arena gathered in the GEMM epilogue
        # by a TRACED per-slot adapter-index operand — heterogeneous
        # adapters decode in one batch, the adapter id is data (never
        # a trace key), so the program-count pins above cannot move.
        # _slot_adapter[slot] names the arena row each slot gathers;
        # row 0 is the all-zero adapter (+0.0 epilogue — the
        # fault_bias value-identity pin), so base requests on a
        # LoRA-enabled engine stay bitwise the base engine.
        self.lora = None
        self._slot_adapter = np.zeros(self.slots, np.int32)
        if lora is not None:
            from .lora import LoRAConfig, LoRAManager
            if not isinstance(lora, LoRAConfig):
                raise TypeError(f"lora must be a LoRAConfig, got "
                                f"{type(lora).__name__}")
            self.lora = LoRAManager(
                lora, hidden=hidden, num_heads=heads,
                num_layers=layers,
                mlp_ratio=int(getattr(model, "mlp_ratio", 4)),
                tp=self.tp, mesh=mesh,
                tp_axis=self._tp_axis or "tp", registry=registry)
        self._registry = registry
        # request tracer (None = off): installed by the scheduler via
        # set_tracer. The engine's only spans are the hierarchical-KV
        # migrations (swap_out / swap_out_store / swap_in) — emitted
        # through event_current against the thread-local trace binding
        # the scheduler's admission path holds, since the engine never
        # sees a Request
        self._tracer = None
        self._key = jax.random.PRNGKey(seed)
        self.decode_traces = 0
        self.chunk_traces = 0
        self.verify_traces = 0
        self.swap_in_traces = 0
        self.swap_out_traces = 0
        self.tokens_generated = 0
        # cumulative seconds the HOST spent blocked waiting for device
        # results (every forcing site — token readback, finiteness
        # verdicts, the sync() barrier — is timed into this). The
        # scheduler differences it around each heartbeat to split beat
        # wall time into host-think vs device-wait: the basis of the
        # serving.heartbeat.* gauges and the pipelined watchdog's
        # host-portion budget.
        self.device_wait_s = 0.0
        # the same seconds, split where the work happens (always on; the
        # three sum to device_wait_s): building and uploading a
        # program's operands, the compiled call itself, and the forced
        # reads that wait for its results
        self.upload_s = 0.0
        self.launch_s = 0.0
        self.readback_s = 0.0
        # the non-finite guard's host-side view, refreshed by every
        # sampling call: per-slot flags for the last decode step, one
        # flag for the last chunk. True means
        # the sampled logits row was entirely finite (the token is
        # trustworthy); False is the quarantine signal the scheduler's
        # fault policy consumes.
        self.last_decode_finite = np.ones(self.slots, bool)
        self.last_chunk_finite = True
        self.last_verify_finite = True
        self.last_verify_finite_slots = np.ones(self.slots, bool)
        self.nonfinite_events = 0
        # the decode program's second token operand while no step is in
        # flight (decode_dispatch's ``after``): read by no row then
        self._no_prev_tokens = jnp.zeros(self.slots, jnp.int32)
        # under a mesh each program body runs shard_map'd over the
        # tensor-parallel axis (params split per the rule table, the
        # pool on heads, every host operand replicated); mesh=None
        # wraps nothing — the verbatim single-chip programs
        # a model with per-slot state runs the same two programs
        # with the state threaded through (one more operand each)
        stateful = self.slot_state
        self._jit_decode = jax.jit(
            self._state_decode_impl if stateful else
            self._tp_wrap(self._paged_decode_impl, 2),
            donate_argnums=(1,))
        self._jit_chunk = jax.jit(
            self._state_chunk_impl if stateful else
            self._tp_wrap(self._paged_chunk_impl, 2),
            donate_argnums=(1,))
        self._jit_verify = jax.jit(
            self._tp_wrap(self._paged_verify_impl, 3),
            donate_argnums=(1,))
        _logger.info(
            "serving engine (paged%s): %d slots x %d positions, "
            "prefill_len=%d, chunk_len=%d, page_len=%d, %d pages "
            "(+1 sentinel in count), prefix_pool=%d, cache %s "
            "(%.1f MiB%s), top_k=%d",
            f", tp={self.tp}" if mesh is not None else "",
            self.slots, self.max_len, self.prefill_len,
            self.chunk_len, self.page_len, self.num_pages,
            self.prefix_pool, np.dtype(cache_dtype).name,
            self.cache.nbytes() / 2**20,
            f", {self.cache.nbytes() / self.tp / 2**20:.1f}/shard"
            if mesh is not None else "", self.top_k)

        self._emit_tp_gauges()
        self._emit_kv_gauges()
        self._emit_wq_gauges()
        self._emit_lora_gauges()

    # --------------------------------------------------- tensor parallelism
    def _tp_wrap(self, fn, n_extra_out: int):
        """Wrap a paged program body in ``shard_map`` over the engine's
        tensor-parallel mesh: params split per the partition-rule table,
        the KV pool on its heads axis, every other operand (tokens, page
        tables, lengths, scalars, PRNG key) replicated, outputs
        replicated except the pool. ``mesh=None`` returns ``fn``
        untouched — the single-chip baseline is the verbatim program,
        not a degenerate wrap."""
        if self.mesh is None:
            return fn
        from jax.sharding import PartitionSpec as P

        from apex_tpu.utils.compat import shard_map

        cspec = self._cache_spec_tree()

        def wrapped(params, cache, *rest):
            extra = (P(),) * len(rest)
            if self.lora is not None:
                # the two trailing LoRA operands: the stacked arena
                # (split per its own spec tree — the PR 9 rule-table
                # split restated per stacked array) and the adapter-id
                # vector (replicated host data)
                extra = (P(),) * (len(rest) - 2) \
                    + (self.lora.spec_tree(), P())
            return shard_map(
                fn, mesh=self.mesh,
                in_specs=(self._pspec, cspec) + extra,
                out_specs=(cspec,) + (P(),) * n_extra_out,
                check_vma=False)(params, cache, *rest)

        return wrapped

    def _cache_spec_tree(self):
        """The cache pytree's partition-spec tree (mesh engines only):
        pool arrays on the heads axis, quantization scales (when
        present) on THEIR heads axis, None fields stay None — shared
        by every shard_map wrap (model programs and the two swap
        programs alike)."""
        from .sharding import cache_pspec, scale_pspec

        quant = self.kv_quant is not None
        return PagedKVCache(
            k=cache_pspec(self._tp_axis), v=cache_pspec(self._tp_axis),
            k_scale=scale_pspec(self._tp_axis) if quant else None,
            v_scale=scale_pspec(self._tp_axis) if quant else None)

    def _swap_block_pspec(self):
        """A swapped page block's partition spec: ``[layers,
        max_pages, heads/tp, head_dim, page_len]`` per shard — the
        SAME heads-axis split as the pool itself, so each shard's swap
        gather/scatter moves exactly its own slice and the programs
        need no collective at all. None on a single-chip engine."""
        if self.mesh is None:
            return None
        from jax.sharding import PartitionSpec as P

        return P(None, None, self._tp_axis, None, None)

    def _wrap_swap(self, fn, *, extra_in, block_out: int):
        """Wrap a swap program body (``fn(cache, *rest)``) in
        shard_map over the tensor-parallel mesh: the cache per its
        spec tree, ``extra_in`` the per-operand specs for ``rest``
        (None = replicated), and the outputs — ``block_out`` page
        blocks (heads-sharded) for the gather, else the cache tree for
        the scatter. ``mesh=None`` returns ``fn`` untouched, exactly
        like :meth:`_tp_wrap`: the single-chip swap programs are the
        verbatim bodies. The wrapped programs are the collective-free
        pin's subject: swap is pure data movement, each shard moves
        its own heads — compiled HLO must contain ZERO collectives
        (``tests/L0/test_host_tier.py``)."""
        if self.mesh is None:
            return fn
        from jax.sharding import PartitionSpec as P

        from apex_tpu.utils.compat import shard_map

        cspec = self._cache_spec_tree()
        bspec = self._swap_block_pspec()
        in_rest = tuple(P() if s is None else s for s in extra_in)
        out_specs = (bspec,) * block_out if block_out else cspec

        def wrapped(cache, *rest):
            return shard_map(
                fn, mesh=self.mesh, in_specs=(cspec,) + in_rest,
                out_specs=out_specs, check_vma=False)(cache, *rest)

        return wrapped

    def _gather_logits(self, rows):
        """Rejoin vocab-parallel logits: under a mesh the model's tied
        head returns ``[..., vocab/tp]`` local slices (see
        :class:`~apex_tpu.models.transformer_lm.TransformerLM`) and this
        one all-gather — the sharded programs' ONLY gather, applied to
        the rows actually being sampled — restores the full vocabulary
        so sampling and the fused non-finite guard run exactly as on one
        chip. Identity on a single-chip engine."""
        if self.mesh is None:
            return rows
        return jax.lax.all_gather(rows, self._tp_axis,
                                  axis=rows.ndim - 1, tiled=True)

    def _emit_tp_gauges(self) -> None:
        """The ``serving.tp.*`` telemetry snapshot of a sharded engine:
        shard count, the per-program collective inventory (2 psums per
        block + 1 logits all-gather — the numbers the HLO pin asserts),
        and the per-shard pool view (each shard holds every page at
        ``heads/tp`` width, so HBM per chip is the pool bytes over tp).
        Single-chip engines emit nothing."""
        if self._registry is None or self.mesh is None:
            return
        from .sharding import expected_collectives

        coll = expected_collectives(self.cache.layers)
        self._registry.gauge_set("serving.tp.shards", float(self.tp))
        self._registry.gauge_set("serving.tp.psums_per_program",
                                 float(coll["all_reduce"]))
        self._registry.gauge_set("serving.tp.all_gathers_per_program",
                                 float(coll["all_gather"]))
        self._registry.gauge_set("serving.tp.hbm_bytes_per_shard",
                                 self.cache.nbytes() / self.tp)
        self._registry.gauge_set("serving.tp.pool_pages_per_shard",
                                 float(self.num_pages))

    def _emit_kv_gauges(self) -> None:
        """The ``serving.kv.*`` telemetry snapshot: per-token cache
        bytes (``layers * heads * head_dim * itemsize * 2`` — the
        number the quantized tier halves, and the basis of the bench's
        bytes-per-token reduction claim) and, on a quantized engine,
        the largest absolute value the calibrated scales can represent
        (``max(scale) * 127`` — a drifting workload whose true absmax
        exceeds this is CLIPPING, the dashboard signal to recalibrate).
        """
        if self._registry is None:
            return
        c = self.cache
        self._registry.gauge_set("serving.kv.bytes_per_token",
                                 float(c.bytes_per_token()))
        self._registry.gauge_set("serving.kv.page_layers", float(c.layers))
        if getattr(c, "state", None) is not None:
            # what a slot holds beside its pages, whatever its length
            self._registry.gauge_set("serving.state.bytes_per_slot",
                                     float(c.state.bytes_per_slot()))
            self._registry.gauge_set("serving.state.bytes",
                                     float(c.state.nbytes()))
            experts = c.state.expert_tokens.shape[1]
            if experts:
                held = getattr(self._model, "experts_held", None)
                self._registry.gauge_set(
                    "serving.moe.experts_held",
                    float(experts if held is None else len(held)))
                self._registry.gauge_set(
                    "serving.moe.experts_per_token",
                    float(getattr(self._model, "experts_per_token", 1)))
        if c.k_scale is not None:
            from .kv_quant import QMAX
            absmax = max(float(jnp.max(c.k_scale)),
                         float(jnp.max(c.v_scale))) * QMAX
            self._registry.gauge_set("serving.kv.quant_scale_absmax",
                                     absmax)

    def _emit_wq_gauges(self) -> None:
        """The ``serving.wq.*`` telemetry snapshot of a weight-quantized
        engine: mean bytes per WEIGHT parameter (total param-tree bytes
        over weight elements, scale overhead charged in — the basis of
        the bench's weight-bytes reduction claim; ~2.0 on the bf16
        default, ~1.0+scales quantized) and the largest absolute weight
        the calibrated scales can represent (``max(scale) * 127`` — a
        provenance number: it moves only when the checkpoint or margin
        does, so a dashboard step flags a silent weight swap).
        Unquantized engines emit nothing — the family is the tier's
        liveness signal."""
        if self._registry is None or self.weight_quant is None:
            return
        from .weight_quant import (param_bytes, param_count,
                                   quant_scale_absmax)

        self._registry.gauge_set(
            "serving.wq.bytes_per_param",
            param_bytes(self.params) / param_count(self.params))
        self._registry.gauge_set("serving.wq.quant_scale_absmax",
                                 quant_scale_absmax(self.params))

    def _emit_lora_gauges(self) -> None:
        """The ``serving.lora.*`` gauge snapshot of a LoRA-enabled
        engine (host-store bytes at rest + device-resident adapter
        count — the :class:`~apex_tpu.serving.lora.LoRAManager` owns
        the names and the counters). LoRA-less engines emit nothing —
        the family is the tier's liveness signal, like ``serving.wq``.
        """
        if self._registry is None or self.lora is None:
            return
        self.lora.set_registry(self._registry)

    # ------------------------------------------------------- multi-tenant LoRA
    def _lora_args(self, slot: Optional[int] = None):
        """The two trailing operands every compiled program takes on a
        LoRA-enabled engine: the stacked device arena (a pytree of
        traced arrays) and the per-row adapter-index vector — the full
        ``[slots]`` binding for decode/verify, the one ``[1]`` slot's
        for chunk/prefill. Empty on a LoRA-less engine, which keeps
        today's traces verbatim."""
        if self.lora is None:
            return ()
        ids = self._slot_adapter if slot is None \
            else self._slot_adapter[slot:slot + 1]
        return (self.lora.arena, jnp.asarray(ids.copy()))

    def lora_register(self, name: str, sites, *,
                      alpha: float = 1.0) -> None:
        """Admit adapter ``name`` (per-site stacked A/B matrices) into
        the LoRA host store — see :meth:`~apex_tpu.serving.lora
        .LoRAManager.register`. Loud on a LoRA-less engine."""
        if self.lora is None:
            raise ValueError("engine has no LoRA tier — construct "
                             "with Engine(lora=LoRAConfig(...))")
        self.lora.register(name, sites, alpha=alpha)

    def lora_bind(self, slot: int, name: str) -> bool:
        """Bind serving slot ``slot`` to adapter ``name``: acquire a
        (refcount-pinned) arena row — a hit when resident, a
        CRC-verified swap-in when cold — and point the slot's traced
        adapter index at it. False when the arena is full of bound
        adapters (graceful degradation: the caller holds the request
        queued); ``KeyError`` when the adapter is unknown or its
        record failed the swap-in checksum (the loud-reload path)."""
        if self.lora is None:
            raise ValueError("engine has no LoRA tier")
        row = self.lora.acquire(name)
        if row is None:
            return False
        self._slot_adapter[slot] = row
        return True

    def lora_unbind(self, slot: int) -> None:
        """Release slot ``slot``'s adapter binding (no-op when the
        slot holds the zero adapter, or the tier is off). The adapter
        stays arena-resident at refcount 0 — the next bind is a hit."""
        if self.lora is None:
            return
        row = int(self._slot_adapter[slot])
        if row:
            self._slot_adapter[slot] = 0
            self.lora.release(row)

    def lora_audit(self) -> dict:
        """Cross-check the LoRA tier's refcounts against the LIVE slot
        bindings (every bound arena row's refcount must equal the
        number of slots pointing at it) plus the manager's own byte
        ledger and row<->record reconciliation. Raises on any drift;
        returns the reconciled stats."""
        if self.lora is None:
            raise ValueError("engine has no LoRA tier")
        bound: dict = {}
        for slot in range(self.slots):
            row = int(self._slot_adapter[slot])
            if row:
                bound[row] = bound.get(row, 0) + 1
        return self.lora.audit(bound)

    def resident_adapters(self):
        """Device-resident adapter names (the scheduler's snapshot
        column — adapter affinity routes on membership here); None on
        a LoRA-less engine."""
        return None if self.lora is None \
            else self.lora.resident_names()

    @property
    def compiled_programs(self) -> int:
        """Distinct XLA executables traced so far (the compile-count
        discipline the serving tests pin: exactly two across a run
        that exercises chunk prefill and decode, prefix hits included —
        and one more once speculative decoding exercises the verify
        program.
        The hierarchical-KV tier adds AT MOST one more PER DIRECTION:
        the fixed-shape ``swap_out`` block gather
        (traced lazily on the first pressure eviction) and the
        fixed-shape ``swap_in`` block scatter (traced lazily on the
        first hit-after-swap) — both shape-padded to ``max_pages``, so
        no entry size can ever trace a second copy)."""
        return (self.chunk_traces + self.decode_traces
                + self.verify_traces + self.swap_in_traces
                + self.swap_out_traces)

    # ------------------------------------------------------ compiled bodies
    # Every sampling program also returns a per-slot FINITENESS flag —
    # all(isfinite) over the fp32 logits row it samples from — so the
    # host can quarantine a NaN/Inf slot without touching its batchmates
    # (the non-finite guard is FUSED into the existing programs: zero
    # new executables, pinned by the trace-count tests). The decode and
    # chunk programs additionally take a ``fault_bias`` logit offset
    # (per-slot [slots] / scalar) that is 0.0 in production — adding
    # +0.0 to an fp32 row is value-identical, so clean-path tokens are
    # unchanged — and NaN/Inf under a FaultPlan injection, which makes
    # the in-program guard see REAL non-finite logits.
    def _kv_scales_of(self, cache):
        """The ``(k_scale, v_scale)`` pair the quantized tier threads
        into the model's cache modes; None on the bf16 default (a
        static, trace-time choice — quantization is an engine property,
        not an operand)."""
        if cache.k_scale is None:
            return None
        return (cache.k_scale, cache.v_scale)

    @staticmethod
    def _lora_kw(lora, adapter_ids):
        """The model-apply kwargs for the two optional trailing LoRA
        operands — EMPTY when the tier is off, so a LoRA-less engine's
        traces stay verbatim (the bitwise baseline)."""
        return {} if lora is None else {"lora": lora,
                                        "adapter_ids": adapter_ids}

    @staticmethod
    def _accept_longest_prefix(rows, tokens, n_drafted):
        """In-program accept-longest-prefix over fp32 logit ``rows``
        ``[B, K+1, V]`` for draft ``tokens`` ``[B, K+1]`` (per row:
        column 0 is the last committed token, columns 1..K the drafts;
        drafts past ``n_drafted[b]`` are padding and never accepted —
        rows with ``n_drafted[b] == 0`` are fixed-shape passengers and
        accept nothing). Greedy only — every emitted token IS the
        greedy target, which is the whole bitwise-parity argument.
        Returns ``(greedy [B, K+1] int32, n_accepted [B] int32)``."""
        K = tokens.shape[1] - 1
        greedy = jnp.argmax(rows, axis=-1).astype(jnp.int32)  # [B, K+1]
        match = (greedy[:, :K] == tokens[:, 1:]) \
            & (jnp.arange(K, dtype=jnp.int32)[None, :]
               < n_drafted[:, None])
        n_accepted = jnp.sum(
            jnp.cumprod(match.astype(jnp.int32), axis=1),
            axis=1).astype(jnp.int32)
        return greedy, n_accepted

    def _paged_chunk_impl(self, params, cache, tokens, pt_row, offset,
                          n_valid, temperature, fault_bias, key,
                          lora=None, adapter_ids=None):
        self.chunk_traces += 1      # python body runs at trace time only
        offset = jnp.asarray(offset, jnp.int32)
        logits, (k2, v2) = self._model.apply(
            {"params": params}, tokens, train=False,
            cache=(cache.k, cache.v, pt_row), positions=offset[None],
            kv_scales=self._kv_scales_of(cache),
            **self._lora_kw(lora, adapter_ids))
        cache = cache.replace(k=k2, v=v2)
        # sample at the last VALID row: the request's first token when
        # this is the prompt's final chunk, discarded by the host
        # otherwise (one program either way — finality is not traced)
        last = jax.lax.dynamic_index_in_dim(logits[0], n_valid - 1,
                                            keepdims=False)   # [V(/tp)]
        last = self._gather_logits(jnp.asarray(last, jnp.float32)) \
            + fault_bias
        finite = jnp.all(jnp.isfinite(last))
        token = sample_tokens(last[None], temperature[None], key,
                              self.top_k)[0]
        return cache, token, finite

    @staticmethod
    def _chain_tokens(last_tokens, prev_tokens):
        """A decode step's input tokens: the host's ``last_tokens``,
        except where one is negative - that row's newest token has not
        been read yet and is ``prev_tokens``' (the step before's
        un-read result, still on the device). A token id is never
        negative, so the mark costs no operand of its own."""
        return jnp.where(last_tokens < 0, prev_tokens, last_tokens)

    def _paged_decode_impl(self, params, cache, last_tokens, prev_tokens,
                           page_table, lengths, temperature, fault_bias,
                           key, lora=None, adapter_ids=None):
        self.decode_traces += 1     # python body runs at trace time only
        last_tokens = self._chain_tokens(last_tokens, prev_tokens)
        # lengths are HOST state in the paged layout (the allocator owns
        # them); the program is a pure function of the operands. Length
        # growth happens host-side after the call — inactive slots'
        # tables point at the sentinel page, so their discarded write
        # cannot land on a live request's page.
        positions = jnp.minimum(lengths, self.max_len - 1)
        logits, (k2, v2) = self._model.apply(
            {"params": params}, last_tokens[:, None], train=False,
            cache=(cache.k, cache.v, page_table), positions=positions,
            kv_scales=self._kv_scales_of(cache),
            **self._lora_kw(lora, adapter_ids))
        rows = self._gather_logits(jnp.asarray(logits[:, 0, :],
                                               jnp.float32)) \
            + fault_bias[:, None]
        finite = jnp.all(jnp.isfinite(rows), axis=-1)         # [slots]
        tokens = sample_tokens(rows, temperature, key, self.top_k)
        return cache.replace(k=k2, v=v2), tokens, finite

    def _paged_verify_impl(self, params, cache, tokens, page_table,
                           lengths, n_drafted, fault_bias, lora=None,
                           adapter_ids=None):
        self.verify_traces += 1     # python body runs at trace time only
        # unaligned_append: every row's [K+1] draft block lands at an
        # arbitrary mid-generation offset — per-position page scatters
        # instead of the whole-page chunk write (the host grew each
        # verifying slot's table to cover offset + K + 1 before this
        # call). Non-verifying rows arrive with ZEROED table rows and
        # length 0 from verify_batch, so their fixed-shape writes land
        # on the sentinel page and their (discarded) attention reads
        # garbage — a live decode slot's pages are never touched by a
        # verify batch it is not in.
        logits, (k2, v2) = self._model.apply(
            {"params": params}, tokens, train=False,
            cache=(cache.k, cache.v, page_table), positions=lengths,
            unaligned_append=True,
            kv_scales=self._kv_scales_of(cache),
            **self._lora_kw(lora, adapter_ids))
        cache = cache.replace(k=k2, v=v2)
        rows = self._gather_logits(jnp.asarray(logits, jnp.float32)) \
            + fault_bias[:, None, None]
        finite = jnp.all(jnp.isfinite(rows), axis=(1, 2))     # [slots]
        greedy, n_accepted = self._accept_longest_prefix(rows, tokens,
                                                         n_drafted)
        # lengths are host state on the paged layout: the rollback (the
        # host-side length arithmetic) happens in verify_batch after it
        # reads n_accepted — the rejected tail's pages stay allocated
        # to the slot, their K/V unreachable behind the length
        return cache, greedy, n_accepted, finite

    # ------------------------- compiled bodies (paged, per-slot state)
    # The two heartbeat programs for a model that keeps state per slot
    # beside its pages (kv_cache.SlotState: models.zaya.ZayaLM,
    # models.qwen3_next.Qwen3NextLM). Same operands as the paged bodies
    # above plus ONE trailing operand: the slot a chunk belongs to, or
    # the decode batch's active mask (a slot that is mid-prefill rides
    # the decode batch with its real page table, and its state must not
    # move). The state rides in the donated cache pytree; the MODEL reads
    # and writes its blocks where they lie, addressed by a SlotAddr - a
    # small block through a select, a large one by its own kernel - and
    # hands the same buffers back, like the pool.
    def _state_apply(self, params, cache, tokens, addr, page_table, **kw):
        """One model call with the state blocks and their addressing in;
        the cache with pools, blocks and expert counters updated out."""
        st = cache.state
        logits, (k2, v2, blocks, counts) = self._model.apply(
            {"params": params}, tokens, train=False, state=st.blocks,
            addr=addr, cache=(cache.k, cache.v, page_table), **kw)
        st = st.replace(blocks=blocks)
        if st.expert_tokens.shape[1]:
            st = st.replace(expert_tokens=st.expert_tokens + counts)
        return logits, cache.replace(k=k2, v=v2, state=st)

    def _state_chunk_impl(self, params, cache, tokens, pt_row, offset,
                          n_valid, temperature, fault_bias, key, slot):
        self.chunk_traces += 1      # python body runs at trace time only
        offset = jnp.asarray(offset, jnp.int32)
        # the chunk reads the state its predecessor left and leaves the
        # state of its own last VALID position; the chunk at offset 0
        # admits the request and starts from zeros, whatever the slot's
        # last tenant left behind
        logits, cache = self._state_apply(
            params, cache, tokens,
            SlotAddr(slot=jnp.asarray(slot, jnp.int32), fresh=offset == 0),
            pt_row, positions=offset[None], n_valid=n_valid[None])
        # the model returned the logits of the last VALID row only
        last = jnp.asarray(logits[0, 0], jnp.float32) + fault_bias
        finite = jnp.all(jnp.isfinite(last))
        token = sample_tokens(last[None], temperature[None], key,
                              self.top_k)[0]
        return cache, token, finite

    def _state_decode_impl(self, params, cache, last_tokens, prev_tokens,
                           page_table, lengths, temperature, fault_bias,
                           key, active):
        self.decode_traces += 1     # python body runs at trace time only
        last_tokens = self._chain_tokens(last_tokens, prev_tokens)
        positions = jnp.minimum(lengths, self.max_len - 1)
        # only a slot that decoded moves its state (SlotAddr.active)
        logits, cache = self._state_apply(
            params, cache, last_tokens[:, None], SlotAddr(active=active),
            page_table, positions=positions, valid=active[:, None])
        rows_ = jnp.asarray(logits[:, 0, :], jnp.float32) \
            + fault_bias[:, None]
        finite = jnp.all(jnp.isfinite(rows_), axis=-1)        # [slots]
        tokens = sample_tokens(rows_, temperature, key, self.top_k)
        return cache, tokens, finite

    def _slot_arg(self, slot: int):
        """The trailing operand of a stateful engine's chunk call (its
        slot); nothing on a model without slot state, whose
        programs keep the operands they always had."""
        return (np.int32(slot),) if self.slot_state else ()

    def moe_tokens_per_expert(self) -> Optional[np.ndarray]:
        """Tokens routed to each expert by every program since the
        engine was built, ``[layers, num_experts]`` int64 — accumulated
        on the device inside the programs and read HERE, once, when
        asked (one small transfer; never in the beat). None for a model
        with no expert layer. Sets the counters
        ``serving.moe.tokens_routed`` (a layer's total: every token is
        routed ``experts_per_token`` times a layer),
        ``serving.moe.tokens_routed_held`` (the same for the experts this
        chip holds, averaged over the layers and rounded down: the
        routed work it really does) and
        ``serving.moe.tokens_per_expert.e<i>`` (expert ``i``'s sum over
        the layers)."""
        st = getattr(self.cache, "state", None)
        if st is None or not st.expert_tokens.shape[1]:
            return None
        counts = self._readback("moe_counts", lambda: np.asarray(
            st.expert_tokens)).astype(np.int64)
        if self._registry is not None:
            # counters only grow: add what came since the last read
            def _raise_to(name, total):
                have = self._registry.counters.get(name, 0.0)
                self._registry.counter_inc(name, max(0.0, total - have))

            _raise_to("serving.moe.tokens_routed", float(counts[0].sum()))
            held = getattr(self._model, "experts_held", None)
            on_chip = counts if held is None else counts[:, list(held)]
            _raise_to("serving.moe.tokens_routed_held",
                      float(on_chip.sum() // len(counts)))
            for e, n in enumerate(counts.sum(0)):
                _raise_to(f"serving.moe.tokens_per_expert.e{e}", float(n))
        return counts

    def _swap_out_impl(self, cache, page_ids):
        """The hierarchical-KV tier's OUTBOUND compiled program: gather
        the pool pages named by ``page_ids`` ``[max_pages]`` int32 into
        a fresh ``[layers, max_pages, heads, head_dim, page_len]``
        snapshot block per pool array — ONE dispatch per swap-out,
        fixed shape (entries shorter than ``max_pages`` pad their
        trailing ids with the page-0 sentinel, whose garbage is sliced
        off by the worker before storage). The output buffers are the
        SNAPSHOT the async swap rides: dispatched before the entry's
        pages are released, program order sequences this gather ahead
        of any later overwrite, so the worker's deferred force can
        never observe reused pages — write-then-attend protects
        attention readers, not cross-tier copies, which is why the
        snapshot must be taken here and not at completion time. Under
        a mesh each shard gathers its own heads slice (zero
        collectives — pinned from HLO). Pure data movement: no
        attention, no sampling, no PRNG,
        so it owes the tuned tables no ``decode.*`` key."""
        self.swap_out_traces += 1   # python body runs at trace time only
        page_ids = jnp.asarray(page_ids, jnp.int32)
        return cache.k[:, page_ids], cache.v[:, page_ids]

    def _swap_in_impl(self, cache, k_blk, v_blk, page_ids):
        """The hierarchical-KV tier's INBOUND compiled program: scatter
        a host-restored page block ``[layers, max_pages, heads, head_dim,
        page_len]`` into the pool rows named by ``page_ids``
        ``[max_pages]`` int32 — ONE dispatch per swap-in, fixed shape
        (entries shorter than ``max_pages`` pad their trailing ids with
        the page-0 sentinel, whose garbage absorbs the padded writes
        exactly as it absorbs inactive-slot decode writes). Under a
        mesh each shard scatters its own heads slice (zero
        collectives — pinned from HLO). Pure data movement: no
        attention, no sampling, no PRNG,
        so it owes the tuned tables no ``decode.*`` key."""
        self.swap_in_traces += 1    # python body runs at trace time only
        page_ids = jnp.asarray(page_ids, jnp.int32)
        k = cache.k.at[:, page_ids].set(jnp.asarray(k_blk, cache.dtype))
        v = cache.v.at[:, page_ids].set(jnp.asarray(v_blk, cache.dtype))
        return cache.replace(k=k, v=v)

    # ------------------------------------------------------------- host API
    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def prefill_chunk(self, slot: int, chunk: Sequence[int], offset: int,
                      temperature: float = 0.0, *, final: bool = True,
                      fault_bias: float = 0.0) -> int:
        """Ingest one chunk of a prompt into ``slot`` at cache position
        ``offset`` and return the token sampled at the chunk's last
        valid row (host int). The token is the request's first output
        token when ``final`` is True (the time-to-first-token boundary);
        for mid-prompt chunks it is a throwaway — the program samples
        unconditionally so finality never retraces.

        ``final`` is host-side accounting only (tokens_generated and the
        telemetry counters tick once per request, on the real token).

        ``fault_bias`` is the chaos harness's injection operand: a
        float added to the sampled logits row inside the compiled
        program (0.0 in production — value-identical; NaN/Inf under a
        :class:`~apex_tpu.serving.FaultPlan` makes the in-program
        finiteness guard fire for real). The guard's verdict lands in
        :attr:`last_chunk_finite` either way.

        Internally this is :meth:`prefill_chunk_dispatch` followed
        immediately by :meth:`prefill_chunk_reconcile` — the depth-0
        composition IS the bitwise oracle the dispatch-ahead prefill
        path (``pipeline_depth >= 1``) is pinned against.
        """
        return self.prefill_chunk_reconcile(self.prefill_chunk_dispatch(
            slot, chunk, offset, temperature, final=final,
            fault_bias=fault_bias))

    def prefill_chunk_dispatch(self, slot: int, chunk: Sequence[int],
                               offset: int, temperature: float = 0.0,
                               *, final: bool = True,
                               fault_bias: float = 0.0) -> PendingPrefill:
        """Dispatch one chunk-prefill step WITHOUT forcing its sampled
        token to host — the prefill-path half of the dispatch-ahead
        split (:class:`PendingDecode`'s twin). Validates, grows the
        slot's page run, issues the compiled chunk program and updates
        host-side ingestion length; the returned handle's ``token`` /
        ``finite`` stay on device until :meth:`prefill_chunk_reconcile`.
        The force-early lint covers this function by name: no
        ``int()`` / ``np.asarray`` / ``jax.device_get`` may appear in
        its body."""
        n = len(chunk)
        if not 0 < n <= self.chunk_len:
            raise ValueError(f"chunk length {n} not in (0, "
                             f"chunk_len={self.chunk_len}]")
        if not 0 <= slot < self.slots:
            raise ValueError(f"slot {slot} not in [0, {self.slots})")
        if not 0 <= offset <= self.prefill_len - n:
            raise ValueError(
                f"chunk [{offset}, {offset + n}) exceeds prefill_len="
                f"{self.prefill_len}")
        if offset + self.chunk_len > self.max_len:
            # the program writes the PADDED chunk window; past max_len
            # the model's position clip would relocate it over earlier
            # K/V — reject instead of corrupting (scheduler offsets are
            # chunk multiples, which the constructor already bounds;
            # this guards direct callers at arbitrary offsets)
            raise ValueError(
                f"padded chunk window [{offset}, "
                f"{offset + self.chunk_len}) exceeds max_len="
                f"{self.max_len}")
        tokens = np.zeros((1, self.chunk_len), np.int32)
        tokens[0, :n] = chunk       # host list -> int32, no device read
        t0 = time.perf_counter()
        if offset % self.page_len:
            raise ValueError(
                f"paged chunk offset {offset} must be page-aligned "
                f"(page_len={self.page_len})")
        if offset == 0:
            # cold start on a (possibly re-used) slot: stale pages
            # back to the pool, the admission reservation kept (the
            # fresh pages must draw it down, not eat into other
            # slots' promises). A prefix hit instead enters through
            # attach_prefix, which resumes at a non-zero offset.
            self.release_slot(slot, keep_reservation=True)
        self._grow_slot(
            slot, -(-(offset + self.chunk_len) // self.page_len))
        ops = self._operands(lambda: (
            jnp.asarray(tokens),
            jnp.asarray(self._page_table[slot:slot + 1].copy()),
            np.int32(offset), np.int32(n),
            np.float32(temperature), np.float32(fault_bias),
            self._next_key(), *self._lora_args(slot),
            *self._slot_arg(slot)))
        self.cache, token, finite = self._runtime_call(
            "chunk", lambda: self._jit_chunk(self.params, self.cache,
                                             *ops))
        self._host_len[slot] = offset + n
        return PendingPrefill(
            token=token, finite=finite, slot=slot, final=final,
            t_dispatch=t0, dispatch_s=time.perf_counter() - t0)

    def prefill_chunk_reconcile(self, pending: PendingPrefill) -> int:
        """Force a dispatched chunk's sampled token to host and finish
        its accounting (finiteness verdict, ``device_wait_s``, the
        ``serving.prefill_chunk_s`` / ``serving.prefill.chunks`` /
        ``serving.tokens_generated`` counters) — the batched-readback
        half of the dispatch-ahead prefill split. Returns the host
        token; a throwaway unless the chunk was ``final``."""
        if pending.reconciled:
            raise RuntimeError("PendingPrefill already reconciled")
        pending.reconciled = True
        tw = time.perf_counter()
        token, self.last_chunk_finite = self._readback(
            "chunk", lambda: (int(pending.token),      # device sync
                              bool(pending.finite)))
        if not self.last_chunk_finite:
            self._count_nonfinite(1)
        if self._registry is not None:
            self._registry.observe(
                "serving.prefill_chunk_s",
                pending.dispatch_s + time.perf_counter() - tw)
            self._registry.counter_inc("serving.prefill.chunks")
            if pending.final:
                self._registry.counter_inc("serving.tokens_generated")
        if pending.final:
            self.tokens_generated += 1
        return token

    def prefill_chunked(self, slot: int, prompt: Sequence[int],
                        temperature: float = 0.0) -> int:
        """Drain a whole prompt through the chunk-prefill program
        back-to-back and return the first sampled token — for callers
        without a scheduler (warmup, parity tests, ``--generate``).
        Production serving interleaves the same chunks with decode steps instead
        (:class:`~apex_tpu.serving.Scheduler`)."""
        n = len(prompt)
        if not 0 < n <= self.prefill_len:
            raise ValueError(f"prompt length {n} not in (0, "
                             f"prefill_len={self.prefill_len}]")
        token = None
        for lo in range(0, n, self.chunk_len):
            hi = min(lo + self.chunk_len, n)
            token = self.prefill_chunk(slot, list(prompt[lo:hi]), lo,
                                       temperature, final=hi == n)
        return token

    def chunks_for(self, prompt_len: int) -> int:
        """Chunk-prefill steps a prompt of ``prompt_len`` costs
        (``ceil(prompt_len / chunk_len)``)."""
        return -(-int(prompt_len) // self.chunk_len)

    # ------------------------------------------------------- host bookkeeping
    def _alloc_page(self, slot: int) -> int:
        """One fresh page for ``slot`` (drawing down its admission
        reservation when it has one). Pool pressure first evicts LRU
        prefix entries — retained prefixes are a cache, live requests
        are not — then fails loudly: with scheduler-driven admission the
        reservation makes this unreachable; a direct caller that
        overcommits gets an exception, not silent corruption."""
        reserved = self._slot_reserved[slot] > 0
        page = self.pool.alloc(reserved=reserved)
        while page is None and self.prefix_cache is not None \
                and self.prefix_cache.evict_lru():
            page = self.pool.alloc(reserved=reserved)
        if page is None:
            raise RuntimeError(
                f"KV page pool exhausted ({self.num_pages} pages, "
                f"page_len={self.page_len}) — admit through the "
                "scheduler (page reservation) or build a larger pool")
        if reserved:
            self._slot_reserved[slot] -= 1
        return page

    def _grow_slot(self, slot: int, n_pages: int) -> None:
        """Ensure ``slot`` owns at least ``n_pages`` pages (appending
        fresh ones to its table row)."""
        have = int(self._n_pages[slot])
        for i in range(have, min(int(n_pages), self.max_pages)):
            self._page_table[slot, i] = self._alloc_page(slot)
            self._n_pages[slot] = i + 1

    def release_slot(self, slot: int,
                     keep_reservation: bool = False) -> None:
        """Return ``slot``'s pages to the pool (refcounts decide whether
        each is truly freed — pages shared with a retained prefix or
        another slot live on) and reset its table row to the sentinel.
        The scheduler calls this the moment a request finishes — paged
        reclamation is immediate, not deferred to the next overwrite.
        ``keep_reservation`` preserves the slot's admission reservation
        (the cold-start path inside an admitted request)."""
        n = int(self._n_pages[slot])
        if n:
            self.pool.release(self._page_table[slot, :n].tolist())
        self._page_table[slot, :] = 0
        self._n_pages[slot] = 0
        self._host_len[slot] = 0
        if not keep_reservation and self._slot_reserved[slot]:
            self.pool.unreserve(int(self._slot_reserved[slot]))
            self._slot_reserved[slot] = 0

    def pages_required(self, prompt_len: int, max_new_tokens: int) -> int:
        """Worst-case pages a request can touch: the padded prefill
        extent (whole chunks) or the decode growth to its token budget,
        whichever reaches further, all capped at ``max_len``. The
        scheduler reserves this at admission so mid-decode allocation
        can never fail. Deliberately ignores any prefix-hit discount —
        conservative admission keeps the hit/miss counters exact (the
        match runs only for requests that actually admitted)."""
        prefill_extent = min(self.chunks_for(prompt_len)
                             * self.chunk_len, self.max_len)
        occupied = min(int(prompt_len) + int(max_new_tokens),
                       self.max_len)
        return self.pool.pages_for(max(prefill_extent, occupied))

    def try_reserve_slot(self, slot: int, n_pages: int) -> bool:
        """The scheduler's admission gate: set aside ``n_pages`` for
        ``slot``, evicting LRU prefix entries while the pool cannot
        cover the promise. False (nothing changed) when even a fully
        drained prefix cache leaves the pool short — the request stays
        queued."""
        n_pages = int(n_pages)
        while self.pool.available < n_pages:
            if self.prefix_cache is None \
                    or not self.prefix_cache.evict_lru():
                return False
        if not self.pool.reserve(n_pages):
            return False            # unreachable given the loop above
        self._slot_reserved[slot] += n_pages
        return True

    # ------------------------------------------------- hierarchical KV tier
    def _on_host_tier_evict(self, key: int) -> None:
        """The host arena evicted ``key``'s bytes under capacity
        pressure: the swapped index entry now has no backing anywhere —
        drop it (a dangling swapped entry would be the exact rot the
        auditor's cross-tier walk flags). On a SHARED arena every
        co-owning engine hears every eviction — the drop is a no-op
        for keys this engine never indexed, and only the owner ticks
        the eviction counter (N engines must not count one eviction N
        times)."""
        owned = self.prefix_cache.drop(key)
        if self._registry is not None:
            if owned or not self.host_tier_shared:
                self._registry.counter_inc("serving.swap.host_evictions")
            self._registry.gauge_set("serving.swap.host_bytes",
                                     float(self.host_tier.bytes_used))

    def _dispatch_swap_out(self, key, pages) -> bool:
        """The prefix cache's swap-out hook — the ADMISSION-SIDE half
        of a (by default asynchronous) page migration, and the
        dispatch-ahead region the force-early lint covers BY NAME: no
        ``int()`` / ``float()`` / ``np.asarray`` / ``jax.device_get``
        may appear here, because a single forced read silently reverts
        the whole tier to the synchronous admission stall with zero
        token-level symptom (the bytes are right either way — only
        the wall-clock rots).

        Reserves the entry's arena bytes (capacity eviction and the
        decline decision run NOW, on this thread, so async and sync
        arena states evolve identically), DISPATCHES the fixed-shape
        compiled ``swap_out`` gather — the pool-byte snapshot is taken
        by program order at dispatch, BEFORE the caller releases the
        entry's pages for reuse — and hands the un-forced device
        blocks to the :class:`~apex_tpu.serving.SwapWorker`
        (:meth:`_complete_swap_out` forces, checksums and stores them
        off the hot path; ``sync_swap=True`` runs that half inline —
        the pre-async behaviour). False (the caller destroys instead)
        when the tier declines — an entry bigger than the whole arena.
        The admission-path cost of the whole hook is observed as
        ``serving.swap.admit_stall_s`` — the histogram the bench's
        sync-vs-async claim reads."""
        tier = self.host_tier
        if tier is None:
            return False
        m = len(pages)
        if m > self.max_pages:
            return False            # cannot happen by construction
        t0 = time.perf_counter()
        c = self.cache
        # the reservation is pure shape arithmetic — no device read:
        # K and V, m whole pages each, in the pool's storage dtype
        nbytes = 2 * m * c.layers * c.heads * c.page_len * c.head_dim \
            * np.dtype(c.dtype).itemsize
        if not tier.put_pending(key, nbytes, shards=self.tp):
            return False
        # SHAPE-STABLE dispatch: pad the gather to max_pages with the
        # page-0 sentinel (harmless garbage, sliced off by the worker)
        # so every swap-out of every entry size shares one compiled
        # gather — an entry-sized gather would silently recompile
        # mid-serve the first time an unseen page count appears. The
        # gather is UNDONATED, so even this CPU backend dispatches it
        # asynchronously (~0.1 ms) instead of executing it inline.
        ids = np.zeros(self.max_pages, np.int32)
        ids[:m] = list(pages)
        ids_dev = self._operands(lambda: jnp.asarray(ids))
        k_dev, v_dev = self._runtime_call(
            "swap_out", lambda: self._jit_swap_out(self.cache, ids_dev))
        tr = self._tracer
        ctx = None
        if tr is not None:
            # the admission-side span: dispatch cost only (nbytes and
            # m are pure shape arithmetic — this hook, like the rest
            # of the region, performs no forced read); the trace
            # binding is captured NOW so the worker-side store span
            # joins the same request's trace from its own thread
            ctx = tr.current()
            tr.event_current("swap_out", t0=t0, dur=tr.now() - t0,
                             key=key, pages=m, bytes=nbytes)
        job = lambda: self._complete_swap_out(  # noqa: E731
            key, k_dev, v_dev, m, t0, trace_id=ctx)
        if self._swap_worker is None:
            job()                   # sync_swap: the measurable baseline
        else:
            self._swap_worker.submit(key, job)
        if self._registry is not None:
            self._registry.observe("serving.swap.admit_stall_s",
                                   time.perf_counter() - t0)
            self._registry.gauge_set(
                "serving.swap.swap_out_queue_depth",
                0.0 if self._swap_worker is None
                else len(self._swap_worker.pending_keys()))
        return True

    def _complete_swap_out(self, key, k_dev, v_dev, m: int,
                           t0: float, trace_id=None) -> None:
        """The WORKER-SIDE half of a swap-out: force the dispatched
        snapshot blocks to host (the memcpy the async tier moves off
        the admission path), slice off the sentinel padding, and
        complete the arena's pending record (defensive copy + per-
        shard CRC inside :meth:`HostTier.complete`). A record evicted
        (or cleared) while the bytes were in flight discards silently
        — its index entry is already gone. Runs on the
        :class:`~apex_tpu.serving.SwapWorker` thread by default
        (inline under ``sync_swap=True``); the registry is
        thread-safe, so the traffic counters land from here either
        way. On the WORKER thread the force deliberately does NOT
        touch :attr:`device_wait_s` — that ledger belongs to the
        scheduler thread's heartbeat split, and a worker-side force
        blocks nobody's beat; running INLINE (``sync_swap=True``, or
        the post-close degradation) it blocks the scheduler thread
        exactly like the pre-async path did, so the wait is charged —
        the sync baseline's duty-cycle split must not silently
        flatter itself."""
        tier = self.host_tier
        worker = self._swap_worker
        inline = worker is None \
            or threading.current_thread() is not worker._thread
        tw = time.perf_counter()
        # the deferred force
        force = lambda: (np.asarray(k_dev)[:, :m],    # noqa: E731
                         np.asarray(v_dev)[:, :m])
        k_host, v_host = self._readback("swap_out", force) if inline \
            else force()
        stored = tier.complete(key, k_host, v_host)
        tr = self._tracer
        if tr is not None and trace_id is not None:
            # emitted from whichever thread ran the force — the
            # serving-swap-worker daemon by default — with the trace
            # id captured at dispatch: honest cross-thread attribution
            tr.event(trace_id, "swap_out_store", t0=tw,
                     dur=time.perf_counter() - tw, key=key, pages=m,
                     bytes=k_host.nbytes + v_host.nbytes,
                     stored=stored, inline=inline)
        if not stored:
            return                  # evicted mid-flight: bytes dropped
        if self._registry is not None:
            self._registry.counter_inc("serving.swap.swapped_out_pages",
                                       int(m))
            self._registry.observe("serving.swap.out_s",
                                   time.perf_counter() - t0)
            self._registry.gauge_set("serving.swap.host_bytes",
                                     float(tier.bytes_used))

    def _count_swap_verify_failed(self) -> None:
        self.swap_verify_failed += 1
        if self._registry is not None:
            self._registry.counter_inc("serving.swap.verify_failed")

    def _trace_swap_in(self, t0: float, key: int, joined: bool,
                       outcome: str, pages: int) -> None:
        """One ``swap_in`` span per host→device migration attempt,
        attributed to the admitting request via the scheduler's
        thread-local binding (a no-op without a tracer or binding).
        ``outcome`` is ``restored`` / ``verify_failed`` (missing or
        checksum-failed bytes — the CRC verdict) / ``deferred`` (pool
        too tight); ``joined`` marks a hit that waited on its own
        in-flight swap-out."""
        tr = self._tracer
        if tr is not None:
            tr.event_current("swap_in", t0=t0,
                             dur=time.perf_counter() - t0, key=key,
                             joined=joined, outcome=outcome,
                             pages=pages, crc_ok=outcome != "verify_failed")

    def _swap_in(self, key: int):
        """Migrate a swapped prefix entry's page bytes host→device:
        pop + checksum-verify the arena record, allocate fresh pool
        pages (LRU-evicting resident prefixes under pressure, and only
        from capacity NOT promised to admitted requests), write each
        page through the one compiled ``swap_in`` program, and mark
        the entry resident on the new page ids (one refcount per page
        held by the entry, exactly like registration). Returns the
        full restored page list, or None on degradation:

        - missing / checksum-failed / wrong-geometry host bytes → the
          entry is DROPPED and ``serving.swap.verify_failed`` counts —
          a verified miss (the caller re-prefills), never a wrong
          token;
        - pool too tight even after draining resident prefixes → the
          bytes go BACK to the arena and the entry stays swapped (a
          later, less-pressured hit can still restore it).

        A hit racing its own IN-FLIGHT swap-out (the entry is still
        in the *swapping* state) first JOINS the worker's copy —
        counted as ``serving.swap.swap_join_waits``, the wait charged
        to :attr:`device_wait_s` like any forced device read — so the
        arena record is complete (or failed) before it is taken:
        partial bytes are unobservable by construction. A join that
        surfaces the worker job's exception degrades to the same
        verified miss as missing bytes."""
        tier, pcache = self.host_tier, self.prefix_cache
        t0 = time.perf_counter()
        joined = False
        if tier is not None and self._swap_worker is not None \
                and self._swap_worker.in_flight(key):
            joined = True
            if self._registry is not None:
                self._registry.counter_inc("serving.swap.swap_join_waits")
            try:
                self._readback("swap_join",
                               lambda: self._swap_worker.join(key))
            except Exception as e:  # noqa: BLE001 — degrade, never crash
                # the job died before completing: the record is still
                # pending, so take() below returns None and the hit
                # degrades to the usual verified miss
                _logger.warning("swap-out of entry %d failed on the "
                                "worker (%s: %s) — degrading its hit "
                                "to a verified miss", key,
                                type(e).__name__, e)
        rec = tier.take(key) if tier is not None else None
        if rec is None or not rec.valid:
            pcache.drop(key)
            self._count_swap_verify_failed()
            self._trace_swap_in(t0, key, joined, "verify_failed", 0)
            return None
        k_host, v_host = rec.k, rec.v
        c = self.cache
        want = (c.layers, k_host.shape[1] if k_host.ndim == 5 else -1,
                *c.page_shape)
        if k_host.shape != want or v_host.shape != want \
                or k_host.dtype != np.dtype(c.dtype) \
                or v_host.dtype != np.dtype(c.dtype):
            pcache.drop(key)
            self._count_swap_verify_failed()
            self._trace_swap_in(t0, key, joined, "verify_failed", 0)
            return None
        m = int(k_host.shape[1])
        if m > self.max_pages:
            pcache.drop(key)
            self._count_swap_verify_failed()
            self._trace_swap_in(t0, key, joined, "verify_failed", 0)
            return None
        # unreserved allocation must never eat into admission promises:
        # draw only from `available` (free minus reserved), making room
        # by LRU-evicting (= swapping out) resident prefix entries
        while self.pool.available < m:
            if not pcache.evict_lru():
                tier.put(key, k_host, v_host, shards=rec.shards)
                _logger.debug("swap-in of entry %d deferred: pool too "
                              "tight for %d pages", key, m)
                self._trace_swap_in(t0, key, joined, "deferred", 0)
                return None
        pages = [self.pool.alloc() for _ in range(m)]
        # one fixed-shape dispatch restores the whole entry: pad the
        # block to max_pages, trailing ids to the page-0 sentinel
        # (its garbage absorbs the padded writes — the inactive-slot
        # idiom), so every swap-in of every entry size shares ONE
        # executable and ONE dispatch
        P = self.max_pages
        blk_shape = (c.layers, P, *c.page_shape)
        k_blk = np.zeros(blk_shape, k_host.dtype)
        v_blk = np.zeros(blk_shape, v_host.dtype)
        k_blk[:, :m], v_blk[:, :m] = k_host, v_host
        ids = np.zeros(P, np.int32)
        ids[:m] = pages
        ops = self._operands(lambda: (jnp.asarray(k_blk),
                                      jnp.asarray(v_blk),
                                      jnp.asarray(ids)))
        self.cache = self._runtime_call(
            "swap_in", lambda: self._jit_swap_in(self.cache, *ops))
        pcache.swap_in_complete(key, pages)
        self._trace_swap_in(t0, key, joined, "restored", m)
        if self._registry is not None:
            self._registry.counter_inc("serving.swap.swapped_in_pages",
                                       m)
            self._registry.counter_inc("serving.swap.hit_after_swap")
            self._registry.observe("serving.swap.in_s",
                                   time.perf_counter() - t0)
            self._registry.gauge_set("serving.swap.host_bytes",
                                     float(tier.bytes_used))
        return pages

    def attach_prefix(self, slot: int, match) -> bool:
        """Admission-time prefix hit: the matched entry's
        pages become the head of ``slot``'s page table by refcount bump
        — ZERO data movement. Chunk prefill then resumes at the matched
        offset; the first write past the share lands on a fresh page by
        construction (matches are chunk-aligned, chunks cover whole
        pages). Pages the hit shares are refunded from the slot's
        conservative admission reservation.

        A ``match.swapped`` hit (hierarchical KV) first migrates the
        entry's page bytes back from the host tier (:meth:`_swap_in`);
        on success the restored pages share exactly like a resident
        hit. Returns False — with NOTHING attached (the caller must
        treat the admission as a miss and re-prefill cold) — when the
        swap-in degraded; True on every attached hit."""
        if getattr(match, "swapped", False):
            restored = self._swap_in(match.row)
            if restored is None:
                return False
            k = match.length // self.page_len
            match = dataclasses.replace(
                match, pages=tuple(restored[:k]), swapped=False)
        pages = list(match.pages)
        if match.length != len(pages) * self.page_len:
            raise ValueError(
                f"prefix match length {match.length} does not cover "
                f"whole pages (page_len={self.page_len})")
        self.release_slot(slot, keep_reservation=True)
        self.pool.share(pages)
        self._page_table[slot, :len(pages)] = pages
        self._n_pages[slot] = len(pages)
        self._host_len[slot] = match.length
        refund = min(len(pages), int(self._slot_reserved[slot]))
        if refund:
            self._slot_reserved[slot] -= refund
            self.pool.unreserve(refund)
        return True

    def retain_prefix(self, slot: int, prompt: Sequence[int],
                      keys: Optional[Sequence[int]] = None) -> str:
        """Registration: retain ``prompt``'s block-aligned
        prefix by SHARING the pages that already hold it in ``slot`` —
        no copy, no reserved rows. Returns the
        :meth:`PrefixCache.register` outcome; on ``"registered"`` the
        entry holds its own refcount on each page (released at entry
        eviction), so the prefix survives the slot. ``keys`` are the
        prompt's precomputed rolling block keys (the pipelined
        scheduler's hash offload; None hashes inline)."""
        if self.prefix_cache is None:
            raise RuntimeError("engine built without a prefix cache "
                               "(prefix_pool=0)")
        n_blocks = len(prompt) // self.chunk_len
        length = n_blocks * self.chunk_len
        n_pages = length // self.page_len
        pages = tuple(int(p) for p in self._page_table[slot, :n_pages])
        outcome = self.prefix_cache.register(prompt, pages=pages,
                                             keys=keys)
        if outcome == "registered":
            self.pool.share(pages)
        return outcome

    def export_handoff(self, slot: int, key: int,
                       prompt: Sequence[int],
                       keys: Optional[Sequence[int]] = None) -> int:
        """Disaggregated-serving EXPORT: land ``slot``'s ingested
        prefix of ``prompt`` in the host arena under the request's own
        ``key`` (its uid — positive and globally unique, so records
        from every engine sharing one arena coexist), ready for a
        decode-role replica to restore. Two existing mechanisms back
        to back, zero new compiled programs:

        1. :meth:`PrefixCache.register_handoff` retains the prefix as
           an ordinary paged entry on the slot's own pages (refcount
           share, no copy) — capped at ``aligned(n - 1)`` blocks
           exactly like every registration, because the final chunk
           must run through the importer's chunk-prefill program to
           sample the first token;
        2. :meth:`PrefixCache.swap_out_key` migrates it straight to
           the arena through the (async, per-shard-CRC'd, fixed-shape)
           ``swap_out`` gather — the same dispatch the pressure path
           uses, so ``serving.swap.*`` telemetry covers handoff bytes
           and latency for free.

        Returns the exported aligned length (the importer's exact
        resume offset), or 0 when nothing could be exported — prompt
        spans no full block, no tier, or the arena declined — in
        which case the importer simply re-prefills cold (an entry the
        arena declined stays RESIDENT here as an ordinary local
        prefix). Counts ``serving.disagg.handoff_bytes``."""
        if self.prefix_cache is None or self.host_tier is None:
            return 0
        n_blocks = (len(prompt) - 1) // self.chunk_len
        if n_blocks == 0:
            return 0
        length = n_blocks * self.chunk_len
        if int(self._host_len[slot]) < length:
            raise RuntimeError(
                f"slot {slot} has ingested {int(self._host_len[slot])}"
                f" tokens of the {length}-token handoff prefix — "
                "export runs at ingestion completion, not before")
        n_pages = length // self.page_len
        pages = tuple(int(p) for p in self._page_table[slot, :n_pages])
        outcome = self.prefix_cache.register_handoff(
            key, prompt[:length], pages=pages, keys=keys)
        if outcome != "registered":
            return 0
        self.pool.share(pages)
        if not self.prefix_cache.swap_out_key(key):
            return 0
        if self._registry is not None:
            self._registry.counter_inc(
                "serving.disagg.handoff_bytes",
                self.host_tier.nbytes_of(key))
        return length

    @property
    def pages_free(self) -> int:
        """Free pages in the pool right now — the cheap host-only
        capacity gauge the router's least-loaded admission reads per routed request, without the
        fragmentation walk :meth:`pool_stats` pays."""
        return self.pool.free_pages

    def slot_pages(self, slot: int) -> int:
        """Pages currently held by ``slot`` — host bookkeeping only.
        The scheduler sums this over low-priority running slots for ``preemptible_pages``, the
        "reclaimable by preemption" headroom gauge in
        :meth:`Scheduler.load_snapshot`."""
        return int(self._n_pages[slot])

    def pool_stats(self) -> dict:
        """Paged-pool telemetry snapshot: allocator counters plus the
        per-slot fragmentation view (allocated-but-invalid positions
        over allocated positions)."""
        stats = self.pool.stats()
        stats["fragmentation"] = self.pool.fragmentation(
            self._host_len, self._n_pages)
        return stats

    def decode_step(self, last_tokens, active, temperatures,
                    fault_bias=None) -> np.ndarray:
        """One decode step over every slot: ``last_tokens`` [slots] int
        (each slot's most recent token), ``active`` [slots] bool,
        ``temperatures`` [slots] float. Returns the next token per slot
        (host int32 array; inactive rows are noise to discard).

        This is the SYNCHRONOUS shape — :meth:`decode_dispatch`
        immediately followed by :meth:`decode_reconcile`, the depth-0
        oracle path of the async pipelined heartbeat. Both halves run
        the same compiled program over the same operands, so the split
        changes no bytes.

        ``fault_bias`` ([slots] float, default all-zero) is added to
        the fp32 logits rows inside the compiled program — the chaos
        harness's per-slot NaN/Inf injection point (+0.0 elsewhere is
        value-identical, so healthy slots keep their exact tokens).
        The in-program finiteness verdict lands in
        :attr:`last_decode_finite` ([slots] bool); slots flagged False
        sampled from non-finite logits and must be quarantined, not
        trusted."""
        pending = self.decode_dispatch(last_tokens, active, temperatures,
                                       fault_bias=fault_bias)
        out, _finite, _dt = self.decode_reconcile(pending)
        return out

    def decode_dispatch(self, last_tokens, active, temperatures,
                        fault_bias=None, *,
                        after: Optional[PendingDecode] = None
                        ) -> PendingDecode:
        """DISPATCH one decode step and return without waiting for it:
        the compiled call is enqueued on the device (JAX async
        dispatch), host bookkeeping advances speculatively (paged
        lengths grow by one for each active slot — pure arithmetic, the
        same rollback-free contract as PR 8's speculative lengths), and
        the sampled tokens stay ON DEVICE inside the returned
        :class:`PendingDecode` until :meth:`decode_reconcile` reads
        them back in one batched transfer.

        ``after`` is the step dispatched before this one and not read
        yet: a row whose ``last_tokens`` entry is NEGATIVE takes its
        token from ``after``'s un-forced device ``tokens``, selected
        inside the decode program itself (the same launch and the same
        transfers as a step with nothing in flight - no eager op
        beside it). That is how the dispatch-ahead heartbeat chains
        step t+1 onto step t's output without the host ever touching
        the token values: the data dependency stays on the device, and
        the host think-time (admission, telemetry, the next step's
        operands) overlaps the device's execution of the step in
        flight. ``last_tokens`` may also be a DEVICE array as a whole
        (a pending step's ``tokens``).

        Nothing here counts tokens or observes latency — a dispatched
        token is not an emitted token until the reconcile decides it
        survived (a slot that turned out to finish mid-pipeline
        discards its speculated successors), so all accounting lives in
        :meth:`decode_reconcile`."""
        if fault_bias is None:
            fault_bias = np.zeros(self.slots, np.float32)
        else:
            fault_bias = np.asarray(fault_bias, np.float32)
            if fault_bias.shape != (self.slots,):
                raise ValueError(f"fault_bias {fault_bias.shape} must "
                                 f"be [{self.slots}]")
        act = np.asarray(active, bool)
        if after is None and isinstance(last_tokens, np.ndarray) \
                and (last_tokens[act] < 0).any():
            raise ValueError("a negative last_tokens entry marks a row "
                             "that takes its token from `after`, and no "
                             "step was given")
        # nothing in flight: every row's token is the host's, and the
        # program's other token operand is a constant that stays on the
        # device (no transfer)
        prev_tokens = self._no_prev_tokens if after is None \
            else after.tokens
        t0 = time.perf_counter()
        # write-then-attend writes at host_len: make sure each
        # active slot's write page exists BEFORE the program runs
        # (reservation at admission guarantees the pool can cover
        # it; a slot at max_len clamps onto its last page)
        with tracing.phase("engine.grow"):
            for s in np.flatnonzero(act):
                pos = int(self._host_len[s])
                if pos < self.max_len:
                    self._grow_slot(s, self.pool.pages_for(pos + 1))
        ops = self._operands(lambda: (
            jnp.asarray(last_tokens, jnp.int32), prev_tokens,
            jnp.asarray(self._page_table.copy()),
            jnp.asarray(self._host_len.copy()),
            jnp.asarray(temperatures, jnp.float32),
            jnp.asarray(fault_bias), self._next_key(),
            *self._lora_args(),
            *((jnp.asarray(act),) if self.slot_state else ())))
        self.cache, tokens, finite = self._runtime_call(
            "decode", lambda: self._jit_decode(self.params, self.cache,
                                               *ops))
        # write-then-attend: a row at position p attends p + 1
        attended = np.minimum(self._host_len[act], self.max_len - 1) + 1
        grow = act & (self._host_len < self.max_len)
        self._host_len[grow] += 1
        return PendingDecode(tokens=tokens, finite=finite, active=act,
                             t_dispatch=t0, attended=attended)

    def decode_reconcile(self, pending: PendingDecode, valid=None):
        """Read a dispatched decode step back to the host — ONE batched
        token transfer per step, never per-slot ``int()`` calls against
        device arrays — and account for it. Returns ``(tokens, finite,
        step_s)``: host int32 ``[slots]``, host bool ``[slots]``, and
        the dispatch→retire wall seconds (observed as
        ``serving.decode.step_s``; in sync mode this is exactly the old
        per-step measurement, in pipelined mode it still bounds the
        device's execution latency from above).

        ``valid`` ([slots] bool, default the dispatch mask) marks the
        slots whose token the caller will actually consume: the
        pipelined scheduler excludes slots whose request finished (or
        was quarantined / expired) while this step was in flight, so
        ``tokens_generated`` counts only emitted tokens and stays
        comparable with the sync path serving the same stream. The
        block time is charged to :attr:`device_wait_s`; the finiteness
        verdict lands in :attr:`last_decode_finite`.

        It also counts how much of the page table the step's
        decode kernel walked, from the lengths the dispatch recorded:
        ``serving.decode.pages_live`` (sum over decoding rows of
        ``ceil(length / page_len)``: the pages the kernel fetches) and
        ``serving.decode.pages_tabled`` (decoding rows x ``max_pages``:
        what a walk of the whole table would fetch), and how many pages
        that kernel wrote back with the step's tokens in them,
        ``serving.decode.pages_written`` (decoding rows x the pool's
        layers x 2, a K and a V page: the whole of the decode program's
        pool write since the kernel does it)."""
        if pending.reconciled:
            raise RuntimeError("PendingDecode already reconciled — each "
                               "dispatched step reads back exactly once")
        pending.reconciled = True
        valid = pending.active if valid is None \
            else np.asarray(valid, bool)
        # device sync: the step's latency surfaces here
        out, finite = self._readback(
            "decode", lambda: (np.asarray(pending.tokens),
                               np.asarray(pending.finite, bool)))
        dt = time.perf_counter() - pending.t_dispatch
        self.last_decode_finite = finite
        bad = int(np.sum(valid & ~finite))
        if bad:
            self._count_nonfinite(bad)
        n_valid = int(np.sum(valid))
        self.tokens_generated += n_valid
        if self._registry is not None:
            self._registry.observe("serving.decode.step_s", dt)
            self._registry.counter_inc("serving.decode.steps")
            self._registry.counter_inc("serving.tokens_generated",
                                       n_valid)
            # how much of the page table the decode kernel walked:
            # it fetches a row's live pages, not its table
            self._registry.counter_inc(
                "serving.decode.pages_live",
                int(np.sum(-(-pending.attended // self.page_len))))
            self._registry.counter_inc(
                "serving.decode.pages_tabled",
                pending.attended.size * self.max_pages)
            # and wrote: each decoding row's last page, K and V, once a
            # layer of the pool
            self._registry.counter_inc(
                "serving.decode.pages_written",
                pending.attended.size * self.cache.k.shape[0] * 2)
        return out, finite, dt

    def sync(self) -> None:
        """Explicit device barrier: block until every dispatched
        program (decode steps in flight included) has retired. The
        pipelined heartbeat never needs this for correctness — the
        cache is threaded through every call, so program order IS
        dispatch order — but benches and tests use it to close a
        timing window, and the wait is charged to
        :attr:`device_wait_s` like any other forced sync."""
        self._readback("sync", lambda: jax.block_until_ready(
            jax.tree_util.tree_leaves(self.cache)))

    def _runtime_call(self, program: str, fn):
        """Invoke one compiled program (``apex.engine.launch``),
        charging the call's block time to :attr:`launch_s` and
        :attr:`device_wait_s`. On real accelerators JAX dispatch is
        asynchronous — the call returns once the program is enqueued
        and the real wait surfaces at the forced read — but the CPU
        backend executes DONATED-buffer programs synchronously inside
        the call (the cache is donated on every program here), so
        without this the whole device execution would masquerade as
        host think-time, inverting the ``serving.heartbeat.*`` split
        and letting healthy CPU decode breach the watchdog's host
        budget. What the launch costs on silicon is read from
        :attr:`launch_s`, apart from the operands' upload
        (:meth:`_operands`) and the readback (:meth:`_readback`).

        Callers hand host state the allocator keeps mutating (page
        table, lengths, adapter bindings) over as a ``.copy()``: the
        CPU backend may alias a numpy buffer instead of copying it, and
        a program that has not read its operand yet when the host bumps
        a length in place computes the NEXT step's position (seen as a
        dropped token under CPU load)."""
        return self._charged("engine.launch", "launch_s", fn,
                             program=program)

    def _operands(self, build):
        """Build one program's operands (``apex.engine.upload``): the
        ``jnp.asarray`` transfers of host state, the sampling key, the
        adapter arguments - timed apart from the compiled call that
        takes them, charged to :attr:`upload_s` and
        :attr:`device_wait_s`."""
        return self._charged("engine.upload", "upload_s", build)

    def _readback(self, program: str, read):
        """Run one forced read of a program's results
        (``apex.engine.readback``): the host waits here until the
        device has finished and the bytes have crossed. Charged to
        :attr:`readback_s` and :attr:`device_wait_s`."""
        return self._charged("engine.readback", "readback_s", read,
                             program=program)

    def _charged(self, name: str, counter: str, fn, **args):
        """``fn()`` as one phase, its seconds added to ``counter`` and
        to :attr:`device_wait_s` from the same two clock reads - so the
        three counters sum to the fourth exactly."""
        with tracing.phase(name, **args) as p:
            out = fn()
        dt = p.t1 - p.t0
        setattr(self, counter, getattr(self, counter) + dt)
        self.device_wait_s += dt
        return out

    def verify_batch(self, drafts, *, fault_bias=None, offsets=None):
        """One speculative draft-and-verify step for EVERY verifying
        slot at once: ``drafts`` maps ``slot -> (last_token,
        draft_tokens)`` and the whole map is scored by the ONE compiled
        ``[slots, K+1]`` verify program — B verify-eligible slots share
        one program invocation instead of B sequential calls (the same
        fixed-shape discipline as the decode step: slots not in the map
        ride along as padding — their cache bytes are provably
        untouched — and that waste is the price of one executable).

        Each verifying row embeds ``[last_token, d_1 .. d_K]`` at the
        slot's committed length (exactly where a plain decode step
        would write), runs shifted-causal attention, and computes
        ACCEPT-LONGEST-PREFIX in-program. Returns ``(tokens,
        n_accepted)``: ``tokens`` [slots, K+1] int32 greedy targets —
        row ``s``'s ``tokens[s, :n_accepted[s] + 1]`` is that slot's
        emitted output — and ``n_accepted`` [slots] int32 (0 on
        non-verifying rows). Greedy-only; fewer than ``draft_len``
        drafts per row are padded to the fixed shape and excluded from
        acceptance. Every verifying slot needs ``0 < offset`` and
        ``offset + draft_len + 1 <= max_len`` (the scheduler's endgame
        gate) — violated windows raise HERE, before anything mutates
        (a silently-masked row would return ``n_accepted = 0`` with
        nothing committed, indistinguishable from a real zero-accept
        verify, and the caller would emit a token whose K/V never
        landed).

        ``offsets`` (optional ``{slot: expected_offset}``) cross-checks
        the caller's bookkeeping against each verifying slot's
        committed length and raises on drift — the scheduler passes its
        computed offsets so scheduler-vs-engine divergence stays a loud
        error, exactly as the per-slot path always guaranteed.

        ``fault_bias`` ([slots] float, default all-zero) is the chaos
        harness's per-row injection operand. Per-slot verdicts land in
        :attr:`last_verify_finite_slots` (non-verifying rows always
        read True); a False verdict means that row's tokens are garbage
        — quarantine that slot, don't emit.
        """
        if self.spec is None:
            raise RuntimeError(
                "verify_batch needs an engine built with "
                "spec=SpecConfig(...) — the verify program's "
                "[slots, K+1] shape is fixed at construction")
        if not drafts:
            raise ValueError("verify_batch needs at least one "
                             "verifying slot (empty drafts are the "
                             "plain-decode fallback)")
        K = self.spec.draft_len
        tokens = np.zeros((self.slots, K + 1), np.int32)
        n_drafted = np.zeros(self.slots, np.int32)
        for slot, (last_token, d) in drafts.items():
            slot = int(slot)
            if not 0 <= slot < self.slots:
                raise ValueError(f"slot {slot} not in [0, {self.slots})")
            n = len(d)
            if not 1 <= n <= K:
                raise ValueError(f"draft length {n} not in [1, "
                                 f"draft_len={K}] (an empty draft is "
                                 "the plain-decode fallback, not a "
                                 "verify)")
            tokens[slot, 0] = int(last_token)
            tokens[slot, 1:1 + n] = np.asarray(d, np.int32)
            n_drafted[slot] = n
        active = n_drafted > 0
        if fault_bias is None:
            fault_bias = np.zeros(self.slots, np.float32)
        else:
            fault_bias = np.asarray(fault_bias, np.float32)
            if fault_bias.shape != (self.slots,):
                raise ValueError(f"fault_bias {fault_bias.shape} must "
                                 f"be [{self.slots}]")
        # validate EVERY verifying slot's window host-side before
        # anything mutates: a masked row would return n_accepted=0 with
        # nothing committed — indistinguishable from a real zero-accept
        # verify, so the caller would emit a bonus token whose K/V
        # never landed
        for s in np.flatnonzero(active):
            off = int(self._host_len[s])
            if not 0 < off or off + K + 1 > self.max_len:
                raise ValueError(
                    f"verify window [{off}, {off + K + 1}) of slot "
                    f"{s} needs a committed prefix and must fit "
                    f"max_len={self.max_len}")
            if offsets is not None and s in offsets \
                    and int(offsets[s]) != off:
                raise ValueError(
                    f"verify offset {int(offsets[s])} disagrees with "
                    f"slot {s}'s committed length {off}")
        t0 = time.perf_counter()
        for s in np.flatnonzero(active):
            # the write extent must be backed by pages BEFORE the
            # program runs (reservation at admission guarantees the
            # pool can cover it when the scheduler gated the call)
            self._grow_slot(s, self.pool.pages_for(
                int(self._host_len[s]) + K + 1))
        # non-verifying rows: sentinel-only table + offset 0, so
        # their fixed-shape writes can never land on a live page
        vt = np.where(active[:, None], self._page_table, 0)
        vlen = np.where(active, self._host_len, 0)
        ops = self._operands(lambda: (
            jnp.asarray(tokens),
            jnp.asarray(vt.astype(np.int32)),
            jnp.asarray(vlen.astype(np.int32)),
            jnp.asarray(n_drafted), jnp.asarray(fault_bias),
            *self._lora_args()))
        self.cache, dev_out, dev_acc, dev_fin = self._runtime_call(
            "verify", lambda: self._jit_verify(self.params, self.cache,
                                               *ops))
        # ONE batched readback per verify dispatch (tokens, acceptance,
        # verdicts) — the host never int()s a device element per slot
        out, n_accepted, finite = self._readback("verify", lambda: (
            np.asarray(dev_out),            # device sync: step latency
            np.asarray(dev_acc, np.int32), np.asarray(dev_fin, bool)))
        # rollback IS this assignment, per slot: the rejected tail's
        # K/V sits at [offset + m + 1, offset + K + 1), past the
        # committed length — unreachable, and overwritten
        # write-then-attend by the slot's next decode/verify step
        for s in np.flatnonzero(active):
            self._host_len[s] = int(self._host_len[s]) \
                + int(n_accepted[s]) + 1
        self.last_verify_finite_slots = np.where(active, finite, True)
        # keep the long-standing scalar attribute live too: a caller
        # written against the pre-batching API must not read a stale
        # True past a batched verify that flagged a row
        self.last_verify_finite = bool(self.last_verify_finite_slots
                                       .all())
        bad = int(np.sum(active & ~finite))
        if bad:
            self._count_nonfinite(bad)
        emitted = int(np.sum(n_accepted[active])) + int(active.sum())
        self.tokens_generated += emitted
        if self._registry is not None:
            self._registry.observe("serving.spec.verify_s",
                                   time.perf_counter() - t0)
            self._registry.counter_inc("serving.spec.verify_slots",
                                       int(active.sum()))
            self._registry.counter_inc("serving.tokens_generated",
                                       emitted)
        return out, n_accepted

    def verify_step(self, slot: int, last_token: int,
                    drafts: Sequence[int], offset: int, *,
                    fault_bias: float = 0.0):
        """One speculative draft-and-verify step for a single ``slot``
        — a thin wrapper routing through the SAME compiled
        ``[slots, K+1]`` batched program as :meth:`verify_batch` (one
        executable either way; the other rows ride along as padding
        with their cache bytes untouched). Returns ``(tokens,
        n_accepted)`` for the slot: ``tokens`` [K+1] int32 greedy
        targets, ``tokens[:n_accepted + 1]`` the emitted output.
        ``offset`` must equal the slot's committed length and the
        padded window must fit: ``offset + draft_len + 1 <= max_len``.
        The finiteness verdict lands in :attr:`last_verify_finite`."""
        if self.spec is None:
            raise RuntimeError(
                "verify_step needs an engine built with "
                "spec=SpecConfig(...) — the verify program's "
                "[slots, K+1] shape is fixed at construction")
        # draft-length and slot-range validation live in verify_batch
        # (one copy of the contract); only the CALLER-offset window
        # check is this wrapper's own — it validates the argument
        # itself, where verify_batch validates the committed length
        K = self.spec.draft_len
        offset = int(offset)
        if not 0 < offset or offset + K + 1 > self.max_len:
            raise ValueError(
                f"verify window [{offset}, {offset + K + 1}) needs a "
                f"committed prefix and must fit max_len={self.max_len}")
        if not 0 <= slot < self.slots:
            raise ValueError(f"slot {slot} not in [0, {self.slots})")
        bias = np.zeros(self.slots, np.float32)
        bias[slot] = fault_bias
        # verify_batch validates the committed-length window and the
        # offset cross-check (both layouts) before anything mutates
        out, n_accepted = self.verify_batch(
            {slot: (last_token, list(drafts))}, fault_bias=bias,
            offsets={slot: offset})
        self.last_verify_finite = bool(
            self.last_verify_finite_slots[slot])
        return out[slot], int(n_accepted[slot])

    def _count_nonfinite(self, n: int) -> None:
        """One quarantine-worthy non-finite sampling event per affected
        slot: the ``serving.faults.nonfinite`` counter plus the host
        tally (kept registry-less so direct callers see it too)."""
        self.nonfinite_events += int(n)
        if self._registry is not None:
            self._registry.counter_inc("serving.faults.nonfinite",
                                       int(n))

    def page_table_snapshot(self):
        """DEBUG COPIES of the paged host state — ``(page_table,
        n_pages)`` numpy arrays safe to mutate (the chaos harness's
        :meth:`FaultPlan.corrupt_page_table` target and the
        :class:`~apex_tpu.serving.PoolAuditor`'s corruption-detection
        probe). Never hands out the live arrays."""
        return self._page_table.copy(), self._n_pages.copy()

    def lengths(self) -> np.ndarray:
        """Per-slot cache lengths (a copy of the host state)."""
        return self._host_len[:self.slots].copy()

    def program_kernels(self) -> dict:
        """Which Pallas kernels the decode and chunk-prefill programs
        hold once compiled for the attached backend: ``{"decode":
        {kernel: n_calls}, "chunk": {...}}``, counted from each
        program's compiled HLO (:func:`apex_tpu.utils.chip
        .kernel_calls`). The attention dispatchers pick kernel or jnp
        reference at trace time from geometry and backend
        (``page_len % 128``, ``head_dim % 8``, row-block alignment);
        this is where a caller sees which one its geometry got — an
        empty dict means the reference ran.

        Lowers and compiles both programs afresh at the heartbeat's
        operand shapes (with the persistent compile cache on that is a
        read). Trace counters are restored afterwards, so
        :attr:`compiled_programs` keeps counting only what serving
        itself compiled."""
        from apex_tpu.utils.chip import kernel_calls

        return {name: kernel_calls(compiled.as_text())
                for name, compiled in self._compile_programs().items()}

    def program_memory(self) -> dict:
        """What the decode and chunk-prefill programs hold in device
        memory beside their operands, by the compiler's own count:
        ``{"decode": {"argument_bytes", "alias_bytes", "temp_bytes"},
        "chunk": {...}}`` from each compiled program's buffer
        assignment (:func:`apex_tpu.utils.memory_report
        .executable_memory`). The KV pool is donated to both programs
        and written in place, so ``alias_bytes`` holds the pool and
        ``temp_bytes`` stays a small fraction of it; temporaries of the
        pool's size or more mean the compiler is copying the pool
        (a layout the kernels, the writes and the stored array do not
        share), which costs its bytes in time every step and in
        capacity always. A model with per-slot state
        (:class:`~apex_tpu.serving.kv_cache.SlotState`) adds
        ``state_bytes`` to each program: the state rides in the donated
        cache pytree, so it is part of ``alias_bytes`` too. Sets the
        gauges ``serving.kv.pool_bytes``,
        ``serving.kv.decode_temp_bytes`` and
        ``serving.kv.chunk_temp_bytes``.

        Compiles like :meth:`program_kernels` (same programs, same
        restored trace counters). On the CPU the interpreted Pallas
        kernels carry their operands through a loop, so the temporaries
        read there say nothing about a chip."""
        from apex_tpu.utils.memory_report import executable_memory

        out = {}
        for name, compiled in self._compile_programs().items():
            m = executable_memory(compiled)
            out[name] = {"argument_bytes": m.argument_bytes,
                         "alias_bytes": m.alias_bytes,
                         "temp_bytes": m.temp_bytes}
            if self.slot_state:
                # donated and aliased with the pool: part of alias_bytes
                out[name]["state_bytes"] = self.cache.state.nbytes()
        if self._registry is not None:
            self._registry.gauge_set("serving.kv.pool_bytes",
                                     float(self.cache.nbytes()))
            self._registry.gauge_set("serving.kv.decode_temp_bytes",
                                     float(out["decode"]["temp_bytes"]))
            self._registry.gauge_set("serving.kv.chunk_temp_bytes",
                                     float(out["chunk"]["temp_bytes"]))
        return out

    def _compile_programs(self) -> dict:
        """``{"decode": compiled, "chunk": compiled}``: both heartbeat
        programs lowered and compiled afresh at their operand shapes,
        trace counters restored (:meth:`program_kernels`,
        :meth:`program_memory`)."""
        slots_f32 = np.zeros(self.slots, np.float32)
        last = np.zeros(self.slots, np.int32)
        chunk = np.zeros((1, self.chunk_len), np.int32)
        scalars = (np.int32(0), np.int32(1), np.float32(0),
                   np.float32(0), self._key)
        decode_ops = (last, last, self._page_table, self._host_len)
        chunk_ops = (chunk, self._page_table[:1])
        traces = (self.decode_traces, self.chunk_traces)
        try:
            programs = {
                "decode": self._jit_decode.lower(
                    self.params, self.cache, *decode_ops, slots_f32,
                    slots_f32, self._key, *self._lora_args(),
                    *((np.zeros(self.slots, bool),)
                      if self.slot_state else ())),
                "chunk": self._jit_chunk.lower(
                    self.params, self.cache, *chunk_ops, *scalars,
                    *self._lora_args(0), *self._slot_arg(0)),
            }
        finally:
            self.decode_traces, self.chunk_traces = traces
        return {name: lowered.compile()
                for name, lowered in programs.items()}

    def close(self) -> None:
        """Stop the engine's :class:`~apex_tpu.serving.SwapWorker`
        thread (no-op without a host tier or under ``sync_swap``;
        idempotent — the weakref finalizer registered at construction
        runs the same stop). The stop DRAINS first: swap-outs queued
        at kill time complete their arena puts, so a replica killed
        with a non-empty swap queue still reconciles — the cross-tier
        audit walks clean, nothing dangles. After close the engine
        stays usable: further swap-outs run inline (the sync
        degradation)."""
        if self._swap_worker is not None:
            self._swap_worker.stop()

    def set_registry(self, registry) -> None:
        """Swap the telemetry registry (e.g. after a compile-warmup pass,
        so first-trace latency never poisons the serving histograms)."""
        self._registry = registry
        self._emit_tp_gauges()
        self._emit_kv_gauges()
        self._emit_wq_gauges()
        if self.lora is not None:
            self.lora.set_registry(registry)
        self._emit_lora_gauges()

    def set_tracer(self, tracer) -> None:
        """Install a request tracer (``Scheduler(tracer=...)`` calls
        this); the engine's swap-path spans then attribute to the
        admitting request via the scheduler's thread-local binding."""
        self._tracer = tracer

    def reset(self, clear_prefixes: bool = False) -> None:
        """Zero the serving-slot lengths (slot table wipe; K/V left in
        place — length masking makes stale data unreachable). Retained
        prefixes SURVIVE a reset by default (they are warm state, not
        per-request state — a bench window reset must not throw away the
        cache it is measuring); pass ``clear_prefixes=True`` to drop
        them too. The wipe also returns every slot's
        pages to the pool (retained prefixes keep theirs via their own
        refcounts)."""
        if self.lora is not None:
            # a slot wipe drops every live adapter binding; residency
            # (the arena rows) survives — warm state, like prefixes
            self._slot_adapter[:] = 0
            self.lora.release_all()
        for s in range(self.slots):
            self.release_slot(s)
        if clear_prefixes and self.prefix_cache is not None:
            # entry eviction releases each entry's page refs through
            # the pool (the on_evict hook). Swapped entries hold no
            # pages — their host-side bytes are dropped with the
            # arena below (warm resets keep BOTH tiers: a swapped
            # prefix is warm state exactly like a resident one).
            # A SHARED arena belongs to the whole fleet: discard
            # only this engine's own swapped keys, never clear()
            # the sibling engines' records out from under them.
            own_swapped = self.prefix_cache.swapped_keys()
            self.prefix_cache.clear()
            if self.host_tier is not None:
                if self.host_tier_shared:
                    for k in own_swapped:
                        self.host_tier.discard(k)
                else:
                    self.host_tier.clear()
                if self._registry is not None:
                    self._registry.gauge_set(
                        "serving.swap.host_bytes",
                        float(self.host_tier.bytes_used))
