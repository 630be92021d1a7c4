"""Serving-telemetry lint: every ``serving.faults.*`` /
``serving.watchdog.*`` / ``serving.spec.*`` / ``serving.tp.*`` metric
the serving code emits must be documented in ``docs/serving.md``, and
every documented one must be emitted.

Same failure mode as the tuned-keys lint, one layer up: metric names
are stringly typed, so a renamed counter silently orphans its dashboard
row (and a doc'd metric nobody emits is an alert that can never fire).
The fault-isolation layer is exactly where that rot is most expensive —
``serving.faults.nonfinite`` going dark looks identical to "no faults"
— and the speculative layer is next in line: an orphaned
``serving.spec.acceptance_rate`` reads as "speculation off" while the
verify program burns real FLOPs. The tensor-parallel family joined with
the mesh tentpole: ``serving.tp.shards`` / the per-program collective
gauges going dark would make a sharded fleet indistinguishable from a
single-chip one on every dashboard. The ``serving.kv.*`` family joined
with the quantized-cache tentpole: ``serving.kv.bytes_per_token`` is
the capacity claim's basis, and ``serving.kv.quant_scale_absmax`` going
dark would hide that a drifted workload is CLIPPING against its
calibration. The ``serving.heartbeat.*`` family joined with the async
pipelined heartbeat: ``host_s`` / ``device_wait_s`` / ``duty_cycle``
are the duty-cycle claim's basis (the whole point of dispatch-ahead
execution), and ``discarded`` going dark would hide speculated-finality
rollbacks entirely. The ``serving.router.*`` family joined with the
replica-parallel tentpole: ``affinity_hits`` going dark reads as "no
multi-turn reuse" while requests silently re-prefill on cold replicas,
``replica_deaths`` / ``requeued`` going dark makes a dying fleet look
healthy, and the per-replica gauge namespace
(``serving.router.replica<i>.*``) is what keeps N replicas sharing one
registry from clobbering each other's pool gauges. The ``serving.swap.*``
family joined with the hierarchical-KV tentpole: ``hit_after_swap``
going dark reads as "the host tier never pays off" while swap-ins
silently skip real prefill chunks, ``verify_failed`` going dark would
hide that swapped prefixes are rotting (every one a full re-prefill),
and ``host_bytes`` is the tier's capacity claim. The loop is closed
by lint: the set of fault/watchdog/spec/tp/kv/heartbeat/router/swap
metric literals in ``apex_tpu/serving/`` source must EQUAL the set
named in the docs' tables.

The ``serving.wq.*`` family joined with the quantized-weights
tentpole: ``bytes_per_param`` is the weight-capacity claim's basis and
the family's absence on an engine is the signal the tier is OFF — both
gauges going dark would make a quantized fleet indistinguishable from
a bf16 one on every dashboard.

The ``serving.lora.*`` family joined with the multi-tenant LoRA
tentpole: ``loads`` vs ``hits`` is the adapter-affinity routing
claim's measurement basis (a dark ``hits`` reads as "every request
pays a host→device swap-in"), ``evictions`` going dark hides arena
thrash under adapter churn, and ``arena_bytes`` /
``active_adapters`` are the host store's capacity claim.

This file also owns the **eager-gather shape lint** (the PR 13 gotcha,
generalized): an eager ``pool[:, idx_list]`` fancy-index gather over
the device KV pool compiles ONE executable PER INDEX-COUNT — a serving
path whose index list length is data-dependent (per-prefix page
counts) silently recompiles ~165 ms mid-serve the first time an unseen
length appears, wrecking latency percentiles while every parity test
stays green (the bytes are right, only the wall-clock rots). The fix
is always the same: pad the index list to a fixed bound (the page-0
sentinel absorbs padding) so one shape serves all sizes. The lint
AST-scans ``apex_tpu/serving/`` for fancy-index gathers over the pool
arrays and pins the site set to exactly the allowlisted PADDED ones
(both host_tier swap directions), so every new gather must either pad
and join the allowlist deliberately or take a compiled fixed-shape
path.

This file also owns the **force-early lint**: the dispatch-ahead
regions of the serving stack must never force a device value to host
— no ``int()`` / ``float()`` / ``np.asarray()`` / ``np.array()`` /
``jax.device_get`` calls inside :func:`Scheduler._dispatch_decode`,
:func:`Scheduler._pipeline_last_tokens` (the pipelined heartbeat:
everything between a decode dispatch and its reconcile), or
:func:`Engine._dispatch_swap_out` (the async hierarchical-KV
swap-out's admission-side half: it snapshots pool bytes for the
:class:`SwapWorker` by DISPATCHING a compiled gather — a forced read
there silently reverts the tier to the synchronous admission stall).
A single forced read in any of these serializes the host against the
device with ZERO token-level symptom — the exact foot-gun the async
refactors exist to remove, invisible to every parity test because
forcing changes no tokens. Functions are checked BY NAME per file, so
a rename breaks the lint loudly instead of silently un-scoping it.

This file also owns the **span-name lint** (the tracing tentpole's
version of the metric-name loop): span names are stringly typed at
their emit sites (``tracer.event(uid, "admit", ...)``), so a renamed
span silently orphans its row in the ``### Span catalogue`` table in
``docs/serving.md`` — and a documented span nobody emits is a Perfetto
lane a reader will wait for forever. The lint AST-scans
``apex_tpu/serving/`` for calls to the three tracer recording methods
(``.event`` / ``.event_current`` / ``.end_trace``) and extracts each
call's first string-literal positional argument (the span name —
trace ids are never literals), then pins that set EQUAL to the
backticked first column of the catalogue table; the beat's phases
(``tracing.phase("serve.admit")``) are scanned the same way and listed
there under ``apex.`` + name. And the **tracer
force-lint**: the tracer's recording methods run inside the
dispatch-ahead regions' dynamic extent (the heartbeat/swap hooks call
them between dispatch and reconcile), so they get the same
force-early treatment as the regions themselves — no ``int()`` /
``np.asarray`` / ``jax.device_get`` in any hot recording method (the
exporters force freely; they run offline).
"""

import ast
import glob
import os
import re

import pytest

pytestmark = [pytest.mark.serving, pytest.mark.chaos]

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.abspath(os.path.join(HERE, os.pardir, os.pardir))
SRC_DIR = os.path.join(ROOT, "apex_tpu", "serving")
DOC = os.path.join(ROOT, "docs", "serving.md")

# metric families the fault-isolation + speculative + tensor-parallel
# + quantized-KV + async-heartbeat + replica-router layers own.
# NOTE the per-replica namespace: the router emits gauges as
# f"serving.router.replica{i}.<gauge>" — the literal this regex
# extracts from that f-string (source AND docs) is
# "serving.router.replica", which is exactly the namespacing contract
# the docs must name.
_PAT = re.compile(
    r"serving\.(?:faults|watchdog|spec|tp|kv|wq|heartbeat|router|swap"
    r"|disagg|fleet|slo|preempt|lora)"
    r"\.[a-z0-9_]+")


def _emitted():
    refs = {}
    for path in glob.glob(os.path.join(SRC_DIR, "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            for name in _PAT.findall(f.read()):
                refs.setdefault(name, []).append(
                    os.path.relpath(path, ROOT))
    return refs


def _documented():
    with open(DOC) as f:
        return set(_PAT.findall(f.read()))


def test_scan_surface_is_alive():
    """The lint must be looking at real code and real docs — an empty
    scan means the regex or paths broke, not that the code is clean."""
    emitted = _emitted()
    assert emitted, "no serving.faults.*/watchdog.*/spec.* literals " \
        "found under apex_tpu/serving — scan broken?"
    # the metrics the issues headline must exist and come from the
    # layers that own them (engine guard / scheduler watchdog + spec)
    assert os.path.join("apex_tpu", "serving", "engine.py") \
        in emitted.get("serving.faults.nonfinite", [])
    assert os.path.join("apex_tpu", "serving", "scheduler.py") \
        in emitted.get("serving.watchdog.stall", [])
    # the speculative-decoding layer (watchdog warm-start satellite
    # rides the same scan): acceptance + warm-up accounting are live
    sched = os.path.join("apex_tpu", "serving", "scheduler.py")
    for name in ("serving.spec.drafted", "serving.spec.accepted",
                 "serving.spec.acceptance_rate",
                 "serving.spec.tokens_per_step",
                 "serving.watchdog.warmup_s"):
        assert sched in emitted.get(name, []), \
            f"{name} not emitted by the scheduler — spec/watchdog " \
            "telemetry went dark"
    assert os.path.join("apex_tpu", "serving", "engine.py") \
        in emitted.get("serving.spec.verify_s", [])
    # the batched-verify slot-step counter (bench arithmetic's basis)
    # and the tensor-parallel gauge family are engine-emitted
    engine_py = os.path.join("apex_tpu", "serving", "engine.py")
    for name in ("serving.spec.verify_slots", "serving.tp.shards",
                 "serving.tp.psums_per_program",
                 "serving.tp.all_gathers_per_program",
                 "serving.tp.hbm_bytes_per_shard",
                 "serving.tp.pool_pages_per_shard",
                 "serving.kv.bytes_per_token",
                 "serving.kv.quant_scale_absmax"):
        assert engine_py in emitted.get(name, []), \
            f"{name} not emitted by the engine — batched-verify/tp/" \
            "quantized-kv telemetry went dark"
    # the quantized-weights family: the bytes-per-param capacity gauge
    # and the scale-provenance gauge are engine-emitted (and double as
    # the tier's liveness signal — unquantized engines emit neither)
    for name in ("serving.wq.bytes_per_param",
                 "serving.wq.quant_scale_absmax"):
        assert engine_py in emitted.get(name, []), \
            f"{name} not emitted by the engine — quantized-weights " \
            "telemetry went dark"
    # the async-heartbeat family: the host-think/device-wait split and
    # the speculated-finality rollback counter are scheduler-emitted
    for name in ("serving.heartbeat.host_s",
                 "serving.heartbeat.device_wait_s",
                 "serving.heartbeat.duty_cycle",
                 "serving.heartbeat.discarded"):
        assert sched in emitted.get(name, []), \
            f"{name} not emitted by the scheduler — async-heartbeat " \
            "telemetry went dark"
    # the hierarchical-KV family: swap traffic, the host-arena
    # capacity gauge, the hit-after-swap payoff counter and the
    # verified-miss degradation counter are all engine-emitted
    for name in ("serving.swap.swapped_out_pages",
                 "serving.swap.swapped_in_pages",
                 "serving.swap.host_bytes",
                 "serving.swap.hit_after_swap",
                 "serving.swap.verify_failed",
                 "serving.swap.host_evictions",
                 "serving.swap.out_s", "serving.swap.in_s",
                 # the async swap-out's own family: the admission-path
                 # stall histogram (the bench's sync-vs-async claim),
                 # the in-flight-hit join counter and the worker-queue
                 # depth gauge — any of these going dark hides whether
                 # the async tier is actually off the hot path
                 "serving.swap.admit_stall_s",
                 "serving.swap.swap_join_waits",
                 "serving.swap.swap_out_queue_depth"):
        assert engine_py in emitted.get(name, []), \
            f"{name} not emitted by the engine — hierarchical-KV " \
            "telemetry went dark"
    # the replica-router family: routing outcomes, death containment
    # and the per-replica gauge namespace are router-emitted
    router_py = os.path.join("apex_tpu", "serving", "router.py")
    for name in ("serving.router.routed", "serving.router.affinity_hits",
                 "serving.router.spills",
                 "serving.router.replica_deaths",
                 "serving.router.requeued",
                 "serving.router.replicas_alive",
                 "serving.router.replica"):
        assert router_py in emitted.get(name, []), \
            f"{name} not emitted by the router — replica-routing " \
            "telemetry went dark"
    # the disaggregated-serving family: each metric from the layer
    # that owns it — export count + verified-miss re-prefills
    # (scheduler), export bytes (engine), decode-beat isolation
    # (router)
    for name, owner in (("serving.disagg.handoffs", sched),
                        ("serving.disagg.handoff_bytes", engine_py),
                        ("serving.disagg.reprefills", sched),
                        ("serving.disagg.decode_isolation", router_py)):
        assert owner in emitted.get(name, []), \
            f"{name} not emitted by {os.path.basename(owner)} — " \
            "disaggregated-serving telemetry went dark"
    # the process-fleet family: routing outcomes mirror the router's
    # (same dashboard shape, one process per replica), plus the
    # health-detector and restart instrumentation that only exist
    # out-of-process — heartbeat latency, missed-beat hang
    # declarations, and the rolling-restart duration histogram
    fleet_py = os.path.join("apex_tpu", "serving", "fleet.py")
    for name in ("serving.fleet.routed", "serving.fleet.affinity_hits",
                 "serving.fleet.spills", "serving.fleet.worker_deaths",
                 "serving.fleet.requeued", "serving.fleet.restarts",
                 "serving.fleet.hangs_detected",
                 "serving.fleet.workers_alive",
                 "serving.fleet.heartbeat_s",
                 "serving.fleet.restart_s"):
        assert fleet_py in emitted.get(name, []), \
            f"{name} not emitted by the fleet controller — " \
            "process-fleet telemetry went dark"
    # the SLO/preemption family: preempt/resume churn counters and the
    # per-class namespaces (f-string families — the literal the regex
    # extracts from f"serving.slo.class.{cls}.ttft_s" is
    # "serving.slo.class", the namespacing contract the docs name) are
    # all scheduler-emitted — any going dark hides overload shaping
    for name in ("serving.preempt.preemptions",
                 "serving.preempt.resumes",
                 "serving.preempt.resume_reprefills",
                 "serving.slo.deadline_missed",
                 "serving.slo.deadline_rejected",
                 "serving.slo.class", "serving.slo.tenant"):
        assert sched in emitted.get(name, []), \
            f"{name} not emitted by the scheduler — SLO/preemption " \
            "telemetry went dark"
    # the multi-tenant LoRA family: arena churn counters (load-from-
    # host, warm-row hits, LRU evictions) and the residency gauges —
    # all emitted by the host-store/arena layer itself; any going dark
    # makes a thousand-adapter fleet indistinguishable from a base-only
    # one, and ``loads`` vs ``hits`` is the affinity routing claim's
    # entire measurement basis
    lora_py = os.path.join("apex_tpu", "serving", "lora.py")
    for name in ("serving.lora.loads", "serving.lora.hits",
                 "serving.lora.evictions",
                 "serving.lora.arena_bytes",
                 "serving.lora.active_adapters"):
        assert lora_py in emitted.get(name, []), \
            f"{name} not emitted by the LoRA tier — multi-tenant " \
            "adapter telemetry went dark"
    assert _documented(), "docs/serving.md names no fault/watchdog/" \
        "spec metrics — doc section missing?"


def test_every_emitted_fault_metric_is_documented():
    emitted = _emitted()
    documented = _documented()
    missing = {k: v for k, v in emitted.items() if k not in documented}
    assert not missing, (
        f"fault/watchdog metrics emitted in code but absent from "
        f"docs/serving.md (document them in the fault-tolerance "
        f"section): {missing}")


def test_every_documented_fault_metric_is_emitted():
    emitted = set(_emitted())
    stale = _documented() - emitted
    assert not stale, (
        f"docs/serving.md documents fault/watchdog metrics no serving "
        f"code emits (stale doc rows — delete them or wire the "
        f"emitter): {stale}")


# ------------------------------------------------- the force-early lint
# Functions that make up the dispatch-ahead regions, per file: between
# issuing a decode step and reconciling it (scheduler), and between
# dispatching a swap-out gather and the worker's deferred force
# (engine), the host must never block on a device value. These are
# checked by NAME so a rename breaks the lint loudly instead of
# silently un-scoping it.
_DISPATCH_REGION = {
    "scheduler.py": ("_dispatch_decode", "_pipeline_last_tokens",
                     "_dispatch_prefill"),
    "engine.py": ("_dispatch_swap_out", "prefill_chunk_dispatch"),
}

# Call shapes that force a device array to host. ``jnp.*`` stays legal
# (device-side ops); ``np.zeros``/``np.flatnonzero`` over host state
# stay legal (no device operand can reach them in these functions,
# which hold only host bookkeeping + PendingDecode handles).
_FORCING_NAMES = {"int", "float", "bool"}
_FORCING_ATTRS = {("np", "asarray"), ("np", "array"),
                  ("numpy", "asarray"), ("numpy", "array"),
                  ("jax", "device_get"), ("jax", "block_until_ready")}


def _forcing_calls(fn_node):
    bad = []
    for node in ast.walk(fn_node):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Name) and f.id in _FORCING_NAMES:
            bad.append((f.id, node.lineno))
        elif (isinstance(f, ast.Attribute)
              and isinstance(f.value, ast.Name)
              and (f.value.id, f.attr) in _FORCING_ATTRS):
            bad.append((f"{f.value.id}.{f.attr}", node.lineno))
    return bad


def test_dispatch_ahead_region_never_forces_to_host():
    """No code path between a dispatch and its reconcile/completion
    may call ``int()`` / ``float()`` / ``np.asarray`` /
    ``jax.device_get`` on anything: a forced read there stalls the
    host on in-flight device work and silently degrades the async
    path to its synchronous shape (pipeline_depth>=1 to the sync
    beat; the async swap-out to the admission stall) — tokens
    identical, overlap gone, no parity test can catch it."""
    for fname, region in _DISPATCH_REGION.items():
        path = os.path.join(SRC_DIR, fname)
        with open(path) as f:
            tree = ast.parse(f.read(), filename=path)
        found = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)) \
                    and node.name in region:
                found[node.name] = _forcing_calls(node)
        missing = set(region) - set(found)
        assert not missing, (
            f"dispatch-ahead functions {sorted(missing)} not found in "
            f"{fname} — renamed? update _DISPATCH_REGION so the "
            "force-early lint keeps covering the region")
        offenders = {name: calls for name, calls in found.items()
                     if calls}
        assert not offenders, (
            f"host-forcing calls inside {fname}'s dispatch-ahead "
            f"region (function -> [(call, line)]): {offenders} — "
            "these block the host on in-flight device work, the exact "
            "stall the async refactors exist to remove. Move the read "
            "to the reconcile/complete half (_reconcile_oldest / "
            "_complete_swap_out — the batched readback sites).")


# ---------------------------------------------- the eager-gather shape lint
# Fancy-index gathers over the device KV pool arrays that are ALLOWED
# because their index operand is padded to a fixed bound (max_pages,
# page-0 sentinel absorbing the padding) so one compiled shape serves
# every entry size: the compiled swap-out gather's two pool reads
# (its page_ids operand is always a padded [max_pages] array — see
# Engine._dispatch_swap_out). Keyed (file, function, gathered-array)
# so a refactor that moves or renames a site re-reviews its padding
# deliberately.
_PADDED_GATHERS_ALLOWED = {
    ("engine.py", "_swap_out_impl", "cache.k"),
    ("engine.py", "_swap_out_impl", "cache.v"),
}


def _attr_chain(node):
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    else:
        parts.append("<expr>")
    return ".".join(reversed(parts))


def _is_fancy_index(idx):
    """True when any element of the subscript is a VARIABLE index
    (Name/List/expression) rather than a slice or constant — the shape
    of a gather whose compiled shape follows the index length. Slices
    with variable bounds stay legal (their shapes are per-engine
    constants like ``[:slots]``, not per-call data)."""
    elts = idx.elts if isinstance(idx, ast.Tuple) else [idx]
    for e in elts:
        if isinstance(e, (ast.Slice, ast.Constant)):
            continue
        if isinstance(e, ast.UnaryOp) \
                and isinstance(e.operand, ast.Constant):
            continue
        return True
    return False


def _pool_gather_sites():
    """Every fancy-index READ of a pool array (attribute chain ending
    in ``.k`` / ``.v`` — the device K/V pools; ``.at[...]`` functional
    updates are excluded, they live inside compiled bodies with
    fixed-shape operands) under apex_tpu/serving/, attributed to its
    INNERMOST enclosing function."""
    sites = set()
    for path in glob.glob(os.path.join(SRC_DIR, "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            tree = ast.parse(f.read(), filename=path)
        funcs = [n for n in ast.walk(tree)
                 if isinstance(n, (ast.FunctionDef,
                                   ast.AsyncFunctionDef))]
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Subscript)
                    and isinstance(node.ctx, ast.Load)):
                continue
            chain = _attr_chain(node.value)
            if not chain.split(".")[-1] in ("k", "v"):
                continue
            if not _is_fancy_index(node.slice):
                continue
            enclosing = [fn for fn in funcs
                         if fn.lineno <= node.lineno
                         <= (fn.end_lineno or fn.lineno)]
            fname = max(enclosing, key=lambda fn: fn.lineno).name \
                if enclosing else "<module>"
            sites.add((os.path.basename(path), fname, chain))
    return sites


def test_pool_gathers_are_exactly_the_padded_allowlist():
    """Every fancy-index gather over the device pool arrays must be an
    allowlisted PADDED site: an unpadded one compiles a new executable
    per index length — the ~165 ms per-shape mid-serve recompile trap
    (PR 13) that no parity test can see. Set EQUALITY both directions:
    a new gather fails until it pads its index to a fixed bound and
    joins the allowlist deliberately, and a removed/renamed allowlist
    entry fails so the lint never rots into scanning nothing."""
    sites = _pool_gather_sites()
    new = sites - _PADDED_GATHERS_ALLOWED
    assert not new, (
        f"unreviewed fancy-index gathers over the device KV pool: "
        f"{sorted(new)} — an index list whose length is data-dependent "
        "recompiles a fresh executable per length mid-serve (~165 ms "
        "each, PR 13). Pad the index to a fixed bound (page-0 sentinel "
        "absorbs padding) and add the site to "
        "_PADDED_GATHERS_ALLOWED with the padding in place.")
    stale = _PADDED_GATHERS_ALLOWED - sites
    assert not stale, (
        f"allowlisted pool-gather sites no longer found (moved or "
        f"renamed — re-review their padding and update the "
        f"allowlist): {sorted(stale)}")


# ---------------------------------------------------- the span-name lint
# The tracer's three recording methods. Any call of the shape
# ``<anything>.event(...)`` / ``.event_current(...)`` / ``.end_trace(...)``
# under apex_tpu/serving/ is a span emit site; the span name is the
# call's first string-literal positional argument (``event`` and
# ``end_trace`` take the trace id first, but a trace id is never a
# string literal — it's ``request.uid`` — so "first str literal" is
# position-agnostic across all three signatures).
_SPAN_METHODS = {"event", "event_current", "end_trace"}
# ``tracing.phase("serve.admit", ...)``: a region of the beat itself,
# annotated on the profiler's host plane as "apex." + name - the name
# the catalogue lists it under (``Engine._charged`` is the engine's one
# caller of it: a phase plus the counter at the same boundary)
_PHASE_METHODS = {"phase", "_charged"}
_PHASE_PREFIX = "apex."
TRACING_PY = os.path.join(ROOT, "apex_tpu", "telemetry", "tracing.py")


def _spans_emitted():
    """Every span-name literal passed to a tracer recording method
    under apex_tpu/serving/, mapped to the files that emit it."""
    refs = {}
    for path in glob.glob(os.path.join(SRC_DIR, "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            tree = ast.parse(f.read(), filename=path)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if not (isinstance(f, ast.Attribute)
                    and (f.attr in _SPAN_METHODS
                         or f.attr in _PHASE_METHODS)):
                continue
            lits = [a.value for a in node.args
                    if isinstance(a, ast.Constant)
                    and isinstance(a.value, str)]
            if lits:
                name = lits[0] if f.attr in _SPAN_METHODS \
                    else _PHASE_PREFIX + lits[0]
                refs.setdefault(name, []).append(
                    os.path.relpath(path, ROOT))
    return refs


def _spans_documented():
    """The backticked first column of every row of the
    ``### Span catalogue`` table in docs/serving.md."""
    names = set()
    in_section = False
    with open(DOC) as f:
        for line in f:
            if line.startswith("#"):
                in_section = line.strip() == "### Span catalogue"
                continue
            if in_section and line.startswith("| `"):
                names.add(line.split("`")[1])
    return names


def test_span_scan_surface_is_alive():
    """The span lint must be looking at real emit sites and a real doc
    table — and the tentpole's headline spans must come from the
    layers that own them (terminal trio + quarantine from the
    scheduler, routing from the router, the swap pair from the engine,
    the draft span from the scheduler's worker closure)."""
    emitted = _spans_emitted()
    assert emitted, "no tracer recording calls found under " \
        "apex_tpu/serving — span scan broken?"
    sched = os.path.join("apex_tpu", "serving", "scheduler.py")
    for name in ("submit", "queue_wait", "admit", "prefill_chunk",
                 "heartbeat", "draft", "verify", "quarantine",
                 "finish", "expired", "failed",
                 # the disaggregated handoff pair: export at prompt-
                 # ingestion completion, import resolution at admission
                 "handoff_export", "handoff_import",
                 # the SLO pair: committed-state export at preemption,
                 # warm (or verified-cold) re-attach at re-admission
                 "preempt", "resume"):
        assert sched in emitted.get(name, []), \
            f"span {name!r} not emitted by the scheduler — request " \
            "lifecycle tracing went dark"
    assert os.path.join("apex_tpu", "serving", "router.py") \
        in emitted.get("route", [])
    engine_py = os.path.join("apex_tpu", "serving", "engine.py")
    for name in ("swap_out", "swap_out_store", "swap_in"):
        assert engine_py in emitted.get(name, []), \
            f"span {name!r} not emitted by the engine — migration " \
            "tracing went dark"
    # the beat's phases: the scheduler's own and the engine's three
    # ends of a program (upload, launch, readback)
    for name in ("apex.serve.beat", "apex.serve.expire",
                 "apex.serve.admit", "apex.serve.chunk",
                 "apex.serve.spec", "apex.serve.decode",
                 "apex.serve.emit"):
        assert sched in emitted.get(name, []), \
            f"phase {name!r} not marked by the scheduler — the beat " \
            "went dark on the profiler's clock"
    for name in ("apex.engine.grow", "apex.engine.upload",
                 "apex.engine.launch", "apex.engine.readback"):
        assert engine_py in emitted.get(name, []), \
            f"phase {name!r} not marked by the engine"
    assert _spans_documented(), "docs/serving.md has no " \
        "'### Span catalogue' table — doc section missing/renamed?"


def test_every_emitted_span_is_documented():
    emitted = _spans_emitted()
    documented = _spans_documented()
    missing = {k: v for k, v in emitted.items() if k not in documented}
    assert not missing, (
        f"spans emitted in code but absent from docs/serving.md's "
        f"span-catalogue table (add a row): {missing}")


def test_every_documented_span_is_emitted():
    emitted = set(_spans_emitted())
    stale = _spans_documented() - emitted
    assert not stale, (
        f"docs/serving.md's span-catalogue table names spans no "
        f"serving code emits (stale rows — delete them or wire the "
        f"emitter): {stale}")


# ------------------------------------------------ the tracer force-lint
# The tracer's hot recording methods execute inside the serving hooks —
# including the dispatch-ahead regions' dynamic extent (the heartbeat
# span lands between a decode dispatch and its reconcile; the swap_out
# span inside _dispatch_swap_out itself) — so they inherit the regions'
# contract: never force a device value to host. Annotation values are
# stored as passed (Python floats/ints from host bookkeeping); the
# exporters (export_chrome_trace / export_jsonl) normalize with int()
# at export time, offline, and are deliberately NOT in this list.
_TRACER_HOT = ("now", "begin", "event", "event_current", "end_trace",
               "current")


def test_tracer_recording_methods_never_force_to_host():
    """Every definition of a hot tracer recording method (Tracer AND
    its _BoundTracer replica view both define them) must be free of
    host-forcing calls — a single ``int()``/``np.asarray`` there would
    stall every traced heartbeat on in-flight device work, silently
    un-asyncing the PR 11/15 paths for traced runs only (the exact
    divergence-under-observation a tracer must never introduce)."""
    with open(TRACING_PY) as f:
        tree = ast.parse(f.read(), filename=TRACING_PY)
    found = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name in _TRACER_HOT:
            found.setdefault(node.name, []).extend(_forcing_calls(node))
    missing = set(_TRACER_HOT) - set(found)
    assert not missing, (
        f"hot tracer methods {sorted(missing)} not found in "
        "apex_tpu/telemetry/tracing.py — renamed? update _TRACER_HOT "
        "so the force lint keeps covering the recording path")
    offenders = {name: calls for name, calls in found.items() if calls}
    assert not offenders, (
        f"host-forcing calls inside hot tracer recording methods "
        f"(method -> [(call, line)]): {offenders} — these run inside "
        "the dispatch-ahead regions' dynamic extent; move any "
        "normalization to the exporters (offline).")
