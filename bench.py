"""Driver benchmark: ResNet-50 amp-O2 train-step throughput (img/s/chip).

Mirrors the reference's north-star workload (examples/imagenet/main_amp.py:
ResNet-50 + amp O2 + DDP; BASELINE.json — "metric") on one chip with synthetic
data. Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "img/s/chip", "vs_baseline": N}

vs_baseline is relative to the apex O2 V100 per-GPU rate (~820 img/s, NVIDIA
DeepLearningExamples ResNet50v1.5 README — see BASELINE.md; the driver's bar
is >=0.9 on real v5e hardware).

The JSON is self-describing about plausibility: ``mfu_est`` is the
model-FLOPs utilization implied by the measured rate against the chip's
published bf16 peak (``apex_tpu.utils.chip.PEAKS``, keyed by
``device_kind``; an unknown kind is an error), and ``implausible: true``
flags any reading over 1.0.

The headline ``value`` is anchored on DEVICE time when the profiler dump
has device lanes (``basis: "device_trace"``): the profiler's device lanes
time the silicon itself (the reference's nvprof kernel-time column —
SURVEY §6/§7: time the device, not the python loop), so per-window rate =
BATCH*STEPS / device span of the capture (bubbles included;
``duty_cycle`` reports busy/span). The host wall-clock reading (each
window closed by ``block_until_ready``) stays in ``wall_clock``.

Exit code: a serving leg that was asked for and failed still lands as
``{"error": ...}`` in its sub-object (its siblings' rows survive), and
then makes the exit code non-zero. On an accelerator backend the three
legs that start child processes (``process_fleet``, ``host_tier``,
``tensor_parallel``) do not run and say why on stderr: the parent holds
the chip, and a child pinned to the CPU does not belong in a device
record.

The line also carries a ``serving`` sub-object (BENCH_SERVING_LEG=0 to
drop it): a smoke-sized paged-vs-contiguous serving capacity
measurement via ``bench_serving.paged_capacity_stats`` — tokens/s,
max-concurrent-requests vs contiguous rows, and HBM-bytes-per-request
reduction — so the serving stack finally has rows in the tracked
BENCH_* trajectory (ROADMAP's "Recent" gap), plus a nested ``chaos``
sub-object (BENCH_SERVING_CHAOS=0 to drop it): goodput under a seeded
fault-injection schedule vs the fault-free rate, failed/requeued
counts and ``token_mismatched_requests`` (expected 0) via
``bench_serving.chaos_stats``, a nested ``speculative``
sub-object (BENCH_SERVING_SPEC=0 to drop it): draft-and-verify
acceptance rate and tokens-per-slot-step vs plain decode with
``token_mismatched_requests`` (expected 0, bitwise) via
``bench_serving.spec_stats``, a nested ``tensor_parallel``
sub-object (BENCH_SERVING_TP=0 to drop it; BENCH_SERVING_TP=N sizes
the mesh): tp=1 vs tp=N CPU device emulation — per-shard KV HBM
bytes, collective inventory, ``token_mismatched_requests`` (expected
0) — run as a subprocess because the mesh leg must force emulated CPU
devices before any backend initializes, and a nested ``quantized_kv``
sub-object (BENCH_SERVING_QUANT=0 to drop it): the int8-capacity leg
— KV-bytes-per-token reduction, concurrency both modes,
``token_match_rate`` vs the bf16 oracle — via
``bench_serving.quantized_kv_stats``, a nested
``quantized_weights`` sub-object (BENCH_SERVING_WQUANT=0 to drop it):
the int8-weights leg — weight-bytes reduction, bytes-per-param,
HBM-bytes-per-request bf16 vs the combined weights+KV tier,
``token_match_rate`` both quantized modes vs the bf16 oracle — via
``bench_serving.quantized_weights_stats``, and a nested
``async_heartbeat`` sub-object (BENCH_SERVING_ASYNC=0 to drop it):
sync vs dispatch-ahead pipelined serving on one engine — heartbeat
wall per emitted token, duty cycle, ``token_mismatched_requests``
(expected 0, bitwise) — via ``bench_serving.async_stats``, and a
nested ``host_tier`` sub-object (BENCH_SERVING_HOST_TIER=0 to drop
it): the hierarchical-KV leg — a prefix working set larger than the
device pool served tier-off vs sync-swap vs ASYNC swap-out (hit
rate, chunks skipped, TTFT, admission-stall p50/p99 sync vs async
from the telemetry histogram, swap traffic, bitwise exactness, and
the BENCH_SERVING_HOST_TIER_TP mesh-composition sub-leg's
per-shard-record pins) — run as a subprocess like the
tensor-parallel leg so the mesh sub-leg can force emulated CPU
devices, and a
nested ``replica_router`` sub-object (BENCH_SERVING_ROUTER=0 to drop
it; BENCH_SERVING_REPLICAS sizes the fleet): the prefix-aware
least-loaded router at 1 vs N replicas — aggregate tokens/s, p99
TTFT, prefix hit rate affinity vs a random-routing control,
``token_mismatched_requests`` (expected 0, bitwise) — via
``bench_serving.replica_router_stats``, and a nested
``disaggregated`` sub-object (BENCH_SERVING_DISAGG=0 to drop it):
the prefill/decode role-split leg — one fleet over one shared host
arena, colocated vs ``Router(roles=[...])`` with CRC'd KV handoff
(bystander TTFT p50/p99 both modes, the decode-replica
heartbeat-tail isolation, handoff traffic + export/import p50/p99,
zero re-prefills, zero leaked arena bytes, bitwise exactness) — via
``bench_serving.disagg_stats``, and a nested ``overload``
sub-object (BENCH_SERVING_OVERLOAD=0 to drop it): the SLO-aware
preemptive-scheduling leg — the same seeded mixed-class stream at
>1x slot capacity served FIFO vs SLO-aware on identical geometry
(interactive TTFT p50/p99 both modes, per-class deadline-miss rate
against one FIFO-calibrated threshold, met-deadline goodput,
preempt/resume churn, bitwise exactness vs the FIFO serve) — via
``bench_serving.overload_stats``, and a nested ``lora`` sub-object
(BENCH_SERVING_LORA=0 to drop it): the multi-tenant adapter leg —
the mixed-tenant stream heterogeneously batched vs per-adapter
sequential at identical geometry (tokens/s + speedup, adapter churn
+ warm-bind rate, zero recompiles for N adapters, bitwise
exactness between batch compositions) — via
``bench_serving.lora_stats``, and a nested ``process_fleet``
sub-object (BENCH_SERVING_FLEET=0 to drop it;
BENCH_SERVING_REPLICAS sizes the fleet): the out-of-process worker
fleet — 1 worker vs N separate OS processes behind the stdlib
transport (aggregate tokens/s + ``scaling_x``, an honest CPU-box
scaling column since workers share no GIL, p99 TTFT, prefix hit
rate, rolling-restart wall time + per-worker p50/max, health
counters, bitwise exactness vs the 1-worker fleet) — via
``bench_serving.process_fleet_stats``.
Failure-isolated at every layer: a broken serving stack puts
{"error": ...} there, never kills the ResNet row.
"""

from __future__ import annotations

import json
import os
import tempfile
import time

# NOTHING heavy imports at module level: the guard contract (every run,
# including one that exhausts its transient retries, ends in a parseable
# JSON line) only holds for failures raised INSIDE guarded main() — a
# module-level jax/optax import crash or a malformed BENCH_* env value
# parsed at import time dies before the guard is armed and leaves a raw
# traceback as the last output (the BENCH_r05 '"parsed": null' shape).
# Heavy imports and env parsing therefore live in main(); a retry re-runs
# them from scratch, which is exactly what a transient backend hiccup
# needs.

METRIC = "resnet50_amp_o2_train_img_per_sec_per_chip"

V100_O2_IMG_PER_SEC = 820.0

# Analytic ResNet-50 cost: ~4.1 GMACs forward per 224x224 image = ~8.2
# GFLOP at mult+add=2 counting; a training step is ~3x forward
# (backward ~2x). Scaled by (IMAGE/224)^2 for non-default resolutions
# (conv cost is proportional to spatial area).
RESNET50_TRAIN_FLOP_PER_IMG_224 = 3 * 8.2e9

def _child_leg_refused(leg: str):
    """The three legs that start child processes — the process fleet,
    and the host-tier and tensor-parallel legs, which pin their child
    to ``JAX_PLATFORMS=cpu`` — do not run next to an accelerator: a
    fleet worker could not open the chip this process holds, and a
    CPU child's row has no place in a device record. Returns the
    ``skipped`` row (and says why on stderr) on a non-CPU backend,
    None on the CPU."""
    import sys

    import jax

    backend = jax.default_backend()
    if backend == "cpu":
        return None
    reason = (f"not run on the {backend} backend: the leg starts child "
              f"processes, this process holds the chip, and a CPU "
              f"child's row is not a device row")
    print(f"bench.py: {leg} leg {reason}", file=sys.stderr)
    return {"skipped": True, "reason": reason}


def _failed_legs(row, path="") -> list:
    """``path: error`` for every leg sub-row that ended in
    ``{"error": ...}``. The wrappers below keep one broken leg from
    losing its siblings' rows; this is what keeps it from passing."""
    if not isinstance(row, dict):
        return []
    found = [f"{path or 'bench'}: {row['error']}"] if "error" in row \
        else []
    for key, sub in row.items():
        found += _failed_legs(sub, f"{path}.{key}" if path else key)
    return found


def _env_int(name: str, default: str) -> int:
    """BENCH_* env knob as int; a malformed value becomes a clean
    SystemExit INSIDE the guard (one parseable failure line) instead of
    an import-time ValueError before the guard is armed."""
    raw = os.environ.get(name, default)
    try:
        return int(raw)
    except ValueError:
        raise SystemExit(f"{name}={raw!r} is not an integer")


def _read_env() -> dict:
    """All BENCH_* knobs, parsed at main() time (guarded, retry-fresh).

    BENCH_BATCH default 256/chip: the apex-recipe production batch for
    ResNet-50 amp O2 (NVIDIA DeepLearningExamples uses 256/V100-32G; a
    v5e's 16GB holds it in bf16) and large enough that step time is
    compute- rather than dispatch-bound. BENCH_WINDOWS >=3 independent
    windows reported as median+min+spread (VERDICT round-2 weak #1: one
    10-step sample carried no variance information).
    BENCH_TRACE_WINDOWS: device-anchored profiler captures (basis:
    "device_trace"). BENCH_ACCUM_STEPS=N scans N microbatches of
    BATCH/N per optimizer step (amp.make_train_step accum_steps) —
    each jit_step still consumes BATCH images, so img/s stays directly
    comparable to the N=1 rows."""
    return {
        "BATCH": _env_int("BENCH_BATCH", "256"),
        "IMAGE": _env_int("BENCH_IMAGE", "224"),
        "WARMUP": _env_int("BENCH_WARMUP", "2"),
        "STEPS": _env_int("BENCH_STEPS", "10"),
        "WINDOWS": _env_int("BENCH_WINDOWS", "3"),
        "TRACE_WINDOWS": _env_int("BENCH_TRACE_WINDOWS", "3"),
        "ACCUM_STEPS": _env_int("BENCH_ACCUM_STEPS", "1"),
        # BENCH_SERVING_LEG=0 drops the embedded serving capacity row
        "SERVING_LEG": _env_int("BENCH_SERVING_LEG", "1"),
    }


def _median(xs):
    xs = sorted(xs)
    mid = len(xs) // 2
    return xs[mid] if len(xs) % 2 else 0.5 * (xs[mid - 1] + xs[mid])


# Smoke geometry for the embedded serving leg: a tiny paged-vs-
# contiguous capacity measurement (~seconds, CPU-safe). Any exported
# BENCH_SERVING_* knob overrides a field (bench_serving._load_env's
# env-beats-smoke contract), so TPU rows can size it up without code
# changes.
_SERVING_SMOKE = {
    "SIZE": "tiny", "VOCAB": 512, "SLOTS": 4, "MAX_LEN": 128,
    "PREFILL_LEN": 32, "REQUESTS": 12, "NEW_TOKENS": 8, "WINDOWS": 1,
}

# The chaos sub-leg's smoke geometry (it serves its stream TWICE —
# rate 0 + injected — so it is sized below the capacity leg's)
_SERVING_CHAOS_SMOKE = {
    "SIZE": "tiny", "VOCAB": 512, "SLOTS": 4, "MAX_LEN": 128,
    "PREFILL_LEN": 32, "REQUESTS": 6, "NEW_TOKENS": 8, "WINDOWS": 1,
}

# The speculative sub-leg's smoke geometry (two streams, each served
# twice — plain + spec — so it matches the chaos leg's sizing)
_SERVING_SPEC_SMOKE = {
    "SIZE": "tiny", "VOCAB": 512, "SLOTS": 4, "MAX_LEN": 128,
    "PREFILL_LEN": 32, "REQUESTS": 6, "NEW_TOKENS": 8, "WINDOWS": 1,
}

# The quantized-KV sub-leg's smoke geometry (the shared-prefix stream
# served twice — bf16 oracle + int8 — so it matches its siblings'
# sizing; BENCH_SERVING_QUANT_SLOTS et al. still win, env-beats-smoke)
_SERVING_QUANT_SMOKE = {
    "SIZE": "tiny", "VOCAB": 512, "SLOTS": 4, "MAX_LEN": 128,
    "PREFILL_LEN": 32, "REQUESTS": 6, "NEW_TOKENS": 8, "WINDOWS": 1,
}

# The quantized-weights sub-leg's smoke geometry (the shared-prefix
# stream served THREE times — bf16 oracle, int8 weights, int8 weights
# + int8 KV — at identical geometry, so it matches its siblings'
# sizing; env knobs still win, env-beats-smoke)
_SERVING_WQUANT_SMOKE = {
    "SIZE": "tiny", "VOCAB": 512, "SLOTS": 4, "MAX_LEN": 128,
    "PREFILL_LEN": 32, "REQUESTS": 6, "NEW_TOKENS": 8, "WINDOWS": 1,
}

# The async-heartbeat sub-leg's smoke geometry (the stream is served
# twice — sync oracle + dispatch-ahead). Sized LONGER than its
# siblings on purpose: pipelining pays fixed fill/drain beats per
# wave, and a too-short stream measures mostly that overhead. On this
# CPU backend the pipelined row reads a small loss REGARDLESS
# (donated-buffer programs execute synchronously inside dispatch —
# see bench_serving's module docstring); exactness + the heartbeat
# split are the CPU-honest fields, the improvement is the TPU claim.
# BENCH_SERVING_ASYNC_DEPTH et al. still win, env-beats-smoke.
_SERVING_ASYNC_SMOKE = {
    "SIZE": "tiny", "VOCAB": 512, "SLOTS": 4, "MAX_LEN": 128,
    "PREFILL_LEN": 32, "REQUESTS": 8, "NEW_TOKENS": 16, "WINDOWS": 2,
}

# (The host-tier sub-leg runs as a SUBPROCESS — see
# _serving_host_tier_leg — so its smoke geometry is the child's own
# HOST_SMOKE preset in bench_serving.py; exported BENCH_SERVING_*
# knobs still win inside the child, env-beats-smoke.)

# The replica-router sub-leg's smoke geometry (the session stream is
# served THREE ways — 1 replica, N affinity, N random control — so it
# is sized small; REQUESTS is SESSIONS per window, 2 turns each;
# CHUNK_LEN stays small so a turn's history spans several reuse
# blocks). BENCH_SERVING_REPLICAS et al. still win, env-beats-smoke.
_SERVING_ROUTER_SMOKE = {
    "SIZE": "tiny", "VOCAB": 512, "SLOTS": 2, "MAX_LEN": 128,
    "PREFILL_LEN": 48, "CHUNK_LEN": 8, "REQUESTS": 4, "NEW_TOKENS": 8,
    "WINDOWS": 1, "PREFIX_POOL": 4,
}

# The disaggregated sub-leg's smoke geometry (the bystander/heavyweight
# stream is served TWICE — colocated, then role-split with KV handoff —
# so it is sized small; every third request is a heavyweight).
# BENCH_SERVING_REPLICAS et al. still win, env-beats-smoke.
_SERVING_DISAGG_SMOKE = {
    "SIZE": "tiny", "VOCAB": 512, "SLOTS": 2, "MAX_LEN": 128,
    "PREFILL_LEN": 48, "CHUNK_LEN": 8, "SHORT_LEN": 6, "REQUESTS": 6,
    "NEW_TOKENS": 8, "WINDOWS": 1, "PREFIX_POOL": 4,
}

# The overload sub-leg's smoke geometry (the mixed-class stream is
# served TWICE on one engine — FIFO, then SLO-aware with preemption —
# at >1x slot capacity; every third request is interactive). The
# interactive deadline is calibrated at BENCH_SERVING_OVERLOAD_DL_PCT
# percent of the measured FIFO window wall and judged identically in
# both modes. BENCH_SERVING_REQUESTS et al. still win,
# env-beats-smoke.
_SERVING_OVERLOAD_SMOKE = {
    "SIZE": "tiny", "VOCAB": 512, "SLOTS": 2, "MAX_LEN": 128,
    "PREFILL_LEN": 48, "CHUNK_LEN": 8, "SHORT_LEN": 6, "REQUESTS": 12,
    "NEW_TOKENS": 10, "WINDOWS": 1, "PREFIX_POOL": 4,
}

# The process-fleet sub-leg's smoke geometry (the session stream is
# served through TWO fleets — 1 worker, then N — and every worker
# spawn pays interpreter + jax import + compile, so it is sized
# small; the stream matches the router sub-leg's so the thread-vs-
# process rows are comparable). BENCH_SERVING_REPLICAS et al. still
# win, env-beats-smoke.
_SERVING_FLEET_SMOKE = {
    "SIZE": "tiny", "VOCAB": 512, "SLOTS": 2, "MAX_LEN": 128,
    "PREFILL_LEN": 48, "CHUNK_LEN": 8, "REQUESTS": 4, "NEW_TOKENS": 8,
    "WINDOWS": 1, "PREFIX_POOL": 4,
}

# The multi-tenant LoRA sub-leg's smoke geometry (the mixed-tenant
# stream is served TWICE — heterogeneously batched, then per-adapter
# sequential — on identically-built engines, so it is sized small).
# BENCH_SERVING_LORA_ADAPTERS et al. still win, env-beats-smoke.
_SERVING_LORA_SMOKE = {
    "SIZE": "tiny", "VOCAB": 512, "SLOTS": 4, "MAX_LEN": 128,
    "PREFILL_LEN": 32, "REQUESTS": 8, "NEW_TOKENS": 12, "WINDOWS": 1,
}


def _serving_leg() -> dict:
    """The serving trajectory row (ROADMAP: bench_serving.py had no
    BENCH_* row): serve a short-prompt stream on the paged engine vs
    the contiguous baseline at identical pool bytes and fold the
    headline fields — tokens/s, max concurrent requests vs rows,
    HBM-bytes-per-request reduction — into bench.py's one JSON line.
    Failure-isolated: a broken serving stack yields {"error": ...}
    here, never a lost ResNet row."""
    try:
        import bench_serving

        bench_serving._load_env(smoke=dict(_SERVING_SMOKE))
        _, summary = bench_serving.paged_capacity_stats()
        out = {k: summary[k] for k in (
            "value", "unit", "baseline_tokens_per_s",
            "max_concurrent_requests", "contiguous_slots",
            "logical_concurrency_exceeds_rows",
            "hbm_bytes_per_request", "hbm_bytes_per_request_contiguous",
            "hbm_bytes_per_request_reduction_pct", "pool_mib",
            "token_mismatched_requests", "model")}
        out["chaos"] = _serving_chaos_leg()
        out["speculative"] = _serving_spec_leg()
        out["tensor_parallel"] = _serving_tp_leg()
        out["quantized_kv"] = _serving_quant_leg()
        out["quantized_weights"] = _serving_wquant_leg()
        out["async_heartbeat"] = _serving_async_leg()
        out["replica_router"] = _serving_router_leg()
        out["disaggregated"] = _serving_disagg_leg()
        out["overload"] = _serving_overload_leg()
        out["lora"] = _serving_lora_leg()
        out["process_fleet"] = _serving_process_fleet_leg()
        out["host_tier"] = _serving_host_tier_leg()
        return out
    except KeyboardInterrupt:
        raise
    except BaseException as e:  # noqa: BLE001 — the row must not die here
        return {"error": f"{type(e).__name__}: {e}"}


def _serving_chaos_leg() -> dict:
    """The fault-isolation trajectory sub-row: smoke-sized
    goodput-under-injection summary (rate 0 vs BENCH_SERVING_FAULT_PCT)
    from ``bench_serving.chaos_stats``. BENCH_SERVING_CHAOS=0 drops it;
    failure-isolated like its parent — a broken fault layer yields
    {"error": ...} here, never a lost serving (or ResNet) row."""
    if _env_int("BENCH_SERVING_CHAOS", "1") == 0:
        return {"skipped": True}
    try:
        import bench_serving

        bench_serving._load_env(smoke=dict(_SERVING_CHAOS_SMOKE))
        _, summary = bench_serving.chaos_stats()
        return {k: summary[k] for k in (
            "value", "unit", "goodput_rate0_tokens_per_s",
            "goodput_retention_pct", "fault_pct", "clean_requests",
            "failed_requests", "requeued_retries",
            "token_mismatched_requests", "pages_in_use_at_drain")}
    except KeyboardInterrupt:
        raise
    except BaseException as e:  # noqa: BLE001 — the row must not die here
        return {"error": f"{type(e).__name__}: {e}"}


def _serving_spec_leg() -> dict:
    """The speculative-decoding trajectory sub-row: smoke-sized
    draft-and-verify summary (plain vs spec on the shared-prefix and
    multi-turn streams) from ``bench_serving.spec_stats``.
    BENCH_SERVING_SPEC=0 drops it; failure-isolated like its siblings
    — a broken spec layer yields {"error": ...} here, never a lost
    serving (or ResNet) row."""
    if _env_int("BENCH_SERVING_SPEC", "1") == 0:
        return {"skipped": True}
    try:
        import bench_serving

        bench_serving._load_env(smoke=dict(_SERVING_SPEC_SMOKE))
        _, summary = bench_serving.spec_stats()
        return {k: summary[k] for k in (
            "value", "unit", "baseline_tokens_per_s", "acceptance_rate",
            "acceptance_p50", "acceptance_p99", "tokens_per_step",
            "tokens_per_step_plain", "multi_turn_acceptance_rate",
            "multi_turn_tokens_per_step", "token_mismatched_requests",
            "spec_k", "verify_traces")}
    except KeyboardInterrupt:
        raise
    except BaseException as e:  # noqa: BLE001 — the row must not die here
        return {"error": f"{type(e).__name__}: {e}"}


def _serving_quant_leg() -> dict:
    """The quantized-KV trajectory sub-row: smoke-sized int8-capacity
    summary (bf16 oracle vs int8 engine at identical pool bytes —
    KV-bytes-per-token reduction, concurrency both modes, greedy
    token-match-rate) from ``bench_serving.quantized_kv_stats``.
    BENCH_SERVING_QUANT=0 drops it; failure-isolated like its siblings
    — a broken quant tier yields {"error": ...} here, never a lost
    serving (or ResNet) row."""
    if _env_int("BENCH_SERVING_QUANT", "1") == 0:
        return {"skipped": True}
    try:
        import bench_serving

        bench_serving._load_env(smoke=dict(_SERVING_QUANT_SMOKE))
        _, summary = bench_serving.quantized_kv_stats()
        return {k: summary[k] for k in (
            "value", "unit", "baseline_tokens_per_s", "token_match_rate",
            "token_mismatched_requests", "kv_bytes_per_token",
            "kv_bytes_per_token_bf16", "kv_bytes_per_token_reduction_pct",
            "hbm_bytes_per_request", "hbm_bytes_per_request_bf16",
            "hbm_bytes_per_request_reduction_pct",
            "max_concurrent_requests", "max_concurrent_requests_bf16",
            "slots", "slots_bf16", "pool_mib", "quant_scale_absmax",
            "model")}
    except KeyboardInterrupt:
        raise
    except BaseException as e:  # noqa: BLE001 — the row must not die here
        return {"error": f"{type(e).__name__}: {e}"}


def _serving_wquant_leg() -> dict:
    """The quantized-weights trajectory sub-row: smoke-sized
    int8-weights summary (bf16 oracle vs int8 weights vs int8 weights
    + int8 KV at identical geometry — weight-bytes reduction,
    bytes-per-param, HBM-bytes-per-request, greedy token-match-rate
    both quantized modes) from ``bench_serving.quantized_weights_
    stats``. BENCH_SERVING_WQUANT=0 drops it; failure-isolated like
    its siblings — a broken weight tier yields {"error": ...} here,
    never a lost serving (or ResNet) row."""
    if _env_int("BENCH_SERVING_WQUANT", "1") == 0:
        return {"skipped": True}
    try:
        import bench_serving

        bench_serving._load_env(smoke=dict(_SERVING_WQUANT_SMOKE))
        _, summary = bench_serving.quantized_weights_stats()
        return {k: summary[k] for k in (
            "value", "unit", "baseline_tokens_per_s",
            "combined_tokens_per_s", "token_match_rate",
            "token_mismatched_requests", "combined_token_match_rate",
            "combined_token_mismatched_requests", "weight_mib",
            "weight_mib_bf16", "weight_bytes_reduction_pct",
            "bytes_per_param", "bytes_per_param_bf16",
            "hbm_bytes_per_request", "hbm_bytes_per_request_bf16",
            "hbm_bytes_per_request_reduction_pct",
            "quant_scale_absmax", "model")}
    except KeyboardInterrupt:
        raise
    except BaseException as e:  # noqa: BLE001 — the row must not die here
        return {"error": f"{type(e).__name__}: {e}"}


def _serving_async_leg() -> dict:
    """The async-heartbeat trajectory sub-row: smoke-sized
    dispatch-ahead summary (sync vs pipeline_depth=N on one engine —
    heartbeat wall per emitted token, duty cycle, tokens/s, bitwise
    exactness) from ``bench_serving.async_stats``.
    BENCH_SERVING_ASYNC=0 drops it; failure-isolated like its siblings
    — a broken pipelined beat yields {"error": ...} here, never a lost
    serving (or ResNet) row."""
    if _env_int("BENCH_SERVING_ASYNC", "1") == 0:
        return {"skipped": True}
    try:
        import bench_serving

        bench_serving._load_env(smoke=dict(_SERVING_ASYNC_SMOKE))
        _, summary = bench_serving.async_stats()
        return {k: summary[k] for k in (
            "value", "unit", "baseline_tokens_per_s", "pipeline_depth",
            "heartbeat_wall_per_token_ms",
            "heartbeat_wall_per_token_ms_sync",
            "heartbeat_wall_per_token_improvement_pct",
            "duty_cycle", "duty_cycle_sync", "host_s_fraction",
            "discarded_inflight_tokens", "token_mismatched_requests",
            "compiled_programs", "model")}
    except KeyboardInterrupt:
        raise
    except BaseException as e:  # noqa: BLE001 — the row must not die here
        return {"error": f"{type(e).__name__}: {e}"}


def _serving_host_tier_leg() -> dict:
    """The hierarchical-KV trajectory sub-row: smoke-sized
    host-DRAM-tier summary (a prefix working set larger than the
    device pool — tier off vs sync-swap vs ASYNC swap-out: hit rate,
    chunks skipped, TTFT, the telemetry-wired admission-stall p50/p99
    sync vs async, swap traffic, bitwise exactness, plus the
    ``HOST_TIER_TP``-shard mesh-composition sub-leg's
    per-shard-record/token-exactness pins) from
    ``bench_serving.py --host-tier``. Runs as a SUBPROCESS like the
    tensor-parallel leg: the mesh sub-leg must force emulated CPU
    devices BEFORE any jax client initializes, and this process's
    backend is long since live. BENCH_SERVING_HOST_TIER=0 drops it;
    failure-isolated like its siblings — a broken (or timed-out)
    tier yields {"error": ...} here, never a lost serving (or
    ResNet) row."""
    if _env_int("BENCH_SERVING_HOST_TIER", "1") == 0:
        return {"skipped": True}
    refused = _child_leg_refused("host_tier")
    if refused:
        return refused
    try:
        import subprocess
        import sys

        root = os.path.dirname(os.path.abspath(__file__))
        env = dict(os.environ)
        # CPU + emulated devices for the mesh sub-leg; any exported
        # BENCH_SERVING_* knob still wins inside the child
        # (env-beats-smoke — the child applies its own smoke preset)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        proc = subprocess.run(
            [sys.executable, os.path.join(root, "bench_serving.py"),
             "--host-tier"],
            capture_output=True, text=True, env=env, cwd=root,
            timeout=600)
        lines = [ln for ln in proc.stdout.strip().splitlines() if ln]
        summary = json.loads(lines[-1])      # guard contract: last line
        if "error" in summary:
            return {"error": summary["error"],
                    "transient": summary.get("transient", False)}
        return {k: summary[k] for k in (
            "value", "unit", "baseline_tokens_per_s",
            "sync_swap_tokens_per_s",
            "prefix_hit_rate", "prefix_hit_rate_tier_off",
            "hit_rate_improved", "hit_rate_unchanged_vs_sync",
            "prefill_chunks_skipped",
            "prefill_chunks_skipped_tier_off",
            "prefill_chunks_skipped_pct", "ttft_p50_ms",
            "ttft_p50_ms_tier_off", "ttft_p99_ms",
            "ttft_p99_ms_tier_off", "ttft_improved",
            "admit_stall_p50_ms_sync", "admit_stall_p99_ms_sync",
            "admit_stall_p50_ms_async", "admit_stall_p99_ms_async",
            "admit_stall_p99_reduction_pct",
            "admit_stall_p50_reduction_pct", "admit_stall_reduced",
            "admit_stall_p50_reduced",
            "swap_join_waits", "hit_after_swap",
            "swapped_out_pages", "swapped_in_pages",
            "swap_verify_failed", "host_bytes",
            "prefix_working_set_pages", "pool_pages",
            "token_mismatched_requests", "mesh", "model")}
    except KeyboardInterrupt:
        raise
    except BaseException as e:  # noqa: BLE001 — the row must not die here
        return {"error": f"{type(e).__name__}: {e}"}


def _serving_router_leg() -> dict:
    """The replica-parallel trajectory sub-row: smoke-sized
    prefix-aware-router summary (1 replica vs BENCH_SERVING_REPLICAS,
    affinity vs random-routing control — aggregate tokens/s, p99 TTFT,
    prefix hit rate both policies, bitwise exactness) from
    ``bench_serving.replica_router_stats``. BENCH_SERVING_ROUTER=0
    drops it; failure-isolated like its siblings — a broken router
    yields {"error": ...} here, never a lost serving (or ResNet)
    row."""
    if _env_int("BENCH_SERVING_ROUTER", "1") == 0:
        return {"skipped": True}
    try:
        import bench_serving

        bench_serving._load_env(smoke=dict(_SERVING_ROUTER_SMOKE))
        _, summary = bench_serving.replica_router_stats()
        return {k: summary[k] for k in (
            "value", "unit", "replicas", "baseline_tokens_per_s",
            "scaling_x", "ttft_p99_ms", "ttft_p99_ms_one_replica",
            "prefix_hit_rate", "prefix_hit_rate_random",
            "reused_tokens_per_request",
            "reused_tokens_per_request_random",
            "affinity_beats_random", "spills",
            "token_mismatched_requests", "compiled_programs", "model")}
    except KeyboardInterrupt:
        raise
    except BaseException as e:  # noqa: BLE001 — the row must not die here
        return {"error": f"{type(e).__name__}: {e}"}


def _serving_disagg_leg() -> dict:
    """The disaggregated-serving trajectory sub-row: smoke-sized
    prefill/decode role-split summary (one fleet over one shared host
    arena, colocated vs role-split with KV handoff — bystander TTFT
    p50/p99 both modes, the decode-replica heartbeat-tail isolation,
    handoff traffic with export/import p50/p99, zero re-prefills /
    zero leaked arena bytes, bitwise exactness) from
    ``bench_serving.disagg_stats``. BENCH_SERVING_DISAGG=0 drops it;
    failure-isolated like its siblings — a broken handoff layer
    yields {"error": ...} here, never a lost serving (or ResNet)
    row."""
    if _env_int("BENCH_SERVING_DISAGG", "1") == 0:
        return {"skipped": True}
    try:
        import bench_serving

        bench_serving._load_env(smoke=dict(_SERVING_DISAGG_SMOKE))
        _, summary = bench_serving.disagg_stats()
        return {k: summary[k] for k in (
            "value", "unit", "replicas", "decode_replicas",
            "colocated_tokens_per_s",
            "ttft_bystander_p50_ms", "ttft_bystander_p50_ms_colocated",
            "ttft_bystander_p99_ms", "ttft_bystander_p99_ms_colocated",
            "decode_heartbeat_host_p99_ms",
            "decode_heartbeat_host_p99_ms_colocated",
            "decode_beat_tail_improved", "decode_host_p99_isolation_x",
            "decode_isolation", "handoffs", "handoff_bytes",
            "reprefills", "zero_reprefills_clean",
            "handoff_export_p50_ms", "handoff_export_p99_ms",
            "handoff_import_p50_ms", "handoff_import_p99_ms",
            "arena_bytes_after_drain", "token_mismatched_requests",
            "model")}
    except KeyboardInterrupt:
        raise
    except BaseException as e:  # noqa: BLE001 — the row must not die here
        return {"error": f"{type(e).__name__}: {e}"}


def _serving_overload_leg() -> dict:
    """The SLO-scheduling trajectory sub-row: smoke-sized
    overload summary (the same seeded mixed-class stream at >1x slot
    capacity served FIFO vs SLO-aware on identical geometry —
    interactive TTFT p50/p99 both modes, per-class deadline-miss rate
    against one FIFO-calibrated threshold, goodput of met-deadline
    tokens, preempt/resume churn, bitwise exactness vs the FIFO
    serve) from ``bench_serving.overload_stats``.
    BENCH_SERVING_OVERLOAD=0 drops it; failure-isolated like its
    siblings — a broken SLO layer yields {"error": ...} here, never a
    lost serving (or ResNet) row."""
    if _env_int("BENCH_SERVING_OVERLOAD", "1") == 0:
        return {"skipped": True}
    try:
        import bench_serving

        bench_serving._load_env(smoke=dict(_SERVING_OVERLOAD_SMOKE))
        _, summary = bench_serving.overload_stats()
        return {k: summary[k] for k in (
            "value", "unit", "goodput_fifo",
            "tokens_per_s", "tokens_per_s_fifo",
            "ttft_interactive_p50_ms", "ttft_interactive_p50_ms_fifo",
            "ttft_interactive_p99_ms", "ttft_interactive_p99_ms_fifo",
            "deadline_miss_rate_interactive",
            "deadline_miss_rate_interactive_fifo",
            "ttft_p99_improved", "miss_rate_improved",
            "preemptions", "resumes", "resume_reprefills",
            "deadline_rejected", "token_exact_vs_fifo",
            "token_mismatched_requests", "deadline_pct_of_fifo_wall",
            "overload_factor", "model")}
    except KeyboardInterrupt:
        raise
    except BaseException as e:  # noqa: BLE001 — the row must not die here
        return {"error": f"{type(e).__name__}: {e}"}


def _serving_lora_leg() -> dict:
    """The multi-tenant LoRA trajectory sub-row: smoke-sized adapter
    summary (the mixed-tenant stream heterogeneously batched vs
    per-adapter sequential at identical geometry — tokens/s both
    modes + speedup_x, adapter churn + warm-bind rate, arena/host
    occupancy, zero recompiles after warmup, bitwise exactness
    between batch compositions) from ``bench_serving.lora_stats``.
    BENCH_SERVING_LORA=0 drops it; failure-isolated like its
    siblings — a broken adapter tier yields {"error": ...} here,
    never a lost serving (or ResNet) row."""
    if _env_int("BENCH_SERVING_LORA", "1") == 0:
        return {"skipped": True}
    try:
        import bench_serving

        bench_serving._load_env(smoke=dict(_SERVING_LORA_SMOKE))
        _, summary = bench_serving.lora_stats()
        return {k: summary[k] for k in (
            "value", "unit", "baseline_tokens_per_s", "speedup_x",
            "token_mismatched_requests", "adapters", "rank",
            "arena_slots", "lora_hits", "lora_loads",
            "lora_evictions", "warm_bind_rate", "arena_bytes",
            "active_adapters", "compiled_programs",
            "recompiles_after_warmup", "model")}
    except KeyboardInterrupt:
        raise
    except BaseException as e:  # noqa: BLE001 — the row must not die here
        return {"error": f"{type(e).__name__}: {e}"}


def _serving_process_fleet_leg() -> dict:
    """The out-of-process fleet trajectory sub-row: smoke-sized
    process-fleet summary (1 worker vs BENCH_SERVING_REPLICAS
    separate OS processes behind the stdlib transport — aggregate
    tokens/s + scaling_x, the serving bench's one CPU-honest scaling
    column, p99 TTFT, prefix hit rate, rolling-restart timing, health
    counters, bitwise exactness) from
    ``bench_serving.process_fleet_stats``. BENCH_SERVING_FLEET=0
    drops it; failure-isolated like its siblings — a broken fleet
    (or a box that cannot spawn workers) yields {"error": ...} here,
    never a lost serving (or ResNet) row."""
    if _env_int("BENCH_SERVING_FLEET", "1") == 0:
        return {"skipped": True}
    refused = _child_leg_refused("process_fleet")
    if refused:
        return refused
    try:
        import bench_serving

        bench_serving._load_env(smoke=dict(_SERVING_FLEET_SMOKE))
        _, summary = bench_serving.process_fleet_stats()
        return {k: summary[k] for k in (
            "value", "unit", "workers", "baseline_tokens_per_s",
            "scaling_x", "scaling_honest_on_cpu", "ttft_p99_ms",
            "ttft_p99_ms_one_worker", "prefix_hit_rate",
            "reused_tokens_per_request", "affinity_hits", "spills",
            "worker_deaths", "hangs_detected", "restarts",
            "restart_wall_s", "restart_p50_s", "restart_max_s",
            "token_mismatched_requests", "model")}
    except KeyboardInterrupt:
        raise
    except BaseException as e:  # noqa: BLE001 — the row must not die here
        return {"error": f"{type(e).__name__}: {e}"}


def _serving_tp_leg() -> dict:
    """The tensor-parallel trajectory sub-row: the bench_serving.py
    --tensor-parallel smoke (tp=1 vs BENCH_SERVING_TP-shard CPU device
    emulation: tokens/s, per-shard KV HBM bytes, collective inventory,
    token_mismatched_requests — expected 0). Runs as a SUBPROCESS, not
    in-process like its siblings: the leg must force the CPU backend
    with emulated devices BEFORE any jax client initializes, and this
    process's backend is long since live. BENCH_SERVING_TP=0 drops it; failure-isolated like its
    siblings — a broken (or timed-out) mesh layer yields
    {"error": ...} here, never a lost serving (or ResNet) row."""
    if _env_int("BENCH_SERVING_TP", "2") == 0:
        return {"skipped": True}
    refused = _child_leg_refused("tensor_parallel")
    if refused:
        return refused
    try:
        import subprocess
        import sys

        root = os.path.dirname(os.path.abspath(__file__))
        env = dict(os.environ)
        # CPU emulation + smoke geometry; any exported BENCH_SERVING_*
        # knob still wins inside the child (env-beats-smoke)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        proc = subprocess.run(
            [sys.executable, os.path.join(root, "bench_serving.py"),
             "--tensor-parallel"],
            capture_output=True, text=True, env=env, cwd=root,
            timeout=600)
        lines = [ln for ln in proc.stdout.strip().splitlines() if ln]
        summary = json.loads(lines[-1])      # guard contract: last line
        if "error" in summary:
            return {"error": summary["error"],
                    "transient": summary.get("transient", False)}
        return {k: summary[k] for k in (
            "value", "unit", "baseline_tokens_per_s", "tp",
            "hbm_bytes_per_shard", "hbm_bytes_per_shard_tp1",
            "hbm_bytes_per_shard_reduction_pct", "psums_per_program",
            "all_gathers_per_program", "token_mismatched_requests",
            "model", "emulated_devices")}
    except KeyboardInterrupt:
        raise
    except BaseException as e:  # noqa: BLE001 — the row must not die here
        return {"error": f"{type(e).__name__}: {e}"}


def main():
    import jax
    import jax.numpy as jnp
    import optax

    from apex_tpu import amp, pyprof
    from apex_tpu.amp.policy import resolve_policy
    from apex_tpu.models.resnet import create_model
    from apex_tpu.utils import chip

    chip.enable_compile_cache()
    env = _read_env()
    # the one peak table; an unknown device_kind is an error before
    # anything is timed
    peak = chip.peak(jax.devices()[0].device_kind, "bf16_flops")
    BATCH, IMAGE, WARMUP, STEPS = (env["BATCH"], env["IMAGE"],
                                   env["WARMUP"], env["STEPS"])
    WINDOWS, TRACE_WINDOWS = env["WINDOWS"], env["TRACE_WINDOWS"]
    ACCUM_STEPS = env["ACCUM_STEPS"]

    # APEX_TPU_TELEMETRY=run.jsonl|stdout streams per-step telemetry
    # (loss/grad_norm/scaler trajectory + step_time_s) from inside the
    # jitted step; unset costs nothing (telemetry baked out at trace time)
    from apex_tpu import telemetry
    tele = telemetry.from_env()

    model = create_model("resnet50", num_classes=1000, dtype=jnp.bfloat16)
    rng = jax.random.PRNGKey(0)
    x_init = jnp.ones((BATCH, IMAGE, IMAGE, 3), jnp.float32)
    variables = model.init(rng, x_init, train=True)
    params, batch_stats = variables["params"], variables.get("batch_stats", {})

    policy = resolve_policy(opt_level="O2", loss_scale="dynamic")
    optimizer = optax.sgd(optax.constant_schedule(0.1), momentum=0.9)

    def loss_fn(p, model_state, batch):
        images, labels = batch
        logits, updated = model.apply(
            {"params": p, "batch_stats": model_state}, images, train=True,
            mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            jnp.asarray(logits, jnp.float32), labels).mean()
        return loss, updated["batch_stats"]

    if ACCUM_STEPS < 1 or BATCH % ACCUM_STEPS:
        raise SystemExit(f"BENCH_ACCUM_STEPS={ACCUM_STEPS} must be >= 1 "
                         f"and divide BENCH_BATCH={BATCH}")
    init_fn, step_fn = amp.make_train_step(loss_fn, optimizer, policy,
                                           with_model_state=True,
                                           telemetry=tele is not None,
                                           accum_steps=ACCUM_STEPS)
    state = init_fn(params, batch_stats)
    jit_step = jax.jit(step_fn, donate_argnums=(0,))

    images = jax.random.normal(rng, (BATCH, IMAGE, IMAGE, 3), jnp.float32)
    labels = jax.random.randint(rng, (BATCH,), 0, 1000)
    batch = (images, labels)
    batch = amp.to_microbatches(batch, ACCUM_STEPS)

    for _ in range(WARMUP):
        state, _ = jit_step(state, batch)
    jax.block_until_ready(jax.tree_util.tree_leaves(state.params)[0])

    wall_rates = []
    for _ in range(WINDOWS):
        t0 = time.perf_counter()
        for _ in range(STEPS):
            state, metrics = jit_step(state, batch)
        jax.block_until_ready(metrics["loss"])
        dt = time.perf_counter() - t0
        wall_rates.append(BATCH * STEPS / dt)

    if not wall_rates:
        raise SystemExit("BENCH_WINDOWS must be >= 1")
    wall_rates.sort()
    wall_value = _median(wall_rates)
    wall_spread = ((wall_rates[-1] - wall_rates[0]) / wall_value
                   if wall_value else 0.0)

    # Device-anchored windows: each capture's device-lane span times the
    # silicon (bubbles included). Falls back to wall clock when the
    # backend writes no device lanes (e.g. CPU smoke runs).
    dev_rates, duty = [], []
    for _ in range(TRACE_WINDOWS):
        with tempfile.TemporaryDirectory() as td:
            with pyprof.trace(td):
                for _ in range(STEPS):
                    state, metrics = jit_step(state, batch)
                jax.block_until_ready(metrics["loss"])
            try:
                d = pyprof.device_busy(td)
            except FileNotFoundError:
                d = {"span_ms": 0.0, "busy_ms": 0.0}
        if d["span_ms"] > 0:
            dev_rates.append(BATCH * STEPS / (d["span_ms"] / 1e3))
            duty.append(d["busy_ms"] / d["span_ms"])

    dev_rates.sort()
    if dev_rates:
        basis, rates = "device_trace", dev_rates
    else:
        basis, rates = "wall_clock", wall_rates
    img_per_sec = _median(rates)
    spread = (rates[-1] - rates[0]) / img_per_sec if img_per_sec else 0.0
    flop_per_img = RESNET50_TRAIN_FLOP_PER_IMG_224 * (IMAGE / 224.0) ** 2
    mfu = img_per_sec * flop_per_img / peak
    out = {
        "metric": METRIC,
        "value": round(img_per_sec, 2),
        "unit": "img/s/chip",
        "vs_baseline": round(img_per_sec / V100_O2_IMG_PER_SEC, 4),
        "basis": basis,
        "windows": [round(r, 2) for r in rates],
        "min": round(rates[0], 2),
        "spread_pct": round(100.0 * spread, 2),
        "mfu_est": round(mfu, 4),
        "implausible": bool(mfu > 1.0),
        "device_kind": getattr(jax.devices()[0], "device_kind", "unknown"),
        "accum_steps": ACCUM_STEPS,
        "wall_clock": {
            "value": round(wall_value, 2),
            "windows": [round(r, 2) for r in wall_rates],
            "spread_pct": round(100.0 * wall_spread, 2),
        },
    }
    if duty:
        out["duty_cycle"] = round(_median(duty), 4)
    if env["SERVING_LEG"]:
        # the serving trajectory row (tokens/s + HBM-bytes-per-request
        # finally land in the tracked BENCH_* JSON, per ROADMAP)
        out["serving"] = _serving_leg()
    if tele is not None:
        jax.effects_barrier()      # flush in-flight step callbacks
        tele.emit_snapshot()
        tele.close()
    print(json.dumps(out))
    failed = _failed_legs(out)
    if failed:
        # the row above stands; the exit code says a requested leg broke
        raise SystemExit("bench.py: requested leg(s) failed — "
                         + "; ".join(failed))


if __name__ == "__main__":
    # crash contract: any failure still ends in one parseable JSON line
    # ({"metric", "error", "rc": 1}) — no more "parsed": null bench rows.
    # Arming the guard must itself be failure-proof: if importing the
    # telemetry package dies (broken env, half-installed deps), fall back
    # to a stdlib-only failure line so the contract holds even then.
    try:
        from apex_tpu.telemetry import guard_bench_main
    except KeyboardInterrupt:
        raise
    except BaseException as e:  # noqa: BLE001 — the contract is total
        import sys
        import traceback

        traceback.print_exc(file=sys.stderr)
        sys.stdout.write(json.dumps({
            "metric": METRIC, "error": f"{type(e).__name__}: {e}",
            "rc": 1, "transient": False}) + "\n")
        sys.stdout.flush()
        raise SystemExit(1)
    guard_bench_main(main, METRIC)
