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

Exit code: a sub-row that ended in ``{"error": ...}`` makes the exit code
non-zero (``_failed_legs``); the row itself still prints. Serving is
measured by ``benchmarks/run.py`` (``BENCHMARK.json``), not here.
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

def _failed_legs(row, path="") -> list:
    """``path: error`` for every leg sub-row that ended in
    ``{"error": ...}``: a broken leg keeps its siblings' rows, and this
    is what keeps it from passing."""
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
    }


def _median(xs):
    xs = sorted(xs)
    mid = len(xs) // 2
    return xs[mid] if len(xs) % 2 else 0.5 * (xs[mid - 1] + xs[mid])


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
