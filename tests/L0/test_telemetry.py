"""apex_tpu.telemetry — registry/histogram math, JSONL round-trip, the
one-callback-per-step contract under jit, overflow-event emission from a
forced inf grad, comm accounting, the bench crash contract, and the
summarize CLI on a golden run file."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import apex_tpu.telemetry as telemetry
from apex_tpu.amp import init_scaler, make_train_step, resolve_policy
from apex_tpu.telemetry import (JsonlSink, MemorySink, MetricsRegistry,
                                StreamingHistogram)
from apex_tpu.telemetry.summarize import (load_records, render_summary,
                                          summarize_records)

pytestmark = pytest.mark.telemetry


@pytest.fixture
def spy_registry():
    """Fresh default registry with a MemorySink spy; the previous default
    is restored afterwards so tests don't leak sinks into each other."""
    old = telemetry.get_registry()
    spy = MemorySink()
    reg = telemetry.configure(sinks=[spy])
    yield reg, spy
    telemetry.set_registry(old)


# --------------------------------------------------------------- histogram

def test_streaming_histogram_exact_stats_and_quantiles():
    h = StreamingHistogram()
    for v in range(1, 101):          # 1..100, all inside the reservoir
        h.observe(float(v))
    s = h.summary()
    assert s["count"] == 100
    assert s["min"] == 1.0 and s["max"] == 100.0
    assert s["mean"] == pytest.approx(50.5)
    # exact linear-interpolated quantiles of 1..100
    assert s["p50"] == pytest.approx(50.5)
    assert s["p95"] == pytest.approx(95.05)
    assert s["p99"] == pytest.approx(99.01)


def test_streaming_histogram_reservoir_bounded_and_deterministic():
    a = StreamingHistogram(reservoir_size=64)
    b = StreamingHistogram(reservoir_size=64)
    for v in range(10_000):
        a.observe(v)
        b.observe(v)
    assert len(a._sample) == 64
    assert a.count == 10_000 and a.total == b.total
    # fixed-seed RNG: two identically-fed instances agree bit-for-bit
    assert a.summary() == b.summary()
    # the reservoir median of uniform 0..9999 lands near the middle
    assert 2000 < a.quantile(0.5) < 8000


def test_streaming_histogram_skips_nan_counts_real():
    h = StreamingHistogram()
    h.observe(1.0)
    h.observe(float("nan"))
    h.observe(3.0)
    assert h.count == 2
    assert h.mean == pytest.approx(2.0)
    assert not math.isnan(h.quantile(0.5))


# ---------------------------------------------------------------- registry

def test_registry_counters_gauges_and_ring():
    reg = MetricsRegistry(ring_size=4)
    assert reg.counter_inc("n") == 1.0
    assert reg.counter_inc("n", 2.5) == 3.5
    reg.gauge_set("g", 7)
    for i in range(10):
        reg.record_step({"loss": float(i)})
    assert len(reg.records) == 4                       # ring evicts oldest
    assert [r["loss"] for r in reg.records] == [6.0, 7.0, 8.0, 9.0]
    snap = reg.snapshot()
    assert snap["counters"]["n"] == 3.5
    assert snap["gauges"]["g"] == 7.0
    assert snap["histograms"]["train.loss"]["count"] == 10


def test_registry_step_time_and_overflow_counter():
    reg = MetricsRegistry()
    reg.record_step({"found_inf": 0})
    rec = reg.record_step({"found_inf": True})
    assert "step_time_s" in rec and rec["step_time_s"] >= 0.0
    assert rec["found_inf"] == 1                       # bool → numeric
    reg.record_step({"found_inf": np.bool_(True)})
    assert reg.counters["overflow_events"] == 2.0
    assert reg.histograms["train.step_time_s"].count == 2


def test_registry_snapshot_record_reaches_sinks():
    spy = MemorySink()
    reg = MetricsRegistry(sinks=[spy])
    reg.record_step({"loss": 1.0})
    reg.counter_inc("comm.all_reduce.bytes", 4096)
    final = reg.emit_snapshot()
    assert spy.records[-1] is final
    assert final["counters"]["comm.all_reduce.bytes"] == 4096
    assert final["tag"] == "summary"


# ------------------------------------------------------------------- JSONL

def test_jsonl_sink_round_trip(tmp_path):
    path = tmp_path / "run.jsonl"
    sink = JsonlSink(str(path))
    reg = MetricsRegistry(sinks=[sink])
    for i in range(3):
        reg.record_step({"loss": float(i), "loss_scale": 256.0})
    reg.emit_snapshot()
    reg.close()
    records = load_records(str(path))
    assert len(records) == 4
    assert [r["loss"] for r in records[:3]] == [0.0, 1.0, 2.0]
    assert records[3]["histograms"]["train.loss"]["count"] == 3
    # a crashed run's truncated last line is skipped, not fatal
    with open(path, "a") as f:
        f.write('{"loss": 9, "tru')
    assert len(load_records(str(path))) == 4


# ------------------------------------------------- in-jit emission contract

def _amp_setup(telemetry_opt):
    def loss_fn(params, batch):
        x, y = batch
        pred = x @ params["w"].astype(x.dtype)
        return jnp.mean((pred.astype(jnp.float32) - y) ** 2)

    policy = resolve_policy("O2", half_dtype=jnp.float16, verbose=False)
    init_fn, step_fn = make_train_step(loss_fn, optax.sgd(0.1), policy,
                                       telemetry=telemetry_opt)
    state = init_fn({"w": jnp.ones((4, 2), jnp.float32)})
    state = state.replace(scaler=init_scaler("dynamic", init_scale=256.0))
    x = jnp.ones((8, 4), jnp.float32)
    y = jnp.zeros((8, 2), jnp.float32)
    return jax.jit(step_fn), state, (x, y)


def test_amp_step_exactly_one_callback_per_step(spy_registry):
    """The acceptance contract: N executed steps of the jitted amp O2
    train step produce exactly N host callbacks (== N spy records), each
    bundling >= 5 distinct metric series."""
    reg, spy = spy_registry
    step, state, batch = _amp_setup(True)
    n = 7
    for _ in range(n):
        state, _ = step(state, batch)
    jax.effects_barrier()
    assert len(spy.records) == n
    series = set(spy.records[0]) - {"tag", "seq", "time", "step_time_s"}
    assert {"loss", "grad_norm", "loss_scale", "found_inf",
            "overflows"} <= series
    assert all(r["tag"] == "amp" for r in spy.records)
    # host-side wall time per step rides along from the second record on
    assert all("step_time_s" in r for r in spy.records[1:])
    assert reg.histograms["amp.loss"].count == n


def test_amp_step_telemetry_off_stages_nothing(spy_registry):
    _, spy = spy_registry
    step, state, batch = _amp_setup(False)
    for _ in range(3):
        state, _ = step(state, batch)
    jax.effects_barrier()
    assert spy.records == []


def test_amp_step_pinned_registry_bypasses_default(spy_registry):
    _, default_spy = spy_registry
    pinned_spy = MemorySink()
    pinned = MetricsRegistry(sinks=[pinned_spy])
    step, state, batch = _amp_setup(pinned)
    state, _ = step(state, batch)
    jax.effects_barrier()
    assert len(pinned_spy.records) == 1
    assert default_spy.records == []


def test_forced_inf_grad_emits_overflow_event(spy_registry):
    reg, spy = spy_registry
    step, state, batch = _amp_setup(True)
    x, y = batch
    state, _ = step(state, (x, y))                       # clean step
    bad = (x.at[0, 0].set(jnp.float32(1e30)), y)         # overflows f16
    state, metrics = step(state, bad)
    jax.effects_barrier()
    assert bool(metrics["found_inf"])
    clean, overflowed = spy.records
    assert clean["found_inf"] == 0 and overflowed["found_inf"] == 1
    # record_step counted the event and the scaler trajectory moved
    assert reg.counters["overflow_events"] == 1.0
    assert overflowed["loss_scale"] == 256.0             # scale AT the step
    assert float(state.scaler.loss_scale) == 128.0       # halved after


def test_emit_metrics_outside_jit(spy_registry):
    reg, spy = spy_registry
    telemetry.emit_metrics({"x": jnp.float32(2.0), "y": 3}, tag="eager")
    jax.effects_barrier()
    (rec,) = spy.records
    assert rec["tag"] == "eager" and rec["x"] == 2.0 and rec["y"] == 3


def test_accum_window_emits_one_callback_with_window_size(spy_registry):
    """Under accum_steps=N the callback contract is per OPTIMIZER window:
    W executed windows (each scanning N microbatches) produce exactly W
    host callbacks, and every record carries the window size."""
    reg, spy = spy_registry
    n, windows = 4, 3

    def loss_fn(params, batch):
        x, y = batch
        pred = x @ params["w"].astype(x.dtype)
        return jnp.mean((pred.astype(jnp.float32) - y) ** 2)

    policy = resolve_policy("O2", half_dtype=jnp.float16, verbose=False)
    init_fn, step_fn = make_train_step(loss_fn, optax.sgd(0.1), policy,
                                       telemetry=True, accum_steps=n)
    state = init_fn({"w": jnp.ones((4, 2), jnp.float32)})
    state = state.replace(scaler=init_scaler("dynamic", init_scale=256.0))
    step = jax.jit(step_fn)
    batch = (jnp.ones((n, 2, 4), jnp.float32),
             jnp.zeros((n, 2, 2), jnp.float32))
    for _ in range(windows):
        state, _ = step(state, batch)
    jax.effects_barrier()
    assert len(spy.records) == windows          # one per window, not per mb
    assert all(r["accum_steps"] == n for r in spy.records)
    assert reg.histograms["amp.loss"].count == windows


# ------------------------------------------------------------- comm health

def test_account_collective_counters(spy_registry):
    reg, _ = spy_registry
    from apex_tpu import comm

    tree = {"a": jnp.zeros((8, 4), jnp.float32),
            "b": jnp.zeros((16,), jnp.bfloat16)}
    telemetry.account_collective("ddp.allreduce", tree)
    assert reg.counters["comm.ddp.allreduce.calls"] == 1.0
    assert reg.counters["comm.ddp.allreduce.bytes"] == 8 * 4 * 4 + 16 * 2
    assert reg.counters["comm.ddp.allreduce.leaves"] == 2.0

    # the comm collectives account at trace time — once per compilation
    mesh_devs = jax.devices()[:2]
    if len(mesh_devs) == 2:
        from jax.sharding import Mesh, PartitionSpec as P
        from jax.experimental.shard_map import shard_map

        mesh = Mesh(np.array(mesh_devs), ("data",))
        f = shard_map(lambda x: comm.all_reduce(x, "data"), mesh=mesh,
                      in_specs=P("data"), out_specs=P())
        jax.jit(f)(jnp.ones((2, 3), jnp.float32))
        assert reg.counters["comm.all_reduce.calls"] == 1.0
        assert reg.counters["comm.all_reduce.bytes"] == 1 * 3 * 4


def test_timed_context_manager(spy_registry):
    reg, _ = spy_registry
    with telemetry.timed("ckpt.save"):
        pass
    assert reg.counters["ckpt.save.calls"] == 1.0
    assert reg.histograms["ckpt.save"].count == 1


# ------------------------------------------------------ bench crash contract

@pytest.fixture(autouse=True)
def _no_retry_backoff(monkeypatch):
    """Guard retries sleep an exponential backoff in production; zero it
    here so the transient-retry tests stay instant (the backoff itself
    is covered by test_guard_bench_main_backoff_schedule, which restores
    a nonzero base)."""
    monkeypatch.setattr(telemetry, "_RETRY_BACKOFF_S", 0.0)


def test_every_bench_driver_routes_through_guard_bench_main():
    """Every bench_*.py entry point must end in a parseable JSON line on
    ANY outcome — i.e. wrap its main in guard_bench_main. A new bench
    leg that forgets the guard reintroduces the '"parsed": null' failure
    mode this contract exists to kill."""
    import glob

    root = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir)
    drivers = sorted(glob.glob(os.path.join(root, "bench*.py")))
    assert len(drivers) >= 4        # bench, kernels, memory, schedule
    for path in drivers:
        with open(path) as f:
            src = f.read()
        assert "guard_bench_main(" in src, \
            f"{os.path.basename(path)} does not route through " \
            "guard_bench_main"


@pytest.mark.slow          # subprocess re-imports jax: ~15s of wall
def test_bench_py_emits_json_line_even_when_env_parsing_fails():
    """The PR 5 satellite: bench.py's guard contract must hold for
    failures that used to fire BEFORE the guard was armed (module-level
    env parsing / heavy imports — the '"parsed": null' shape).
    A poisoned BENCH_* value now dies inside guarded main(): the LAST
    stdout line is the parseable failure JSON, rc 1."""
    import subprocess
    import sys

    root = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir)
    env = dict(os.environ, BENCH_BATCH="banana", JAX_PLATFORMS="cpu",
               APEX_TPU_BENCH_RETRIES="0")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "bench.py")],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 1
    lines = [ln for ln in proc.stdout.strip().splitlines() if ln]
    parsed = json.loads(lines[-1])          # the contract: LAST line parses
    assert parsed["rc"] == 1 and "BENCH_BATCH" in parsed["error"]
    assert parsed["metric"] == "resnet50_amp_o2_train_img_per_sec_per_chip"
    assert parsed["transient"] is False


def test_guard_bench_main_failure_ends_in_json_line(capsys):
    def exploding_main():
        raise RuntimeError("backend init failed")

    with pytest.raises(SystemExit) as exc:
        telemetry.guard_bench_main(exploding_main, "resnet50_img_per_sec")
    assert exc.value.code == 1
    last = capsys.readouterr().out.strip().splitlines()[-1]
    parsed = json.loads(last)
    assert parsed == {"metric": "resnet50_img_per_sec",
                      "error": "RuntimeError: backend init failed",
                      "rc": 1, "transient": False}


def test_guard_bench_main_success_passes_through(capsys):
    assert telemetry.guard_bench_main(lambda: 42, "m") == 42
    with pytest.raises(SystemExit) as exc:      # clean exits untouched
        telemetry.guard_bench_main(lambda: (_ for _ in ()).throw(
            SystemExit(0)), "m")
    assert exc.value.code == 0


def test_guard_bench_main_retries_transient_then_succeeds(capsys):
    """One infrastructure flake (a reset connection) must not erase the
    perf record — the retry recovers it and no failure JSON is
    emitted."""
    calls = []

    def flaky_main():
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("UNAVAILABLE: connection reset by peer")
        return 42

    assert telemetry.guard_bench_main(flaky_main, "m") == 42
    assert len(calls) == 2
    out = capsys.readouterr().out
    assert "rc" not in out                       # no failure line printed
    # the retry boundary is marked so row aggregators can discard the
    # partial first attempt of a multi-row driver
    marker = json.loads(out.strip().splitlines()[0])
    assert marker["event"] == "transient_retry"
    assert marker["discard_preceding"] is True


def test_guard_bench_main_classifies_runtime_unavailable_transient(capsys):
    """A JaxRuntimeError whose message is the runtime's UNAVAILABLE
    status over a reset connection must classify as transient
    end-to-end: retried (with the ``transient_retry`` discard marker),
    recovered when the retry succeeds, and tagged ``"transient": true``
    when it persists, so one flaky backend can never zero out a bench
    round."""

    class JaxRuntimeError(RuntimeError):
        pass

    RESET = ("UNAVAILABLE: failed to connect to all addresses; last "
           "error: connection reset by peer")
    assert telemetry._is_transient_error(f"JaxRuntimeError: {RESET}")
    calls = []

    def reset_flaky():
        calls.append(1)
        if len(calls) == 1:
            raise JaxRuntimeError(RESET)
        return {"value": 1.0}

    assert telemetry.guard_bench_main(reset_flaky, "m") == {"value": 1.0}
    assert len(calls) == 2
    marker = json.loads(
        capsys.readouterr().out.strip().splitlines()[0])
    assert marker["event"] == "transient_retry"
    assert "UNAVAILABLE" in marker["error"]

    calls.clear()

    def reset_persistent():
        calls.append(1)
        raise JaxRuntimeError(RESET)

    with pytest.raises(SystemExit) as exc:
        telemetry.guard_bench_main(reset_persistent, "m", retries=2)
    assert exc.value.code == 1
    assert len(calls) == 3                       # original + two retries
    parsed = json.loads(
        capsys.readouterr().out.strip().splitlines()[-1])
    assert parsed["transient"] is True, \
        "a persistent connection reset must be read as infra " \
        "noise, not a perf regression"
    assert parsed["error"] == f"JaxRuntimeError: {RESET}"


def test_guard_bench_main_persistent_transient_tags_true(capsys):
    calls = []

    def always_flaky():
        calls.append(1)
        raise RuntimeError("UNAVAILABLE: connection reset by peer")

    with pytest.raises(SystemExit) as exc:
        telemetry.guard_bench_main(always_flaky, "m", retries=1)
    assert exc.value.code == 1
    assert len(calls) == 2                       # original + one retry
    parsed = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert parsed["transient"] is True
    assert parsed["rc"] == 1


def test_guard_bench_main_deterministic_error_never_retries(capsys):
    calls = []

    def broken_main():
        calls.append(1)
        raise ValueError("BENCH_WINDOWS must be >= 1")

    with pytest.raises(SystemExit):
        telemetry.guard_bench_main(broken_main, "m", retries=3)
    assert len(calls) == 1                       # no retry on real bugs
    parsed = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert parsed["transient"] is False


def test_guard_bench_main_transient_systemexit_retries():
    """SystemExit with a transient message string retries too (some
    drivers wrap backend errors in SystemExit)."""
    calls = []

    def flaky_exit():
        calls.append(1)
        if len(calls) == 1:
            raise SystemExit("UNAVAILABLE: connection reset by peer")
        return "ok"

    assert telemetry.guard_bench_main(flaky_exit, "m") == "ok"
    assert len(calls) == 2


def test_guard_bench_main_retries_default_from_env(monkeypatch, capsys):
    """APEX_TPU_BENCH_RETRIES raises the retry budget without touching
    any bench driver (a single retry can be exhausted by back-to-back
    resets)."""
    monkeypatch.setenv("APEX_TPU_BENCH_RETRIES", "3")
    calls = []

    def triple_flaky():
        calls.append(1)
        if len(calls) <= 3:
            raise RuntimeError("UNAVAILABLE: connection reset by peer")
        return 42

    assert telemetry.guard_bench_main(triple_flaky, "m") == 42
    assert len(calls) == 4                       # original + 3 retries


def test_guard_bench_main_env_retries_zero_and_malformed(monkeypatch):
    monkeypatch.setenv("APEX_TPU_BENCH_RETRIES", "0")
    calls = []

    def flaky():
        calls.append(1)
        raise RuntimeError("connection reset")

    with pytest.raises(SystemExit):
        telemetry.guard_bench_main(flaky, "m")
    assert len(calls) == 1                       # env 0 → no retry
    # malformed env degrades to the default of 1, never crashes
    monkeypatch.setenv("APEX_TPU_BENCH_RETRIES", "yes please")
    assert telemetry._env_retries() == 1
    monkeypatch.setenv("APEX_TPU_BENCH_RETRIES", "-2")
    assert telemetry._env_retries() == 0         # clamped, not negative
    monkeypatch.delenv("APEX_TPU_BENCH_RETRIES")
    assert telemetry._env_retries() == 1


def test_guard_bench_main_backoff_schedule(monkeypatch):
    """Transient retries back off exponentially (0.5, 1, 2, ... capped)
    instead of hammering the same mid-hiccup infrastructure."""
    monkeypatch.setattr(telemetry, "_RETRY_BACKOFF_S", 0.5)
    sleeps = []
    monkeypatch.setattr(telemetry.time, "sleep",
                        lambda s: sleeps.append(s))

    def always_flaky():
        raise RuntimeError("UNAVAILABLE: connection reset by peer")

    with pytest.raises(SystemExit):
        telemetry.guard_bench_main(always_flaky, "m", retries=6)
    assert sleeps == [0.5, 1.0, 2.0, 4.0, 8.0, 8.0]   # capped at 8 s


# -------------------------------------------------------------- summarize

GOLDEN = os.path.join(os.path.dirname(__file__), os.pardir, "data",
                      "telemetry_golden.jsonl")


def test_summarize_golden_run_file():
    records = load_records(GOLDEN)
    summary = summarize_records(records)
    assert summary["steps"] == {"amp": 8}
    loss = summary["metrics"]["amp.loss"]
    assert loss["count"] == 8
    assert loss["mean"] == pytest.approx(4.5)
    assert loss["p50"] == pytest.approx(4.5)
    assert loss["p95"] == pytest.approx(7.65)
    # counters come from the run's final snapshot record
    assert summary["counters"]["overflow_events"] == 1
    text = render_summary(summary)
    assert "amp.loss" in text and "p95" in text and "overflow_events" in text


def test_summarize_cli_on_golden_file(capsys):
    from apex_tpu.telemetry.__main__ import main

    assert main(["summarize", GOLDEN]) == 0
    out = capsys.readouterr().out
    for col in ("count", "mean", "p50", "p95"):
        assert col in out
    assert "amp.loss" in out and "steps: amp=8" in out

    assert main(["summarize", GOLDEN, "--json"]) == 0
    machine = json.loads(capsys.readouterr().out)
    assert machine["metrics"]["amp.loss"]["count"] == 8


def test_summarize_cli_rejects_empty_file(tmp_path):
    from apex_tpu.telemetry.__main__ import main

    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    with pytest.raises(SystemExit):
        main(["summarize", str(empty)])


# ------------------------------------------------------------- prometheus

def test_render_prometheus_golden():
    """The exposition format is a wire contract: pin an exact golden
    render — counter/gauge typing, name sanitization (dots → ``_``),
    the per-replica router-gauge namespace collapsing into ONE labeled
    family, the fixed histogram bucket ladder with exact cumulative
    counts, and deterministic ordering (sorted families, sorted label
    sets) so scrapes diff cleanly."""
    reg = MetricsRegistry()
    reg.counter_inc("serving.faults.nonfinite", 2)
    reg.counter_inc("overflow_events")
    reg.gauge_set("serving.kv.bytes_per_token", 512)
    reg.gauge_set("serving.router.replica1.queue_depth", 1)
    reg.gauge_set("serving.router.replica0.queue_depth", 3)
    for v in (0.25, 0.75, 3.0):          # exact binary floats: sum == 4
        reg.observe("serving.ttft_s", v)
    golden = "\n".join([
        "# TYPE overflow_events counter",
        "overflow_events 1",
        "# TYPE serving_faults_nonfinite counter",
        "serving_faults_nonfinite 2",
        "# TYPE serving_kv_bytes_per_token gauge",
        "serving_kv_bytes_per_token 512",
        "# TYPE serving_router_replica_queue_depth gauge",
        'serving_router_replica_queue_depth{replica="0"} 3',
        'serving_router_replica_queue_depth{replica="1"} 1',
        "# TYPE serving_ttft_s histogram",
        'serving_ttft_s_bucket{le="0.0005"} 0',
        'serving_ttft_s_bucket{le="0.001"} 0',
        'serving_ttft_s_bucket{le="0.0025"} 0',
        'serving_ttft_s_bucket{le="0.005"} 0',
        'serving_ttft_s_bucket{le="0.01"} 0',
        'serving_ttft_s_bucket{le="0.025"} 0',
        'serving_ttft_s_bucket{le="0.05"} 0',
        'serving_ttft_s_bucket{le="0.075"} 0',
        'serving_ttft_s_bucket{le="0.1"} 0',
        'serving_ttft_s_bucket{le="0.25"} 1',
        'serving_ttft_s_bucket{le="0.5"} 1',
        'serving_ttft_s_bucket{le="0.75"} 2',
        'serving_ttft_s_bucket{le="1"} 2',
        'serving_ttft_s_bucket{le="2.5"} 2',
        'serving_ttft_s_bucket{le="5"} 3',
        'serving_ttft_s_bucket{le="7.5"} 3',
        'serving_ttft_s_bucket{le="10"} 3',
        'serving_ttft_s_bucket{le="25"} 3',
        'serving_ttft_s_bucket{le="50"} 3',
        'serving_ttft_s_bucket{le="100"} 3',
        'serving_ttft_s_bucket{le="+Inf"} 3',
        "serving_ttft_s_sum 4",
        "serving_ttft_s_count 3",
    ]) + "\n"
    assert reg.render_prometheus() == golden
    # identical state renders identically (scrape-diff stability)
    assert reg.render_prometheus() == golden


def test_render_prometheus_sanitizes_malformed_names():
    """Anything outside ``[a-zA-Z0-9_:]`` becomes ``_`` and a leading
    digit gets a ``_`` prefix — a malformed metric name must never
    produce a line a Prometheus scraper rejects (one bad line fails
    the WHOLE scrape)."""
    import re

    reg = MetricsRegistry()
    reg.counter_inc("3bad.metric-name!x")
    reg.gauge_set("weird metric/name", 1)
    text = reg.render_prometheus()
    assert "_3bad_metric_name_x 1" in text
    assert "weird_metric_name 1" in text
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        name = line.split("{")[0].split(" ")[0]
        assert re.fullmatch(r"[a-zA-Z_:][a-zA-Z0-9_:]*", name), \
            f"invalid prometheus metric name in exposition: {name!r}"


def test_render_prometheus_reservoir_scaled_buckets_exact_sum_count():
    """Past the reservoir, bucket counts are uniformly scaled estimates
    but ``_sum``/``_count`` stay exact — with every observation equal,
    the scaled buckets are exact too, pinning the scale arithmetic."""
    reg = MetricsRegistry(reservoir_size=64)
    for _ in range(10_000):
        reg.observe("h", 0.5)
    text = reg.render_prometheus()
    assert 'h_bucket{le="0.25"} 0' in text
    assert 'h_bucket{le="0.5"} 10000' in text
    assert 'h_bucket{le="+Inf"} 10000' in text
    assert "h_sum 5000" in text
    assert "h_count 10000" in text


# ------------------------------------------------------------ env opt-in

def test_from_env_unset_is_noop(monkeypatch):
    monkeypatch.delenv(telemetry.ENV_VAR, raising=False)
    before = telemetry.get_registry()
    assert telemetry.from_env() is None
    assert telemetry.get_registry() is before


def test_from_env_starts_run(monkeypatch, tmp_path):
    old = telemetry.get_registry()
    path = tmp_path / "env.jsonl"
    monkeypatch.setenv(telemetry.ENV_VAR, str(path))
    try:
        reg = telemetry.from_env()
        assert reg is telemetry.get_registry() and reg is not old
        reg.record_step({"loss": 1.0})
        reg.close()
        assert len(load_records(str(path))) == 1
    finally:
        telemetry.set_registry(old)


# ------------------------------------------------------- logging promotion

def test_get_logger_namespace_and_transformer_alias():
    import logging

    import apex_tpu
    from apex_tpu.transformer.log_util import (get_transformer_logger,
                                               set_logging_level)

    assert apex_tpu.get_logger("amp").name == "apex_tpu.amp"
    assert apex_tpu.get_logger().name == "apex_tpu"
    # the transformer helpers are thin aliases over the same namespace
    assert get_transformer_logger("x").name == "apex_tpu.transformer.x"
    set_logging_level(logging.DEBUG)
    assert logging.getLogger("apex_tpu.transformer").level == logging.DEBUG
    set_logging_level(logging.WARNING)
