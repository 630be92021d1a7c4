"""apex_tpu.utils.chip and the guards that keep a device record honest:
one peak table, one compile-cache placement, kernel counting from HLO,
bench.py's exit code and child-process legs, the fleet's refusal to
spawn workers that could never open a chip — plus the CPU rehearsal of
chip_smoke.py's phases at a tiny size (slow tier)."""

import os
import sys

import jax
import pytest

from apex_tpu.utils import chip

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                    os.pardir, os.pardir))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def test_peak_table_knows_v5e_and_refuses_unknown_kinds():
    assert chip.peak("TPU v5 lite") == 197e12
    assert chip.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(ValueError, match="no published .* 'cpu'"):
        chip.peak("cpu")
    with pytest.raises(ValueError, match="TPU v9 imaginary"):
        chip.peak("TPU v9 imaginary", "hbm_bytes_per_s")
    # the benches keep no table of their own
    for bench in ("bench.py", "bench_kernels.py"):
        with open(os.path.join(ROOT, bench)) as f:
            src = f.read()
        assert "394e12" not in src and "197" not in src
        assert "chip.peak(" in src or "import peak" in src


def test_kernel_calls_counts_named_mosaic_calls_only():
    hlo = "\n".join([
        '  %a.1 = bf16[8] custom-call(%x), custom_call_target="tpu_custom'
        '_call", metadata={op_name="jit(f)/jvp(M)/blk/flash_attention_fwd'
        '/pallas_call" stack_frame_id=1}, backend_config={}',
        '  %a.2 = bf16[8] custom-call(%x), custom_call_target="tpu_custom'
        '_call", metadata={op_name="jit(f)/transpose(jvp(xentropy_bwd))'
        '/pallas_call"}',
        '  %a.3 = bf16[8] custom-call(%x), custom_call_target="tpu_custom'
        '_call", metadata={op_name="jit(f)/blk2/flash_attention_fwd'
        '/pallas_call"}',
        '  %b = f32[8] custom-call(%x), custom_call_target="Sharding", '
        'metadata={op_name="jit(f)/layer_norm_fwd/pallas_call"}',
        '  %c = f32[8] add(%x, %x)',
    ])
    assert chip.kernel_calls(hlo) == {"flash_attention_fwd": 2,
                                      "xentropy_bwd": 1}
    assert chip.kernel_calls("") == {}
    assert "flash_attention_fwd x2" in chip.format_kernels(
        chip.kernel_calls(hlo))
    assert chip.format_kernels({}).startswith("none")


def test_compile_cache_is_placed_by_the_environment_or_one_fixed_dir(
        monkeypatch):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert chip.enable_compile_cache() == "/somewhere/else"
    assert updates == []            # set from outside: nothing set in code

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = chip.enable_compile_cache()
    assert path == os.path.join(ROOT, ".jax_compile_cache")
    assert updates == [("jax_compilation_cache_dir", path)]
    # exported, so child processes (fleet workers) share it — and a
    # second call finds it in the environment and sets nothing again
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == path
    assert chip.enable_compile_cache() == path and len(updates) == 1
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_compile_cache/" in f.read().split()


def test_bench_exit_code_sees_every_failed_leg():
    import bench

    row = {"metric": "m", "value": 1.0, "serving": {
        "value": 2.0, "chaos": {"error": "RuntimeError: boom"},
        "speculative": {"skipped": True},
        "host_tier": {"mesh": {"error": "nested"}}}}
    assert bench._failed_legs(row) == [
        "serving.chaos: RuntimeError: boom",
        "serving.host_tier.mesh: nested"]
    assert bench._failed_legs({"serving": {"error": "all of it"}}) == [
        "serving: all of it"]
    assert bench._failed_legs({"value": 1.0, "serving": {"value": 2}}) == []


def test_bench_child_process_legs_do_not_run_next_to_a_chip(monkeypatch,
                                                            capsys):
    import bench

    assert bench._child_leg_refused("process_fleet") is None    # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for leg, fn in (("process_fleet", bench._serving_process_fleet_leg),
                    ("host_tier", bench._serving_host_tier_leg),
                    ("tensor_parallel", bench._serving_tp_leg)):
        out = fn()
        assert out["skipped"] is True and "tpu backend" in out["reason"]
        assert f"{leg} leg not run on the tpu backend" \
            in capsys.readouterr().err


def test_fleet_refuses_workers_that_could_not_open_a_chip(monkeypatch):
    from jax._src import xla_bridge

    from apex_tpu.serving import fleet

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    fleet.check_worker_backend(8)               # CPU workers always start

    monkeypatch.delenv("JAX_PLATFORMS")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert xla_bridge.backends_are_initialized()
    with pytest.raises(RuntimeError, match="already live on tpu"):
        fleet.check_worker_backend(1)
    with pytest.raises(RuntimeError, match="already live on tpu"):
        fleet.FleetController([{"model": {"preset": "tiny"}}])

    monkeypatch.setattr(xla_bridge, "backends_are_initialized",
                        lambda: False)
    monkeypatch.setattr(fleet, "_host_tpu_chips", lambda: 4)
    with pytest.raises(RuntimeError, match="8 workers .* 4 TPU chip"):
        fleet.check_worker_backend(8)       # more workers than chips
    with pytest.raises(RuntimeError, match="not yet given a chip each"):
        fleet.check_worker_backend(2)       # fewer, but none is pinned
    fleet.check_worker_backend(1)               # one worker takes the host
    monkeypatch.setattr(fleet, "_host_tpu_chips", lambda: 0)
    fleet.check_worker_backend(8)               # no chips: CPU workers


# ------------------------------------------ chip_smoke.py, rehearsed tiny
TINY_LM = ["--size", "tiny", "--vocab-size", "512", "--opt-level", "O2"]


@pytest.mark.slow       # ~1 min: two engines + a train step at seq 640
def test_chip_smoke_lm_phase_runs_end_to_end_at_tiny_size():
    """Everything but the kernels' presence holds on the CPU; and the
    kernel gate fires, which is what it is for. The serve geometry is
    chip_smoke's own (512 + 128 = 5 pages of 128): only there do the
    paged and contiguous kernels walk the cache in the same blocks, and
    greedy tokens of a barely-trained model survive nothing less than
    identical arithmetic."""
    import chip_smoke

    failures = chip_smoke.lm_phase(TINY_LM + [
        "--seq-len", "640", "-b", "4", "--iters", "3", "--generate", "128",
        "--gen-prompts", "5", "--gen-slots", "2", "--gen-prompt-len",
        "512"])
    assert failures and all("is not in the compiled program" in f
                            for f in failures), failures
    assert len(failures) == len(chip_smoke.TRAIN_KERNELS) \
        + len(chip_smoke.SERVE_KERNELS)


@pytest.mark.slow
def test_chip_smoke_resnet_phase_runs_end_to_end_at_tiny_size():
    import chip_smoke

    assert chip_smoke.resnet_phase([
        "-a", "resnet18", "-b", "8", "--image-size", "32",
        "--num-classes", "10", "--opt-level", "O2", "--synthetic",
        "--iters", "3"]) == []


@pytest.mark.slow
def test_chip_smoke_parallel_phase_on_four_virtual_devices(eight_devices):
    import chip_smoke

    assert chip_smoke.parallel_phase(TINY_LM + [
        "--seq-len", "64", "-b", "8", "--iters", "4", "--deterministic"],
        chip_smoke.PARALLEL_RTOL) == []
