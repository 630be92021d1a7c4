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


def test_fleet_refuses_workers_that_could_not_open_a_chip(monkeypatch):
    from jax._src import xla_bridge

    from apex_tpu.serving import fleet

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    fleet.check_worker_backend(8)               # CPU workers always start

    monkeypatch.delenv("JAX_PLATFORMS")
    jax.devices()           # the state under test: a backend is live
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
@pytest.mark.parametrize("tamper", [False, True],
                         ids=["untouched", "one_token_replaced"])
def test_chip_smoke_holds_served_tokens_to_the_plain_forward(tamper):
    """The serve check's rule at tiny size: every served token's logit
    within ``SERVED_LOGIT_GAP`` of its position's best in the model's
    plain forward over prompt + output. Served tokens pass; one of them
    replaced by that position's WORST token fails, and the failure names
    the request."""
    import jax.numpy as jnp
    import numpy as np

    import chip_smoke
    from apex_tpu import serving
    from apex_tpu.amp.policy import resolve_policy
    from apex_tpu.models.transformer_lm import TransformerLM

    m = TransformerLM(vocab_size=101, hidden=32, num_layers=2, num_heads=4,
                      max_seq_len=64)
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                    train=False)["params"]
    eng = serving.Engine(m, params, slots=2, max_len=64, prefill_len=24,
                         chunk_len=8,
                         policy=resolve_policy("O0", verbose=False))
    rng = np.random.default_rng(0)
    reqs = serving.Scheduler(eng).run(
        [serving.Request(prompt=rng.integers(1, 101, n).tolist(),
                         max_new_tokens=6) for n in (5, 8, 19)])
    assert chip_smoke.served_token_failures(m, params, reqs) == []
    if tamper:
        victim = reqs[1]
        seq = jnp.asarray([list(victim.prompt) + victim.output_tokens[:3]])
        worst = int(jnp.argmin(m.apply({"params": params}, seq,
                                       train=False)[0, -1]))
        victim.output_tokens[3] = worst
        (failure,) = chip_smoke.served_token_failures(m, params, reqs)
        assert f"request {victim.uid} token 3" in failure
        assert "below its position's best logit" in failure


TINY_LM = ["--size", "tiny", "--vocab-size", "512", "--opt-level", "O2"]


@pytest.mark.slow       # ~1 min: an engine + a train step at seq 640
def test_chip_smoke_lm_phase_runs_end_to_end_at_tiny_size():
    """Everything but the kernels' presence holds on the CPU; and the
    kernel gate fires, which is what it is for. The serve geometry is
    chip_smoke's own (512 + 128 = 5 pages of 128)."""
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
