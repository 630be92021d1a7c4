"""The chip rehearsal, kept as tests: compile the main path's kernels
for a DESCRIBED TPU v5e (no chip attached) at GPT-2 shapes and assert
each really is a Mosaic kernel (``tpu_custom_call``) in the compiled
program.

Interpret mode — what every other kernel test here runs — cannot see
what the TPU compiler refuses: a block whose last two dims neither tile
(8, 128) nor equal the array's (how ``paged_decode_attention`` was
refused at every shape until PR 21), or a working set past scoped VMEM.
The TPU compiler is installed with jaxlib's libtpu and compiles for a
topology that is described, not attached, at about two seconds a kernel.

Steering is done HERE, not by an option of the program: the kernels ask
``jax.default_backend()`` (cpu -> interpret) and read their tuned blocks
by ``jax.devices()[0].device_kind``, so the fixture answers "tpu" and
loads the packaged v5e blocks for the duration of a case. A compile
that passes is not a chip run (``chip_smoke.py`` is).
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from apex_tpu.kernels import vmem
from apex_tpu.utils.chip import kernel_calls

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                    os.pardir, os.pardir))
BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    """Sharding on one device of a described v5e 2x2 host; skips where
    this installation cannot describe the topology."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def as_v5e(monkeypatch):
    """Kernel dispatch takes its chip branch with the packaged v5e
    blocks; the persistent compile cache is off (a described-device
    compile is written to it but cannot be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    saved, loaded = vmem.overrides(), vmem._auto_load_done
    vmem._auto_load_done = True
    vmem.load_overrides(vmem.packaged_path("TPU v5 lite"))
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
        vmem.clear_overrides()
        for k, v in saved.items():
            vmem.set_override(k, v)
        vmem._auto_load_done = loaded


# GPT-2 small: 12 heads x 64; the 16 x 128 cases are the next width up
# (the "medium" preset's heads at a 128-lane head_dim).
B, S, H, V = 8, 1024, 768, 50304
PAGE, MAX_PAGES = 128, 8


def _flash():
    from apex_tpu.kernels.flash_attention import flash_attention

    def fn(q, k, v):
        return jax.grad(lambda *a: flash_attention(
            *a, causal=True).astype(F32).sum(), argnums=(0, 1, 2))(q, k, v)
    return fn, [((B, 12, S, 64), BF16)] * 3


def _layer_norm():
    from apex_tpu.kernels.layer_norm import layer_norm

    def fn(x, g, b):
        return jax.grad(lambda *a: layer_norm(*a).astype(F32).sum(),
                        argnums=(0, 1, 2))(x, g, b)
    return fn, [((B * S, H), BF16), ((H,), F32), ((H,), F32)]


def _xentropy():
    from apex_tpu.kernels.xentropy import softmax_cross_entropy_loss

    def fn(logits, labels):
        return jax.grad(lambda lg: softmax_cross_entropy_loss(
            lg, labels).mean())(logits)
    return fn, [((B, S, V), F32), ((B, S), I32)]


def _adam():
    from apex_tpu.kernels.multi_tensor import fused_adam_step

    def fn(p, g, m, v):
        return fused_adam_step(p, g, m, v, lr=1e-3, beta1=0.9,
                               beta2=0.999, eps=1e-8, weight_decay=0.01,
                               step=1)
    return fn, [((124_475_904,), F32)] * 4      # GPT-2 small, flat


def _scales(heads, kv_dtype):
    return [((heads,), F32)] * 2 if kv_dtype == I8 else [None, None]


def _pool(pages, heads, d, stacked):
    """One layer's pool, or (``stacked``) three layers of it as the
    serving engine holds them, the middle one read."""
    if stacked:
        return (3, pages, heads, d, PAGE), 1
    return (pages, heads, PAGE, d), None


def _paged_decode(heads, d, kv_dtype, stacked=False, kv_heads=None):
    from apex_tpu.kernels.decode_attention import paged_decode_attention

    pool, layer = _pool(B * MAX_PAGES + 1, kv_heads or heads, d, stacked)

    def fn(q, k, v, table, lengths, ks, vs):
        return paged_decode_attention(q, k, v, table, lengths,
                                      k_scale=ks, v_scale=vs, layer=layer)
    return fn, [((B, heads, d), BF16), (pool, kv_dtype), (pool, kv_dtype),
                ((B, MAX_PAGES), I32), ((B,), I32),
                *_scales(heads, kv_dtype)]


def _prefill(heads, d, kv_dtype, chunk=256):
    from apex_tpu.kernels.prefill_attention import prefill_attention

    def fn(q, k, v, offsets, ks, vs):
        return prefill_attention(q, k, v, offsets, k_scale=ks, v_scale=vs)
    L = PAGE * MAX_PAGES
    return fn, [((1, heads, chunk, d), BF16), ((1, heads, L, d), kv_dtype),
                ((1, heads, L, d), kv_dtype), ((1,), I32),
                *_scales(heads, kv_dtype)]


def _paged_prefill(heads, d, kv_dtype, stacked=False, chunk=256,
                   kv_heads=None):
    from apex_tpu.kernels.prefill_attention import paged_prefill_attention

    pool, layer = _pool(MAX_PAGES + 1, kv_heads or heads, d, stacked)

    def fn(q, k, v, table, offsets, ks, vs):
        return paged_prefill_attention(q, k, v, table, offsets,
                                       k_scale=ks, v_scale=vs, layer=layer)
    return fn, [((1, heads, chunk, d), BF16), (pool, kv_dtype),
                (pool, kv_dtype), ((1, MAX_PAGES), I32), ((1,), I32),
                *_scales(heads, kv_dtype)]


def _grouped_gemm(tokens, groups=16, k=2048, n=4096):
    """One call of the expert layer's product: by default at ZAYA1-8B's
    widths, 16 experts, gate and up fused (2048 -> 4096)."""
    from apex_tpu.kernels.grouped_gemm import grouped_gemm

    return grouped_gemm, [((tokens, k), BF16), ((groups, k, n), BF16),
                          ((groups,), I32), ((groups,), I32)]


# Qwen3-Next-80B-A3B's linear layers as its cell holds them: 9 of them,
# 192 slots, 32 value heads of a 128 x 128 float32 matrix
GDN_STATE = (9, 192, 32, 128, 128)


def _gdn_step():
    from apex_tpu.kernels.gated_delta import gated_delta_step

    def fn(state, q, k, v, g, beta, active):
        return gated_delta_step(state, 4, q, k, v, g, beta, active)
    rows = (192, 32, 128)
    return fn, [(GDN_STATE, F32), (rows, F32), (rows, F32), (rows, F32),
                ((192, 32), F32), ((192, 32), F32), ((192,), jnp.bool_)]


def _gdn_chunk():
    from apex_tpu.kernels.gated_delta import gated_delta_chunk

    def fn(state, slot, fresh, q, k, v, g, beta):
        return gated_delta_chunk(state, 4, slot, fresh, q, k, v, g, beta)
    seq = (256, 32, 128)
    return fn, [(GDN_STATE, F32), ((), I32), ((), jnp.bool_), (seq, F32),
                (seq, F32), (seq, F32), ((256, 32), F32), ((256, 32), F32)]


CASES = {
    "paged_decode_grouped_8q2kv_x128": (
        _paged_decode, (8, 128, BF16, True, 2), ["paged_decode_attention"]),
    "paged_prefill_grouped_8q2kv_x128": (
        _paged_prefill, (8, 128, BF16, True, 256, 2),
        ["paged_prefill_attention"]),
    "paged_decode_grouped_16q2kv_x256": (
        _paged_decode, (16, 256, BF16, True, 2), ["paged_decode_attention"]),
    "paged_prefill_grouped_16q2kv_x256": (
        _paged_prefill, (16, 256, BF16, True, 256, 2),
        ["paged_prefill_attention"]),
    "gated_delta_step_192x32": (_gdn_step, (), ["gated_delta_step"]),
    "gated_delta_chunk_256x32": (_gdn_chunk, (), ["gated_delta_chunk"]),
    # a block of the top-10 layer's rows over 64 held experts of 512
    "grouped_gemm_64x_gate_up": (_grouped_gemm, (512, 64, 2048, 1024),
                                 ["moe_grouped_gemm"]),
    "grouped_gemm_64x_down": (_grouped_gemm, (512, 64, 512, 2048),
                              ["moe_grouped_gemm"]),
    "grouped_gemm_decode_96": (_grouped_gemm, (96,), ["moe_grouped_gemm"]),
    "grouped_gemm_chunk_256": (_grouped_gemm, (256,), ["moe_grouped_gemm"]),
    "flash_fwd_bwd": (_flash, (), ["flash_attention_fwd",
                                   "flash_attention_bwd_dq",
                                   "flash_attention_bwd_dkv"]),
    "layer_norm_fwd_bwd": (_layer_norm, (), ["layer_norm_fwd",
                                             "layer_norm_bwd"]),
    "xentropy_padded_vocab": (_xentropy, (), ["xentropy_fwd",
                                              "xentropy_bwd"]),
    "flat_fused_adam": (_adam, (), ["multi_tensor_adam"]),
    "paged_decode_bf16_12x64": (_paged_decode, (12, 64, BF16),
                                ["paged_decode_attention"]),
    "paged_decode_int8_12x64": (_paged_decode, (12, 64, I8),
                                ["paged_decode_attention"]),
    "paged_decode_bf16_16x128": (_paged_decode, (16, 128, BF16),
                                 ["paged_decode_attention"]),
    "paged_decode_int8_16x128": (_paged_decode, (16, 128, I8),
                                 ["paged_decode_attention"]),
    "paged_decode_stacked_bf16": (_paged_decode, (12, 64, BF16, True),
                                  ["paged_decode_attention"]),
    "paged_decode_stacked_int8": (_paged_decode, (12, 64, I8, True),
                                  ["paged_decode_attention"]),
    "paged_prefill_stacked_bf16": (_paged_prefill, (12, 64, BF16, True),
                                   ["paged_prefill_attention"]),
    "paged_prefill_stacked_int8": (_paged_prefill, (12, 64, I8, True),
                                   ["paged_prefill_attention"]),
    "prefill_bf16": (_prefill, (12, 64, BF16), ["prefill_attention"]),
    "paged_prefill_bf16": (_paged_prefill, (12, 64, BF16),
                           ["paged_prefill_attention"]),
    "paged_prefill_int8": (_paged_prefill, (12, 64, I8),
                           ["paged_prefill_attention"]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e_and_stays_a_kernel(case, one_chip, as_v5e):
    build, build_args, want = CASES[case]
    fn, shapes = build(*build_args)
    args = [None if s is None
            else jax.ShapeDtypeStruct(s[0], s[1], sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()   # raises what the chip would
    have = kernel_calls(compiled.as_text())
    assert all(have.get(k) for k in want), \
        f"{case}: expected Mosaic kernels {want}, the program holds {have}"


# The serving configurations of the benchmark, as their engines call
# the kernel: (rows, query heads, K/V heads, head_dim, table pages,
# layers, pool pages).
CELL_GEOMETRY = {
    "gpt2_large_24x1024": (24, 20, 20, 64, 8, 36, 193),
    "zaya1_8b_96x2048": (96, 8, 2, 128, 16, 20, 1537),
    "qwen3next_192x2048": (192, 16, 2, 256, 16, 3, 3073),
}


@pytest.mark.parametrize("write", [False, True], ids=["read", "write"])
@pytest.mark.parametrize("cell", sorted(CELL_GEOMETRY))
def test_paged_decode_at_a_cells_geometry_fits_its_vmem(cell, write,
                                                        one_chip, as_v5e):
    """The whole stacked pool of a benchmark cell, one middle layer
    read: one kernel, its pages a step read off the page's bytes, and
    the working set it states under the scoped limit it asks for (which
    the compile above all accepts). ``write``: the decode program's
    call, handed the rows' new K/V and the donated pools - still the one
    kernel, the pools its outputs aliased to its inputs, and no other
    instruction of the program yields anything pool-shaped: no copy, no
    select, no scatter."""
    import re

    from apex_tpu.kernels import decode_attention as da
    rows, h, h_kv, d, table, layers, pool = CELL_GEOMETRY[cell]
    shapes = [((rows, h, d), BF16), ((layers, pool, h_kv, d, PAGE), BF16),
              ((layers, pool, h_kv, d, PAGE), BF16), ((rows, table), I32),
              ((rows,), I32)]
    if write:
        shapes += [((rows, h_kv, d), BF16)] * 2
    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip)
            for s, t in shapes]

    def fn(q, k, v, pt, lengths, new_k=None, new_v=None):
        return da.paged_decode_attention(q, k, v, pt, lengths, new_k=new_k,
                                         new_v=new_v, layer=layers // 2)
    compiled = jax.jit(fn, donate_argnums=(1, 2) if write else ()).lower(
        *args).compile()
    text = compiled.as_text()
    assert kernel_calls(text) == {"paged_decode_attention": 1}
    page_bytes = h_kv * d * PAGE * 2
    pages = da._pages_per_step(page_bytes, table)
    step_bytes = vmem.overrides()["decode.paged_step_bytes"]
    assert 1 <= pages <= table
    assert pages == 1 or pages * page_bytes <= step_bytes
    working, limit = da._paged_decode_vmem(args[1], args[0], pages, write)
    assert 4 * pages * page_bytes < working < limit <= 64 * 2 ** 20, \
        (working, limit)
    # nothing pool-sized beside the pool: the kernel reads it where it is
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 8 * 2 ** 20
    makes_a_pool = set(re.findall(
        rf"= bf16\[{layers},{pool},{h_kv},{d},{PAGE}\]\S* ([\w-]+)\(", text))
    if write:
        # and writes it where it is: both pools go out as they came in
        assert mem.alias_size_in_bytes == 2 * layers * pool * page_bytes
        assert makes_a_pool == {"parameter", "get-tuple-element"}
    else:
        assert makes_a_pool == {"parameter"}


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_qwen3next_programs_at_the_cells_geometry(program, one_chip, as_v5e):
    """The decode and the chunk program of the ``qwen3_next`` cell as the
    engine's stateful bodies call the model - the donated cache with the
    page pool and both state blocks in, the same buffers out - at the
    cell's 192 slots x 2,048 positions and published widths, one period
    of layers (3 linear + 1 full) of its three. Every kernel is there
    once a layer that has it, the cache is aliased whole and the
    temporaries stay a small fraction of the 1.2 GB recurrent block: no
    select over it, no copy of it."""
    from apex_tpu.models import Qwen3NextLM
    from apex_tpu.serving.kv_cache import (CacheSpec, PagedKVCache, SlotAddr,
                                           SlotState)

    slots, max_pages, chunk = 192, 16, 256
    m = Qwen3NextLM(num_layers=4, experts_held=tuple(range(64)),
                    dtype=BF16, inference_dtype=BF16, param_dtype=BF16)
    spec = CacheSpec.of(m)
    sd = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=one_chip)  # noqa: E731,E501
    params = jax.tree_util.tree_map(
        lambda a: sd(a.shape, BF16),
        jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0),
                                      jnp.zeros((1, 8), I32),
                                      train=False))["params"])
    pool = sd((spec.page_layers, slots * max_pages + 1, spec.kv_heads,
               spec.head_dim, PAGE), BF16)
    cache = PagedKVCache(k=pool, v=pool, state=SlotState(
        blocks={b.name: sd((b.layers, slots) + b.shape, b.dtype or BF16)
                for b in spec.state},
        expert_tokens=sd((spec.counter_layers, spec.num_experts), I32)))

    def run(params, cache, tokens, addr, pt, **kw):
        st = cache.state
        logits, (k, v, blocks, counts) = m.apply(
            {"params": params}, tokens, train=False, state=st.blocks,
            addr=addr, cache=(cache.k, cache.v, pt), **kw)
        return cache.replace(k=k, v=v, state=st.replace(
            blocks=blocks, expert_tokens=st.expert_tokens + counts)), \
            jnp.argmax(logits[:, 0], -1)

    if program == "decode":
        def fn(params, cache, last, pt, lengths, active):
            return run(params, cache, last[:, None], SlotAddr(active=active),
                       pt, positions=lengths, valid=active[:, None])
        args = [sd((slots,), I32), sd((slots, max_pages), I32),
                sd((slots,), I32), sd((slots,), jnp.bool_)]
        want = {"gated_delta_step": 3, "paged_decode_attention": 1,
                "moe_grouped_gemm": 8}
    else:
        def fn(params, cache, tokens, pt, offset, n_valid, slot):
            return run(params, cache, tokens,
                       SlotAddr(slot=slot, fresh=offset == 0), pt,
                       positions=offset[None], n_valid=n_valid[None])
        args = [sd((1, chunk), I32), sd((1, max_pages), I32), sd((), I32),
                sd((), I32), sd((), I32)]
        want = {"gated_delta_chunk": 3, "paged_prefill_attention": 1,
                "moe_grouped_gemm": 8}
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args).compile()
    assert kernel_calls(compiled.as_text()) == want
    mem = compiled.memory_analysis()
    held = sum(int(jnp.prod(jnp.asarray(a.shape))) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(cache))
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < 200 * 2 ** 20, mem.temp_size_in_bytes


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_ling3_programs_at_the_cells_geometry(program, one_chip, as_v5e):
    """The decode and the chunk program of the ``ling_v3`` cell as the
    engine's stateful bodies call the model, at the cell's 32 slots x
    33,792 positions, 1,024-row chunks and published widths, its whole
    six layers (2 dense + 4 expert MLPs, 5 Kimi delta layers + 1 latent):
    every kernel is there once a layer that has it - the latent layer's
    under their own names, with ONE pool and a V of zero heads - the cache
    is aliased whole, and the temporaries stay far under the 1.25 GB pool
    and the 0.34 GB recurrent block: no select over, no copy of either,
    and no expansion of the latents."""
    from apex_tpu.models import LingLM
    from apex_tpu.serving.kv_cache import (CacheSpec, PagedKVCache, SlotAddr,
                                           SlotState)

    slots, max_pages, chunk = 32, 264, 1024
    m = LingLM(num_layers=6, experts_held=tuple(range(128)),
               dtype=BF16, inference_dtype=BF16, param_dtype=BF16)
    spec = CacheSpec.of(m)
    assert (spec.kv_heads, spec.head_dim, spec.value_dim) == (1, 576, 512)
    sd = lambda s, t: jax.ShapeDtypeStruct(s, t, sharding=one_chip)  # noqa: E731,E501
    params = jax.tree_util.tree_map(
        lambda a: sd(a.shape, BF16),
        jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0),
                                      jnp.zeros((1, 8), I32),
                                      train=False))["params"])
    pages = slots * max_pages + 1
    cache = PagedKVCache(
        k=sd((spec.page_layers, pages, 1, spec.head_dim, PAGE), BF16),
        v=sd((spec.page_layers, pages, 0, spec.head_dim, PAGE), BF16),
        state=SlotState(
            blocks={b.name: sd((b.layers, slots) + b.shape, b.dtype or BF16)
                    for b in spec.state},
            expert_tokens=sd((spec.counter_layers, spec.num_experts), I32)))

    def run(params, cache, tokens, addr, pt, **kw):
        st = cache.state
        logits, (k, v, blocks, counts) = m.apply(
            {"params": params}, tokens, train=False, state=st.blocks,
            addr=addr, cache=(cache.k, cache.v, pt), **kw)
        return cache.replace(k=k, v=v, state=st.replace(
            blocks=blocks, expert_tokens=st.expert_tokens + counts)), \
            jnp.argmax(logits[:, 0], -1)

    if program == "decode":
        def fn(params, cache, last, pt, lengths, active):
            return run(params, cache, last[:, None], SlotAddr(active=active),
                       pt, positions=lengths, valid=active[:, None])
        args = [sd((slots,), I32), sd((slots, max_pages), I32),
                sd((slots,), I32), sd((slots,), jnp.bool_)]
        want = {"kda_step": 5, "mla_decode_attention": 1,
                "moe_grouped_gemm": 8}
    else:
        def fn(params, cache, tokens, pt, offset, n_valid, slot):
            return run(params, cache, tokens,
                       SlotAddr(slot=slot, fresh=offset == 0), pt,
                       positions=offset[None], n_valid=n_valid[None])
        args = [sd((1, chunk), I32), sd((1, max_pages), I32), sd((), I32),
                sd((), I32), sd((), I32)]
        want = {"kda_chunk": 5, "mla_prefill_attention": 1,
                "moe_grouped_gemm": 8}
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args).compile()
    assert kernel_calls(compiled.as_text()) == want
    mem = compiled.memory_analysis()
    held = sum(int(jnp.prod(jnp.asarray(a.shape))) * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(cache))
    assert held > 1.5e9
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < 600 * 2 ** 20, mem.temp_size_in_bytes
    print(f"ling3 {program}: arguments {mem.argument_size_in_bytes}, "
          f"aliased {mem.alias_size_in_bytes}, temporaries "
          f"{mem.temp_size_in_bytes}")


def test_xentropy_at_unpadded_gpt2_vocab_takes_the_reference(one_chip,
                                                            as_v5e):
    """The gate chip_smoke's per-kernel check exists for: at 50257 (not
    a multiple of 128) the fused loss compiles — to ``xent_reference``,
    with no kernel in the program."""
    fn, shapes = _xentropy()
    shapes[0] = ((B, S, 50257), F32)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    assert kernel_calls(jax.jit(fn).lower(*args).compile().as_text()) == {}


def test_chip_smoke_refuses_the_cpu_without_compiling(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_LOG_COMPILES="1",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        capture_output=True, text=True, env=env, cwd=str(tmp_path),
        timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no accelerator" in proc.stderr
    assert "Compiling" not in proc.stderr
    assert not os.path.exists(tmp_path / "cache")


def test_ensure_devices_raises_on_a_short_accelerator(monkeypatch):
    """A TPU backend with fewer chips than asked must raise, naming
    both numbers — never carry on with virtual CPU devices."""
    import jax.extend.backend as backend

    from apex_tpu import comm

    class FakeChip:
        platform, device_kind = "tpu", "TPU v5 lite"

    def no_switch():
        raise AssertionError("ensure_devices switched backends")

    chips = [FakeChip()]
    monkeypatch.setattr(comm.jax, "devices", lambda: chips)
    monkeypatch.setattr(backend, "clear_backends", no_switch)
    with pytest.raises(RuntimeError, match=r"tpu backend has 1 device"
                       r".*TPU v5 lite.*4 were asked"):
        comm.ensure_devices(4)
    assert comm.ensure_devices(1) is chips
