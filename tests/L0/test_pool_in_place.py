"""The paged KV pool is written in place — the structure of the three
paged programs, pinned from their jaxprs on the CPU.

What the chip pays for is the compiled program, and on the chip that is
read from ``Engine.program_memory()`` (``chip_smoke.py``); the CPU's
buffer assignment says nothing about it (the interpreted Pallas call
carries its operands through a loop). What the CPU CAN pin is the shape
of the program the compiler is handed: the stacked pool
``[layers, num_pages, heads, head_dim, page_len]`` goes through the
forward pass as one value, each layer's write is a scatter into it (the
chunk and the verify program) or the decode kernel's own, its pool
outputs aliased to its pool inputs (the decode program: no scatter of
the pool is left in it), the
kernels take it whole, and nothing of a layer's size or more is sliced,
gathered, stacked, transposed or copied on the way. A program that
slices a layer out and restacks the pool (what the engine did before)
fails every case here.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.transformer_lm import TransformerLM
from apex_tpu.serving import Engine, KVQuantConfig, SpecConfig

pytestmark = pytest.mark.serving

# DRAFT + 1 = 8 query rows: the fewest the verify program's kernel takes
# (below that the paged prefill attention gives way to its reference)
LAYERS, HEADS, SLOTS, PAGE, MAX_LEN, DRAFT = 3, 2, 2, 128, 256, 7
# primitives that MOVE their operand: none may yield a layer of the pool
MOVERS = {"slice", "dynamic_slice", "squeeze", "gather", "concatenate",
          "transpose", "copy", "copy_p", "broadcast_in_dim", "reshape",
          "dynamic_update_slice", "select_n", "convert_element_type"}


@pytest.fixture(scope="module")
def lm_and_params():
    m = TransformerLM(vocab_size=96, hidden=32, num_layers=LAYERS,
                      num_heads=HEADS, max_seq_len=MAX_LEN)
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                    train=False)["params"]
    return m, params


def _engine(lm_and_params, quant):
    m, params = lm_and_params
    return Engine(m, params, slots=SLOTS, max_len=MAX_LEN, chunk_len=PAGE,
                  page_len=PAGE, spec=SpecConfig(draft_len=DRAFT),
                  kv_quant=KVQuantConfig() if quant else None)


def _program(eng, name):
    """(impl, operands after params and cache) of one paged program, at
    the shapes the engine calls it with."""
    f32 = np.zeros(SLOTS, np.float32)
    i32 = np.zeros(SLOTS, np.int32)
    if name == "decode":
        return eng._paged_decode_impl, (i32, i32, eng._page_table,
                                        eng._host_len, f32, f32, eng._key)
    if name == "chunk":
        return eng._paged_chunk_impl, (
            np.zeros((1, eng.chunk_len), np.int32), eng._page_table[:1],
            np.int32(0), np.int32(1), np.float32(0), np.float32(0),
            eng._key)
    return eng._paged_verify_impl, (
        np.zeros((SLOTS, DRAFT + 1), np.int32), eng._page_table,
        eng._host_len, i32, f32)


def _walk(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold
    (jit, cond, loops) — a Pallas kernel's own body excepted: what runs
    inside the kernel works on blocks in VMEM, not on the pool."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk(sub)


def _aliased_operand(e, var):
    """``e`` is the jitted call of a kernel that writes the pool: the
    operand of ``e`` that its output ``var`` is aliased to by the
    ``pallas_call``'s ``input_output_aliases``."""
    inner = e.params["jaxpr"].jaxpr
    (call,) = [k for k in inner.eqns if k.primitive.name == "pallas_call"]
    out = call.outvars.index(inner.outvars[e.outvars.index(var)])
    (src,) = [i for i, o in call.params["input_output_aliases"] if o == out]
    assert call.invars[src].aval.shape == var.aval.shape
    return e.invars[inner.invars.index(call.invars[src])]


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("name", ["decode", "chunk", "verify"])
def test_paged_program_writes_the_pool_in_place(lm_and_params, name, quant):
    eng = _engine(lm_and_params, quant)
    impl, rest = _program(eng, name)
    traces = (eng.decode_traces, eng.chunk_traces, eng.verify_traces)
    closed = jax.make_jaxpr(impl)(eng.params, eng.cache, *rest)
    eng.decode_traces, eng.chunk_traces, eng.verify_traces = traces
    jaxpr = closed.jaxpr
    pool_shape = eng.cache.k.shape
    assert pool_shape == (LAYERS, SLOTS * (MAX_LEN // PAGE) + 1, HEADS,
                          32 // HEADS, PAGE)
    layer_size = int(np.prod(pool_shape[1:]))

    # 1. nothing of a layer's size or more is moved
    moved = [(e.primitive.name, tuple(o.aval.shape))
             for e in _walk(jaxpr) if e.primitive.name in MOVERS
             for o in e.outvars
             if int(np.prod(o.aval.shape)) >= layer_size]
    assert not moved, f"{name}: pool-sized values are moved: {moved}"

    # 2. the kernels are handed the pool itself, one call per layer
    kernels = [e for e in _walk(jaxpr) if e.primitive.name == "pallas_call"]
    assert len(kernels) == LAYERS
    for e in kernels:
        pools = [v for v in e.invars
                 if tuple(v.aval.shape) == pool_shape]
        assert len(pools) == 2, f"{name}: a kernel reads {e.invars}"

    # 3. each pool output is a chain of writes rooted at its input:
    # scatters in the chunk and the verify program; in the decode
    # program the kernels themselves, each pool output aliased to its
    # pool input, and no scatter of the pool at all
    n_params = len(jax.tree.leaves(eng.params))
    producer = {o: e for e in jaxpr.eqns for o in e.outvars}
    writes = {"decode": 1, "chunk": 1, "verify": DRAFT + 1}[name]
    link = "jit" if name == "decode" else "scatter"
    for which in (0, 1):                        # cache leaves: k, v, ...
        var, steps = jaxpr.outvars[which], 0
        while var in producer:
            e = producer[var]
            assert e.primitive.name == link, \
                f"{name}: the pool passes through {e.primitive.name}"
            var = (_aliased_operand(e, var) if name == "decode"
                   else e.invars[0])
            steps += 1
        assert var is jaxpr.invars[n_params + which], \
            f"{name}: pool output {which} is not rooted at its input"
        assert steps == LAYERS * writes
    if name == "decode":
        scattered = [e for e in _walk(jaxpr)
                     if e.primitive.name.startswith("scatter")
                     and tuple(e.outvars[0].aval.shape) == pool_shape]
        assert not scattered, f"decode still scatters the pool: {scattered}"


def test_program_memory_keys_and_gauges(lm_and_params):
    from apex_tpu import telemetry

    m, params = lm_and_params
    reg = telemetry.MetricsRegistry()
    eng = Engine(m, params, slots=SLOTS, max_len=MAX_LEN, chunk_len=PAGE,
                 page_len=PAGE, registry=reg)
    before = eng.compiled_programs
    mem = eng.program_memory()
    assert eng.compiled_programs == before      # counters restored
    assert set(mem) == {"decode", "chunk"}
    for prog in mem.values():
        assert set(prog) == {"argument_bytes", "alias_bytes", "temp_bytes"}
        assert all(isinstance(v, int) and v >= 0 for v in prog.values())
        # the donated pool is an argument
        assert prog["argument_bytes"] >= eng.cache.nbytes()
    gauges = reg.snapshot()["gauges"]
    assert gauges["serving.kv.pool_bytes"] == eng.cache.nbytes()
    assert gauges["serving.kv.decode_temp_bytes"] == \
        mem["decode"]["temp_bytes"]
    assert gauges["serving.kv.chunk_temp_bytes"] == \
        mem["chunk"]["temp_bytes"]
    assert set(eng.program_kernels()) == {"decode", "chunk"}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_pool_writes_land_where_the_page_table_says(dtype):
    """The two in-place writes against plain numpy indexing: a token per
    row at (layer, page, :, :, offset), whole pages at (layer, page)."""
    from apex_tpu.models.transformer_lm import (_pool_write_pages,
                                                _pool_write_tokens)

    rng = np.random.default_rng(3)
    shape = (3, 7, 2, 8, 16)                 # [layers, pages, h, d, pl]
    draw = (lambda s: rng.integers(-127, 128, size=s)) \
        if dtype == jnp.int8 else (lambda s: rng.normal(size=s))
    pool = jnp.asarray(draw(shape), dtype)
    # rows 0 and 2 live on pages of their own, rows 1 and 3 are dead
    # slots that both name the sentinel page 0
    page_ids = jnp.asarray([4, 0, 6, 0], jnp.int32)
    off = jnp.asarray([0, 5, 15, 9], jnp.int32)
    new = jnp.asarray(draw((4, 2, 8)), dtype)
    got = np.asarray(_pool_write_tokens(pool, 1, page_ids, off, new),
                     np.float32)
    want = np.asarray(pool, np.float32).copy()
    for b in (0, 2):
        want[1, int(page_ids[b]), :, :, int(off[b])] = \
            np.asarray(new[b], np.float32)
    live = np.ones(7, bool)
    live[0] = False                          # nothing reads the sentinel
    assert (got[:, live] == want[:, live]).all()
    assert (got[[0, 2]] == want[[0, 2]]).all()      # other layers whole

    chunk = jnp.asarray(draw((1, 2, 32, 8)), dtype)  # [B, h, 2 pages, d]
    got = np.asarray(_pool_write_pages(pool, 2, jnp.asarray([[5, 3]]),
                                       chunk), np.float32)
    want = np.asarray(pool, np.float32).copy()
    c = np.asarray(chunk, np.float32)[0]             # [h, 32, d]
    want[2, 5] = c[:, :16].transpose(0, 2, 1)
    want[2, 3] = c[:, 16:].transpose(0, 2, 1)
    assert (got == want).all()
