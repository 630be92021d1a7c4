"""Kernel microbenchmarks: Pallas kernels vs their jnp/XLA compositions on
the SAME backend, at LM-production shapes (VERDICT round-1 item 1b).

Every fused kernel family gets a measured same-device speedup (or a
documented "XLA wins, fallback kept" verdict) — the evidence tier backing
the SURVEY N2/N4/N8/N10/N11 kernel list. Results are recorded in
BASELINE.md. Run on the real chip:

    python bench_kernels.py            # all suites
    python bench_kernels.py flash ln   # a subset

Prints one JSON line per row:
  {"bench": ..., "shape": ..., "pallas_ms": ..., "xla_ms": ...,
   "speedup": ...}
Times are device-lane occupancy from a profiler capture (see ``timeit``),
so they mean something only on the chip.
"""

from __future__ import annotations

import functools
import json
import sys
import time

import jax
import jax.numpy as jnp


def timeit(fn, *args, warmup=2, steps=10, donate=()):
    """Per-step DEVICE time of a jitted callable, ms.

    ``donate``: the numbers of the arguments the callable updates in
    place. They are donated, and each call's are the LAST ``len(donate)``
    results of the call before (a program that writes a buffer it is
    handed is timed without the copy an undonated buffer costs).

    Anchored on the profiler's device-lane occupancy
    (pyprof.device_busy busy_ms / steps): host wall clock around a
    microkernel times dispatch, not silicon. Occupancy rather than span
    because microkernel steps are far shorter than the host's enqueue
    latency: the device sits idle between iterations, and that idle is
    the host's fault, not the kernel's. Falls back to median wall time
    on host-only backends."""
    import tempfile

    from apex_tpu import pyprof

    fn = jax.jit(fn, donate_argnums=tuple(donate))
    args = list(args)

    def call():
        out = fn(*args)
        if donate:
            for n, new in zip(donate, out[len(out) - len(donate):]):
                args[n] = new
        return out
    for _ in range(warmup):
        out = call()
    jax.block_until_ready(out)
    with tempfile.TemporaryDirectory() as td:
        with pyprof.trace(td):
            for _ in range(steps):
                out = call()
            jax.block_until_ready(out)
        try:
            d = pyprof.device_busy(td)
        except FileNotFoundError:
            d = {"busy_ms": 0.0}
    if d["busy_ms"] > 0:
        return d["busy_ms"] / steps
    times = []
    for _ in range(steps):
        t0 = time.perf_counter()
        out = call()
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) * 1e3)
    times.sort()
    return times[len(times) // 2]


def _device_peaks():
    """(HBM GB/s, bf16 TFLOP/s) of the attached chip, from the one
    table keyed by ``device_kind``; an unknown kind raises."""
    from apex_tpu.utils.chip import peak

    kind = jax.devices()[0].device_kind
    return (peak(kind, "hbm_bytes_per_s") / 1e9,
            peak(kind, "bf16_flops") / 1e12)


def row(bench, shape, pallas_ms, xla_ms, gbytes=None, gflops=None):
    """One result row, self-describing about plausibility: if the measured
    time implies bandwidth/compute beyond the chip's physical limits the
    clock did not time the device and the speedup column is NOT
    meaningful.

    ``roofline_ms`` is the analytic floor on the attached chip —
    max(bytes / HBM bandwidth, flops / bf16 peak)
    (``pct_of_roofline`` = roofline/measured; 100 = at the roofline,
    >120 = the clock is non-physical, same condition as ``implausible``)."""
    HBM_GBPS, PEAK_TFLOPS = _device_peaks()
    out = {
        "bench": bench, "shape": shape,
        "pallas_ms": round(pallas_ms, 3), "xla_ms": round(xla_ms, 3),
        "speedup": round(xla_ms / pallas_ms, 2),
    }
    implausible = False
    roofline_s = 0.0
    if gbytes is not None:
        bw = gbytes / (pallas_ms / 1e3)
        out["implied_gbps"] = round(bw, 1)
        roofline_s = max(roofline_s, gbytes / HBM_GBPS)
        implausible |= bw > 1.2 * HBM_GBPS
    if gflops is not None:
        tf = gflops / 1e3 / (pallas_ms / 1e3)
        out["implied_tflops"] = round(tf, 1)
        roofline_s = max(roofline_s, gflops / 1e3 / PEAK_TFLOPS)
        implausible |= tf > 1.2 * PEAK_TFLOPS
    if roofline_s > 0.0:
        out["roofline_ms"] = round(roofline_s * 1e3, 3)
        out["pct_of_roofline"] = round(100.0 * roofline_s * 1e3 / pallas_ms,
                                       1)
    out["implausible"] = bool(implausible)
    print(json.dumps(out), flush=True)


# ------------------------------------------------------------------ flash
def bench_flash():
    from apex_tpu.kernels.flash_attention import flash_attention, \
        mha_reference

    for b, h, s, d in ((8, 8, 2048, 128), (2, 8, 8192, 128)):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (b, h, s, d), jnp.bfloat16)
                   for kk in ks)

        def fwd_k(q, k, v):
            return flash_attention(q, k, v, causal=True)

        def fwd_x(q, k, v):
            return mha_reference(q, k, v, causal=True, scale=d ** -0.5)

        # causal fwd: 2 matmuls x 2*b*h*s^2*d flops, halved by tile skip
        gf = 2 * 2 * b * h * s * s * d / 2 / 1e9
        row("flash_fwd_causal", f"b{b} h{h} s{s} d{d}",
            timeit(fwd_k, q, k, v), timeit(fwd_x, q, k, v), gflops=gf)

        def bwd_k(q, k, v):
            return jax.grad(
                lambda q, k, v: jnp.sum(
                    flash_attention(q, k, v, causal=True)
                    .astype(jnp.float32)),
                argnums=(0, 1, 2))(q, k, v)

        def bwd_x(q, k, v):
            return jax.grad(
                lambda q, k, v: jnp.sum(
                    mha_reference(q, k, v, causal=True, scale=d ** -0.5)
                    .astype(jnp.float32)),
                argnums=(0, 1, 2))(q, k, v)

        row("flash_fwd_bwd_causal", f"b{b} h{h} s{s} d{d}",
            timeit(bwd_k, q, k, v), timeit(bwd_x, q, k, v),
            gflops=3.5 * gf)


# --------------------------------------------------------------------- ln
def bench_ln():
    from apex_tpu.kernels.layer_norm import layer_norm, layer_norm_reference

    for rows_, hidden in ((8192, 4096), (4096, 8192)):
        x = jax.random.normal(jax.random.PRNGKey(1), (rows_, hidden),
                              jnp.bfloat16)
        w = jnp.ones((hidden,))
        b = jnp.zeros((hidden,))

        gb = 2 * rows_ * hidden * 2 / 1e9      # read x + write y, bf16
        row("layer_norm_fwd", f"{rows_}x{hidden}",
            timeit(layer_norm, x, w, b),
            timeit(layer_norm_reference, x, w, b), gbytes=gb)

        def bwd_k(x, w, b):
            return jax.grad(lambda x, w, b: jnp.sum(
                layer_norm(x, w, b).astype(jnp.float32)),
                argnums=(0, 1, 2))(x, w, b)

        def bwd_x(x, w, b):
            return jax.grad(lambda x, w, b: jnp.sum(
                layer_norm_reference(x, w, b).astype(jnp.float32)),
                argnums=(0, 1, 2))(x, w, b)

        row("layer_norm_fwd_bwd", f"{rows_}x{hidden}",
            timeit(bwd_k, x, w, b), timeit(bwd_x, x, w, b),
            gbytes=2.5 * gb)


# ---------------------------------------------------------------- xentropy
def bench_xentropy():
    from apex_tpu.kernels.xentropy import (softmax_cross_entropy_loss,
                                           xent_reference)

    n, v = 8192, 32768
    logits = jax.random.normal(jax.random.PRNGKey(2), (n, v), jnp.bfloat16)
    labels = jax.random.randint(jax.random.PRNGKey(3), (n,), 0, v)

    gb = n * v * 2 / 1e9                       # logits read, bf16
    row("xentropy_fwd", f"{n}x{v}",
        timeit(lambda l: softmax_cross_entropy_loss(l, labels), logits),
        timeit(lambda l: xent_reference(l, labels), logits), gbytes=gb)

    def bwd_k(l):
        return jax.grad(lambda l: jnp.sum(
            softmax_cross_entropy_loss(l, labels)))(l)

    def bwd_x(l):
        return jax.grad(lambda l: jnp.sum(xent_reference(l, labels)))(l)

    row("xentropy_fwd_bwd", f"{n}x{v}",
        timeit(bwd_k, logits), timeit(bwd_x, logits), gbytes=3 * gb)


# ------------------------------------------------------------ lm head
def bench_lm_head():
    """Fused LM-head+CE vs the composed tail (head GEMM + fused CE
    kernel — the exact pair the recipe's --fused-head replaces), both
    differentiated through x and the head weight."""
    from apex_tpu.kernels.lm_head_loss import lm_head_xentropy
    from apex_tpu.kernels.xentropy import softmax_cross_entropy_loss

    n, h, v = 8184, 768, 32768
    x = jax.random.normal(jax.random.PRNGKey(4), (n, h), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(5), (v, h), jnp.float32) * 0.02
    y = jax.random.randint(jax.random.PRNGKey(6), (n,), 0, v)

    def fused(x, w):
        return jax.grad(lambda x, w: lm_head_xentropy(
            x, w, y, compute_dtype=jnp.bfloat16).mean(),
            argnums=(0, 1))(x, w)

    def composed(x, w):
        def loss(x, w):
            logits = jax.lax.dot_general(
                x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            return softmax_cross_entropy_loss(logits, y).mean()
        return jax.grad(loss, argnums=(0, 1))(x, w)

    # compute floor: 4 GEMM-equivalents (fwd + recomputed fwd + dW + dx)
    gf = 4 * 2 * n * h * v / 1e9
    row("lm_head_fused_vs_composed_f_b", f"{n}x{h} V{v}",
        timeit(fused, x, w), timeit(composed, x, w), gflops=gf)


# ------------------------------------------------------------ multi-tensor
def bench_adam():
    # big-tensor case: few large leaves (optax's per-leaf chain is already
    # one fused elementwise op per leaf here — the launch-count win is small)
    _bench_adam_tree(
        "fused_adam_step", {
            f"w{i}": jax.random.normal(jax.random.PRNGKey(i),
                                       (4096, 1528), jnp.float32)
            for i in range(20)})
    # many-small-tensors case: the scenario multi_tensor_apply exists for
    # (120 leaves from 256 to ~147K elements — conv-net-like sizes)
    leaves = {}
    kidx = 0
    for i in range(40):
        for shape in ((256,), (64, 64), (3, 3, 128, 128)):
            leaves[f"p{kidx}"] = jax.random.normal(
                jax.random.PRNGKey(kidx), shape, jnp.float32)
            kidx += 1
    _bench_adam_tree("fused_adam_step_many_small", leaves)


def _bench_adam_tree(name, leaves):
    """Both fused_adam layouts vs the optax.adamw baseline. The row's
    pallas_ms column is the DEFAULT layout (tree, round 5 — per-leaf
    state, XLA-fused); a second row prices the round-1..4 flat
    superbuffer so its flatten/unflatten cost stays on the record."""
    import optax
    from apex_tpu.optimizers.fused_adam import fused_adam
    grads = jax.tree_util.tree_map(
        lambda p: jnp.full(p.shape, 1e-3, p.dtype), leaves)

    tx_o = optax.adamw(1e-3, weight_decay=0.01)
    st_o = tx_o.init(leaves)

    def step_optax(p, s):
        u, s2 = tx_o.update(grads, s, p)
        return optax.apply_updates(p, u), s2

    optax_ms = timeit(step_optax, leaves, st_o)
    n = sum(x.size for x in jax.tree_util.tree_leaves(leaves))
    gb = 7 * n * 4 / 1e9                       # read p,m,v,g; write p,m,v
    for layout in ("tree", "flat"):
        tx_f = fused_adam(1e-3, weight_decay=0.01, layout=layout)
        st_f = tx_f.init(leaves)

        def step_fused(p, s):
            u, s2 = tx_f.update(grads, s, p)
            return optax.apply_updates(p, u), s2

        row(f"{name}_{layout}",
            f"{n / 1e6:.1f}M params, {len(leaves)} tensors",
            timeit(step_fused, leaves, st_f), optax_ms, gbytes=gb)


# ---------------------------------------------------------- causal softmax
def bench_causal_softmax():
    from apex_tpu.kernels.causal_softmax import (causal_softmax,
                                                 causal_softmax_reference)

    x = jax.random.normal(jax.random.PRNGKey(4), (16, 2048, 2048),
                          jnp.bfloat16)
    gb = 2 * 16 * 2048 * 2048 * 2 / 1e9
    row("causal_softmax_fwd", "16x2048x2048",
        timeit(functools.partial(causal_softmax, scale=0.125), x),
        timeit(functools.partial(causal_softmax_reference, scale=0.125), x),
        gbytes=gb)


# ---------------------------------------------------------- masked softmax
def bench_masked_softmax():
    from apex_tpu.kernels.masked_softmax import (masked_softmax,
                                                 masked_softmax_reference)

    b, h, sq, sk = 4, 8, 1024, 1024       # BERT-large-ish padded block
    x = jax.random.normal(jax.random.PRNGKey(6), (b, h, sq, sk),
                          jnp.bfloat16)
    m = jax.random.bernoulli(jax.random.PRNGKey(7), 0.3, (b, 1, sq, sk))
    m = m.at[..., 0].set(False)
    gb = 2 * b * h * sq * sk * 2 / 1e9 + b * sq * sk / 1e9
    row("masked_softmax_fwd", f"{b}x{h}x{sq}x{sk} mask b1",
        timeit(functools.partial(masked_softmax, scale=0.125), x, m),
        timeit(functools.partial(masked_softmax_reference, scale=0.125),
               x, m),
        gbytes=gb)


# ------------------------------------------------------------- group norm
def bench_group_norm():
    from apex_tpu.kernels.group_norm import (group_norm_nhwc,
                                             group_norm_reference)

    n, h, w, c = 8, 64, 64, 512           # diffusion UNet mid-block shape
    x = jax.random.normal(jax.random.PRNGKey(5), (n, h, w, c), jnp.bfloat16)
    g = jnp.ones((c,))
    b = jnp.zeros((c,))
    gb = 2 * n * h * w * c * 2 / 1e9
    row("group_norm_silu_fwd", f"{n}x{h}x{w}x{c} g32",
        timeit(lambda x: group_norm_nhwc(x, 32, g, b, act="silu"), x),
        timeit(lambda x: group_norm_reference(x, 32, g, b, act="silu"), x),
        gbytes=gb)


# ----------------------------------------------------------- paged decode
# The benchmark's two serving geometries as their engines call the kernel
# (rows, query heads, K/V heads, head_dim, table pages, pool pages) and
# the lengths their traffic keeps live (about 355 and 550 tokens a row).
PAGED_DECODE_CELLS = {
    "gpt2_large_24x1024": (24, 20, 20, 64, 8, 193, (64, 650)),
    "zaya1_8b_96x2048": (96, 8, 2, 128, 16, 1537, (100, 1000)),
}
PAGED_DECODE_LAYERS = 4


def paged_decode_case(cell, seed=0):
    """``(q, k_pool, v_pool, page_table, lengths)`` of one cell: a
    stacked bf16 pool of a few layers, every row its own pages, table
    entries past a row's live pages the sentinel."""
    import numpy as np

    rows, h, h_kv, d, table, pool, (lo, hi) = PAGED_DECODE_CELLS[cell]
    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (PAGED_DECODE_LAYERS, pool, h_kv, d, 128)
    kp, vp = (jax.random.normal(k, shape, jnp.bfloat16) for k in ks[:2])
    q = jax.random.normal(ks[2], (rows, h, d), jnp.bfloat16)
    lengths = rng.integers(lo, hi, size=rows).astype(np.int32)
    pt = rng.permutation(np.arange(1, pool))[:rows * table]
    pt = pt.reshape(rows, table).astype(np.int32)
    pt[np.arange(table)[None, :] >= -(-lengths[:, None] // 128)] = 0
    return q, kp, vp, jnp.asarray(pt), jnp.asarray(lengths)


def paged_decode_ms(cell, case=None, write=None):
    """Device ms of ONE layer's call of the kernel at ``cell``, the mean
    over the pool's layers, and the GB it has to move: the live K/V it
    reads and, where it writes, each row's K and V page written back.
    ``write``: ``None`` the read-only call; ``"kernel"`` the call handed
    every row's new K/V, the decode program's form (the pools donated,
    as the engine's are); ``"xla"`` what that call replaced, the
    gather-select-scatter of ``_pool_write_tokens`` in front of the
    read-only call."""
    from apex_tpu.kernels.decode_attention import (_pool_write_tokens,
                                                   paged_decode_attention)

    q, kp, vp, pt, lengths = case or paged_decode_case(cell)
    rows, (_, _, h_kv, d, page_len) = q.shape[0], kp.shape
    new = jax.random.normal(jax.random.PRNGKey(7), (2, rows, h_kv, d),
                            kp.dtype)

    def layers(q, kp, vp, pt, lengths):
        out = 0.0
        for i in range(PAGED_DECODE_LAYERS):
            if write == "kernel":
                ctx, kp, vp = paged_decode_attention(
                    q, kp, vp, pt, lengths, new_k=new[0], new_v=new[1],
                    layer=i)
            else:
                if write == "xla":
                    pos = lengths - 1
                    ids = jnp.take_along_axis(
                        pt, (pos // page_len)[:, None], axis=1)[:, 0]
                    kp = _pool_write_tokens(kp, i, ids, pos % page_len,
                                            new[0])
                    vp = _pool_write_tokens(vp, i, ids, pos % page_len,
                                            new[1])
                ctx = paged_decode_attention(q, kp, vp, pt, lengths,
                                             layer=i)
            out = out + ctx.astype(jnp.float32)
        return (out, kp, vp) if write else out
    ms = timeit(layers, q, kp, vp, pt, lengths,
                donate=(1, 2) if write else ()) / PAGED_DECODE_LAYERS
    page_bytes = 2 * h_kv * d * page_len * kp.dtype.itemsize  # K and V
    pages = float(jnp.sum(-(-lengths // 128))) + (rows if write else 0)
    return ms, pages * page_bytes / 1e9


def bench_paged_decode():
    """The paged decode kernel against its gather-then-attend oracle
    (what XLA makes of the same read) at the benchmark's two serving
    geometries; the roofline counts the LIVE pages' bytes. Then the call
    the decode program makes, which also writes the step's K/V
    (``paged_decode_write``), against the XLA write it replaced in front
    of the read-only kernel: bytes are the live pages read plus one K and
    one V page a row written back."""
    from apex_tpu.kernels.decode_attention import \
        paged_decode_attention_reference

    for cell in PAGED_DECODE_CELLS:
        case = paged_decode_case(cell)
        ms, gbytes = paged_decode_ms(cell, case)
        d = case[0].shape[-1]
        xla = timeit(lambda *a: paged_decode_attention_reference(
            *a, scale=1 / d ** 0.5, layer=1), *case)
        row("paged_decode", cell, ms, xla, gbytes=gbytes)
        # each timing donates its pools: a case of its own
        ms, gbytes = paged_decode_ms(cell, paged_decode_case(cell),
                                     write="kernel")
        xla, _ = paged_decode_ms(cell, paged_decode_case(cell), write="xla")
        row("paged_decode_write", cell, ms, xla, gbytes=gbytes)


SUITES = {"flash": bench_flash, "ln": bench_ln, "xentropy": bench_xentropy,
          "paged_decode": bench_paged_decode,
          "lm_head": bench_lm_head,
          "adam": bench_adam, "causal_softmax": bench_causal_softmax,
          "masked_softmax": bench_masked_softmax,
          "group_norm": bench_group_norm}


# ------------------------------------------------------------------ sweep
# Block-shape sweep (VERDICT round-2 item 4): per kernel, time each
# candidate block config on THIS device and emit the best as a tuned-
# overrides JSON consumable by apex_tpu.kernels.vmem.load_overrides /
# APEX_TPU_TUNED. A row whose clock did not time the device self-flags
# (``implausible``) and its ranking carries no signal.

def _sweep_knob(results, key, candidates, measure):
    """Time ``measure()`` under each override value; record the best."""
    from apex_tpu.kernels import vmem

    best_v, best_ms = None, float("inf")
    for v in candidates:
        vmem.set_override(key, v)
        # overrides are read at TRACE time; jit caches key on function
        # identity + avals, so a reused callable (e.g. layer_norm itself)
        # would silently time the first candidate's trace for all values
        jax.clear_caches()
        try:
            ms = measure()
        except Exception as e:  # a config Mosaic rejects is a data point
            print(json.dumps({"sweep": key, "value": v,
                              "error": str(e)[:120]}), flush=True)
            continue
        finally:
            vmem.remove_override(key)  # other pinned knobs stay
        print(json.dumps({"sweep": key, "value": v, "ms": round(ms, 3)}),
              flush=True)
        if ms < best_ms:
            best_v, best_ms = v, ms
    if best_v is not None:
        results[key] = best_v


def sweep(out_path="tuned_blocks.json"):
    from apex_tpu.kernels import vmem

    # sweep from the HEURISTIC baseline: block the packaged per-device
    # tuned file from auto-loading (and drop anything already loaded) so
    # re-tuning on a device kind that ships a file measures the same
    # regime the original sweep did — not candidates layered on top of
    # the previous answers
    vmem._auto_load_done = True
    vmem.clear_overrides()

    results = {}

    # flash attention q/k blocks at the LM shape
    from apex_tpu.kernels.flash_attention import flash_attention
    b, h, s, d = 4, 8, 2048, 128
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (b, h, s, d), jnp.bfloat16)
               for kk in ks)

    def flash_ms():
        return timeit(lambda q, k, v: flash_attention(q, k, v, causal=True),
                      q, k, v)

    def flash_bwd_ms():
        def bwd(q, k, v):
            return jax.grad(
                lambda q, k, v: jnp.sum(
                    flash_attention(q, k, v, causal=True)
                    .astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)
        return timeit(bwd, q, k, v)

    _sweep_knob(results, "flash.block_q", (64, 128, 256, 512), flash_ms)
    if "flash.block_q" in results:
        vmem.set_override("flash.block_q", results["flash.block_q"])
    # block_k is lane-aligned to 128 (values below clamp up — see
    # flash_attention._resolve_blocks), so 64 would duplicate 128
    _sweep_knob(results, "flash.block_k", (128, 256, 512, 1024), flash_ms)
    # backward-specific blocks (flash.bwd_block_q/_k; consulted only when
    # dropout is off — the fwd mask seeds can't replay on another
    # geometry), swept with the fwd bests pinned
    for k_, v_ in results.items():
        vmem.set_override(k_, v_)
    _sweep_knob(results, "flash.bwd_block_q", (64, 128, 256, 512),
                flash_bwd_ms)
    if "flash.bwd_block_q" in results:
        vmem.set_override("flash.bwd_block_q", results["flash.bwd_block_q"])
    _sweep_knob(results, "flash.bwd_block_k", (128, 256, 512, 1024),
                flash_bwd_ms)
    vmem.clear_overrides()

    # layer norm row block
    from apex_tpu.kernels.layer_norm import layer_norm
    x = jax.random.normal(jax.random.PRNGKey(1), (8192, 4096), jnp.bfloat16)
    w, bb = jnp.ones((4096,)), jnp.zeros((4096,))
    _sweep_knob(results, "layer_norm.block_rows", (16, 64, 128, 256, 512),
                lambda: timeit(layer_norm, x, w, bb))

    # xentropy row block (vocab-heavy rows)
    from apex_tpu.kernels.xentropy import softmax_cross_entropy_loss
    logits = jax.random.normal(jax.random.PRNGKey(2), (4096, 32768),
                               jnp.bfloat16)
    labels = jax.random.randint(jax.random.PRNGKey(3), (4096,), 0, 32768)
    _sweep_knob(results, "xentropy.block_rows", (8, 16, 32, 64),
                lambda: timeit(
                    lambda l: softmax_cross_entropy_loss(l, labels), logits))

    # multi-tensor superbuffer rows
    from apex_tpu.optimizers.fused_adam import fused_adam
    import optax
    leaves = {f"w{i}": jax.random.normal(jax.random.PRNGKey(i),
                                         (1024, 1528), jnp.float32)
              for i in range(20)}
    grads = jax.tree_util.tree_map(
        lambda p: jnp.full(p.shape, 1e-3, p.dtype), leaves)
    # layout="flat": multi_tensor.block_rows is read only inside the
    # superbuffer Pallas kernel — the tree default never consults it
    tx = fused_adam(1e-3, weight_decay=0.01, layout="flat")
    st = tx.init(leaves)

    def adam_ms():
        def step(p, s):
            u, s2 = tx.update(grads, s, p)
            return optax.apply_updates(p, u), s2
        return timeit(step, leaves, st)

    _sweep_knob(results, "multi_tensor.block_rows", (64, 128, 256, 512),
                adam_ms)

    # causal softmax q block
    from apex_tpu.kernels.causal_softmax import causal_softmax
    xs = jax.random.normal(jax.random.PRNGKey(4), (8, 2048, 2048),
                           jnp.bfloat16)
    _sweep_knob(results, "causal_softmax.block_q", (32, 64, 128, 256, 512),
                lambda: timeit(
                    functools.partial(causal_softmax, scale=0.125), xs))

    # masked softmax q block (v5e: 128->256 closed its gap to XLA parity)
    from apex_tpu.kernels.masked_softmax import masked_softmax
    xm = jax.random.normal(jax.random.PRNGKey(6), (4, 8, 1024, 1024),
                           jnp.bfloat16)
    mm = jax.random.bernoulli(jax.random.PRNGKey(7), 0.9, (4, 1, 1024, 1024))
    _sweep_knob(results, "masked_softmax.block_q", (32, 64, 128, 256, 512),
                lambda: timeit(
                    functools.partial(masked_softmax, scale=0.125), xm, mm))

    # group norm spatial blocks — fwd and bwd separately (on v5e they
    # want opposite extremes: fwd 1024, bwd 128)
    from apex_tpu.kernels.group_norm import group_norm_nhwc
    xg = jax.random.normal(jax.random.PRNGKey(8), (8, 64, 64, 512),
                           jnp.bfloat16)
    gg, gb = jnp.ones((512,)), jnp.zeros((512,))
    _sweep_knob(results, "group_norm.block_spatial",
                (128, 256, 512, 1024, 2048),
                lambda: timeit(lambda x: group_norm_nhwc(
                    x, 32, gg, gb, act="silu"), xg))

    def gn_bwd_ms():
        def bwd(x, g_, b_):
            return jax.grad(lambda x, g_, b_: jnp.sum(
                group_norm_nhwc(x, 32, g_, b_, act="silu")
                .astype(jnp.float32)), argnums=(0, 1, 2))(x, g_, b_)
        return timeit(bwd, xg, gg, gb)

    _sweep_knob(results, "group_norm.bwd_block_spatial",
                (64, 128, 256, 512), gn_bwd_ms)

    # the paged decode kernel's bytes in flight a buffer: ONE number for
    # both serving geometries (their pages are 320 KB and 64 KB), so the
    # measure is the two cells' ms a layer weighted by their layers
    _sweep_knob(results, "decode.paged_step_bytes",
                tuple(kb * 1024 for kb in (64, 128, 256, 384, 512, 768,
                                           1024, 2048)),
                lambda: 36 * paged_decode_ms("gpt2_large_24x1024")[0]
                + 20 * paged_decode_ms("zaya1_8b_96x2048")[0])

    with open(out_path, "w") as f:
        json.dump(results, f, indent=1, sort_keys=True)
    print(json.dumps({"sweep_best": results, "written": out_path}),
          flush=True)


def main(argv):
    if argv and argv[0] == "--sweep":
        out = argv[1] if len(argv) > 1 else "tuned_blocks.json"
        print(json.dumps({"device": str(jax.devices()[0]),
                          "backend": jax.default_backend()}), flush=True)
        sweep(out)
        return
    names = argv or list(SUITES)
    bad = [n for n in names if n not in SUITES]
    if bad:
        raise SystemExit(f"unknown suite(s) {', '.join(map(repr, bad))}; "
                         f"pick from {', '.join(sorted(SUITES))}")
    print(json.dumps({"device": str(jax.devices()[0]),
                      "backend": jax.default_backend()}), flush=True)
    for name in names:
        SUITES[name]()


if __name__ == "__main__":
    # crash contract: any failure still ends in one parseable JSON
    # line ({"metric", "error", "rc": 1}) instead of a bare traceback
    from apex_tpu.telemetry import guard_bench_main
    from apex_tpu.utils.chip import enable_compile_cache
    enable_compile_cache()
    guard_bench_main(lambda: main(sys.argv[1:]), "bench_kernels")
