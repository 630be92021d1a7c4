"""Chip probe: the grouped GEMM of the drop-nothing expert layer at the
serving shapes (PR 30). Never a cell. Times one expert sublayer's three
products (gate, up, down; 16 experts of 2048 x 2048) over tokens sorted
by expert, at a decode beat's 96 tokens and a chunk's 256, built three
ways - the program's own Pallas kernel (``kernels/grouped_gemm.py``),
``jax.experimental.pallas.ops.tpu.megablox.gmm`` and
``jax.lax.ragged_dot`` - and, for the first, with gate and up fused as
one ``[E, H, 2F]`` operand or as two calls, at several block shapes.

    chiprun -- python3 benchmarks/checks/probe_gmm.py [--layers 4] [--iters 30]

Prints one line per variant (``ms`` a layer, the weights' GB/s, the
widest difference from the one-hot dense form) and writes them to
``chiprun_out/probe_gmm.jsonl``. A layer's weights are 403 MB: the least
a layer can take is 0.49 ms at 819 GB/s.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                os.pardir, os.pardir)))

SIZES = (16, 2048, 2048)      # experts, hidden, expert width


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--tokens", type=int, nargs="+", default=[96, 256])
    ap.add_argument("--sizes", type=int, nargs=3, default=list(SIZES),
                    help="experts, hidden, expert width (a CPU rehearsal)")
    a = ap.parse_args()
    E, H, F = a.sizes
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from apex_tpu.kernels.grouped_gemm import (group_ranges, grouped_gemm,
                                               grouped_gemm_reference)

    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}", flush=True)
    key = jax.random.PRNGKey(0)
    L = a.layers
    ws = []
    for i in range(L):
        k1, k2 = jax.random.split(jax.random.fold_in(key, i))
        ws.append(((jax.random.normal(k1, (E, H, 2 * F), jnp.float32)
                    / np.sqrt(H)).astype(jnp.bfloat16),
                   (jax.random.normal(k2, (E, F, H), jnp.float32)
                    / np.sqrt(F)).astype(jnp.bfloat16)))
    weight_bytes = 3 * E * H * F * 2

    def mlp(prod_gu, prod_d, x, w_gu, w_d):
        gu = prod_gu(x, w_gu)
        h = jax.nn.silu(jnp.asarray(gu[:, :F], jnp.float32)) \
            * jnp.asarray(gu[:, F:], jnp.float32)
        return prod_d(jnp.asarray(h, x.dtype), w_d)

    def variants(T, sizes, starts, ends):
        def own(bk, bn, out=None):
            return lambda x, w: grouped_gemm(x, w, starts, ends,
                                             block_k=bk, block_n=bn,
                                             out_dtype=out)

        def own_split(bk, bn):
            def gu(x, w):
                return jnp.concatenate(
                    [grouped_gemm(x, w[:, :, :F], starts, ends, block_k=bk,
                                  block_n=bn),
                     grouped_gemm(x, w[:, :, F:], starts, ends, block_k=bk,
                                  block_n=bn)], -1)
            return gu

        Tp = -(-T // 128) * 128

        def mega(tiling, out=jnp.bfloat16):
            def f(x, w):
                xp = jnp.pad(x, ((0, Tp - T), (0, 0)))
                return gmm(xp, w, sizes, preferred_element_type=out,
                           tiling=tiling)[:T]
            return f

        def ragged(out=jnp.bfloat16):
            return lambda x, w: jax.lax.ragged_dot(
                x, w, sizes, preferred_element_type=out)

        v = {}
        for bk, bn in ((2048, 512), (2048, 1024), (1024, 1024),
                       (2048, 256), (1024, 512), (512, 2048)):
            v[f"own fused k{bk} n{bn}"] = (own(bk, bn),
                                           own(bk, bn, jnp.float32))
        v["own split k2048 n512"] = (own_split(2048, 512),
                                     own(2048, 512, jnp.float32))
        for tiling in ((128, 2048, 512), (128, 1024, 1024), (128, 512, 512),
                       (128, 2048, 256)):
            v[f"megablox {tiling}"] = (mega(tiling),
                                       mega(tiling, jnp.float32))
        v["ragged_dot"] = (ragged(), ragged(jnp.float32))
        return v

    out_path = os.path.join("chiprun_out", "probe_gmm.jsonl")
    os.makedirs("chiprun_out", exist_ok=True)
    for T in a.tokens:
        # the skewed routing at the first size only: a probe's time is the
        # chip's
        for routing in (("uniform", "skewed") if T == a.tokens[0]
                        else ("uniform",)):
            rng = np.random.default_rng(T)
            if routing == "uniform":
                choice = rng.integers(0, E, T)
            else:   # half the tokens on one expert, two experts empty
                choice = np.where(rng.random(T) < 0.5, 3,
                                  rng.integers(0, E - 2, T))
            choice = jnp.sort(jnp.asarray(choice, jnp.int32))
            sizes, starts, ends = group_ranges(choice, E)
            x = (jax.random.normal(jax.random.fold_in(key, 99), (T, H),
                                   jnp.float32)).astype(jnp.bfloat16)
            ref = mlp(lambda x_, w: grouped_gemm_reference(x_, w, starts,
                                                           ends),
                      lambda h, w: grouped_gemm_reference(
                          h, w, starts, ends, out_dtype=jnp.float32),
                      x, *ws[0])
            for name, (pgu, pd) in variants(T, sizes, starts, ends).items():
                @jax.jit
                def layers(x, ws):
                    y = jnp.zeros((T, H), jnp.float32)
                    for w_gu, w_d in ws:
                        y = y + mlp(pgu, pd, x, w_gu, w_d)
                    return y
                try:
                    got = jax.jit(lambda x, w: mlp(pgu, pd, x, *w))(x, ws[0])
                    err = float(jnp.max(jnp.abs(got - ref)))
                    layers(x, ws).block_until_ready()
                    t0 = time.perf_counter()
                    for _ in range(a.iters):
                        r = layers(x, ws)
                    r.block_until_ready()
                    ms = (time.perf_counter() - t0) * 1e3 / a.iters / L
                    line = {"tokens": T, "routing": routing,
                            "variant": name, "ms_per_layer": ms,
                            "weights_GBps": weight_bytes / ms / 1e6,
                            "max_abs_diff": err,
                            "ref_absmax": float(jnp.max(jnp.abs(ref)))}
                except Exception as e:  # noqa: BLE001 - a probe reports
                    line = {"tokens": T, "routing": routing,
                            "variant": name,
                            "error": f"{type(e).__name__}: {e}"[:300]}
                print(json.dumps(line), flush=True)
                with open(out_path, "a") as f:
                    f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
