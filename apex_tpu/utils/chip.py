"""The attached accelerator, as the entry points need to know it: what
it is, its published peaks, where compiled programs are cached, and
which Pallas kernels a compiled program really holds.

Shared by ``chip_smoke.py``, ``bench*.py`` and the
``examples/*/main_amp.py`` recipes so that none of them keeps a private
copy of a peak figure, a cache path or a kernel-name regex.
"""

from __future__ import annotations

import os
import re
import time

__all__ = ["PEAKS", "peak", "device_summary", "enable_compile_cache",
           "kernel_calls", "format_kernels", "compile_and_report"]

# Per-chip peaks keyed by ``jax.devices()[0].device_kind``. Source:
# Google Cloud documentation, "TPU v5e" system architecture page —
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s. A kind that
# is not here is an error (:func:`peak`), never a default: a utilisation
# computed against a guessed peak is not a measurement.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9},
}


def peak(device_kind: str, key: str = "bf16_flops") -> float:
    """The published ``key`` peak of one ``device_kind`` chip."""
    try:
        return PEAKS[device_kind][key]
    except KeyError:
        raise ValueError(
            f"no published {key!r} peak for device_kind "
            f"{device_kind!r}; known kinds: {sorted(PEAKS)} — add the "
            f"figure and its source to apex_tpu.utils.chip.PEAKS") from None


def device_summary() -> dict:
    """``{"platform", "kind", "count"}`` of the default backend, as JAX
    reports it — the line every measurement names its device with."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


# One fixed directory inside the checkout (git-ignored): the path is part
# of JAX's cache key, so a directory that moves never hits.
_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir,
    ".jax_compile_cache"))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already
    honours it and nothing is set in code; where it is not, the cache
    goes to one fixed directory in the checkout, exported through the
    environment so child processes (fleet workers) share it. Call it
    first thing in an entry point, before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    os.environ["JAX_COMPILATION_CACHE_DIR"] = _CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
    return _CACHE_DIR


# `custom-call(...)` lines targeting Mosaic; the kernel is the scope
# element right before `/pallas_call` in the op_name (the `name=` every
# pallas_call in apex_tpu.kernels passes), possibly wrapped by autodiff
# as `jvp(name)` / `transpose(jvp(name))`.
_KERNEL_OP = re.compile(r'op_name="[^"]*?(\w+)\)*/pallas_call"')


def kernel_calls(hlo_text: str) -> dict:
    """Count the Pallas (Mosaic) kernels a compiled program holds:
    ``{kernel_name: n_calls}`` from ``compiled.as_text()``. Empty on a
    backend where the kernels ran interpreted or gave way to their jnp
    reference — which is exactly what callers want to see."""
    counts: dict = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = _KERNEL_OP.search(line)
        name = m.group(1) if m else "unnamed"
        counts[name] = counts.get(name, 0) + 1
    return counts


def format_kernels(counts: dict) -> str:
    """``name x n`` list for an entry point's own output."""
    if not counts:
        return "none (jnp reference / interpret paths only)"
    return ", ".join(f"{k} x{v}" for k, v in sorted(counts.items()))


def compile_and_report(name: str, jitted, *args):
    """Compile ``jitted`` for ``args`` ahead of time and say which path
    it got. Returns ``(compiled, kernels, line)``: call ``compiled`` in
    place of ``jitted`` (same executable, one compile), hand
    ``kernels`` to whoever must fail when a kernel is missing, and
    print ``line`` — ``=> <name>: compiled in N s for <platform>;
    Pallas kernels: ...`` — which is how an entry point's own output
    says whether its fused kernels are in the program or quietly gave
    way to their references."""
    import jax

    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    dt = time.perf_counter() - t0
    kernels = kernel_calls(compiled.as_text())
    line = (f"=> {name}: compiled in {dt:.1f}s for "
            f"{jax.default_backend()}; Pallas kernels: "
            f"{format_kernels(kernels)}")
    return compiled, kernels, line
