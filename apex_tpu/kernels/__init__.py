"""apex_tpu.kernels — the Pallas (Mosaic) kernel tier.

TPU-native equivalents of the reference's csrc/ CUDA kernels (SURVEY §3.2).
Every kernel:

- accumulates in fp32 regardless of I/O dtype (matching apex's kernels);
- has a pure-jnp reference implementation used both as the CPU/interpret
  fallback and as the oracle in tests (the reference's test strategy:
  fused-vs-composed-eager comparison, tests/L0/run_fused_layer_norm/);
- auto-falls back to the jnp path off-TPU so the suite runs hermetically
  (the reference's "usable as pure-Python when exts missing" property).
"""

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def mosaic_dtype_ok(*xs) -> bool:
    """TPU Mosaic has no fp16 (the MXU/VPU are bf16/fp32 machines): a
    float16 operand must take the jnp fallback, where XLA upconverts —
    found by the on-silicon scaler soak, whose fp16 model crashed every
    fused kernel's real lowering. interpret mode is unaffected (callers
    keep `or interpret`). Accepts arrays OR bare dtypes; None skipped."""
    import jax.numpy as jnp
    import numpy as np

    def dt(x):
        return np.dtype(getattr(x, "dtype", x))

    return all(dt(x) != jnp.float16 for x in xs if x is not None)


from .layer_norm import (  # noqa: E402,F401
    layer_norm, rms_norm, layer_norm_reference, rms_norm_reference)
from .multi_tensor import (  # noqa: E402,F401
    fused_scale, fused_axpby, fused_l2norm, fused_adam_step, fused_sgd_step)
from .decode_attention import (  # noqa: E402,F401
    decode_attention_reference)
from .prefill_attention import (  # noqa: E402,F401
    prefill_attention, prefill_attention_reference)
