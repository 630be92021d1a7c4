"""The O1 per-op cast engine — trace-time analogue of apex's monkey-patching.

Reference: apex/amp/_initialize.py installs wrappers over every op named in
apex/amp/lists/ (torch_overrides.py — FP16_FUNCS, FP32_FUNCS, CASTS) so that,
under O1, tensor-core ops run half, reductions/losses/norms run fp32, and
binary CASTS ops promote operands. JAX traces instead of patching, so the
engine is ambient-context + consultation: :func:`make_train_step` (and
``amp.initialize``'s policy_apply) install the active policy for the duration
of the traced forward, and policy-aware modules ask :func:`op_compute_dtype`
what dtype the table assigns their op.

The context is thread-local Python state consulted at *trace* time only —
nothing here appears in the jaxpr except the casts it decides on. Because
jit caches by jaxpr inputs, the active policy is ALSO salted into jax's
jit cache key (``include_in_jit_key`` config state): a user-jitted
policy-aware function traced under one ambient policy re-traces — instead
of silently reusing stale cast decisions — when called under another
(ADVICE r2 #1; apex re-applies its patches on every ``amp.initialize``,
so stale wrappers cannot survive a policy change there either).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
import jax.numpy as jnp

from . import lists

__all__ = ["autocast", "active_policy", "op_compute_dtype", "resolve_dtype",
           "cast_op_inputs", "trace_token"]

_tls = threading.local()

# jit-cache salt: a jax user context carrying the active policy — part of
# the tracing/lowering/compilation cache key, so jit distinguishes traces
# made under different ambient policies.
_policy_state = jax.make_user_context(default_value=None)


def active_policy():
    """The Policy installed by the innermost :func:`autocast`, or None."""
    return getattr(_tls, "policy", None)


def trace_token():
    """A hashable fingerprint of the active policy (None outside
    :func:`autocast`). jit already re-traces on policy changes via the
    cache salt; pass this as an extra static argument for caches jax does
    not manage (e.g. functools.lru_cache over traced helpers)."""
    return active_policy()


@contextlib.contextmanager
def autocast(policy):
    """Install ``policy`` as the ambient op-cast policy (the O1 engine's
    analogue of apex applying its patches at ``amp.initialize`` time —
    scoped, because trace-time globals must not leak across steps).

    Entering also salts jax's jit cache with the policy, so re-entering a
    previously-jitted function under a different policy re-traces it with
    the new cast decisions rather than reusing the old executable."""
    prev = getattr(_tls, "policy", None)
    _tls.policy = policy
    try:
        with _policy_state(policy):
            yield policy
    finally:
        _tls.policy = prev


def op_compute_dtype(op_name: str, *operand_dtypes):
    """Dtype the active policy assigns to ``op_name``, or None for "no
    opinion" (run in operand dtype). Delegates to
    :meth:`Policy.op_dtype`; returns None when no policy is active."""
    pol = active_policy()
    if pol is None:
        return None
    return pol.op_dtype(op_name, *operand_dtypes)


def resolve_dtype(explicit, op_name: str, default=None):
    """Module-side dtype resolution: an explicit user dtype always wins;
    otherwise the active policy's table opinion; otherwise ``default``.

    The pattern for policy-aware flax modules: declare ``dtype: Optional[Any]
    = None`` and resolve with the op name the apex tables classify
    (``conv2d``, ``linear``, ``layer_norm``, ``batch_norm``, ...).
    """
    if explicit is not None:
        return explicit
    d = op_compute_dtype(op_name)
    return d if d is not None else default


def cast_op_inputs(op_name: str, *arrays):
    """Cast floating arrays to the table dtype for ``op_name`` (no-op when
    the policy has no opinion). Returns the arrays in order.

    For CASTS entries the target is the widest floating operand dtype —
    apex's promote wrapper (lists/torch_overrides.py — CASTS).
    """
    dtypes = []
    for a in arrays:
        try:
            dtypes.append(jnp.asarray(a).dtype)
        except (TypeError, ValueError):
            dtypes.append(None)
    target = op_compute_dtype(op_name,
                              *[d for d in dtypes if d is not None])
    if target is None:
        return arrays if len(arrays) != 1 else arrays[0]
    out = []
    for a, d in zip(arrays, dtypes):
        if d is not None and jnp.issubdtype(d, jnp.floating):
            out.append(jnp.asarray(a, target))
        else:
            out.append(a)
    return tuple(out) if len(out) != 1 else out[0]
