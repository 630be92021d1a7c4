"""``shard_map`` under the name every call site imports.

The one installation this repo targets (JAX 0.9.0) has top-level
:func:`jax.shard_map` with the ``check_vma`` keyword, which is the idiom
library, bench and test call sites use. This module re-exports it so
those call sites keep one import line::

    from apex_tpu.utils.compat import shard_map
"""

from jax import shard_map

__all__ = ["shard_map"]
