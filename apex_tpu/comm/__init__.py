"""apex_tpu.comm — the distributed communication backend.

The reference's comm backend is NCCL reached through ``torch.distributed``
(apex/parallel/distributed.py — flat_dist_call calls dist.all_reduce;
apex/transformer uses dist.all_gather / reduce_scatter / batch_isend_irecv;
contrib adds raw NCCL + CUDA IPC). On TPU none of that exists or is needed:
the fabric is ICI (intra-slice) + DCN (cross-slice), and the collectives are
XLA ops emitted from ``jax.lax`` primitives under ``shard_map``/``pjit`` on a
``jax.sharding.Mesh``.

This module is the single place upper layers get their mesh and collectives
from, so nothing else in the framework calls raw ``jax.lax`` comm ops or
constructs meshes ad-hoc (SURVEY §3.4's "thin comm module" design). Axis
conventions:

- ``data``  — data parallel; outermost, so multi-slice layouts put it on DCN.
- ``model`` — tensor/sequence parallel (Megatron TP group); innermost → ICI.
- ``pipe``  — pipeline stages, between the two.
- ``expert``— reserved extension point (the reference has no EP; SURVEY §3.3).

Process bootstrap: `jax.distributed.initialize` (multi-host), not
WORLD_SIZE/RANK env bootstrap (reference: apex/parallel/multiproc.py).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = [
    "AXIS_DATA", "AXIS_MODEL", "AXIS_PIPE", "AXIS_EXPERT", "AXIS_CONTEXT",
    "make_mesh", "make_hybrid_mesh", "default_mesh", "get_mesh", "set_mesh",
    "reset_mesh", "axis_size",
    "all_reduce", "all_reduce_max", "all_gather", "reduce_scatter",
    "ppermute", "broadcast_from", "axis_index", "initialize_distributed",
]

AXIS_DATA = "data"
AXIS_MODEL = "model"
AXIS_PIPE = "pipe"
AXIS_EXPERT = "expert"
AXIS_CONTEXT = "context"  # sequence/context parallel (ring attention)

_MESH: Optional[Mesh] = None


def initialize_distributed(**kwargs):
    """Multi-host bootstrap. TPU equivalent of the reference's
    ``torch.distributed.init_process_group("nccl", init_method="env://")``
    (examples/imagenet/main_amp.py — args.distributed block): on TPU pods the
    coordinator/process ids come from the runtime, so this is one call."""
    jax.distributed.initialize(**kwargs)


def make_mesh(axes: Dict[str, int], devices: Optional[Sequence] = None) -> Mesh:
    """Build a Mesh from ``{axis_name: size}`` in the given axis order.

    Axis order is physical: earlier axes change slowest across the device
    list, so callers should order axes outermost-first (``data`` before
    ``model``) to keep TP collectives on ICI neighbours — the TPU analogue of
    apex putting NCCL rings inside a node.
    """
    devices = list(devices if devices is not None else jax.devices())
    names = tuple(axes)
    sizes = tuple(int(axes[n]) for n in names)
    need = int(np.prod(sizes)) if sizes else 1
    if need > len(devices):
        raise ValueError(
            f"mesh {dict(axes)} needs {need} devices, have {len(devices)}")
    arr = np.asarray(devices[:need], dtype=object).reshape(sizes)
    return Mesh(arr, names)


def make_hybrid_mesh(ici_axes: Dict[str, int],
                     dcn_axes: Dict[str, int]) -> Mesh:
    """Multi-slice mesh: ``dcn_axes`` partition ACROSS slices (riding DCN,
    the slow fabric), ``ici_axes`` within a slice (ICI). This is how the
    SURVEY §3.4 mapping scales past one slice: put data parallelism (the
    once-per-step grad allreduce) on DCN and TP/SP/PP (the per-layer
    collectives) on ICI — the TPU analogue of apex keeping NCCL rings
    inside a node and gradient averaging across nodes.

    Example on 4 slices of a v5e-64::

        mesh = comm.make_hybrid_mesh(ici_axes={"pipe": 4, "model": 16},
                                     dcn_axes={"data": 4})

    Axis names may appear in only one of the two dicts (size 1 elsewhere).
    On a single slice (or hosts whose devices carry no slice topology,
    e.g. the CPU test backend) this degrades to :func:`make_mesh` with the
    DCN axes outermost — same names, same shape, so code written against
    the hybrid mesh runs unchanged in CI.
    """
    overlap = set(ici_axes) & set(dcn_axes)
    if overlap:
        raise ValueError(
            f"axes {sorted(overlap)} appear in both ici_axes and dcn_axes; "
            f"an axis lives on exactly one fabric")
    names = tuple(dcn_axes) + tuple(ici_axes)
    devices = jax.devices()
    n_slices = len({getattr(d, "slice_index", 0) for d in devices})
    if n_slices > 1:
        from jax.experimental import mesh_utils

        ici_shape = [ici_axes.get(n, 1) for n in names]
        dcn_shape = [dcn_axes.get(n, 1) for n in names]
        arr = mesh_utils.create_hybrid_device_mesh(
            ici_shape, dcn_shape, devices=devices)
        return Mesh(arr, names)
    # single slice / no slice topology: plain mesh, DCN axes outermost
    # ({**dcn, **ici} insertion order is exactly `names`)
    return make_mesh({**dcn_axes, **ici_axes})


def ensure_devices(n: int) -> list:
    """Return ≥ ``n`` devices of the attached platform.

    On an accelerator backend that has fewer than ``n`` this RAISES,
    naming what is attached and what was asked: a recipe asked to span
    four chips on a one-chip host must not carry on somewhere else. Only
    a CPU backend that is short is widened — to ``n`` virtual CPU
    devices, which is how hermetic runs of the multi-device recipes
    work (the test suite gets its 8 from ``tests/conftest.py`` and never
    reaches this branch).

    **Call this at program start, before creating any jax arrays or
    compiled computations.** Widening re-initialises the CPU backend
    (``jax_num_cpu_devices`` refuses to change on a live one, hence the
    ``clear_backends`` first), which invalidates every live array and
    jitted executable; to prevent silent corruption it refuses to do so
    while arrays are live.
    """
    devices = jax.devices()
    if len(devices) >= n:
        return devices
    platform = devices[0].platform
    if platform != "cpu":
        raise RuntimeError(
            f"ensure_devices({n}): the attached {platform} backend has "
            f"{len(devices)} device(s) ({devices[0].device_kind}) and "
            f"{n} were asked for; run on a host with at least {n} "
            f"chips, or set JAX_PLATFORMS=cpu for a virtual-device "
            f"rehearsal")
    import gc

    from jax.extend.backend import clear_backends

    live = jax.live_arrays()
    if live:
        # dead-but-uncollected arrays (reference cycles, pytest-pinned
        # tracebacks) must not trigger a spurious refusal
        gc.collect()
        live = jax.live_arrays()
    if live:
        raise RuntimeError(
            f"ensure_devices({n}) would re-initialise the CPU backend, "
            f"invalidating {len(live)} live array(s). Call it before "
            "creating any arrays or compiled computations (recipe "
            "start), as the examples do.")
    clear_backends()
    jax.config.update("jax_num_cpu_devices", n)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(f"need {n} devices, have {len(devices)}")
    return devices


def default_mesh() -> Mesh:
    """All local devices on a single ``data`` axis — what plain apex DDP
    (pure data parallelism) corresponds to."""
    return make_mesh({AXIS_DATA: len(jax.devices())})


def set_mesh(mesh: Mesh) -> Mesh:
    """Install the process-global mesh (parallel_state-style registry;
    reference: apex/transformer/parallel_state.py keeps module globals)."""
    global _MESH
    _MESH = mesh
    return mesh


def get_mesh() -> Mesh:
    global _MESH
    if _MESH is None:
        _MESH = default_mesh()
    return _MESH


def reset_mesh() -> None:
    """Drop the installed mesh (parallel_state.destroy_model_parallel path);
    the next get_mesh() lazily rebuilds the data-only default."""
    global _MESH
    _MESH = None


def axis_size(axis_name: str, mesh: Optional[Mesh] = None) -> int:
    mesh = mesh if mesh is not None else get_mesh()
    return int(mesh.shape.get(axis_name, 1))


# ----------------------------------------------------------------- collectives
# Thin wrappers so upper layers never touch jax.lax comm primitives directly.
# All of these are only meaningful inside shard_map/pmap with the named axis
# bound; under plain jit they raise NameError from XLA, matching the reference
# where dist.all_reduce without init_process_group raises.

def _account(op: str, tree) -> None:
    """Comm-health accounting (apex_tpu.telemetry): bytes/calls/leaves
    counters per collective. Runs at TRACE time — once per compiled
    program, so the counters read what ONE execution moves (see
    telemetry.account_collective). Lazy import keeps this module
    importable standalone and the disabled path one dict lookup."""
    from apex_tpu import telemetry

    telemetry.account_collective(op, tree)


def all_reduce(x, axis_name: str, op: str = "sum"):
    """dist.all_reduce equivalent. op: sum|mean|max|min."""
    _account("all_reduce", x)
    if op == "sum":
        return jax.lax.psum(x, axis_name)
    if op == "mean":
        return jax.lax.pmean(x, axis_name)
    if op == "max":
        return jax.lax.pmax(x, axis_name)
    if op == "min":
        return jax.lax.pmin(x, axis_name)
    raise ValueError(f"unknown reduce op {op!r}")


def all_reduce_max(x, axis_name: str):
    _account("all_reduce", x)
    return jax.lax.pmax(x, axis_name)


def all_gather(x, axis_name: str, axis: int = 0, tiled: bool = True):
    """dist.all_gather equivalent (concatenate along ``axis``)."""
    _account("all_gather", x)
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: str, axis: int = 0):
    """dist.reduce_scatter equivalent (sum + scatter along ``axis``)."""
    _account("reduce_scatter", x)
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=axis,
                                tiled=True)


def ppermute(x, axis_name: str, perm):
    """Point-to-point collective permute — the TPU stand-in for every
    send/recv pattern in the reference (pipeline p2p_communication._communicate
    and the halo exchanges of contrib peer_memory/nccl_p2p)."""
    _account("ppermute", x)
    return jax.lax.ppermute(x, axis_name, perm)


def axis_index(axis_name: str):
    """This shard's coordinate along the axis (dist.get_rank equivalent)."""
    return jax.lax.axis_index(axis_name)


def broadcast_from(x, axis_name: str, src: int = 0):
    """dist.broadcast equivalent: every member gets src's value. Apex DDP
    broadcasts params from rank 0 at init (distributed.py — __init__'s
    flat_dist_call(dist.broadcast)); under SPMD initialization is already
    replicated, so this exists for API parity and odd cases.

    One-to-many can't be a single ppermute (sources must be unique); the
    SPMD form is mask + psum, which XLA lowers to a broadcast from src.
    """
    _account("broadcast", x)
    x = jnp.asarray(x)
    idx = jax.lax.axis_index(axis_name)
    masked = jnp.where(idx == src, x, jnp.zeros_like(x))
    # psum promotes bool/narrow ints; the broadcast contract preserves dtype
    return jax.lax.psum(masked, axis_name).astype(x.dtype)


def replicated_sharding(mesh: Optional[Mesh] = None) -> NamedSharding:
    mesh = mesh if mesh is not None else get_mesh()
    return NamedSharding(mesh, PartitionSpec())


def data_sharding(mesh: Optional[Mesh] = None,
                  axis: str = AXIS_DATA) -> NamedSharding:
    """Batch-dim sharding over the data axis."""
    mesh = mesh if mesh is not None else get_mesh()
    return NamedSharding(mesh, PartitionSpec(axis))
