"""The routing-policy core shared by :class:`~apex_tpu.serving.Router`
(threads in one interpreter) and
:class:`~apex_tpu.serving.FleetController` (one OS process per
replica).

Both fronts make the same two-signal decision — longest probed prefix
first, host-side load as the tie-break, spill across the candidate
order, fleet-level :class:`~apex_tpu.serving.QueueFull` carrying the
MAX of the per-replica ``retry_after_s`` hints — and the decision must
stay IDENTICAL whether the inputs arrived as in-process method calls
or as deserialized wire forms: the fleet's bitwise-parity pin
(`tests/L0/test_fleet.py`) compares token streams across the two
fronts, and any drift in ranking order would silently re-home requests
and break it. So the decision functions live HERE, pure and
host-only: no engine, no scheduler, no socket — just candidate
indices, probed match lengths and :meth:`Scheduler.load_snapshot`
dicts (or their wire forms — the ranking reads only the snapshot's
load keys, which serialization preserves verbatim).

Nothing in this module imports jax, numpy-heavy machinery or the
serving stack: a controller process that never builds an engine can
rank a fleet with only these functions and the snapshots its workers
shipped over.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Mapping, Optional, Sequence

__all__ = [
    "PLACEMENTS_CAP",
    "ROUTE_POLICIES",
    "fleet_retry_hint",
    "note_placement",
    "random_order",
    "rank_replicas",
]

#: The routing policies a replica front accepts: ``"affinity"``
#: (longest probed prefix, load tie-break), ``"least_loaded"`` (load
#: only), ``"random"`` (seeded control row).
ROUTE_POLICIES = ("affinity", "least_loaded", "random")

#: Placement-log entries kept (insertion order; re-placement
#: refreshes). Far above any live-request census — the cap only sheds
#: long-finished uids.
PLACEMENTS_CAP = 65536


def rank_replicas(candidates: Sequence[int],
                  match_lens: Mapping[int, int],
                  snapshots: Mapping[int, Mapping],
                  priority: int = 0,
                  adapter_hits: Optional[Mapping[int, int]] = None,
                  ) -> List[int]:
    """The candidate replicas best-first: longest probed prefix match,
    then resident-adapter hit (desc — see below), then free slots
    (desc), queue depth (asc), free pool pages (desc), host-arena
    headroom (desc), index (the deterministic last resort).
    ``snapshots[i]`` is a :meth:`Scheduler.load_snapshot` dict — or its
    wire form: the key set is part of the snapshot's versioned wire
    contract, so both fronts rank on identical fields. ``pages_free``
    / ``host_bytes_free`` may be None (no host tier) and
    rank as 0 — absent capacity is not headroom.

    ``priority`` is the routed request's STATIC base priority
    (``SLOConfig.base_priority`` — deterministic arithmetic, no clock,
    so both fronts compute the identical value). For a prioritized
    request (> 0) the pages tie-break counts ``preemptible_pages`` —
    pages a replica could reclaim by preempting lower-priority work —
    as free: a prioritized arrival ranks a preemption-rich replica as
    having that headroom NOW. Priority-0 requests (and snapshots
    predating the field — ``.get`` tolerates both wire v1 and literal
    test dicts) rank exactly as before.

    ``adapter_hits`` is the LoRA-affinity signal: ``adapter_hits[i]``
    is 1 when the routed request's adapter is resident in replica
    ``i``'s device arena (its snapshot's ``resident_adapters``
    membership — a bind there is a hit, elsewhere a swap-in), 0
    otherwise. Ranked right after the prefix match and before free
    slots: re-homing a resident adapter costs a full arena row
    re-place, more than a slot's worth of queueing. None (base-model
    requests, LoRA-less fleets) ranks exactly as before."""
    return sorted(candidates, key=lambda i: (
        -match_lens[i],
        -(adapter_hits[i] if adapter_hits is not None else 0),
        -snapshots[i]["slots_free"],
        snapshots[i]["queue_depth"],
        -((snapshots[i]["pages_free"] or 0)
          + ((snapshots[i].get("preemptible_pages") or 0)
             if priority > 0 else 0)),
        # hierarchical-KV tie-break: of two replicas equal on
        # slots/queue/pages, prefer the one with more host-arena
        # headroom — landing work on a replica whose swap arena is
        # nearly full accelerates its swapped-prefix shedding
        -(snapshots[i]["host_bytes_free"] or 0),
        i))


def random_order(candidates: Sequence[int], rng) -> List[int]:
    """The ``"random"`` policy's seeded shuffle (the bench's control
    row): a plain permutation of the candidates drawn from the
    caller's ``numpy`` Generator, so a front holding the same seed
    routes the same stream identically."""
    return [int(i) for i in rng.permutation(list(candidates))]


def fleet_retry_hint(
        hints: Iterable[Optional[float]]) -> Optional[float]:
    """The fleet-level ``retry_after_s``: the MAX of the per-replica
    hints (the fleet has space when its slowest-to-free replica does);
    None when no replica offered a measured hint — a replica with no
    decode EMA contributes None and never fakes a number."""
    return max((h for h in hints if h is not None), default=None)


def note_placement(placements: Dict[int, int], uid: int,
                   index: int, cap: int = PLACEMENTS_CAP) -> None:
    """Record ``uid`` → replica ``index`` in the bounded placement log
    (observability state — routing never reads it back). Pop-then-set
    refreshes insertion order, so the cap always sheds the
    LONGEST-finished uid first."""
    placements.pop(uid, None)
    placements[uid] = index
    while len(placements) > cap:
        placements.pop(next(iter(placements)))
