"""One fleet worker process: ``python -m apex_tpu.serving.fleet_worker
--socket <path> --replica <i>``.

Spawned by :class:`~apex_tpu.serving.FleetController`, never by hand:
the worker connects back to the controller's AF_UNIX socket,
identifies itself with a hello frame, builds its OWN engine +
:class:`~apex_tpu.serving.Scheduler` from the spec the controller
ships in the ``init`` RPC, and then serves a strict request-response
loop until ``close`` (or its process is killed — the fleet's chaos
``replica_death`` is a real SIGKILL at this process).

Everything that crosses the transport is a versioned wire form (see
:mod:`~apex_tpu.serving.fleet`); the worker's replies carry the same
``id`` as the request, so a controller that timed out on one RPC can
discard the late reply by id instead of desyncing. A handler
exception is reported as an ``error`` reply — the controller decides
whether that is fatal — EXCEPT :class:`~apex_tpu.serving.QueueFull`
on ``submit``, which is a protocol-level outcome (``queue_full`` +
the measured ``retry_after_s`` hint), not an error: the controller's
spill loop consumes it.

:func:`build_engine_from_spec` is module-level and importable on
purpose: the fleet's bitwise-parity test builds its IN-PROCESS oracle
engines with the same function and the same spec dicts it hands the
controller, so the only difference between the two fronts is the
process boundary. Engine construction is deterministic — the model's
parameters come from ``init_seed`` via ``jax.random.PRNGKey``, so two
processes building from one spec hold bitwise-identical weights on
the same backend.
"""

from __future__ import annotations

import argparse
import socket
import time
from typing import List, Optional

__all__ = ["build_engine_from_spec", "build_scheduler_from_spec",
           "main"]


def build_engine_from_spec(spec: dict):
    """Deterministically build an :class:`~apex_tpu.serving.Engine`
    from a plain-dict ``spec`` (the only engine description that can
    cross a process boundary)::

        {"model": {"vocab_size": 64, "hidden": 32, ...}     # TransformerLM
                  | {"preset": "small", "vocab_size": ...}, # create_lm
         "init_seed": 0,                # PRNGKey for m.init → params
         "engine": {"slots": 2, "max_len": 64, "prefill_len": 24,
                    "chunk_len": 8, "prefix_pool": 4, "seed": 5,
                    "policy": "O0",     # resolved by name per process
                    # optional: page_len, num_pages, top_k,
                    # "lora": {"rank": 4, ...} → per-worker LoRAConfig,
                    "host_tier_bytes": 1 << 20}}  # → per-worker HostTier

    Imports live inside the function: the controller imports this
    module's codec-free helpers without paying for jax, and the test
    suite calls it directly to build bitwise-identical oracle
    engines.
    """
    import jax
    import jax.numpy as jnp

    from apex_tpu.amp.policy import resolve_policy
    from apex_tpu.models.transformer_lm import TransformerLM, create_lm
    from apex_tpu.serving import Engine

    model_kw = dict(spec.get("model", {}))
    if "preset" in model_kw:
        size = model_kw.pop("preset")
        m = create_lm(size=size, **model_kw)
    else:
        m = TransformerLM(**model_kw)
    params = m.init(
        jax.random.PRNGKey(int(spec.get("init_seed", 0))),
        jnp.zeros((1, 8), jnp.int32), train=False)["params"]
    eng_kw = dict(spec.get("engine", {}))
    policy = resolve_policy(eng_kw.pop("policy", "O0"), verbose=False)
    tier_bytes = eng_kw.pop("host_tier_bytes", None)
    if tier_bytes is not None:
        eng_kw["host_tier"] = int(tier_bytes)
    lora_kw = eng_kw.pop("lora", None)
    if lora_kw is not None:
        # the LoRA tier crosses the process boundary as a plain dict
        # of LoRAConfig fields — resolved per process, like policy
        from apex_tpu.serving.lora import LoRAConfig
        eng_kw["lora"] = LoRAConfig(**dict(lora_kw))
    return Engine(m, params, policy=policy, **eng_kw)


def build_scheduler_from_spec(engine, scheduler_kw: dict, *,
                              role: str = "both", registry=None):
    """The worker's :class:`~apex_tpu.serving.Scheduler` from the
    controller-shipped plain-value keywords (callable seams —
    fault_policy, on_requeue — cannot cross and stay None)."""
    from apex_tpu.serving import Scheduler

    return Scheduler(engine, role=role, registry=registry,
                     **dict(scheduler_kw))


class _WorkerState:
    """Everything one worker process owns: its engine, scheduler,
    per-process telemetry registry, and the completion cursor (the
    index into ``scheduler.completed`` up to which the controller has
    already absorbed results)."""

    def __init__(self, replica: int):
        self.replica = int(replica)
        self.engine = None
        self.sched = None
        self.registry = None
        self.sched_kw: dict = {}
        self.completed_seen = 0


def _geometry(state: _WorkerState) -> dict:
    eng = state.engine
    pc = getattr(eng, "prefix_cache", None)
    return {
        "slots": eng.slots,
        "max_len": eng.max_len,
        "prefill_len": eng.prefill_len,
        "chunk_len": eng.chunk_len,
        "retain_prefixes": bool(state.sched.retain_prefixes),
        "block_len": pc.block_len if pc is not None else None,
        "role": state.sched.role,
    }


def _handle(state: _WorkerState, msg: dict) -> Optional[dict]:
    """Dispatch one RPC. Returns the reply payload (without the id),
    or None for one-way ops that must not answer. Raising propagates
    to the serve loop, which reports it as an ``error`` reply."""
    from apex_tpu.serving import (PoolAuditor, QueueFull,
                                  request_from_wire, request_to_wire,
                                  snapshot_to_wire)
    from apex_tpu.telemetry import MetricsRegistry

    op = msg["op"]

    if op == "ping":
        return {"pong": True}

    if op == "hang":
        # the chaos worker_hang: stop answering the transport while
        # the process stays alive — exactly what the controller's
        # missed-beat detector (and nothing else) must catch. The
        # sleep outlives any test; the controller SIGKILLs the
        # process once it declares the hang.
        time.sleep(float(msg.get("hang_s", 3600.0)))
        return None                         # pragma: no cover

    if op == "init":
        state.registry = MetricsRegistry()
        state.engine = build_engine_from_spec(msg["spec"])
        state.sched_kw = dict(msg.get("scheduler") or {})
        state.sched = build_scheduler_from_spec(
            state.engine, state.sched_kw,
            role=msg.get("role", "both"), registry=state.registry)
        state.sched.replica_index = int(msg.get("replica",
                                                state.replica))
        state.completed_seen = 0
        return {"ok": True, "geometry": _geometry(state)}

    if op == "probe":
        match_len = 0
        prompt = msg.get("prompt")
        pc = getattr(state.engine, "prefix_cache", None)
        if prompt is not None and pc is not None:
            match_len = pc.probe(prompt, keys=msg.get("keys"))
        return {"match_len": int(match_len),
                "snapshot":
                    snapshot_to_wire(state.sched.load_snapshot())}

    if op == "submit":
        r = request_from_wire(msg["request"])
        is_handoff = bool(msg.get("is_handoff"))
        try:
            state.sched.submit(r, prefix_keys=msg.get("prefix_keys"),
                               count_rejection=False,
                               _handoff=is_handoff)
        except QueueFull as e:
            return {"queue_full": True,
                    "retry_after_s": e.retry_after_s}
        if is_handoff and msg.get("handoff") is not None:
            _import_handoff(state, r, msg["handoff"],
                            msg.get("prefix_keys"))
        return {"ok": True}

    if op == "step":
        progress = state.sched.step()
        done = state.sched.completed[state.completed_seen:]
        state.completed_seen = len(state.sched.completed)
        return {"progress": bool(progress),
                "completed": [request_to_wire(r) for r in done]}

    if op == "drain":
        drained = state.sched.drain_requests()
        return {"requests": [request_to_wire(r) for r in drained]}

    if op == "take_handoffs":
        return {"handoffs": _export_handoffs(state)}

    if op == "lora_register":
        # adapter payloads cross as plain {site: (A, B)} numpy pairs —
        # the same by-value discipline as disagg arena records; the
        # engine CRCs them at rest like any local registration
        state.engine.lora_register(msg["name"], msg["sites"],
                                   alpha=float(msg.get("alpha", 1.0)))
        return {"ok": True}

    if op == "prefix_stats":
        pc = getattr(state.engine, "prefix_cache", None)
        return {"stats": pc.stats() if pc is not None else {}}

    if op == "metrics":
        return {"snapshot": state.registry.snapshot()}

    if op == "audit_drained":
        # the cross-process zero-leak pin: the pool's invariants hold
        # (audit raises PoolInvariantError otherwise) and a clearing
        # reset leaves nothing but the sentinel allocated
        aud = PoolAuditor()
        aud.audit(state.engine)
        state.engine.reset(clear_prefixes=True)
        after = aud.audit(state.engine)
        if after["pages_in_use"] != 0:
            raise RuntimeError(
                f"{after['pages_in_use']} page(s) still allocated "
                "after a clearing reset — the drain leaked")
        return {"audit": after}

    if op == "set_role":
        # elastic re-role on the SAME engine: pool, prefix cache and
        # arena survive; only the scheduler (whose role gates
        # admission) is rebuilt. The controller drained us first.
        state.sched.close()
        state.sched = build_scheduler_from_spec(
            state.engine, state.sched_kw, role=msg["role"],
            registry=state.registry)
        state.sched.replica_index = state.replica
        state.completed_seen = 0
        return {"ok": True, "geometry": _geometry(state)}

    if op == "close":
        if state.sched is not None:
            state.sched.close()
        return {"ok": True, "bye": True}

    raise ValueError(f"unknown op {op!r}")


def _import_handoff(state: _WorkerState, r, record_wire: dict,
                    keys) -> None:
    """Decode-side handoff adoption: import the shipped arena record
    into THIS worker's host tier under its original key (a request
    uid — positive, so it can never collide with the cache's negative
    synthetic keys), register it as a born-swapped prefix, and note
    the pairing so admission resolves it (CRC-verified swap-in on the
    happy path, the counted verified-miss re-prefill otherwise). A
    declined import (arena too small) degrades to the cold handoff —
    the request re-prefills, never faults."""
    eng = state.engine
    tier = getattr(eng, "host_tier", None)
    if tier is None:                        # pragma: no cover
        return
    key = tier.import_record(record_wire)
    if key is None:
        return                              # declined: cold handoff
    cap = ((len(r.prompt) - 1) // eng.chunk_len) * eng.chunk_len
    outcome = eng.prefix_cache.register_handoff(
        key, r.prompt[:cap], n_pages=cap // eng.page_len, keys=keys)
    if outcome == "registered":
        state.sched.note_handoff(r.uid, key)
    else:                                   # pragma: no cover
        tier.discard(key)


def _export_handoffs(state: _WorkerState) -> List[dict]:
    """Prefill-side handoff export: pop every READY hand-over from
    the scheduler, drop the exporter's cache entry (the swapped
    entry's arena bytes stay), and POP the arena record itself into a
    wire form — bytes and swap-out CRCs by value. A record the arena
    evicted (or that never finished its swap-out) exports as None:
    the key-less cold handoff, per the verified-miss contract."""
    from apex_tpu.serving import request_to_wire

    eng = state.engine
    tier = getattr(eng, "host_tier", None)
    out = []
    for r, key, keys in state.sched.take_handoffs():
        record_wire = None
        if key is not None:
            eng.prefix_cache.drop(key)
            if tier is not None:
                record_wire = tier.export_record(key)
        out.append({"request": request_to_wire(r),
                    "record": record_wire, "keys": keys})
    return out


def main(argv: Optional[List[str]] = None) -> int:
    from .fleet import recv_frame, send_frame

    ap = argparse.ArgumentParser(
        description="apex_tpu fleet worker (spawned by "
                    "FleetController — not a user entry point)")
    ap.add_argument("--socket", required=True,
                    help="controller's transport address: an AF_UNIX "
                         "socket path, or tcp:host:port")
    ap.add_argument("--replica", required=True, type=int,
                    help="this worker's fleet index")
    args = ap.parse_args(argv)

    state = _WorkerState(args.replica)
    if args.socket.startswith("tcp:"):
        _, host, port = args.socket.split(":", 2)
        conn = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        conn.connect((host, int(port)))
        # the RPC frames are small and strictly request-response:
        # never let Nagle hold a reply back
        conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    else:
        conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        conn.connect(args.socket)
    import os
    send_frame(conn, {"op": "hello", "replica": state.replica,
                      "pid": os.getpid()})
    try:
        while True:
            try:
                msg = recv_frame(conn)
            except (EOFError, OSError):
                break           # controller went away: exit quietly
            try:
                reply = _handle(state, msg)
            except BaseException as e:      # noqa: BLE001 — reported
                reply = {"error": f"{type(e).__name__}: {e}"}
            if reply is None:
                continue                    # one-way op
            reply["id"] = msg.get("id")
            try:
                send_frame(conn, reply)
            except (EOFError, OSError):
                break
            if msg.get("op") == "close" and "error" not in reply:
                break
    finally:
        conn.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
