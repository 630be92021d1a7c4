"""Process-level replica fleet: out-of-process workers behind a
stdlib transport, fronted by a :class:`FleetController`.

The :class:`~apex_tpu.serving.Router` scales replicas as THREADS in
one interpreter — N replicas share one GIL and one core pool, which
is why its CPU-regime notes carry every aggregate-throughput claim to
silicon. This module takes the same fleet out-of-process: each
replica is a separate OS process (``python -m
apex_tpu.serving.fleet_worker``) owning its own JAX runtime, engine,
scheduler and telemetry registry, and the controller talks to it over
a thin stdlib transport. Per-process runtimes stop sharing a GIL, so
replica *scaling* finally becomes an honest CPU-box measurement too —
and the same seam is where a multi-host pod fleet lands.

**Transport.** One AF_UNIX listening socket per controller (in a
private temp dir); each worker connects at startup and sends a hello.
Frames are length-prefixed pickles::

    +----------------+----------------------------------------+
    | 4 bytes, >I    | pickled payload (versioned wire dicts) |
    | payload length |                                        |
    +----------------+----------------------------------------+

Every payload that crosses is an EXPLICIT wire form — requests and
load snapshots via :func:`~apex_tpu.serving.request_to_wire` /
:func:`~apex_tpu.serving.snapshot_to_wire` (scheduler.py), disagg
arena records via :func:`~apex_tpu.serving.record_to_wire`
(host_tier.py) — each versioned and loud on a version mismatch, so a
controller and worker from different trees fail fast instead of
deserializing garbage. RPCs are strictly request-response per worker
connection with a monotonic ``id``; stale replies (a pong that lost
its race against a ping timeout) are discarded by id, never
misattributed.

**Routing** is the Router's decision code, verbatim: the controller
ranks candidates with :mod:`~apex_tpu.serving.routing_policy` (the
SAME functions the in-process Router calls) over serialized probe
results and load snapshots polled per routed request, spills across
the order, and raises fleet-level
:class:`~apex_tpu.serving.QueueFull` with the max-of-hints
``retry_after_s`` only when every live worker is saturated. That
sharing is what makes the bitwise pin possible: in-process Router vs
process fleet produce token-identical streams on a seeded greedy
session workload (``tests/L0/test_fleet.py``).

**Health.** Every controller step pings every live worker
(``ping_timeout_s`` per ping). A missed ping marks the worker
*suspect* — it stops receiving routed work and step RPCs — and
``max_missed_beats`` consecutive misses declare it dead: the process
is killed (it may be alive-but-hung — the ``worker_hang`` fault kind
injects exactly that), its un-finished requests re-route onto
survivors with no retry charged, and its load gauges zero. A
transport EOF (the process actually died) skips the grace period and
declares death immediately.

**Rolling restart** (:meth:`FleetController.rolling_restart`): one
worker at a time, drain → close → wait → respawn → rejoin. Drained
requests re-route onto the rest of the fleet with their paid-compute
counters absorbed and no retry charged; the respawned worker rejoins
cold and re-registers prefixes warm as re-routed multi-turn traffic
lands on it (post-restart hit rate > 0, pinned via
``PrefixCache.stats_since`` deltas over the ``prefix_stats`` RPC).

**Elastic scale**: :meth:`~FleetController.add_replica` /
:meth:`~FleetController.remove_replica` under live traffic (the new
member is probed per routed request like any other — cold caches lose
affinity ties and win least-loaded ties, so it fills), and
:meth:`~FleetController.set_role` re-roles a worker under traffic
shift (the PR 17 residue: a disaggregated fleet refits a prefill
worker to decode when the mix moves). Disagg handoffs cross the
process boundary BY VALUE: the prefill worker exports the finished
arena record (bytes + swap-out CRCs — :meth:`HostTier.export_record`),
the controller ships it, and the decode worker imports it into its own
arena, where the ordinary CRC-verified swap-in resumes at the
committed offset; corruption anywhere degrades to the verified miss,
never a wrong token.

Telemetry: the controller emits ``serving.fleet.routed`` /
``affinity_hits`` / ``spills`` / ``requeued`` / ``worker_deaths`` /
``hangs_detected`` / ``restarts`` counters, the
``serving.fleet.workers_alive`` gauge, and the
``serving.fleet.heartbeat_s`` / ``serving.fleet.restart_s``
histograms; per-worker load gauges reuse the Router's documented
``serving.router.replica<i>.*`` namespace (one dashboard serves both
fronts, and ``render_prometheus`` already collapses it into labeled
families). Each worker process keeps its own
:class:`~apex_tpu.telemetry.MetricsRegistry`;
:meth:`FleetController.metrics_snapshot` merges them into one fleet
view (counters summed fleet-wide — the Router's shared-registry
semantics — gauges and histograms namespaced per worker). Request
``uid``\\ s cross the boundary verbatim in every wire form, so the
controller's ``route`` spans and a worker's completion records refer
to the same trace identity.
"""

from __future__ import annotations

import collections
import os
import pickle
import shutil
import socket
import struct
import subprocess
import sys
import tempfile
import time
import weakref
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from apex_tpu.log_util import get_logger

from .prefix_cache import PrefixCache
from .routing_policy import (ROUTE_POLICIES, fleet_retry_hint,
                             note_placement, random_order,
                             rank_replicas)
from .scheduler import (QueueFull, Request, RequestStatus,
                        request_from_wire, request_to_wire,
                        snapshot_from_wire)

__all__ = ["FleetController", "WorkerDied", "WorkerHandle",
           "recv_frame", "send_frame"]

_logger = get_logger("serving")

# ------------------------------------------------------------------ framing

_FRAME_HEADER = struct.Struct(">I")

#: Frames above this are a protocol error, not a big message: the
#: largest legitimate payload (a disagg record's page bytes) is tens
#: of MB on any geometry this stack runs.
MAX_FRAME_BYTES = 1 << 30


def send_frame(sock: socket.socket, obj) -> None:
    """Write ``obj`` as one length-prefixed pickle frame (4-byte
    big-endian length + payload). Pickle rather than JSON because
    arena-record wire forms carry raw ``bytes``; every dict that
    crosses is still an explicit versioned wire form — the pickle is
    transport encoding, never the contract."""
    payload = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    if len(payload) > MAX_FRAME_BYTES:
        raise ValueError(f"frame of {len(payload)} bytes exceeds the "
                         f"{MAX_FRAME_BYTES}-byte transport bound")
    sock.sendall(_FRAME_HEADER.pack(len(payload)) + payload)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise EOFError(
                f"peer closed mid-frame ({len(buf)}/{n} bytes)")
        buf += chunk
    return bytes(buf)


def recv_frame(sock: socket.socket):
    """Read one frame (blocking, honoring the socket's timeout).
    Raises :class:`EOFError` on a closed peer — the transport-level
    death signal — and ``ValueError`` on a length prefix past the
    transport bound (a desynced or corrupt stream, not a message)."""
    (n,) = _FRAME_HEADER.unpack(_recv_exact(sock, _FRAME_HEADER.size))
    if n > MAX_FRAME_BYTES:
        raise ValueError(f"frame length {n} exceeds the "
                         f"{MAX_FRAME_BYTES}-byte transport bound "
                         "(desynced stream?)")
    return pickle.loads(_recv_exact(sock, n))


class WorkerDied(RuntimeError):
    """The transport to a worker broke mid-RPC (EOF / reset): the
    process is gone or unreachable. The controller converts this into
    a death event — never into a caller-visible request failure."""


class WorkerHandle:
    """One worker process: its :class:`subprocess.Popen`, its
    connected transport socket, and its health state. RPCs are
    strictly request-response with a per-handle monotonic id; replies
    with a stale id (e.g. a pong that lost its race against a ping
    timeout) are discarded, so one timed-out RPC never desyncs the
    stream for the next."""

    def __init__(self, index: int, proc: subprocess.Popen,
                 conn: socket.socket, role: str):
        self.index = int(index)
        self.proc = proc
        self.conn = conn
        self.role = role
        self.alive = True
        self.missed_beats = 0
        self.geometry: Optional[dict] = None
        self._seq = 0

    def rpc(self, op: str, *, timeout: Optional[float] = None,
            **payload) -> dict:
        """One request-response round trip. Raises
        :class:`WorkerDied` on a broken transport, ``TimeoutError``
        when no matching reply lands within ``timeout`` (the caller
        decides whether that is a missed beat or a death), and
        ``RuntimeError`` when the worker reports an application-level
        error."""
        self._seq += 1
        seq = self._seq
        try:
            self.conn.settimeout(timeout)
            send_frame(self.conn, {"op": op, "id": seq, **payload})
            while True:
                reply = recv_frame(self.conn)
                if reply.get("id") == seq:
                    break               # stale replies fall through
        except socket.timeout as e:
            raise TimeoutError(
                f"worker {self.index} {op} RPC timed out after "
                f"{timeout}s") from e
        except (EOFError, OSError) as e:
            raise WorkerDied(
                f"worker {self.index} transport broke during {op}: "
                f"{e}") from e
        if "error" in reply:
            raise RuntimeError(
                f"worker {self.index} {op} failed: {reply['error']}")
        return reply

    def send_oneway(self, op: str, **payload) -> None:
        """Fire-and-forget (no reply expected — the ``hang``
        injection, which by design never answers). Transport errors
        are swallowed: a one-way to a corpse is a no-op."""
        try:
            self.conn.settimeout(5.0)
            send_frame(self.conn, {"op": op, "id": None, **payload})
        except (EOFError, OSError):
            pass

    def destroy(self) -> None:
        """Kill the process (idempotent) and close the transport."""
        if self.proc.poll() is None:
            self.proc.kill()
        try:
            self.proc.wait(timeout=30)
        except subprocess.TimeoutExpired:       # pragma: no cover
            pass
        try:
            self.conn.close()
        except OSError:                         # pragma: no cover
            pass
        self.alive = False


def _kill_procs(procs: List[subprocess.Popen]) -> None:
    """Finalizer backstop: no worker process may outlive a forgotten
    controller (the no-orphan contract even without close())."""
    for p in procs:
        if p.poll() is None:
            p.kill()
    for p in procs:
        try:
            p.wait(timeout=10)
        except Exception:                       # pragma: no cover
            pass


#: Scheduler keywords a fleet init may ship to workers — everything a
#: plain value can express. Callables and live objects (fault_policy,
#: auditor, tracer, on_requeue) cannot cross a process boundary and
#: are rejected loudly at construction. ``slo`` rides along because
#: SLOConfig is a frozen picklable dataclass; ``tenant_ledger`` is
#: deliberately ABSENT — a TenantLedger is process-local shared state
#: (and refuses to pickle), so each worker process builds its own from
#: the shipped config's tenant_weights (per-process fairness scope,
#: documented in docs/serving.md "Overload & SLO").
_WIRE_SCHED_KW = ("max_queue", "default_timeout_s", "eos_id",
                  "chunk_budget", "retain_prefixes",
                  "speculative", "pipeline_depth", "slo")


def _host_tpu_chips() -> int:
    """TPU chips this host exposes, counted from the device files the
    TPU runtime opens — WITHOUT touching jax, whose first backend call
    would take the chips for this process."""
    import glob

    return len(glob.glob("/dev/accel[0-9]*")
               or glob.glob("/dev/vfio/[0-9]*"))


def check_worker_backend(n_workers: int) -> None:
    """Refuse, at once and by name, a fleet whose workers could never
    reach their device. Workers inherit this process's environment
    verbatim and are not (yet — ROADMAP R6) given a chip each, and a
    TPU chip belongs to one process at a time: so a parent whose own
    jax backend already holds the chips, and any accelerator fleet of
    more than one worker (wider than the host's chips or not), would
    sit out ``spawn_timeout_s`` and die with a transport error.
    ``JAX_PLATFORMS=cpu`` in the environment means CPU workers, which
    share nothing and always start."""
    if os.environ.get("JAX_PLATFORMS", "").lower() == "cpu":
        return
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        import jax

        platform = jax.default_backend()
        if platform == "cpu":
            return
        raise RuntimeError(
            f"FleetController: this process's jax backend is already "
            f"live on {platform} and holds its chip(s); worker "
            f"processes inherit the environment and could not open "
            f"them. Start the fleet from a process that has not "
            f"touched jax, or export JAX_PLATFORMS=cpu for CPU workers")
    chips = _host_tpu_chips()
    if chips and n_workers > 1:
        raise RuntimeError(
            f"FleetController: {n_workers} workers asked for on a host "
            f"with {chips} TPU chip(s). A chip belongs to one process "
            f"and workers are not yet given a chip each (ROADMAP R6): "
            f"every worker would try to open all of them. Run one "
            f"worker, or export JAX_PLATFORMS=cpu for CPU workers")


class FleetController:
    """N out-of-process replica workers behind one prefix-aware
    least-loaded ``submit()`` — the :class:`~apex_tpu.serving.Router`
    surface, out-of-process (see module docstring).

    Parameters
    ----------
    specs:
        One engine-spec dict per worker (see
        :func:`~apex_tpu.serving.fleet_worker.build_engine_from_spec`
        for the schema) — usually N references to the same dict.
        Specs must be plain serializable values: each worker builds
        its OWN engine from its spec, which is also what makes the
        fleet's bitwise-parity pin meaningful (a test builds the
        in-process oracle engines from the same specs).
    registry:
        CONTROLLER-side :class:`~apex_tpu.telemetry.MetricsRegistry`
        (``serving.fleet.*`` + per-worker load gauges). Workers keep
        their own per-process registries;
        :meth:`metrics_snapshot` merges all of them into one view.
    route_policy / seed / roles / fault_plan / tracer:
        Exactly the Router's parameters. ``fault_plan`` is a
        CONTROLLER-tier plan: ``replica_death`` specs kill a real
        worker process (SIGKILL — no drain, the crash-consistency
        path), ``worker_hang`` specs make a worker stop answering its
        transport so the missed-beat detector must catch it.
    heartbeat ``ping_timeout_s`` / ``max_missed_beats``:
        One ping per live worker per step; a missed ping suspends
        routing to the worker, ``max_missed_beats`` consecutive
        misses (or any transport EOF, immediately) declare it dead.
    rpc_timeout_s:
        The working-RPC bound (init/submit/step/drain) — generous,
        because a worker's first step may be compiling.
    transport:
        ``None`` / ``("unix",)`` for the default AF_UNIX socket in a
        private temp dir; ``("tcp", host, port)`` for an AF_INET
        listener (``port=0`` picks a free port). Same frame codec,
        same RPC surface — the loopback TCP fleet is bitwise the
        AF_UNIX one.
    **scheduler_kw:
        Plain-value :class:`~apex_tpu.serving.Scheduler` keywords
        (:data:`_WIRE_SCHED_KW`), shipped to and applied by every
        worker.
    """

    def __init__(self, specs: Sequence[dict], *, registry=None,
                 route_policy: str = "affinity", seed: int = 0,
                 roles: Optional[Sequence[str]] = None,
                 fault_plan=None, tracer=None,
                 ping_timeout_s: float = 5.0,
                 max_missed_beats: int = 3,
                 rpc_timeout_s: float = 600.0,
                 spawn_timeout_s: float = 180.0,
                 python: Optional[str] = None,
                 transport: Optional[Sequence] = None,
                 **scheduler_kw):
        specs = [dict(s) for s in specs]
        if not specs:
            raise ValueError("FleetController needs at least one "
                             "worker spec")
        if route_policy not in ROUTE_POLICIES:
            raise ValueError(f"route_policy {route_policy!r} not in "
                             f"{ROUTE_POLICIES}")
        for k in scheduler_kw:
            if k not in _WIRE_SCHED_KW:
                raise ValueError(
                    f"scheduler keyword {k!r} cannot cross a process "
                    f"boundary (wire-able keywords: {_WIRE_SCHED_KW}; "
                    "role/on_requeue are fleet policy — pass "
                    "roles=[...])")
        self.roles: List[str] = [str(r) for r in roles] \
            if roles is not None else ["both"] * len(specs)
        if len(self.roles) != len(specs):
            raise ValueError(f"roles has {len(self.roles)} entries "
                             f"for {len(specs)} workers")
        self._validate_role_mix(self.roles)
        check_worker_backend(len(specs))
        self.registry = registry
        self.route_policy = route_policy
        self.fault_plan = fault_plan
        self.tracer = tracer
        self._rng = np.random.default_rng(seed)
        self._sched_kw = dict(scheduler_kw)
        # routing reads only STATIC priority arithmetic from the
        # config (base_priority — no clock), so controller and workers
        # rank identically from the same shipped SLOConfig
        self._slo = self._sched_kw.get("slo")
        self._specs = specs
        self._python = python or sys.executable
        self.ping_timeout_s = float(ping_timeout_s)
        self.max_missed_beats = int(max_missed_beats)
        self.rpc_timeout_s = float(rpc_timeout_s)
        self.spawn_timeout_s = float(spawn_timeout_s)

        self.workers: List[WorkerHandle] = []
        self.placements: Dict[int, int] = {}    # observability log
        self._home: Dict[int, int] = {}         # uid -> live placement
        self._inflight: Dict[int, Request] = {}  # canonical requests
        self._t0: Dict[int, float] = {}         # controller submit clock
        self.completed: List[Request] = []
        self._overflow: collections.deque = collections.deque()
        self._handoff_overflow: collections.deque = collections.deque()
        self._tick = 0
        self._closed = False
        self.affinity_enabled = False
        self._hasher: Optional[PrefixCache] = None

        self._dir = tempfile.mkdtemp(prefix="apex-fleet-")
        # transport: None / ("unix",) binds the default AF_UNIX path;
        # ("tcp", host, port) binds an AF_INET listener (port 0 asks
        # the OS for a free one — the bound port is re-read from
        # getsockname, so tests never race for a fixed port). The
        # frame codec is address-family-agnostic; workers get the
        # address as a "tcp:host:port" --socket argument.
        if transport is None or tuple(transport) == ("unix",):
            self._sock_path = os.path.join(self._dir, "fleet.sock")
            self._listener = socket.socket(socket.AF_UNIX,
                                           socket.SOCK_STREAM)
            self._listener.bind(self._sock_path)
            self._worker_addr = self._sock_path
        elif transport[0] == "tcp":
            kind, host, port = transport
            self._sock_path = None
            self._listener = socket.socket(socket.AF_INET,
                                           socket.SOCK_STREAM)
            self._listener.setsockopt(socket.SOL_SOCKET,
                                      socket.SO_REUSEADDR, 1)
            self._listener.bind((str(host), int(port)))
            bound_port = self._listener.getsockname()[1]
            self._worker_addr = f"tcp:{host}:{bound_port}"
        else:
            raise ValueError(
                f"unknown transport spec {transport!r} — expected "
                "None, ('unix',) or ('tcp', host, port)")
        self._listener.listen(64)
        # every Popen ever spawned (respawns included): the finalizer
        # and close() reap them ALL — no worker outlives the fleet
        self._procs: List[subprocess.Popen] = []
        self._finalizer = weakref.finalize(self, _kill_procs,
                                           self._procs)
        try:
            procs = [self._launch(i) for i in range(len(specs))]
            conns = self._accept(len(specs))
            for i, proc in enumerate(procs):
                self.workers.append(WorkerHandle(
                    i, proc, conns[i], self.roles[i]))
            for i, w in enumerate(self.workers):
                self._init_worker(w, specs[i])
            self._finish_geometry()
        except BaseException:
            self.close()
            raise

    # ----------------------------------------------------------- spawning
    def _launch(self, index: int) -> subprocess.Popen:
        """Start worker ``index``'s process (it connects back to the
        fleet socket and says hello). The environment is inherited
        verbatim — ``JAX_PLATFORMS=cpu`` in the parent reaches every
        worker — plus a PYTHONPATH entry for this tree so ``python
        -m apex_tpu.serving.fleet_worker`` resolves regardless of
        cwd."""
        env = dict(os.environ)
        pkg_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        prev = env.get("PYTHONPATH")
        env["PYTHONPATH"] = pkg_root + (
            os.pathsep + prev if prev else "")
        proc = subprocess.Popen(
            [self._python, "-m", "apex_tpu.serving.fleet_worker",
             "--socket", self._worker_addr, "--replica", str(index)],
            env=env)
        self._procs.append(proc)
        return proc

    def _accept(self, n: int) -> Dict[int, socket.socket]:
        """Accept ``n`` worker connections (workers identify
        themselves in their hello frame — accept order is
        connection-race order, never worker order)."""
        conns: Dict[int, socket.socket] = {}
        self._listener.settimeout(self.spawn_timeout_s)
        try:
            while len(conns) < n:
                conn, _ = self._listener.accept()
                conn.settimeout(self.spawn_timeout_s)
                if conn.family == socket.AF_INET:
                    conn.setsockopt(socket.IPPROTO_TCP,
                                    socket.TCP_NODELAY, 1)
                hello = recv_frame(conn)
                if hello.get("op") != "hello":
                    conn.close()
                    raise RuntimeError(
                        f"expected a worker hello, got {hello!r}")
                conns[int(hello["replica"])] = conn
        except socket.timeout as e:
            raise RuntimeError(
                f"worker spawn timed out after {self.spawn_timeout_s}s "
                f"({len(conns)}/{n} connected) — check the worker "
                "process logs") from e
        return conns

    def _init_worker(self, w: WorkerHandle, spec: dict) -> None:
        reply = w.rpc("init", timeout=self.spawn_timeout_s, spec=spec,
                      scheduler=self._sched_kw, role=w.role,
                      replica=w.index)
        w.geometry = reply["geometry"]

    def _finish_geometry(self) -> None:
        """Post-init fleet validation — the Router's geometry and
        affinity rules, read from the workers' init replies."""
        geos = [w.geometry for w in self.workers]
        g0 = {k: geos[0][k] for k in ("slots", "max_len",
                                      "prefill_len", "chunk_len")}
        for i, g in enumerate(geos[1:], 1):
            gi = {k: g[k] for k in g0}
            if gi != g0:
                raise ValueError(
                    f"worker {i} serving geometry {gi} differs from "
                    f"worker 0's {g0} — the fleet routes any request "
                    "to any worker, so geometry must agree")
        self.affinity_enabled = (
            self.route_policy == "affinity"
            and all(g["retain_prefixes"] for g in geos))
        if self.affinity_enabled:
            blocks = {g["block_len"] for g in geos}
            if len(blocks) > 1:
                raise ValueError(
                    f"prefix block_len differs across workers "
                    f"({sorted(blocks)}): one set of rolling hashes "
                    "must probe every cache")
            # a host-only hasher: the controller computes each
            # prompt's rolling block keys ONCE and ships them in
            # probe and submit payloads (same hash function, no
            # engine, no recompute per worker)
            self._hasher = PrefixCache(block_len=blocks.pop())

    @staticmethod
    def _validate_role_mix(roles: Sequence[str]) -> None:
        if any(r != "both" for r in roles):
            if not any(r in ("prefill", "both") for r in roles):
                raise ValueError(
                    f"roles {list(roles)} has no prefill-capable "
                    "worker: nothing can ingest a prompt")
            if not any(r in ("decode", "both") for r in roles):
                raise ValueError(
                    f"roles {list(roles)} has no decode-capable "
                    "worker: nothing can emit a token")

    @property
    def _mixed(self) -> bool:
        return any(w.role != "both" for w in self.workers)

    # ------------------------------------------------------------- routing
    def _alive_indices(self) -> List[int]:
        idx = [i for i, w in enumerate(self.workers)
               if w.alive and w.missed_beats == 0]
        if not idx:
            raise RuntimeError(
                "no live workers — the fleet is an outage, not a "
                "routing event")
        return idx

    def _capable_indices(self, capability: Optional[str]) -> List[int]:
        idx = self._alive_indices()
        if capability is None or not self._mixed:
            return idx
        want = ("prefill", "both") if capability == "prefill" \
            else ("decode", "both")
        idx = [i for i in idx if self.workers[i].role in want]
        if not idx:
            raise RuntimeError(
                f"no live {capability}-capable worker — the fleet "
                "lost a whole role tier (outage, not a routing "
                "event)")
        return idx

    def _route_order(self, request: Request,
                     capability: Optional[str] = None):
        """``(keys, ordered_workers, match_lens)`` — the Router's
        `_route_order`, with probes and load snapshots arriving as
        wire forms over one ``probe`` RPC per candidate. A worker
        whose transport breaks mid-probe is declared dead and simply
        drops out of the candidate set."""
        alive = self._capable_indices(capability)
        if self.route_policy == "random":
            order = random_order(alive, self._rng)
            snaps = self._poll(alive)
            order = [i for i in order if i in snaps]
            if not order:
                raise RuntimeError("no live workers — the fleet is "
                                   "an outage, not a routing event")
            return None, order, {i: 0 for i in order}
        keys = None
        send_prompt = False
        if self.affinity_enabled:
            if len(request.prompt) < self._hasher.block_len:
                keys = []       # sub-block: can never match, skip probes
            else:
                prompt = tuple(request.prompt)
                keys = self._hasher.block_keys(
                    prompt, len(prompt) // self._hasher.block_len)
                send_prompt = True
        lens: Dict[int, int] = {i: 0 for i in alive}
        snaps: Dict[int, dict] = {}
        for i in alive:
            try:
                reply = self.workers[i].rpc(
                    "probe", timeout=self.rpc_timeout_s,
                    prompt=[int(t) for t in request.prompt]
                    if send_prompt else None,
                    keys=keys if send_prompt else None)
            except (WorkerDied, TimeoutError) as e:
                self._declare_dead(i, reason=str(e))
                continue
            lens[i] = int(reply["match_len"])
            snaps[i] = snapshot_from_wire(reply["snapshot"])
        cand = [i for i in alive if i in snaps]
        if not cand:
            raise RuntimeError("no live workers — the fleet is an "
                               "outage, not a routing event")
        pri = self._slo.base_priority(request) \
            if self._slo is not None else 0
        # LoRA adapter affinity — the Router's rule verbatim, read
        # from the snapshot wire form's resident_adapters column
        hits = None
        if request.adapter is not None:
            hits = {i: int(request.adapter
                           in (snaps[i].get("resident_adapters") or ()))
                    for i in cand}
        return keys, rank_replicas(cand, lens, snaps, priority=pri,
                                   adapter_hits=hits), lens

    def _poll(self, indices: Sequence[int]) -> Dict[int, dict]:
        """Load snapshots (wire → plain dict) for ``indices``; dead
        transports drop out after being declared."""
        snaps: Dict[int, dict] = {}
        for i in list(indices):
            try:
                reply = self.workers[i].rpc(
                    "probe", timeout=self.rpc_timeout_s,
                    prompt=None, keys=None)
            except (WorkerDied, TimeoutError) as e:
                self._declare_dead(i, reason=str(e))
                continue
            snaps[i] = snapshot_from_wire(reply["snapshot"])
        return snaps

    def lora_register(self, name: str, sites, *,
                      alpha: float = 1.0) -> None:
        """Broadcast adapter ``name`` into every LIVE worker's LoRA
        host store (by value — ``{site: (A, B)}`` numpy pairs cross
        the frame codec like disagg arena records). Any worker's
        rejection (no LoRA tier, bad geometry, store full of pinned
        records) propagates loudly: the fleet routes any adapter
        request to any worker, so registration must be all-or-error,
        never a partial fleet that serves some replicas and fails
        others."""
        for i in self._alive_indices():
            self.workers[i].rpc("lora_register",
                                timeout=self.rpc_timeout_s,
                                name=str(name), sites=sites,
                                alpha=float(alpha))

    def submit(self, request: Request) -> Request:
        """Route ``request`` to the best live worker — the Router's
        submit contract verbatim: spills across the ranked order,
        fleet-level :class:`QueueFull` with the max-of-hints
        ``retry_after_s`` when every live worker is saturated."""
        t_route = self.tracer.now() if self.tracer is not None else 0.0
        keys, order, lens = self._route_order(request, "prefill")
        hints: List[Optional[float]] = []
        n_spilled = 0
        for i in order:
            try:
                reply = self.workers[i].rpc(
                    "submit", timeout=self.rpc_timeout_s,
                    request=request_to_wire(request),
                    prefix_keys=keys, handoff=None,
                    is_handoff=False)
            except (WorkerDied, TimeoutError) as e:
                self._declare_dead(i, reason=str(e))
                continue
            if "queue_full" in reply:
                hints.append(reply["retry_after_s"])
                n_spilled += 1
                continue
            note_placement(self.placements, request.uid, i)
            self._home[request.uid] = i
            self._inflight[request.uid] = request
            self._t0.setdefault(request.uid, time.perf_counter())
            if self.registry is not None:
                self.registry.counter_inc("serving.fleet.routed")
                if lens.get(i, 0) > 0:
                    self.registry.counter_inc(
                        "serving.fleet.affinity_hits")
                if n_spilled:
                    self.registry.counter_inc("serving.fleet.spills",
                                              n_spilled)
            if self.tracer is not None:
                self.tracer.event(request.uid, "route", t0=t_route,
                                  dur=self.tracer.now() - t_route,
                                  pid=i, replica=i,
                                  policy=self.route_policy,
                                  affinity_len=lens.get(i, 0),
                                  spills=n_spilled)
            return request
        hint = fleet_retry_hint(hints)
        if self.registry is not None:
            self.registry.counter_inc("serving.requests.rejected")
        suffix = f" (retry_after_s~{hint:.3f})" if hint else ""
        raise QueueFull(
            f"all {len(order)} live worker queues at capacity; retry "
            f"after a step() or shed load{suffix}", retry_after_s=hint)

    # ------------------------------------------------------------ stepping
    def step(self) -> bool:
        """One controller beat: consume scheduled chaos (process
        kills, hangs), run the heartbeat detector, re-route overflow,
        step every live worker and absorb its completions, then move
        disagg handoffs. Returns True if anything progressed."""
        tick = self._tick
        self._tick += 1
        if self.fault_plan is not None:
            for victim in self.fault_plan.take_replica_deaths(tick):
                self.kill_worker(victim, tick=tick)
            for victim in self.fault_plan.take_worker_hangs(tick):
                if 0 <= victim < len(self.workers) \
                        and self.workers[victim].alive:
                    _logger.warning(
                        "injecting worker_hang into worker %d at "
                        "tick %d", victim, tick)
                    self.workers[victim].send_oneway("hang")
        self._check_heartbeats()
        progress = self._drain_overflow()
        for i in list(self._alive_indices()):
            w = self.workers[i]
            if not w.alive:
                continue
            try:
                reply = w.rpc("step", timeout=self.rpc_timeout_s)
            except (WorkerDied, TimeoutError) as e:
                self._declare_dead(i, reason=str(e))
                continue
            progress = bool(reply["progress"]) or progress
            for wire in reply["completed"]:
                self._absorb_completion(wire)
                progress = True
        if self._mixed:
            progress = self._collect_handoffs() or progress
        self._emit_gauges()
        return progress

    def _check_heartbeats(self) -> None:
        """Ping every live worker. EOF → dead now; a timeout →
        suspect (missed beat, no routing) until ``max_missed_beats``
        consecutive misses declare it dead — the ``worker_hang``
        detector (an alive-but-unresponsive process never EOFs)."""
        for i, w in enumerate(self.workers):
            if not w.alive:
                continue
            t0 = time.perf_counter()
            try:
                w.rpc("ping", timeout=self.ping_timeout_s)
            except WorkerDied as e:
                self._declare_dead(i, reason=str(e))
                continue
            except TimeoutError:
                w.missed_beats += 1
                _logger.warning(
                    "worker %d missed heartbeat %d/%d", i,
                    w.missed_beats, self.max_missed_beats)
                if w.missed_beats >= self.max_missed_beats:
                    if self.registry is not None:
                        self.registry.counter_inc(
                            "serving.fleet.hangs_detected")
                    self._declare_dead(
                        i, reason=f"{w.missed_beats} consecutive "
                        "missed heartbeats")
                continue
            w.missed_beats = 0
            if self.registry is not None:
                self.registry.observe("serving.fleet.heartbeat_s",
                                      time.perf_counter() - t0)

    def _declare_dead(self, index: int, *, reason: str = "") -> None:
        """A worker is gone (transport EOF, missed-beat breach, or a
        kill): reap the process, re-route its un-finished canonical
        requests onto the survivors with no retry charged, zero its
        gauges. Raises only when the fleet is now empty — that is an
        outage."""
        w = self.workers[index]
        if not w.alive:
            return
        w.destroy()
        victims = [uid for uid, home in self._home.items()
                   if home == index]
        drained: List[Request] = []
        for uid in victims:
            self._home.pop(uid, None)
            r = self._inflight.pop(uid, None)
            if r is not None:
                drained.append(r)
        if self.registry is not None:
            self.registry.counter_inc("serving.fleet.worker_deaths")
            if drained:
                self.registry.counter_inc("serving.fleet.requeued",
                                          len(drained))
            prefix = f"serving.router.replica{index}."
            for gauge in ("queue_depth", "slots_busy", "pages_free",
                          "host_bytes_free"):
                self.registry.gauge_set(prefix + gauge, 0.0)
        _logger.warning(
            "worker %d died at controller tick %d (%s): %d "
            "request(s) re-routing onto %d survivor(s)", index,
            self._tick, reason or "declared dead", len(drained),
            sum(w.alive for w in self.workers))
        self._overflow.extend(drained)
        if not any(w.alive for w in self.workers):
            raise RuntimeError(
                "the fleet's last worker died — outage, not a "
                "routing event")
        self._drain_overflow()

    def kill_worker(self, index: int, *,
                    tick: Optional[int] = None) -> None:
        """HARD-kill worker ``index``'s process (SIGKILL — no drain,
        no goodbye: the chaos ``replica_death`` path and the
        operator's dead-backend hammer). Un-finished requests
        re-route with no retry charged. Idempotent on a dead worker;
        killing the LAST live worker raises — an outage, and
        silently absorbing it would strand every re-routed
        request."""
        index = int(index)
        if not 0 <= index < len(self.workers):
            raise ValueError(f"worker {index} out of range "
                             f"[0, {len(self.workers)})")
        if not self.workers[index].alive:
            return
        if sum(w.alive for w in self.workers) == 1:
            raise RuntimeError(
                f"worker {index} is the last one alive — a fleet of "
                "zero cannot absorb its requests (outage, not a "
                "routing event)")
        _logger.warning("killing worker %d at tick %s", index,
                        self._tick if tick is None else tick)
        self._declare_dead(index, reason="killed")

    def _drain_overflow(self) -> bool:
        placed = False
        for _ in range(len(self._overflow)):
            r = self._overflow.popleft()
            try:
                self.submit(r)
                placed = True
            except QueueFull:
                self._overflow.append(r)
        return placed

    def _absorb_completion(self, wire: dict) -> None:
        """Fold a completion wire back onto the controller's
        canonical :class:`Request` (the object the caller submitted):
        outputs, terminal status and per-episode timings are the
        worker's; ``latency_s`` is re-stamped from the CONTROLLER's
        submit clock (perf_counter bases don't cross processes, and
        the controller's clock spans re-routes)."""
        done = request_from_wire(wire)
        r = self._inflight.pop(done.uid, None)
        self._home.pop(done.uid, None)
        if r is None:
            return      # stale (already re-routed after a drain race)
        for f in ("output_tokens", "status", "finish_reason",
                  "ttft_s", "queue_wait_s", "prefill_s", "chunks",
                  "reused_tokens", "spec_drafted", "spec_accepted",
                  "retries", "error"):
            setattr(r, f, getattr(done, f))
        t0 = self._t0.pop(done.uid, None)
        r.latency_s = (time.perf_counter() - t0) \
            if t0 is not None else done.latency_s
        self.completed.append(r)

    def _absorb_progress(self, r: Request, wire: dict) -> None:
        """Fold a DRAINED request's paid-compute counters onto the
        canonical object before it re-routes (chunks / prefill_s /
        reused tokens / spec counters accumulate across homes, like an
        in-process drain; retries stay untouched — a drain is never
        the request's fault)."""
        done = request_from_wire(wire)
        for f in ("prefill_s", "chunks", "reused_tokens",
                  "spec_drafted", "spec_accepted", "retries"):
            setattr(r, f, getattr(done, f))
        r.output_tokens = []
        r.status = RequestStatus.QUEUED

    # ------------------------------------------------------------ handoffs
    def _collect_handoffs(self) -> bool:
        """Move ready disagg handoffs: prefill workers export
        ``(request, record wire, keys)`` triples — the arena record's
        bytes and CRCs BY VALUE — and each lands on the best
        decode-capable worker, which imports the record into its own
        arena. An export that came back record-less (evicted or still
        pending at collection) stays a valid handoff: the decode side
        re-prefills cold, per the verified-miss contract."""
        ready: List[Tuple[Request, Optional[dict], list]] = \
            list(self._handoff_overflow)
        self._handoff_overflow.clear()
        for i in self._alive_indices():
            if self.workers[i].role != "prefill":
                continue
            try:
                reply = self.workers[i].rpc(
                    "take_handoffs", timeout=self.rpc_timeout_s)
            except (WorkerDied, TimeoutError) as e:
                self._declare_dead(i, reason=str(e))
                continue
            for item in reply["handoffs"]:
                wire = item["request"]
                r = self._inflight.get(wire["uid"])
                if r is None:       # pragma: no cover — defensive
                    r = request_from_wire(wire)
                    self._inflight[r.uid] = r
                else:
                    self._absorb_progress(r, wire)
                self._home.pop(r.uid, None)
                ready.append((r, item["record"], item["keys"]))
        placed = False
        for r, rec, keys in ready:
            placed = self._dispatch_handoff(r, rec, keys) or placed
        return placed

    def _dispatch_handoff(self, r: Request, rec: Optional[dict],
                          keys) -> bool:
        t_route = self.tracer.now() if self.tracer is not None else 0.0
        _keys, order, lens = self._route_order(r, "decode")
        n_spilled = 0
        for i in order:
            try:
                reply = self.workers[i].rpc(
                    "submit", timeout=self.rpc_timeout_s,
                    request=request_to_wire(r), prefix_keys=keys,
                    handoff=rec, is_handoff=True)
            except (WorkerDied, TimeoutError) as e:
                self._declare_dead(i, reason=str(e))
                continue
            if "queue_full" in reply:
                n_spilled += 1
                continue
            note_placement(self.placements, r.uid, i)
            self._home[r.uid] = i
            if self.registry is not None and n_spilled:
                self.registry.counter_inc("serving.fleet.spills",
                                          n_spilled)
            if self.tracer is not None:
                self.tracer.event(r.uid, "route", t0=t_route,
                                  dur=self.tracer.now() - t_route,
                                  pid=i, replica=i,
                                  policy=self.route_policy,
                                  affinity_len=lens.get(i, 0),
                                  spills=n_spilled, handoff=True)
            return True
        self._handoff_overflow.append((r, rec, keys))
        return False

    # ---------------------------------------------------------- lifecycle
    def _graceful_stop(self, index: int) -> None:
        """Drain worker ``index`` and stop its process cleanly:
        drained requests absorb their paid counters and join the
        overflow (re-routed, no retry charged). A worker that dies
        MID-drain degrades to the hard-death path — its requests
        re-route from the controller's canonical copies instead."""
        w = self.workers[index]
        try:
            reply = w.rpc("drain", timeout=self.rpc_timeout_s)
            for wire in reply["requests"]:
                r = self._inflight.get(wire["uid"])
                if r is None:       # pragma: no cover — defensive
                    r = request_from_wire(wire)
                    self._inflight[r.uid] = r
                else:
                    self._absorb_progress(r, wire)
                self._home.pop(r.uid, None)
                self._overflow.append(r)
            w.rpc("close", timeout=self.rpc_timeout_s)
        except (WorkerDied, TimeoutError, RuntimeError) as e:
            _logger.warning(
                "worker %d died during drain (%s) — falling back to "
                "hard-death re-route", index, e)
            self._declare_dead(index, reason=f"died during drain: {e}")
            return
        try:
            w.proc.wait(timeout=30)
        except subprocess.TimeoutExpired:       # pragma: no cover
            pass
        w.destroy()
        victims = [uid for uid, home in self._home.items()
                   if home == index]
        for uid in victims:         # pragma: no cover — drain got all
            self._home.pop(uid, None)
            r = self._inflight.pop(uid, None)
            if r is not None:
                self._overflow.append(r)
        if self.registry is not None:
            prefix = f"serving.router.replica{index}."
            for gauge in ("queue_depth", "slots_busy", "pages_free",
                          "host_bytes_free"):
                self.registry.gauge_set(prefix + gauge, 0.0)

    def _respawn(self, index: int) -> None:
        """Start a fresh process for slot ``index`` and rejoin it to
        the fleet (same spec, same role, geometry re-validated)."""
        proc = self._launch(index)
        conns = self._accept(1)
        if index not in conns:
            raise RuntimeError(
                f"respawned worker {index} connected with the wrong "
                f"identity {sorted(conns)}")
        w = WorkerHandle(index, proc, conns[index], self.roles[index])
        self.workers[index] = w
        self._init_worker(w, self._specs[index])
        self._check_new_geometry(w)

    def _check_new_geometry(self, w: WorkerHandle) -> None:
        ref = next((x.geometry for x in self.workers
                    if x is not w and x.geometry is not None), None)
        if ref is not None:
            keys = ("slots", "max_len", "prefill_len", "chunk_len")
            gi = {k: w.geometry[k] for k in keys}
            g0 = {k: ref[k] for k in keys}
            if gi != g0:
                raise ValueError(
                    f"worker {w.index} serving geometry {gi} differs "
                    f"from the fleet's {g0}")
        if self.affinity_enabled and not w.geometry["retain_prefixes"]:
            raise ValueError(
                f"worker {w.index} joined without prefix retention "
                "but the fleet routes by affinity")

    def rolling_restart(self) -> None:
        """Restart every live worker, one at a time: drain → close →
        wait → respawn → rejoin. The fleet keeps serving throughout
        (drained requests re-route, no retry charged); each respawned
        worker rejoins cold and re-registers prefixes warm as traffic
        lands on it. Per-worker restart latency lands in the
        ``serving.fleet.restart_s`` histogram."""
        for index in [i for i, w in enumerate(self.workers)
                      if w.alive]:
            if not self.workers[index].alive:
                continue            # died while restarting a sibling
            if sum(w.alive for w in self.workers) == 1:
                raise RuntimeError(
                    f"worker {index} is the last one alive — a "
                    "rolling restart needs survivors to drain onto")
            t0 = time.perf_counter()
            self._graceful_stop(index)
            self._respawn(index)
            if self.registry is not None:
                self.registry.counter_inc("serving.fleet.restarts")
                self.registry.observe("serving.fleet.restart_s",
                                      time.perf_counter() - t0)
            _logger.info("worker %d restarted in %.3fs", index,
                         time.perf_counter() - t0)
            self._drain_overflow()

    def respawn_worker(self, index: int) -> None:
        """Revive a DEAD slot (after a chaos kill, a hang
        declaration, or a crash): spawn a fresh process from the
        slot's spec and rejoin it — cold caches, same geometry, same
        role. Counts as a restart. Raises on a live slot (use
        :meth:`rolling_restart` to recycle those)."""
        index = int(index)
        if self.workers[index].alive:
            raise RuntimeError(
                f"worker {index} is alive — respawn_worker revives "
                "dead slots; rolling_restart recycles live ones")
        t0 = time.perf_counter()
        self._respawn(index)
        if self.registry is not None:
            self.registry.counter_inc("serving.fleet.restarts")
            self.registry.observe("serving.fleet.restart_s",
                                  time.perf_counter() - t0)
        _logger.info("worker %d respawned in %.3fs", index,
                     time.perf_counter() - t0)
        self._drain_overflow()

    def add_replica(self, spec: Optional[dict] = None,
                    role: str = "both") -> int:
        """Grow the fleet under live traffic: spawn a new worker
        (``spec`` defaults to worker 0's), join it, and return its
        index. The next routed request probes it like any other
        member — cold caches lose affinity ties and win least-loaded
        ties, so the new member fills naturally."""
        spec = dict(spec) if spec is not None else dict(self._specs[0])
        index = len(self.workers)
        self._validate_role_mix([w.role for w in self.workers
                                 if w.alive] + [str(role)])
        check_worker_backend(
            sum(w.alive for w in self.workers) + 1)
        self._specs.append(spec)
        self.roles.append(str(role))
        proc = self._launch(index)
        conns = self._accept(1)
        if index not in conns:
            raise RuntimeError(
                f"new worker {index} connected with the wrong "
                f"identity {sorted(conns)}")
        w = WorkerHandle(index, proc, conns[index], str(role))
        self.workers.append(w)
        self._init_worker(w, spec)
        self._check_new_geometry(w)
        _logger.info("worker %d (%s) joined the fleet", index, role)
        return index

    def remove_replica(self, index: int) -> None:
        """Shrink the fleet under live traffic: drain worker
        ``index`` (its requests re-route, no retry charged) and stop
        its process. The slot stays dead — indices are stable.
        Removing the last live worker raises."""
        index = int(index)
        if not 0 <= index < len(self.workers):
            raise ValueError(f"worker {index} out of range "
                             f"[0, {len(self.workers)})")
        if not self.workers[index].alive:
            return
        if sum(w.alive for w in self.workers) == 1:
            raise RuntimeError(
                f"worker {index} is the last one alive — removing it "
                "is an outage, not elasticity")
        remaining = [w.role for i, w in enumerate(self.workers)
                     if w.alive and i != index]
        self._validate_role_mix(remaining)
        self._graceful_stop(index)
        self._drain_overflow()
        _logger.info("worker %d removed from the fleet", index)

    def set_role(self, index: int, role: str) -> None:
        """Re-role worker ``index`` under traffic shift (the
        disaggregated fleet's elastic refit: a prefill worker becomes
        a decode worker when the mix moves). The worker drains (its
        requests re-route), rebuilds its scheduler in the new role on
        the SAME engine — pool, prefix cache and arena survive — and
        rejoins. Raises if the resulting mix would lose a whole role
        tier."""
        index = int(index)
        role = str(role)
        w = self.workers[index]
        if not w.alive:
            raise RuntimeError(f"worker {index} is dead — respawn it "
                               "before re-roling")
        mix = [x.role for i, x in enumerate(self.workers)
               if x.alive and i != index] + [role]
        self._validate_role_mix(mix)
        reply = w.rpc("drain", timeout=self.rpc_timeout_s)
        for wire in reply["requests"]:
            r = self._inflight.get(wire["uid"])
            if r is not None:
                self._absorb_progress(r, wire)
                self._home.pop(r.uid, None)
                self._overflow.append(r)
        w.rpc("set_role", timeout=self.rpc_timeout_s, role=role)
        w.role = role
        self.roles[index] = role
        _logger.info("worker %d re-roled to %s", index, role)
        self._drain_overflow()

    # ------------------------------------------------------------ telemetry
    def _emit_gauges(self) -> None:
        if self.registry is None:
            return
        self.registry.gauge_set(
            "serving.fleet.workers_alive",
            float(sum(w.alive for w in self.workers)))
        for i, snap in self._poll(self._alive_indices()).items():
            prefix = f"serving.router.replica{i}."
            self.registry.gauge_set(prefix + "queue_depth",
                                    float(snap["queue_depth"]))
            self.registry.gauge_set(prefix + "slots_busy",
                                    float(snap["slots_busy"]))
            if snap["pages_free"] is not None:
                self.registry.gauge_set(prefix + "pages_free",
                                        float(snap["pages_free"]))
            if snap["host_bytes_free"] is not None:
                self.registry.gauge_set(
                    prefix + "host_bytes_free",
                    float(snap["host_bytes_free"]))

    def metrics_snapshot(self) -> dict:
        """One fleet view over N+1 registries: the controller's
        counters/gauges/histograms, every live worker's counters
        SUMMED in (fleet-wide aggregates — the Router's
        shared-registry semantics), and worker gauges/histogram
        summaries namespaced ``worker<i>/<name>`` (they are
        per-process readings; summing them would be a lie)."""
        if self.registry is not None:
            merged = self.registry.snapshot()
        else:
            merged = {"counters": {}, "gauges": {}, "histograms": {}}
        for i in range(len(self.workers)):
            w = self.workers[i]
            if not w.alive:
                continue
            try:
                snap = w.rpc("metrics",
                             timeout=self.rpc_timeout_s)["snapshot"]
            except (WorkerDied, TimeoutError) as e:
                self._declare_dead(i, reason=str(e))
                continue
            for k, v in snap["counters"].items():
                merged["counters"][k] = \
                    merged["counters"].get(k, 0.0) + v
            for k, v in snap["gauges"].items():
                merged["gauges"][f"worker{i}/{k}"] = v
            for k, v in snap["histograms"].items():
                merged["histograms"][f"worker{i}/{k}"] = v
        return merged

    def prefix_stats(self, index: int) -> dict:
        """Worker ``index``'s prefix-cache counters (the warm-restart
        pin reads deltas of these across a restart)."""
        return self.workers[index].rpc(
            "prefix_stats", timeout=self.rpc_timeout_s)["stats"]

    def audit_worker(self, index: int) -> dict:
        """Run the worker's own :class:`~apex_tpu.serving
        .PoolAuditor` + clearing reset and return the audit dict —
        the cross-process zero-leak pin (raises through the RPC if
        the worker's pool invariants fail)."""
        return self.workers[index].rpc(
            "audit_drained", timeout=self.rpc_timeout_s)["audit"]

    # ---------------------------------------------------------------- runs
    @property
    def pending(self) -> int:
        """Requests the fleet still owes the caller."""
        return len(self._overflow) + len(self._handoff_overflow) \
            + len(self._inflight)

    def run(self, requests: Sequence[Request] = (),
            max_steps: int = 100000) -> List[Request]:
        """Submit ``requests`` (stepping through :class:`QueueFull`
        backpressure) and step until every one is terminal — the
        Router's run loop over the process fleet. Returns the
        submitted list; results land on the SAME objects the caller
        passed (completions are folded back onto them)."""
        requests = list(requests)
        t0 = time.perf_counter()
        tok0 = sum(len(r.output_tokens) for r in self.completed)
        for r in requests:
            while True:
                try:
                    self.submit(r)
                    break
                except QueueFull:
                    if not self.step():
                        time.sleep(0.002)
        steps = 0
        while self.pending and steps < max_steps:
            if not self.step():
                time.sleep(0.002)
            steps += 1
        dt = time.perf_counter() - t0
        toks = sum(len(r.output_tokens)
                   for r in self.completed) - tok0
        if self.registry is not None and dt > 0:
            self.registry.gauge_set("serving.tokens_per_s", toks / dt)
        _logger.info(
            "fleet served %d request(s) over %d/%d live worker(s): "
            "%d tokens in %.3fs (%.1f tok/s)", len(requests),
            sum(w.alive for w in self.workers), len(self.workers),
            toks, dt, toks / dt if dt > 0 else float("inf"))
        return requests

    def close(self) -> None:
        """Stop every worker process and release the transport.
        Idempotent — safe mid-construction, safe after kills, safe
        twice. Live workers get one polite close RPC, then the
        process is reaped regardless; the temp socket dir is removed.
        The weakref finalizer backstops a forgotten controller: no
        worker process ever outlives the fleet object."""
        if self._closed:
            return
        self._closed = True
        for w in self.workers:
            if w.alive:
                try:
                    w.rpc("close", timeout=10.0)
                except (WorkerDied, TimeoutError, RuntimeError):
                    pass
            w.destroy()
        _kill_procs(self._procs)
        try:
            self._listener.close()
        except OSError:                         # pragma: no cover
            pass
        shutil.rmtree(self._dir, ignore_errors=True)
