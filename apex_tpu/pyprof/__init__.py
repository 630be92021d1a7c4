"""apex_tpu.pyprof — profiling subsystem (reference: apex/pyprof, P42).

The reference's pyprof has three stages: ``pyprof.nvtx.init()`` monkey-patches
torch ops to emit NVTX ranges; ``pyprof/parse`` ingests nvprof/Nsight sqlite
dumps; ``pyprof/prof`` turns them into per-kernel flop/byte reports.

TPU-native mapping (SURVEY §6 — tracing):

- NVTX ranges → :func:`annotate` (``jax.named_scope`` inside traced code, so
  the scope lands in the XLA HLO and shows up in the profiler UI, plus a host
  ``TraceAnnotation`` for eager sections).
- nvprof capture → :func:`trace` around ``jax.profiler`` (perfetto dump).
- the flop/byte report → :func:`cost_report`, straight from XLA's own cost
  analysis of the compiled executable — no dump parsing, the compiler knows.
- pyprof/parse + pyprof/prof (sqlite dump → per-kernel table) →
  :func:`analyze`: parse the captured trace's device lane into per-op rows
  (occurrences, ms, flops, bytes) and :func:`report` to format them.
- iteration timing (main_amp.py --prof N's role) → :class:`StepTimer`.
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import re
import time
import warnings
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from apex_tpu.telemetry import tracing

__all__ = ["init", "annotate", "trace", "cost_report", "analyze", "report",
           "device_busy", "step_device_throughput",
           "device_throughput_line", "StepTimer"]

_enabled = True


def init(enabled: bool = True):
    """Reference: pyprof.nvtx.init() — global enable switch.

    Gates :func:`trace` and eager uses of :func:`annotate`. Inside jitted
    code the switch is read at TRACE time and baked into the cached
    executable — flip it before the first call of a jitted function (or
    ``jax.clear_caches()``), the same way the reference requires init()
    before the ops it patches are first invoked."""
    global _enabled
    _enabled = enabled


@contextlib.contextmanager
def annotate(name: str):
    """Named range visible in both the XLA profile (named_scope) and host
    timeline. Usable inside and outside jit. The host half is
    :func:`apex_tpu.telemetry.tracing.phase` - the one way this package
    marks a host region: ``apex.<name>`` on the profiler's host plane
    and a record in the flight recorder."""
    if not _enabled:
        yield
        return
    with jax.named_scope(name), tracing.phase(name):
        yield


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture a profiler trace (perfetto) to ``log_dir`` — the nvprof
    capture stage. View with tensorboard or ui.perfetto.dev."""
    if not _enabled:
        yield
        return
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def cost_report(fn: Callable, *args, **kwargs) -> Dict[str, Any]:
    """Per-executable flop/byte report from XLA's cost analysis.

    The reference's pyprof/prof derives flops & bytes per kernel from
    captured traces; XLA computes the same quantities at compile time, so the
    report comes from ``jit(fn).lower(...).compile().cost_analysis()``.
    Returns {'flops', 'bytes_accessed', 'arithmetic_intensity', 'raw'}.
    """
    compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    analyses = compiled.cost_analysis()
    # cost_analysis: dict (newer jax) or list of per-device dicts (older)
    raw = analyses if isinstance(analyses, dict) else (analyses or [{}])[0]
    flops = float(raw.get("flops", 0.0))
    if "bytes accessed" in raw:
        # aggregate key already equals the sum of the per-operand
        # 'bytes accessedN{}' breakdown keys — don't double count
        in_bytes = float(raw["bytes accessed"])
    else:
        in_bytes = sum(float(v) for k, v in raw.items()
                       if k.startswith("bytes accessed"))
    report = {
        "flops": flops,
        "bytes_accessed": in_bytes,
        "arithmetic_intensity": flops / in_bytes if in_bytes else 0.0,
        "raw": dict(raw),
    }
    return report


def _trace_files(trace_dir: str) -> List[str]:
    """The newest profile run's dumps under ``trace_dir`` (one per
    host), or ``trace_dir`` itself if it is already a dump: the
    chrome-trace ``*.trace.json.gz`` where the profiler wrote them,
    else the run's ``*.xplane.pb`` (all that this JAX writes on the
    chip)."""
    if os.path.isfile(trace_dir):
        return [trace_dir]
    runs = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*")))
    if not runs:
        raise FileNotFoundError(
            f"no profile runs under {trace_dir!r} — capture one with "
            "pyprof.trace(log_dir) first")
    files = sorted(glob.glob(os.path.join(runs[-1], "*.trace.json.gz"))) \
        or sorted(glob.glob(os.path.join(runs[-1], "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(
            f"no *.trace.json.gz and no *.xplane.pb in {runs[-1]!r}")
    return files


def _leaf_spans(evs: List[dict],
                lane_of: Optional[Callable[[dict], tuple]] = None
                ) -> List[dict]:
    """Drop spans that PROPERLY enclose another span on the same lane —
    parents double-count their children's time. One sorted sweep per lane
    with an open-interval stack. Identical intervals are siblings (two
    same-timestamp ops), not parent/child. ``lane_of`` defaults to
    (pid, tid); pass a richer key when events come from several files
    whose pid namespaces are independent."""
    if lane_of is None:
        lane_of = lambda e: (e.get("pid"), e.get("tid"))  # noqa: E731
    lanes: Dict[tuple, List[dict]] = {}
    for e in evs:
        lanes.setdefault(lane_of(e), []).append(e)
    out: List[dict] = []
    for lane in lanes.values():
        lane.sort(key=lambda e: (float(e.get("ts", 0.0)),
                                 -float(e.get("dur", 0.0))))
        parents: set = set()
        stack: List[tuple] = []          # (start_ts, end_ts, id(event))
        for e in lane:
            ts = float(e.get("ts", 0.0))
            end = ts + float(e.get("dur", 0.0))
            while stack and ts >= stack[-1][1]:
                stack.pop()
            if stack and (stack[-1][0], stack[-1][1]) != (ts, end):
                # e nests PROPERLY inside the top — and inside every twin
                # of the top (identical intervals sit adjacent on the
                # stack as siblings; each one encloses e equally)
                top = (stack[-1][0], stack[-1][1])
                for s_ts, s_end, s_id in reversed(stack):
                    if (s_ts, s_end) != top:
                        break
                    parents.add(s_id)
            stack.append((ts, end, id(e)))
        out += [e for e in lane if id(e) not in parents]
    return out


_HLO_OPCODE = re.compile(r" = .*? ([\w\-]+)\(")


def _xplane_events(path: str, fi: int) -> List[tuple]:
    """One ``.xplane.pb`` (or a recording kept as gzipped text proto,
    ``.txtpb.gz``) as the same (lane_name, file_idx, event) triples a
    chrome dump gives: a device plane's ``XLA Ops`` line (one event per
    executed HLO operation; the other lines mirror the same execution)
    and every line of the host planes, times in microseconds. On this
    JAX a device event is named by its whole HLO instruction: the
    instruction's own name is kept, its opcode stands in for a missing
    ``hlo_category``, and an event's stats are its ``args``."""
    from jax.profiler import ProfileData

    if path.endswith(".txtpb.gz"):
        with gzip.open(path, "rt") as f:
            pd = ProfileData.from_serialized_xspace(
                ProfileData.text_proto_to_serialized_xspace(f.read()))
    else:
        pd = ProfileData.from_file(path)
    events: List[tuple] = []
    with warnings.catch_warnings():
        # nanobind's stats iterator has no __module__: a warning an event
        warnings.simplefilter("ignore", DeprecationWarning)
        for pid, plane in enumerate(pd.planes):
            dev = plane.name.startswith("/device:")
            for tid, line in enumerate(plane.lines):
                if dev and line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    args = dict(ev.stats)
                    name = ev.name
                    if dev:
                        op = _HLO_OPCODE.search(name)
                        name = name.split(" = ", 1)[0].strip().lstrip("%")
                        args.setdefault("hlo_category",
                                        op.group(1) if op else "")
                    events.append((plane.name, fi, {
                        "name": name, "ph": "X", "pid": pid, "tid": tid,
                        "ts": ev.start_ns / 1e3, "dur": ev.duration_ns / 1e3,
                        "args": args}))
    return events


def _load_events(trace_dir: str) -> List[tuple]:
    """All complete ('X') events of the newest dump as (lane_name,
    file_idx, event) triples. pid namespaces are PER FILE (one dump per
    host), so each event is classified against its own file's
    process_name metadata and lanes never mix across files."""
    events: List[tuple] = []
    for fi, path in enumerate(_trace_files(trace_dir)):
        if not path.endswith(".json.gz"):
            events += _xplane_events(path, fi)
            continue
        with gzip.open(path, "rt") as f:
            data = json.load(f)
        evs = data.get("traceEvents", [])
        pids = {e["pid"]: e.get("args", {}).get("name", "")
                for e in evs
                if e.get("ph") == "M" and e.get("name") == "process_name"}
        events += [(pids.get(e.get("pid"), ""), fi, e)
                   for e in evs if e.get("ph") == "X"]
    return events


def _device_ops(events: List[tuple]) -> tuple:
    """(ops, file_of) for the device lanes of :func:`_load_events` output:
    per-op HLO events when the backend cost-annotates them
    (``hlo_category``), else the proper-nesting leaf sweep so region
    wrappers (jit_fn(...)) don't double-count their children."""
    file_of = {id(e): fi for _, fi, e in events}
    dev = [e for lane, _, e in events if lane.startswith("/device:")]
    ops = [e for e in dev if "hlo_category" in e.get("args", {})]
    if not ops:
        ops = _leaf_spans(dev, lane_of=lambda e: (file_of[id(e)],
                                                  e.get("pid"),
                                                  e.get("tid")))
    return ops, file_of


def analyze(trace_dir: str, top: Optional[int] = None) -> List[Dict[str, Any]]:
    """Per-op table from a captured trace — the reference's pyprof/parse +
    pyprof/prof stages (nvprof sqlite → per-kernel name/occurrence/ns/
    flops/bytes report) applied to the ``jax.profiler`` dump that
    :func:`trace` writes.

    Reads the device lanes' HLO-op events (each carries its duration plus
    XLA's own ``model_flops`` / ``bytes_accessed``) and aggregates by op
    name. Returns rows sorted by total time, descending::

        {"name", "category", "occurrences", "total_ms", "mean_ms",
         "flops", "bytes", "intensity", "pct_time"}

    ``flops``/``bytes`` are totals across occurrences; ``intensity`` is
    flops/byte; ``pct_time`` is this op's share of all device-op time.
    When the dump has no cost-annotated device ops (host-only capture,
    or a backend without per-op HLO args), leaf spans are tabulated
    instead — parents that enclose other spans are dropped so region
    wrappers don't double-count their children — with zero flops/bytes.
    """
    events = _load_events(trace_dir)
    ops, file_of = _device_ops(events)
    if not ops:
        # host-only capture: tabulate the host lanes' leaf spans instead
        ops = _leaf_spans(
            [e for _, _, e in events],
            lane_of=lambda e: (file_of[id(e)], e.get("pid"),
                               e.get("tid")))

    rows: Dict[str, Dict[str, Any]] = {}
    for e in ops:
        args = e.get("args", {})
        r = rows.setdefault(e["name"], {
            "name": e["name"],
            "category": args.get("hlo_category", ""),
            "occurrences": 0, "total_ms": 0.0,
            "flops": 0.0, "bytes": 0.0,
        })
        r["occurrences"] += 1
        r["total_ms"] += float(e.get("dur", 0.0)) / 1e3   # dur is µs
        r["flops"] += float(args.get("model_flops", 0.0))
        r["bytes"] += float(args.get("raw_bytes_accessed",
                                     args.get("bytes_accessed", 0.0)))
    total_ms = sum(r["total_ms"] for r in rows.values()) or 1.0
    out = sorted(rows.values(), key=lambda r: -r["total_ms"])
    for r in out:
        r["mean_ms"] = r["total_ms"] / r["occurrences"]
        r["intensity"] = r["flops"] / r["bytes"] if r["bytes"] else 0.0
        r["pct_time"] = 100.0 * r["total_ms"] / total_ms
    return out[:top] if top else out


def device_busy(trace_dir: str) -> Dict[str, float]:
    """Device-time summary of a captured trace — the timing anchor that
    wall-clock measurement can't provide when dispatch is remote (the
    reference's equivalent is nvprof's kernel-time column, which times the
    GPU itself rather than the host loop; SURVEY §6 tracing / §7's
    "time the device, not the python loop" rule).

    Reads the ``/device:`` lanes' complete events and returns::

        {"busy_ms":  sum of leaf device-op durations (idle gaps excluded),
         "span_ms":  max over lanes of (last op end − first op start),
         "n_events": leaf device ops counted,
         "n_lanes":  device lanes seen}

    All readings come from the single BUSIEST device lane (most leaf-op
    time): chrome dumps split one device into sub-lanes ("XLA Ops",
    "Steps", copy streams, …) that mirror the same execution, so summing
    across lanes would double-count occupancy. ``span_ms`` is that lane's
    elapsed time, first op start to last op end (inter-op bubbles
    included); ``busy_ms`` its pure occupancy — ``busy_ms/span_ms`` is
    the duty cycle (ops overlapping *within* the lane can push it
    marginally over 1). ``n_lanes`` counts all device lanes seen. All
    zeros when the dump has no device lanes (host-only backends) —
    callers must fall back to wall clock.
    """
    events = _load_events(trace_dir)
    ops, file_of = _device_ops(events)
    if not ops:
        return {"busy_ms": 0.0, "span_ms": 0.0, "n_events": 0, "n_lanes": 0}
    n_lanes = len({(file_of[id(e)], e.get("pid"), e.get("tid"))
                   for lane, _, e in events
                   if lane.startswith("/device:")})
    per_lane: Dict[tuple, List[dict]] = {}
    for e in ops:
        key = (file_of[id(e)], e.get("pid"), e.get("tid"))
        per_lane.setdefault(key, []).append(e)
    lane_ops = max(per_lane.values(),
                   key=lambda es: sum(float(e.get("dur", 0.0)) for e in es))
    busy_us = sum(float(e.get("dur", 0.0)) for e in lane_ops)
    starts = [float(e.get("ts", 0.0)) for e in lane_ops]
    ends = [float(e.get("ts", 0.0)) + float(e.get("dur", 0.0))
            for e in lane_ops]
    span_us = max(ends) - min(starts)
    return {"busy_ms": busy_us / 1e3, "span_ms": span_us / 1e3,
            "n_events": len(lane_ops), "n_lanes": n_lanes}


def report(rows: List[Dict[str, Any]]) -> str:
    """Format :func:`analyze` rows as the aligned text table the
    reference's ``python -m pyprof.prof`` prints."""
    hdr = f"{'op':<40} {'n':>5} {'ms':>10} {'%':>6} {'GFLOP':>10} " \
          f"{'MB':>10} {'F/B':>8}"
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        lines.append(
            f"{r['name'][:40]:<40} {r['occurrences']:>5} "
            f"{r['total_ms']:>10.3f} {r['pct_time']:>6.1f} "
            f"{r['flops'] / 1e9:>10.3f} {r['bytes'] / 1e6:>10.3f} "
            f"{r['intensity']:>8.2f}")
    return "\n".join(lines)


def step_device_throughput(step_fn, state, batch, n, items_per_step):
    """Time ``n`` steps of a ``(state, batch) -> (state, metrics)`` train
    step on the profiler's DEVICE lanes and return a reading, or ``None``
    when no reading is possible — the recipes' ``--prof-device`` flag
    (the apex recipes' --prof role on device time).

    Observation-only by contract: the steps run on a deep COPY of
    ``state`` (donated input buffers would otherwise be invalidated under
    the caller's feet and the real state silently advanced past its step
    count), and EVERY failure — profiler already active, corrupt dump,
    a crash inside the profiled step — degrades to ``None`` rather than
    raising, so a timing nicety can never cost the caller its checkpoint.

    Returns ``{"items_per_s", "ms_per_step", "duty"}``.
    """
    if n <= 0:
        return None
    import tempfile

    import jax
    import jax.numpy as jnp

    try:
        prof_state = jax.tree_util.tree_map(jnp.copy, state)
        with tempfile.TemporaryDirectory() as td:
            with trace(td):
                metrics = None
                for _ in range(n):
                    prof_state, metrics = step_fn(prof_state, batch)
                jax.block_until_ready(metrics)
            d = device_busy(td)
    except Exception:  # noqa: BLE001 — observation-only, see docstring
        return None
    if d["span_ms"] <= 0:
        return None
    return {"items_per_s": n * items_per_step / (d["span_ms"] / 1e3),
            "ms_per_step": d["span_ms"] / n,
            "duty": d["busy_ms"] / d["span_ms"]}


def device_throughput_line(step_fn, state, batch, n, items_per_step,
                           unit):
    """The recipes' shared ``--prof-device`` rendering: one formatted
    line for the reading of :func:`step_device_throughput`, ``None``
    when the flag is off (``n == 0`` — print nothing). Negative ``n``
    gets its own diagnostic so a typo isn't misread as a backend
    problem. Never raises (same contract as the underlying helper)."""
    if n == 0:
        return None
    if n < 0:
        return f"device throughput: n/a (--prof-device {n} ignored)"
    r = step_device_throughput(step_fn, state, batch, n, items_per_step)
    if r is None:
        return ("device throughput: n/a (no device lanes, or profiling "
                "unavailable)")
    return (f"device throughput: {r['items_per_s']:,.1f} {unit} "
            f"({r['ms_per_step']:.2f} ms/step, duty {r['duty']:.2f})")


class StepTimer:
    """Wall-clock iteration timing with warmup skip — the role of the
    imagenet recipe's --prof flag plus its img/s accounting, reusable.

    jax dispatch is async: synchronize inside the timed block (or pass
    ``sync=``) or you measure enqueue time, not execution time.

    >>> timer = StepTimer(warmup=3)
    >>> for batch in loader:
    ...     with timer.step(items=batch_size):
    ...         state, m = jit_step(state, batch)  # noqa
    ...         m["loss"].block_until_ready()      # sync point
    >>> print(timer.report())
    """

    def __init__(self, warmup: int = 3, sync: Optional[Callable] = None):
        self.warmup = warmup
        self.sync = sync
        self._times: List[float] = []
        self._items: List[int] = []
        self._count = 0

    @contextlib.contextmanager
    def step(self, items: int = 1):
        t0 = time.perf_counter()
        yield
        if self.sync is not None:
            self.sync()
        dt = time.perf_counter() - t0
        self._count += 1
        if self._count > self.warmup:
            self._times.append(dt)
            self._items.append(items)

    @property
    def times(self) -> np.ndarray:
        return np.asarray(self._times)

    def report(self) -> Dict[str, float]:
        if not self._times:
            return {"steps": 0}
        t = self.times
        items = float(np.sum(self._items))
        return {
            "steps": len(t),
            "mean_s": float(t.mean()),
            "p50_s": float(np.percentile(t, 50)),
            "p90_s": float(np.percentile(t, 90)),
            "items_per_s": items / float(t.sum()),
        }
