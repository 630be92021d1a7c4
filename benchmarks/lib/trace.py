"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the
per-layer readers ask of it. Read with ``jax.profiler.ProfileData`` and
nothing else.

What a TPU trace holds (looked at by hand, PR 24): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` carries one event per executed
HLO operation (a Pallas kernel appears under the ``name=`` its
``pallas_call`` was given) and whose line ``XLA Modules`` carries one event
per executed program (``jit_<fn>(<fingerprint>)``); host threads are lines
of the plane ``/host:CPU``, where ``jax.profiler.TraceAnnotation`` spans
appear under their own names. All planes share one clock, in nanoseconds.

On the CPU backend (the harness's own tests) there is no device plane;
events that carry an ``hlo_op`` stat on the host plane stand in, so that
the same code is exercised. No number from such a trace is ever written
under a device metric's name: ``common.require_chip`` ends a real run
before this module is reached.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."

_SUFFIX = re.compile(r"([._]\d+)+$")
_FINGERPRINT = re.compile(r"\(\d+\)$")


def clean(name: str) -> str:
    """An event's name without what changes from compile to compile:
    ``fusion.123`` -> ``fusion``; ``jit_step(1234)`` -> ``jit_step``. On
    this JAX a device op's event is named by its whole HLO instruction
    (``%fusion.12 = bf16[8,128]{1,0} fusion(...), kind=kLoop``): the
    instruction's own name, before `` = ``, is what counts."""
    name = name.split(" = ", 1)[0].strip().lstrip("%")
    name = _FINGERPRINT.sub("", name)
    return _SUFFIX.sub("", name) or name


_HLO = re.compile(r"^%?[\w.\-]+ = (?P<shape>.*?) (?P<op>[\w\-]+)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_KIND = re.compile(r"kind=k(\w+)")


def fusion_label(text: str):
    """What an XLA fusion computes, as far as its HLO line says: its kind
    and the shapes it writes (layouts dropped), e.g.
    ``fusion:Output f32[16384,50304]`` - the trace of this JAX carries no
    module path, so the shape is what names the layer."""
    m = _HLO.match(text)
    if not m or m.group("op") != "fusion":
        return None
    shape = _LAYOUT.sub("", m.group("shape"))
    if len(shape) > 72:
        shape = shape[:69] + "..."
    kind = _KIND.search(text)
    return f"fusion:{kind.group(1) if kind else '?'} {shape}"


def scope_of(stats: dict):
    """The flax module path an op's metadata carries, shortened: layer
    indices folded (``block_7`` -> ``block_*``), jit/jvp wrappers
    dropped, the last three elements kept."""
    path = None
    for key in ("tf_op", "op_name", "name", "long_name"):
        v = stats.get(key)
        if isinstance(v, str) and "/" in v:
            path = v
            break
    if path is None:
        return None
    parts = []
    for el in path.split("/"):
        el = el.strip()
        while True:
            m = re.fullmatch(r"(?:jit|jvp|transpose|pjit|checkpoint|remat|"
                             r"vmap|custom_jvp|custom_vjp)\((.*)\)", el)
            if not m:
                break
            el = m.group(1)
        if not el or el in ("main", "step_fn", "jit", "pjit"):
            continue
        parts.append(re.sub(r"_\d+$", "_*", el))
    return "/".join(parts[-3:]) if parts else None


class Trace:
    """Device operations, programs and harness spans of one traced
    window. Times in seconds on the trace's own clock."""

    def __init__(self, ops, modules, spans, fallback_cpu=False):
        # ops/modules: {device: [(start, end, name, group)]}; spans:
        # [(start, end, name)]
        self.ops, self.modules, self.spans = ops, modules, spans
        self.fallback_cpu = fallback_cpu
        win = [s for s in spans if s[2] == WINDOW_SPAN]
        if win:
            self.w0, self.w1 = win[0][0], win[0][1]
        else:
            starts = [e[0] for v in ops.values() for e in v]
            ends = [e[1] for v in ops.values() for e in v]
            self.w0, self.w1 = (min(starts), max(ends)) if starts else (0, 0)
        self.window_s = self.w1 - self.w0

    # ------------------------------------------------------------ busy
    def _intervals(self, dev):
        out = []
        for s, e, _, _ in self.ops.get(dev, ()):
            s, e = max(s, self.w0), min(e, self.w1)
            if e > s:
                out.append((s, e))
        out.sort()
        merged = []
        for s, e in out:
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        return merged

    def busy_by_device(self):
        return {d: sum(e - s for s, e in self._intervals(d))
                for d in self.ops}

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        b = self.busy_by_device()
        return sum(b.values()) / len(b) if b else 0.0

    def idle_share_busiest(self):
        """1 - busy/window on the busiest chip; None without device ops."""
        b = self.busy_by_device()
        if not b or self.window_s <= 0:
            return None
        return 1.0 - max(b.values()) / self.window_s

    # ---------------------------------------------------------- events
    def op_events(self, pattern: str, dev=None):
        """``[(start, end)]`` of device ops inside the window whose clean
        name matches the regular expression, on one device (default: the
        first)."""
        rx = re.compile(pattern)
        devs = [dev] if dev is not None else sorted(self.ops)[:1]
        return [(s, e) for d in devs for s, e, n, _ in self.ops.get(d, ())
                if rx.fullmatch(n) and s >= self.w0 and e <= self.w1]

    def module_events(self, pattern: str, dev=None):
        rx = re.compile(pattern)
        devs = [dev] if dev is not None else sorted(self.modules)[:1]
        return [(s, e) for d in devs
                for s, e, n, _ in self.modules.get(d, ())
                if rx.search(n) and s >= self.w0 and e <= self.w1]

    def module_names(self):
        return sorted({n for v in self.modules.values()
                       for _, _, n, _ in v})

    # ------------------------------------------------------- breakdown
    def breakdown(self, default_host="untracked", top=10, min_gap_s=20e-6):
        """Device operations by time (fusions grouped under the module
        path they carry, where the trace has it) and idle gaps by the
        harness span the host was in, busiest chip."""
        b = self.busy_by_device()
        if not b:
            return {"device_ops": [], "idle_gaps": []}
        dev = max(b, key=b.get)
        by = defaultdict(float)
        for s, e, n, g in self.ops[dev]:
            s, e = max(s, self.w0), min(e, self.w1)
            if e > s:
                by[g or n] += e - s
        spans = sorted((s for s in self.spans if s[2] != WINDOW_SPAN),
                       key=lambda s: s[1] - s[0])     # innermost first
        gaps = defaultdict(float)
        prev = self.w0
        for s, e in self._intervals(dev) + [[self.w1, self.w1]]:
            if s - prev >= min_gap_s:
                mid = 0.5 * (prev + s)
                name = next((n for a, z, n in spans if a <= mid <= z),
                            default_host)
                gaps[name] += s - prev
            prev = max(prev, e)
        rank = lambda d: [[k, v] for k, v in sorted(          # noqa: E731
            d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": rank(by), "idle_gaps": rank(gaps)}


def find_xplane(trace_dir: str) -> str:
    if os.path.isfile(trace_dir):
        return trace_dir
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_profile(path: str):
    """``ProfileData`` of an ``.xplane.pb`` as the profiler wrote it, or
    of a trimmed recording kept as gzipped text proto (``.txtpb.gz``, see
    ``checks/trim_trace.py``)."""
    from jax.profiler import ProfileData

    path = find_xplane(path)
    if path.endswith(".txtpb.gz"):
        import gzip

        with gzip.open(path, "rt") as f:
            return ProfileData.from_serialized_xspace(
                ProfileData.text_proto_to_serialized_xspace(f.read()))
    return ProfileData.from_file(path)


def load(trace_dir: str) -> Trace:
    pd = read_profile(trace_dir)
    ops, modules, spans = {}, {}, []
    host_ops = []
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:") \
            and "CUSTOM" not in plane.name.upper()
        for line in plane.lines:
            if is_dev and line.name in (OPS_LINE, MODULES_LINE):
                dst = (ops if line.name == OPS_LINE else modules
                       ).setdefault(plane.name, [])
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    name = clean(ev.name)
                    group = None
                    if line.name == OPS_LINE and "fusion" in name:
                        group = scope_of(dict(ev.stats))
                        group = (f"{group} [{name}]" if group
                                 else fusion_label(ev.name))
                    dst.append((s, s + ev.duration_ns * 1e-9, name, group))
            elif not is_dev:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = ev.start_ns * 1e-9
                        spans.append((s, s + ev.duration_ns * 1e-9,
                                      ev.name))
                    elif not ops and ev.duration_ns > 0:
                        st = dict(ev.stats)
                        if "hlo_op" in st:
                            s = ev.start_ns * 1e-9
                            host_ops.append(
                                (s, s + ev.duration_ns * 1e-9,
                                 clean(ev.name), None))
    fallback = False
    if not ops and host_ops:
        ops, fallback = {"/host:CPU": host_ops}, True
    return Trace(ops, modules, spans, fallback_cpu=fallback)


def describe(trace_dir: str, top=40) -> str:
    """What a trace holds, for looking at one by hand: planes, lines,
    event counts, the commonest event names and the stat keys they
    carry."""
    pd = read_profile(trace_dir)
    out = []
    for plane in pd.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(evs)} events")
            tot = defaultdict(float)
            keys = {}
            for ev in evs:
                tot[clean(ev.name)] += ev.duration_ns * 1e-9
                if clean(ev.name) not in keys:
                    keys[clean(ev.name)] = {
                        k: (str(v)[:120]) for k, v in ev.stats}
            for n, t in sorted(tot.items(), key=lambda kv: -kv[1])[:top]:
                out.append(f"    {t:10.6f}s {n}  {keys[n]}")
    return "\n".join(out)
