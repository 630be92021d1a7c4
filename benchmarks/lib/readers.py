"""The reader kinds a per-layer metric's file may name. Each metric is a
file ``benchmarks/metrics/<name>.json`` with a ``kind`` and that kind's
parameters; a later PR adds a metric of a kind that is here as one new
file and one new entry of ``BENCHMARK.json``; a variant of a metric
that is here (``<name>.<variant>``, for cells that report another
end-to-end metric) is one new entry alone.

A reader is given the run's context (counters and series the driver
collected, the reduced trace, the configuration, the traffic, the peaks)
and returns a number, or None where it finds nothing to read - the
harness then leaves the metric out of the line. A share of a roofline or
of a peak is never reported as 0.
"""

from __future__ import annotations

import importlib
import os

from . import common, flops


def _by_name(name: str, default):
    """``fn`` of the module a kind has always used, or ``module:fn`` of a
    module that a later PR adds under ``benchmarks/lib/`` - so that a new
    work function or reader kind is a new file, not an edit."""
    if ":" in name:
        mod, name = name.split(":", 1)
        return getattr(importlib.import_module(f"{__package__}.{mod}"), name)
    return default[name] if isinstance(default, dict) \
        else getattr(default, name)


def counter(ctx, p):
    return ctx["counters"].get(p["key"])


def span_stat(ctx, p):
    """A statistic over a series of host-clock readings."""
    vals = ctx["series"].get(p["series"])
    if not vals:
        return None
    return common.stat(vals, p["stat"])


def rate_mfu(ctx, p):
    """Model operations per second over the chip's peak: operations per
    item from the configuration's shapes times the measured rate."""
    if "flops_per_item_fn" in p:
        per_item = _by_name(p["flops_per_item_fn"], flops)(ctx["cfg"],
                                                           ctx["traffic"])
        per_s = per_item * ctx["rates"][p["rate"]]
    else:
        per_s = ctx["rates"].get(p["rate"])
    if not per_s:
        return None
    return 100.0 * per_s / ctx["peaks"]["bf16_flops"]


def idle_share(ctx, p):
    tr = ctx.get("trace")
    share = tr.idle_share_busiest() if tr is not None else None
    return None if share is None else 100.0 * share


def program_time(ctx, p):
    """Device duration of one compiled program, from the trace's program
    line."""
    tr = ctx.get("trace")
    if tr is None:
        return None
    evs = tr.module_events(p["pattern"])
    if not evs:
        return None
    return common.stat([(e - s) * 1e3 for s, e in evs], p["stat"])


def kernel_roofline(ctx, p):
    """Least time the chip could take for the kernel's calls in the
    traced window - the larger of operations over peak FLOP/s and bytes
    over peak bytes/s, from ``flops.<work_fn>`` - over the device time of
    its events."""
    tr = ctx.get("trace")
    if tr is None:
        return None
    total, n = 0.0, None
    for pat in p["patterns"]:
        evs = tr.op_events(pat)
        total += sum(e - s for s, e in evs)
        n = len(evs) if n is None else min(n, len(evs))
    if not n or total <= 0:
        return None
    try:
        f, b = _by_name(p["work_fn"], flops)(ctx, n)
    except (KeyError, TypeError):
        return None
    t_f = f / ctx["peaks"]["bf16_flops"]
    t_b = b / ctx["peaks"]["hbm_bytes_per_s"]
    if max(t_f, t_b) <= 0:
        return None
    ctx.setdefault("notes", []).append(
        f"{p['work_fn']}: {n} calls, {total:.4f}s on device; bound by "
        f"{'compute' if t_f >= t_b else 'memory'} "
        f"({t_f:.5f}s vs {t_b:.5f}s)")
    return 100.0 * max(t_f, t_b) / total


KINDS = {"counter": counter, "span_stat": span_stat, "rate_mfu": rate_mfu,
         "idle_share": idle_share, "program_time": program_time,
         "kernel_roofline": kernel_roofline}


def _spec(name: str):
    """``metrics/<name>.json``; a quantity split by the end-to-end metric
    its cells report (``decode_prog_ms_p50.chat``) reads the one file of
    the quantity, ``metrics/decode_prog_ms_p50.json``, unless the variant
    brings a file of its own."""
    for stem in (name, name.split(".", 1)[0]):
        path = os.path.join(common.BENCH_DIR, "metrics", stem + ".json")
        if os.path.exists(path):
            return common.load_json(path)
    raise FileNotFoundError(f"no benchmarks/metrics file for {name!r}")


def read_all(bench, cell, ctx):
    """Every per-layer metric that lists this cell (or lists none)."""
    out = {}
    for m in bench["per_layer"]:
        cells = m.get("workloads")
        if cells is not None and cell not in cells:
            continue
        spec = _spec(m["name"])
        v = _by_name(spec["kind"], KINDS)(ctx, spec)
        if v is not None:
            out[m["name"]] = v
    for note in ctx.get("notes", []):
        common.log(note)
    return out
