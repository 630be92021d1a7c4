"""What every cell's driver shares: where the checkout is, which chip is
attached and its published peaks, the compile cache, the compile counter,
the garbage collector's quiet window, and the result line.

The yardstick lives here and not in the program: a later PR that claims a
gain may not move it.
"""

from __future__ import annotations

import gc
import json
import os
import statistics
import sys
import time

T_PROCESS_START = time.perf_counter()   # set at first import, by run.py

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
BENCH_DIR = os.path.join(ROOT, "benchmarks")
OUT_DIR = os.path.join(ROOT, ".bench_out")     # git-ignored, inside checkout


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark_json():
    return load_json(ROOT, "BENCHMARK.json")


def peaks_table():
    return load_json(BENCH_DIR, "lib", "peaks.json")


def out_dir(workload: str) -> str:
    d = os.path.join(OUT_DIR, workload)
    os.makedirs(d, exist_ok=True)
    return d


def log(*a):
    """Progress goes to standard error; standard output is the recipe's
    own lines and, last, the result."""
    print("[bench]", *a, file=sys.stderr, flush=True)


def require_chip(chips: int, check: bool = True) -> dict:
    """The device as JAX reports it, with its peaks. A platform that is
    not ``tpu``, a ``device_kind`` with no published peaks, or fewer chips
    than the cell asks for ends the run before anything is measured.
    ``check=False`` is for the CPU tests of the harness only."""
    import jax

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    table = peaks_table()["kinds"]
    if not check:
        dev["peaks"] = next(iter(table.values()))
        return dev
    if dev["platform"] != "tpu":
        raise SystemExit(f"benchmark: platform is {dev['platform']!r}, not "
                         "'tpu' - no accelerator, nothing measured")
    if dev["kind"] not in table:
        raise SystemExit(f"benchmark: no published peaks for device_kind "
                         f"{dev['kind']!r} in benchmarks/lib/peaks.json")
    if dev["count"] < chips:
        raise SystemExit(f"benchmark: cell needs {chips} chips, JAX sees "
                         f"{dev['count']}")
    dev["peaks"] = table[dev["kind"]]
    return dev


def enable_compile_cache() -> str:
    """JAX's persistent cache at the program's own fixed path inside the
    checkout (or where JAX_COMPILATION_CACHE_DIR says), small programs
    included, so that a second run finds every program."""
    import jax
    from apex_tpu.utils import chip

    d = chip.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return d


class CompileCounter:
    """Counts backend compilations through JAX's own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring as mon

        self.n = 0
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.n += 1


class QuietGC:
    """No collection inside the window from objects the set-up left:
    collect, freeze what survives, switch the collector off; undo after."""

    def __enter__(self):
        gc.collect()
        gc.freeze()
        gc.disable()
        return self

    def __exit__(self, *exc):
        gc.enable()
        gc.unfreeze()
        return False


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation between order
    statistics, over ALL the values given."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of nothing")
    if len(v) == 1:
        return float(v[0])
    k = (len(v) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(v) - 1)
    return float(v[lo] + (v[hi] - v[lo]) * (k - lo))


def stat(values, which: str) -> float:
    if which == "mean":
        return float(statistics.fmean(values))
    if which == "max":
        return float(max(values))
    if which == "sum":
        return float(sum(values))
    if which.startswith("p"):
        return percentile(values, float(which[1:]))
    raise ValueError(f"unknown statistic {which!r}")


def memory_in_use_bytes() -> int:
    """Bytes of live arrays on the fullest chip, now."""
    import jax

    return max((int((d.memory_stats() or {}).get("bytes_in_use", 0))
                for d in jax.local_devices()), default=0)


def memory_peak_bytes(in_window: int = 0, program_temp: int = 0) -> int:
    """Peak bytes on the fullest chip. The allocator's own peak
    (``memory_stats()["peak_bytes_in_use"]``) counts live arrays and, on
    this runtime, NOT the temporaries a running program holds (PR 21 and
    PR 24 read it at the state's size under a step whose logits alone are
    3.3 GB). So where the driver of a cell knows the timed program, it
    hands in the live bytes it read inside the window and that program's
    temporaries by the compiler's count (``memory_analysis()``): both are
    held at once in every step, and their sum is a lower bound of the true
    peak. The larger of the two readings is reported."""
    import jax

    peak = max((int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                for d in jax.local_devices()), default=0)
    return max(peak, int(in_window) + int(program_temp))


def emit_result(*, bench, cell, trace, correct, attempted, failed, values,
                device, compared, extra_device=None, breakdown=None,
                extra=None):
    """Print each number compared beside its limit (standard error, last
    lines), then the one result object as the last line of standard
    output. ``values`` maps metric name -> number for whatever was read;
    a reader that found nothing left its metric out. ``extra``: further
    keys of the line, which the driver ignores (the schedule's use,
    the engine's own seconds per beat)."""
    defs = bench["per_layer"] if trace else bench["end_to_end"]
    metrics = {}
    for m in defs:
        cells = m.get("workloads")
        if cells is not None and cell not in cells:
            continue
        if m["name"] in values and values[m["name"]] is not None:
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"],
           "memory_peak_bytes": device["memory_peak_bytes"]}
    if extra_device:
        dev.update(extra_device)
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": dev}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line.update(extra or {})
    line["compared"] = compared
    sys.stdout.flush()
    for name, c in compared.items():
        print(f"[bench] compared {name}: {c['value']:.6g} "
              f"(limit {c['limit']:.6g}, {'ok' if c['ok'] else 'FAIL'})",
              file=sys.stderr)
    print(f"[bench] correct={bool(correct)}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


def compare(name_values_limits):
    """``{name: (value, limit)}`` -> the ``compared`` object and whether
    all held. A value that is not a number fails."""
    out, ok = {}, True
    for name, (v, lim) in name_values_limits.items():
        good = (v == v) and v <= lim
        out[name] = {"value": float(v), "limit": float(lim), "ok": bool(good)}
        ok = ok and good
    return out, ok
