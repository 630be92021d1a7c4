"""Cut a profiler trace down to what the reduction reads, for keeping a
small recording beside the benchmark: the device planes' ``XLA Ops`` and
``XLA Modules`` lines and the harness's own ``bench.*`` spans, inside the
first ``--seconds`` of the ``bench.window`` span, written as gzipped text
proto that ``benchmarks.lib.trace.read_profile`` reads back.

    python3 benchmarks/checks/trim_trace.py <trace dir> <out.txtpb.gz> --seconds 0.4
"""

from __future__ import annotations

import argparse
import gzip
import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                os.pardir, os.pardir)))

from benchmarks.lib import trace  # noqa: E402


def _quote(s: str) -> str:
    return s.replace("\\", "\\\\").replace('"', '\\"')


def trim(src: str, dst: str, seconds: float, name_chars: int = 160):
    pd = trace.read_profile(src)
    w0 = None
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == trace.WINDOW_SPAN:
                    w0 = ev.start_ns
    if w0 is None:
        raise SystemExit("no bench.window span in the trace")
    w1 = w0 + seconds * 1e9
    out, pid = [], 0
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:") \
            and "CUSTOM" not in plane.name.upper()
        lines, meta = [], {}
        for lid, line in enumerate(plane.lines, 1):
            if is_dev and line.name not in (trace.OPS_LINE,
                                            trace.MODULES_LINE):
                continue
            evs = []
            for ev in line.events:
                if not is_dev and not ev.name.startswith(trace.SPAN_PREFIX):
                    continue
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if ev.name == trace.WINDOW_SPAN:
                    s, e = w0, w1
                elif s < w0 or e > w1:
                    continue
                name = ev.name[:name_chars]
                kind = trace._KIND.search(ev.name)
                if kind and not trace._KIND.search(name):
                    name += " ..., " + kind.group(0)
                mid = meta.setdefault(name, len(meta) + 1)
                evs.append(f"    events {{ metadata_id: {mid} offset_ps: "
                           f"{int(round((s - w0) * 1000))} duration_ps: "
                           f"{int(round((e - s) * 1000))} }}")
            if evs:
                lines.append(f'  lines {{ id: {lid} name: "{_quote(line.name)}"'
                             f" timestamp_ns: 1000\n" + "\n".join(evs)
                             + "\n  }")
        if not lines:
            continue
        pid += 1
        out.append(f'planes {{ id: {pid} name: "{_quote(plane.name)}"\n'
                   + "\n".join(lines) + "\n" + "\n".join(
                       f'  event_metadata {{ key: {i} value {{ id: {i} name: '
                       f'"{_quote(n)}" }} }}' for n, i in meta.items())
                   + "\n}")
    with gzip.open(dst, "wt", compresslevel=9) as f:
        f.write("\n".join(out) + "\n")
    return os.path.getsize(dst)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("src")
    ap.add_argument("dst")
    ap.add_argument("--seconds", type=float, default=0.4)
    a = ap.parse_args()
    print(trim(a.src, a.dst, a.seconds), "bytes")
