"""Readings that a serving cell's limit is set from, taken on the chip at
the cell's own size and load, several seeds in one process (``chiprun --
python3 benchmarks/checks/calibrate_serve.py --workload gpt2l.serve.backlog
--seeds 201 202 203 --seconds 45``): per seed one run of the cell as the
benchmark makes it, and over the same prompts and served tokens the
control - the plain reference with both operands of every GEMM in
float8_e4m3fn - read by the number ``correct`` compares. Prints one line
per seed and a summary. Not part of a benchmark run."""

from __future__ import annotations

import argparse
import io
import json
import os
import re
import sys
from contextlib import redirect_stderr

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                os.pardir, os.pardir)))

from benchmarks.lib import common, serve  # noqa: E402


class _Tee(io.StringIO):
    def write(self, s):
        sys.__stderr__.write(s)
        return super().write(s)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="gpt2l.serve.backlog")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=45)
    ap.add_argument("--control", default="fp8")
    ap.add_argument("--tiny", action="store_true",
                    help="the toy cell on the CPU, to rehearse this script")
    a = ap.parse_args()
    bench = common.benchmark_json()
    out = {}
    for seed in a.seeds:
        args = argparse.Namespace(seed=seed, seconds=a.seconds, trace=0,
                                  workload=a.workload)
        buf = _Tee()
        with redirect_stderr(buf):
            if a.tiny:
                from benchmarks.checks import tiny_serve
                tiny_serve.run_serve(seed, a.seconds, 0,
                                     control=a.control)
            else:
                cell = next(w for w in bench["workloads"]
                            if w["name"] == a.workload)
                cfg = common.load_json(common.ROOT, next(
                    c["file"] for c in bench["configs"]
                    if c["name"] == cell["config"]))
                tr = common.load_json(common.BENCH_DIR, "traffic",
                                      cell["traffic"] + ".json")
                serve.run(cell, cfg, tr, args, bench, control=a.control)
        m = re.search(r"control \S+: widest gap of its first tokens (\S+) "
                      r"\(program's served tokens: (\S+)\)", buf.getvalue())
        out[str(seed)] = {"control": float(m.group(1)),
                          "program": float(m.group(2))}
    print(json.dumps({"workload": a.workload, "control": a.control,
                      "readings": out,
                      "program_max": max(v["program"] for v in out.values()),
                      "control_min": min(v["control"]
                                         for v in out.values())}))


if __name__ == "__main__":
    main()
