"""Readings that the ``serve_zaya`` cell's limits are set from, taken on
the chip at the cell's own size and load, several seeds in one process
(``chiprun -- python3 benchmarks/checks/calibrate_zaya.py --seeds 301 302
303 --seconds 20``): per seed one run of the cell as the benchmark makes
it and, over the same prompts and served tokens, the control - the plain
reference with both operands of every matrix product in float8_e4m3fn.
Per seed the per-token arrays (gap, control's gap, tie margin) go to
``chiprun_out/zaya_check_seed<n>.npz``; the last line sums up, for a grid
of per-token gaps, the share of the program's served tokens and of the
control's first tokens that lie further below the reference's best, and
for a grid of tie margins the share of served tokens on a tie and the
program's widest gap off them. Not part of a benchmark run."""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                os.pardir, os.pardir)))

import numpy as np  # noqa: E402

from benchmarks.lib import common, serve_zaya  # noqa: E402

CELL = "zaya1.serve.backlog96"
GAPS = (0.0, 0.02, 0.07, 0.2)
MARGINS = (0.001, 0.005, 0.01, 0.02, 0.05)


def readings(path):
    """Of one seed's arrays: ``{"n", "off_best": {gap: (program's share,
    control's share)}, "ties": {margin: (share on a tie, program's widest
    gap off ties)}}``."""
    z = np.load(path)
    g, c, t = z["gaps"], z["ctrl"], z["ties"]
    return {"n": int(len(g)),
            "off_best": {str(x): (float(np.mean(g > x)),
                                  float(np.mean(c > x))) for x in GAPS},
            "ties": {str(d): (float(np.mean(t < d)),
                              float(g[t >= d].max(initial=0.0)))
                     for d in MARGINS}}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=45)
    ap.add_argument("--control", default="fp8")
    ap.add_argument("--tiny", action="store_true",
                    help="the toy cell on the CPU, to rehearse this script")
    a = ap.parse_args()
    bench = common.benchmark_json()
    stem = os.path.join(common.ROOT, "chiprun_out", "zaya_check")
    os.environ["BENCH_CHECK_DUMP"] = stem
    out = {}
    for seed in a.seeds:
        if a.tiny:
            from benchmarks.checks import tiny_zaya
            tiny_zaya.run_serve(seed, a.seconds, 0, control=a.control)
        else:
            cell = next(w for w in bench["workloads"] if w["name"] == CELL)
            cfg = common.load_json(common.ROOT, next(
                c["file"] for c in bench["configs"]
                if c["name"] == cell["config"]))
            tr = common.load_json(common.BENCH_DIR, "traffic",
                                  cell["traffic"] + ".json")
            args = argparse.Namespace(seed=seed, seconds=a.seconds, trace=0,
                                      workload=CELL)
            serve_zaya.run(cell, cfg, tr, args, bench, control=a.control)
        out[str(seed)] = readings(f"{stem}_seed{seed}.npz")
    print(json.dumps({"workload": CELL, "control": a.control,
                      "readings": out}))


if __name__ == "__main__":
    main()
