"""Readings that the ``qwen3next.serve.backlog`` cell's limit is set from,
taken on the chip at the cell's own size and load, several seeds in one
process (``chiprun -- python3 benchmarks/checks/calibrate_qwen3next.py
--seeds 401 402 403 --seconds 20 --control-seeds 401``): per seed one run
of the cell as the benchmark makes it and, for the ``--control-seeds``,
over the same prompts and served tokens the control - the plain reference
with both operands of every matrix product in float8_e4m3fn. Per seed the
per-token arrays (gap, control's gap, tie margin) go to
``chiprun_out/qwen3next_check_seed<n>.npz`` and the run's result line to
``chiprun_out/qwen3next_calibrate.jsonl``; the last line sums up, per
seed, the program's and the control's widest gap and, for a grid of
per-token gaps, the share of served tokens further below the reference's
best, and for a grid of tie margins the share of served tokens on a tie
and the program's widest gap off them. Not part of a benchmark run."""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                os.pardir, os.pardir)))

import numpy as np  # noqa: E402

from benchmarks.lib import common, serve_model  # noqa: E402

CELL = "qwen3next.serve.backlog"
GAPS = (0.0, 0.02, 0.07, 0.2, 0.5)
MARGINS = (0.005, 0.02, 0.05, 0.1, 0.25)


def readings(path, controlled):
    """Of one seed's arrays: ``{"n", "widest": (program's, control's),
    "off_best": {gap: (program's share, control's share)}, "ties":
    {margin: (share on a tie, program's widest gap off ties)}}``."""
    z = np.load(path)
    g, c, t = z["gaps"], z["ctrl"], z["ties"]
    none = lambda v: v if controlled else None                  # noqa: E731
    return {"n": int(len(g)),
            "widest": (float(g.max(initial=0.0)),
                       none(float(c.max(initial=0.0)))),
            "off_best": {str(x): (float(np.mean(g > x)),
                                  none(float(np.mean(c > x))))
                         for x in GAPS},
            "ties": {str(d): (float(np.mean(t < d)),
                              float(g[t >= d].max(initial=0.0)))
                     for d in MARGINS}}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=None,
                    help="the seeds that also read the control "
                         "(default: all of them)")
    ap.add_argument("--seconds", type=float, default=45)
    ap.add_argument("--control", default="fp8")
    ap.add_argument("--tiny", action="store_true",
                    help="the toy cell on the CPU, to rehearse this script")
    a = ap.parse_args()
    bench = common.benchmark_json()
    out_dir = os.path.join(common.ROOT, "chiprun_out")
    stem = os.path.join(out_dir, "qwen3next_check")
    os.environ["BENCH_CHECK_DUMP"] = stem
    controlled = set(a.seeds if a.control_seeds is None else a.control_seeds)
    out = {}
    for seed in a.seeds:
        control = a.control if seed in controlled else None
        line = io.StringIO()
        with contextlib.redirect_stdout(line):
            if a.tiny:
                from benchmarks.checks import tiny_qwen3next
                tiny_qwen3next.run_serve(seed, a.seconds, 0, control=control)
            else:
                cell = next(w for w in bench["workloads"]
                            if w["name"] == CELL)
                cfg = common.load_json(common.ROOT, next(
                    c["file"] for c in bench["configs"]
                    if c["name"] == cell["config"]))
                tr = common.load_json(common.BENCH_DIR, "traffic",
                                      cell["traffic"] + ".json")
                args = argparse.Namespace(seed=seed, seconds=a.seconds,
                                          trace=0, workload=CELL)
                serve_model.run(cell, cfg, tr, args, bench, control=control)
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "qwen3next_calibrate.jsonl"),
                  "a") as f:
            f.write(json.dumps({"seed": seed, "line": json.loads(
                line.getvalue().strip().splitlines()[-1])}) + "\n")
        out[str(seed)] = readings(f"{stem}_seed{seed}.npz",
                                  control is not None)
    print(json.dumps({"workload": CELL, "control": a.control,
                      "readings": out}))


if __name__ == "__main__":
    main()
