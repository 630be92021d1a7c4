"""Readings that a ``serve_model`` cell's limit is set from, for any such
cell: several seeds in one process, the float8 control over the
``--control-seeds``' own prompts and served tokens (``python3
benchmarks/checks/calibrate_model.py --workload keye.serve.longctx --seeds
501 502 --seconds 45 --control-seeds 501 --out-dir <dir>``, on one chip).
Per seed the per-token arrays (gap, control's gap, tie margin) go to
``<dir>/<cell>_check_seed<n>.npz`` and the run's result line to
``<dir>/<cell>_calibrate.jsonl``; the last line sums them up as
``calibrate_qwen3next.py`` does (whose reduction this imports).
``--tiny`` runs the cell's toy instead, on the CPU: the module
``checks/tiny_<name>.py`` named after the traffic file's
``reference_<name>``. Not part of a benchmark run."""

from __future__ import annotations

import argparse
import contextlib
import importlib
import io
import json
import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                os.pardir, os.pardir)))

from benchmarks.checks.calibrate_qwen3next import readings  # noqa: E402
from benchmarks.lib import common, serve_model  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True,
                    help="a cell of BENCHMARK.json whose traffic is of the "
                         "serve_model kind")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=None,
                    help="the seeds that also read the control "
                         "(default: all of them)")
    ap.add_argument("--seconds", type=float, default=45)
    ap.add_argument("--control", default="fp8")
    ap.add_argument("--tiny", action="store_true",
                    help="the cell's toy on the CPU, to rehearse this script")
    ap.add_argument("--out-dir", required=True,
                    help="where the arrays and result lines are written")
    a = ap.parse_args()
    bench = common.benchmark_json()
    cell = next(w for w in bench["workloads"] if w["name"] == a.workload)
    tr = common.load_json(common.BENCH_DIR, "traffic",
                          cell["traffic"] + ".json")
    if tr["kind"] != "serve_model":
        raise SystemExit(f"{a.workload}: traffic kind {tr['kind']!r}, not "
                         "serve_model")
    out_dir = os.path.abspath(a.out_dir)
    stem = os.path.join(out_dir, f"{a.workload}_check")
    os.environ["BENCH_CHECK_DUMP"] = stem
    controlled = set(a.seeds if a.control_seeds is None else a.control_seeds)
    out = {}
    for seed in a.seeds:
        control = a.control if seed in controlled else None
        line = io.StringIO()
        with contextlib.redirect_stdout(line):
            if a.tiny:
                name = tr["model"]["reference"].removeprefix("reference_")
                importlib.import_module(f"benchmarks.checks.tiny_{name}") \
                    .run_serve(seed, a.seconds, 0, control=control)
            else:
                cfg = common.load_json(common.ROOT, next(
                    c["file"] for c in bench["configs"]
                    if c["name"] == cell["config"]))
                args = argparse.Namespace(seed=seed, seconds=a.seconds,
                                          trace=0, workload=a.workload)
                serve_model.run(cell, cfg, tr, args, bench, control=control)
        os.makedirs(out_dir, exist_ok=True)
        with open(f"{os.path.join(out_dir, a.workload)}_calibrate.jsonl",
                  "a") as f:
            f.write(json.dumps({"seed": seed, "line": json.loads(
                line.getvalue().strip().splitlines()[-1])}) + "\n")
        out[str(seed)] = readings(f"{stem}_seed{seed}.npz",
                                  control is not None)
        print(json.dumps({"seed": seed, "readings": out[str(seed)]}),
              file=sys.stderr, flush=True)
    print(json.dumps({"workload": a.workload, "control": a.control,
                      "readings": out}))


if __name__ == "__main__":
    main()
