"""Readings that the ``ling3.serve.longdoc`` cell's limit is set from, as
``calibrate_qwen3next.py`` for its cell (whose reduction of the per-token
arrays this imports): several seeds in one process, the float8 control
over the ``--control-seeds``' own prompts and served tokens
(``chiprun -- python3 benchmarks/checks/calibrate_ling3.py --seeds 501 502
--seconds 20 --control-seeds 501``). Arrays go to
``chiprun_out/ling3_check_seed<n>.npz``, result lines to
``chiprun_out/ling3_calibrate.jsonl``. Not part of a benchmark run."""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                os.pardir, os.pardir)))

from benchmarks.checks.calibrate_qwen3next import readings  # noqa: E402
from benchmarks.lib import common, serve_model  # noqa: E402

CELL = "ling3.serve.longdoc"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=None)
    ap.add_argument("--seconds", type=float, default=45)
    ap.add_argument("--control", default="fp8")
    ap.add_argument("--tiny", action="store_true",
                    help="the toy cell on the CPU, to rehearse this script")
    a = ap.parse_args()
    bench = common.benchmark_json()
    out_dir = os.path.join(common.ROOT, "chiprun_out")
    stem = os.path.join(out_dir, "ling3_check")
    os.environ["BENCH_CHECK_DUMP"] = stem
    controlled = set(a.seeds if a.control_seeds is None else a.control_seeds)
    out = {}
    for seed in a.seeds:
        control = a.control if seed in controlled else None
        line = io.StringIO()
        with contextlib.redirect_stdout(line):
            if a.tiny:
                from benchmarks.checks import tiny_ling3
                tiny_ling3.run_serve(seed, a.seconds, 0, control=control)
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
        with open(os.path.join(out_dir, "ling3_calibrate.jsonl"), "a") as f:
            f.write(json.dumps({"seed": seed, "line": json.loads(
                line.getvalue().strip().splitlines()[-1])}) + "\n")
        out[str(seed)] = readings(f"{stem}_seed{seed}.npz",
                                  control is not None)
        print(json.dumps({"seed": seed, "readings": out[str(seed)]}),
              file=sys.stderr, flush=True)
    print(json.dumps({"workload": CELL, "control": a.control,
                      "readings": out}))


if __name__ == "__main__":
    main()
