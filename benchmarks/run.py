"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data: ``BENCHMARK.json`` names the
cell's configuration (``benchmarks/configs/<config>.json``) and traffic
mix (``benchmarks/traffic/<traffic>.json``); the traffic file names the
driver kind; each per-layer metric is ``benchmarks/metrics/<name>.json``.
The last line of standard output is the result object.
"""

from __future__ import annotations

import argparse
import importlib
import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                os.pardir)))

from benchmarks.lib import common  # noqa: E402  (starts the set-up clock)


DRIVERS = {"train_recipe": "train", "serve_open_loop": "serve"}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = common.benchmark_json()
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        raise SystemExit(f"benchmark: no workload {args.workload!r} in "
                         "BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = common.load_json(common.ROOT, cfg_entry["file"])
    traffic = common.load_json(common.BENCH_DIR, "traffic",
                               cell["traffic"] + ".json")
    # the traffic file names its driver kind; a kind that a later PR adds
    # is a module of that name under benchmarks/lib/ with a ``run``
    module = DRIVERS.get(traffic["kind"], traffic["kind"])
    try:
        driver = importlib.import_module(f"benchmarks.lib.{module}")
    except ModuleNotFoundError as e:
        if e.name != f"benchmarks.lib.{module}":
            raise
        raise SystemExit(f"benchmark: unknown traffic kind "
                         f"{traffic['kind']!r}")
    driver.run(cell, cfg, traffic, args, bench)


if __name__ == "__main__":
    main()
