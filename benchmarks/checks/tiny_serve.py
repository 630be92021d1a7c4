"""The toy serving cell of the harness's own checks (see ``tiny.py``)."""

from __future__ import annotations

import argparse

from benchmarks.checks import tiny
from benchmarks.lib import common


def serve_traffic():
    t = common.load_json(common.BENCH_DIR, "traffic", "serve.chat.json")
    t["engine"] = {"slots": 4, "max_len": 256, "chunk_len": 128,
                   "page_len": 128}
    t.update(block=16, blocks=40, rate_per_s=6.0, trace_seconds=0.5,
             max_total=256)
    t["prompt"] = {"median": 60, "sigma": 0.8, "min": 8, "max": 200}
    t["output"] = {"median": 6, "sigma": 0.5, "min": 2, "max": 12}
    t["preroll"] = {"until": "seconds", "seconds": 1.0}
    t["check"]["sample"] = 16
    # the toy's own limit: sound runs read up to 0.0036 over 8 seeds and
    # the float8 control 0.029 at the least, some 80 served tokens a run
    # (CPU, PR 24). The cells' own limit is in their traffic files.
    t["check"]["limits"] = {"served_logit_gap": 0.01}
    return t


def run_serve(seed, seconds, trace, device_check=False, fault=None,
              traffic=None, control=None):
    from benchmarks.lib import serve

    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace,
                              workload="tiny.serve")
    return serve.run({"name": "tiny.serve", "chips": 1},
                     dict(tiny.TINY_SERVE_CFG), traffic or serve_traffic(),
                     args, tiny.bench_with("tiny.serve", tiny.SERVE_CELL),
                     device_check=device_check, fault=fault,
                     control=control)
