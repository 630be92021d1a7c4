"""A toy-sized cell for the harness's own checks (CPU, or the chip when a
small trace is to be recorded). Never a benchmark cell: its numbers mean
nothing."""

from __future__ import annotations

import argparse
import copy
import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                os.pardir, os.pardir)))

from benchmarks.lib import common  # noqa: E402

TRAIN_CELL = "gpt2s.train.b16s1024"
SERVE_CELL = "gpt2l.serve.chat"
TINY_CFG = {"n_embd": 128, "n_layer": 2, "n_head": 4, "n_positions": 64,
            "vocab_size": 512, "recipe_size": "tiny"}
TINY_SERVE_CFG = {"n_embd": 128, "n_layer": 2, "n_head": 4,
                  "n_positions": 256, "vocab_size": 512}


def bench_with(cell_name, like):
    """BENCHMARK.json with a toy cell that reports what ``like`` does."""
    bench = copy.deepcopy(common.benchmark_json())
    for m in bench["per_layer"] + bench["end_to_end"]:
        if like in m.get("workloads", ()):
            m["workloads"].append(cell_name)
    return bench


def train_traffic():
    t = common.load_json(common.BENCH_DIR, "traffic", "train.b16s1024.json")
    t["argv"] = ["4" if a == "16" else "64" if a == "1024" else a
                 for a in t["argv"]]
    t.update(batch=4, seq_len=64, items_per_step=256, warm_steps=4,
             trace_seconds=0.5)
    t["data"]["tokens"] = 100000
    t["check"]["rows_per_block"] = 2
    # a toy leaf has 128 elements where the cell's smallest has 768, so
    # its norms swing more: sound runs read up to 0.0036 and 0.0066 here,
    # the float8 control 0.0115 and 0.011 at the least, half a batch left
    # out 0.36 and 0.065, an unchanged state 1 and 1 (CPU, PR 24). So at
    # this size only the gradient separates the control. The cell's own
    # limits are in its traffic file.
    t["check"]["limits"] = {"grad_norm_gap": 0.008, "param_change_gap": 0.03}
    return t


def run_train(seed, seconds, trace, device_check=False, fault=None):
    from benchmarks.lib import train

    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace,
                              workload="tiny.train")
    return train.run({"name": "tiny.train", "chips": 1}, dict(TINY_CFG),
                     train_traffic(), args,
                     bench_with("tiny.train", TRAIN_CELL),
                     device_check=device_check, fault=fault)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("kind", choices=("train", "serve"))
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=2)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--chip", type=int, default=0)
    ap.add_argument("--fault", default=None)
    a = ap.parse_args()
    if a.kind == "train":
        run_train(a.seed, a.seconds, a.trace, bool(a.chip), a.fault)
    else:
        from benchmarks.checks import tiny_serve
        tiny_serve.run_serve(a.seed, a.seconds, a.trace, bool(a.chip),
                             a.fault)
