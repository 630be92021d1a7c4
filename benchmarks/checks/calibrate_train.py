"""Readings that the training cell's limits are set from, taken on the
chip at the cell's own size (``chiprun -- python3
benchmarks/checks/calibrate_train.py --seeds 101 102 103``): the control
(the reference with every GEMM's operands in float8_e4m3fn) and the
planted fault (half of the batch left out) against the float32 reference,
by the numbers ``correct`` compares. The program's own readings come from
the cell's runs. Not part of a benchmark run."""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                os.pardir, os.pardir)))

from benchmarks.lib import common, reference_lm, train  # noqa: E402


CONTROLS = (("control_fp8", {"lowp": "fp8"}),
            ("fault_half_batch", {"faults": ("half_batch",)}))


def readings(cfg, traffic, seed):
    """``{label: {number: value}}`` for the control and the planted fault
    put in the program's place, against the float32 reference, on the
    batches the recipe would draw from this seed's token file."""
    names = reference_lm.leaf_names(cfg)
    B, S = int(traffic["batch"]), int(traffic["seq_len"])
    kw = dict(lr=float(traffic["lr"]),
              weight_decay=float(traffic["weight_decay"]),
              rows_per_block=int(traffic["check"]["rows_per_block"]))
    limits = {"loss_gap": 0, "grad_norm_gap": 0, "param_change_gap": 0}
    path = os.path.join(common.out_dir("calibrate"), "tokens.npy")
    train.write_token_stream(path, seed, int(traffic["data"]["tokens"]),
                             int(cfg["vocab_size"]))
    data = np.load(path)
    rng = np.random.default_rng(seed)
    batches = [np.stack([data[i:i + S + 1] for i in
                         rng.integers(0, len(data) - S, size=B)])
               for _ in range(int(traffic["check"]["steps"]))]
    p0 = reference_lm.recipe_init(cfg, seed % (2**31 - 1))
    res = {}
    for label, opts in CONTROLS:
        other = reference_lm.train_steps(cfg, p0, batches, **kw, **opts)
        ref = reference_lm.train_steps(cfg, p0, batches, **kw,
                                       program_final=other["final"])
        prog = {"losses": other["losses"],
                "grad_norms": other["grad_norms"],
                "change_norms": ref["program_change_norms"]}
        res[label] = {k: v[0] for k, v in train.compare_training(
            ref, prog, names, limits).items()}
        common.log(f"seed {seed} {label}: {json.dumps(res[label])}")
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="gpt2s.train.b16s1024")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    a = ap.parse_args()
    bench = common.benchmark_json()
    cell = next(w for w in bench["workloads"] if w["name"] == a.workload)
    cfg = common.load_json(common.ROOT, next(
        c["file"] for c in bench["configs"] if c["name"] == cell["config"]))
    traffic = common.load_json(common.BENCH_DIR, "traffic",
                               cell["traffic"] + ".json")
    common.require_chip(cell["chips"])
    common.enable_compile_cache()
    print(json.dumps({str(seed): readings(cfg, traffic, seed)
                      for seed in a.seeds}))


if __name__ == "__main__":
    main()
