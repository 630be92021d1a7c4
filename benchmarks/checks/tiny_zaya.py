"""The toy cell of the ``serve_zaya`` kind, for the harness's own checks
(see ``tiny.py``): the ``zaya`` architecture at hidden 64, 4 query and 2
K/V heads of 16, 4 experts of width 32, 3 layers, vocabulary 256. Never a
benchmark cell: its numbers mean nothing."""

from __future__ import annotations

import argparse

from benchmarks.checks import tiny
from benchmarks.lib import common

CELL = "zaya1.serve.backlog96"
TINY_ZAYA_CFG = {
    "model_type": "zaya", "hidden_size": 64, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "num_experts": 4, "num_experts_per_tok": 1, "moe_intermediate_size": 32,
    "router_hidden_size": 16, "vocab_size": 256, "cca_time0": 2,
    "cca_time1": 2, "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-5,
    "rope_parameters": {"hybrid": {"rope_theta": 5000000,
                                   "partial_rotary_factor": 0.5}},
    "max_position_embeddings": 1024}


def serve_traffic():
    t = common.load_json(common.BENCH_DIR, "traffic", "serve.backlog96.json")
    t["engine"] = {"slots": 4, "max_len": 256, "chunk_len": 128,
                   "page_len": 128}
    t["scheduler"] = {"max_queue": 8}
    t.update(block=16, blocks=40, trace_seconds=0.5, max_total=256)
    t["prompt"] = {"median": 60, "sigma": 0.8, "min": 8, "max": 200}
    t["output"] = {"median": 6, "sigma": 0.5, "min": 2, "max": 12}
    # the toy's own limit: sound runs read 0 off the reference's best
    # and the float8 control 0.074 at the least, some 100 served tokens a
    # run (CPU, PR 30). The cell's own limit is in its traffic file.
    t["check"] = dict(t["check"], sample=16,
                      limits={"off_best_share": 0.03})
    return t


def run_serve(seed, seconds, trace, device_check=False, traffic=None,
              control=None):
    from benchmarks.lib import serve_zaya

    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace,
                              workload="tiny.zaya")
    return serve_zaya.run({"name": "tiny.zaya", "chips": 1,
                           "config": "tiny-zaya"},
                          dict(TINY_ZAYA_CFG), traffic or serve_traffic(),
                          args, tiny.bench_with("tiny.zaya", CELL),
                          device_check=device_check, control=control)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=2)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--chip", type=int, default=0)
    ap.add_argument("--control", default=None)
    a = ap.parse_args()
    run_serve(a.seed, a.seconds, a.trace, bool(a.chip), control=a.control)
