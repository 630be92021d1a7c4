"""The toy cell of the ``serve_model`` kind, for the harness's own checks
(see ``tiny.py``): the ``qwen3_next`` architecture at hidden 64, two
periods of (linear, full) layers, 2 key and 4 value heads of 128 in the
linear layers (the kernels' own tiling, so they run, interpreted), 4
query and 2 K/V heads of 32 in the full ones, 16 experts of width 32 at
4 a token with a shared expert, vocabulary 256. Never a benchmark cell:
its numbers mean nothing."""

from __future__ import annotations

import argparse

from benchmarks.checks import tiny
from benchmarks.lib import common

CELL = "qwen3next.serve.backlog"
TINY_Q3N_CFG = {
    "model_type": "qwen3_next", "hidden_size": 64, "num_hidden_layers": 4,
    "full_attention_interval": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "partial_rotary_factor": 0.25,
    "rope_theta": 10000000, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_key_head_dim": 128,
    "linear_value_head_dim": 128, "linear_conv_kernel_dim": 4,
    "num_experts": 16, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "norm_topk_prob": True, "rms_norm_eps": 1e-6, "vocab_size": 256,
    "tie_word_embeddings": False, "max_position_embeddings": 1024}


def serve_traffic():
    t = common.load_json(common.BENCH_DIR, "traffic",
                         "serve.backlog.qwen3next.json")
    t["engine"] = {"slots": 4, "max_len": 256, "chunk_len": 128,
                   "page_len": 128}
    t["scheduler"] = {"max_queue": 8}
    t.update(block=16, blocks=40, trace_seconds=0.5, max_total=256)
    t["prompt"] = {"median": 60, "sigma": 0.8, "min": 8, "max": 200}
    t["output"] = {"median": 6, "sigma": 0.5, "min": 2, "max": 12}
    # the toy's own rule and limit (CPU, PR 34, six seeds, some 100
    # served tokens a run): with 4 experts a token of 16 at hidden 64 a
    # flipped tie moves a quarter of a layer, so the WIDEST gap reads ties
    # (sound runs 0.004 to 2.40, the float8 control 0.71 to 1.26) and the
    # share of tokens further than token_gap below the best is compared
    # instead: sound runs 0 to 0.029, the control 0.227 at the least. The
    # cell's own rule and limit are in its traffic file.
    t["check"] = dict(t["check"], sample=16, rule="off_best_share",
                      limits={"off_best_share": 0.1})
    return t


def run_serve(seed, seconds, trace, device_check=False, traffic=None,
              control=None, fault=None):
    from benchmarks.lib import serve_model

    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace,
                              workload="tiny.qwen3next")
    return serve_model.run({"name": "tiny.qwen3next", "chips": 1,
                            "config": "tiny-qwen3next"},
                           dict(TINY_Q3N_CFG), traffic or serve_traffic(),
                           args, tiny.bench_with("tiny.qwen3next", CELL),
                           device_check=device_check, control=control,
                           fault=fault)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=2)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--chip", type=int, default=0)
    ap.add_argument("--control", default=None)
    a = ap.parse_args()
    run_serve(a.seed, a.seconds, a.trace, bool(a.chip), control=a.control)
