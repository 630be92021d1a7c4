"""The toy cell of the ``serve_model`` kind for the ``ling_v3``
architecture, for the harness's own checks (see ``tiny.py``): hidden 64,
one period of six layers (two dense, five Kimi delta layers of 2 heads of
128 x 128 - the kernels' own tiling, so they run, interpreted - and one
latent layer with a latent of 32 + a rotary key of 16), 16 experts in 4
groups of which 2 are kept, 4 a token, a shared expert, vocabulary 256.
Never a benchmark cell: its numbers mean nothing."""

from __future__ import annotations

import argparse

from benchmarks.checks import tiny
from benchmarks.lib import common

CELL = "ling3.serve.longdoc"
TINY_LING_CFG = {
    "model_type": "ling_v3", "hidden_size": 64, "num_hidden_layers": 6,
    "layer_group_size": 6, "first_k_dense_replace": 2,
    "num_attention_heads": 2, "head_dim": 128, "short_conv_kernel_size": 4,
    "kda_lower_bound": -5, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 16, "v_head_dim": 16, "rope_theta": 6000000,
    "q_lora_rank": None, "intermediate_size": 96, "num_experts": 16,
    "num_experts_per_tok": 4, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 32, "n_group": 4,
    "topk_group": 2, "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "score_function": "sigmoid", "rms_norm_eps": 1e-6, "vocab_size": 256,
    "max_position_embeddings": 1024}


def serve_traffic():
    t = common.load_json(common.BENCH_DIR, "traffic",
                         "serve.longdoc.ling3.json")
    t["engine"] = {"slots": 4, "max_len": 384, "chunk_len": 128,
                   "page_len": 128}
    t["scheduler"] = {"max_queue": 8, "chunk_budget": 1}
    t.update(block=16, blocks=40, trace_seconds=0.5, max_total=384)
    t["prompt"] = {"median": 120, "sigma": 0.6, "min": 16, "max": 320}
    t["output"] = {"median": 6, "sigma": 0.5, "min": 2, "max": 12}
    # the toy's own rule and limit (CPU, PR 36), as the qwen3_next toy's
    # and for its reason: with 4 experts a token of 16 at hidden 64 a
    # flipped tie moves a quarter of a layer, so the WIDEST gap reads ties
    # (a sound run read 0.78) and the share of tokens further than
    # token_gap below the best is compared instead (test_ling3.py holds
    # the float8 control above the limit and sound runs under it). The
    # cell's own rule and limit are in its traffic file.
    t["check"] = dict(t["check"], sample=16, rule="off_best_share",
                      limits={"off_best_share": 0.1})
    return t


def run_serve(seed, seconds, trace, device_check=False, traffic=None,
              control=None, fault=None):
    from benchmarks.lib import serve_model

    args = argparse.Namespace(seed=seed, seconds=seconds, trace=trace,
                              workload="tiny.ling3")
    return serve_model.run({"name": "tiny.ling3", "chips": 1,
                            "config": "tiny-ling3"},
                           dict(TINY_LING_CFG), traffic or serve_traffic(),
                           args, tiny.bench_with("tiny.ling3", CELL),
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
