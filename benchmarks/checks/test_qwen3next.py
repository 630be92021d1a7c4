"""The ``serve_model`` kind and the ``qwen3_next`` configuration (PR 34):
the reference against cases written out by hand, the work functions
against counts done by hand, the traffic file's lengths (the ``zaya``
cell's, letter for letter), what the cell reports, a planted fault read as
not correct, and the control - the reference in float8 put in the
program's place - failing ``correct`` where the bfloat16 path passes, at
the toy size of ``tiny_qwen3next.py``."""

import json
import os
import re

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.checks import tiny_qwen3next
from benchmarks.lib import common, readers, traffic
from benchmarks.lib import reference_qwen3next as rq
from benchmarks.lib import work_qwen3next as work

BENCH = common.benchmark_json()
CELL = "qwen3next.serve.backlog"
CFG = common.load_json(common.ROOT,
                       "benchmarks/configs/qwen3-next-80b-a3b.json")
MIX = common.load_json(common.BENCH_DIR, "traffic",
                       "serve.backlog.qwen3next.json")
ZAYA_MIX = common.load_json(common.BENCH_DIR, "traffic",
                            "serve.backlog96.json")


# --------------------------------------------------- the reference, by hand
def test_reference_delta_rule_by_hand():
    """One head, 2 x 2 state, two tokens, every number written out."""
    q = jnp.asarray([[[1., 0.]], [[0., 1.]]])
    k = jnp.asarray([[[1., 0.]], [[1., 0.]]])
    v = jnp.asarray([[[2., 4.]], [[6., 8.]]])
    g = jnp.log(jnp.asarray([[0.5], [0.5]]))
    beta = jnp.asarray([[1.0], [0.5]])
    o = np.asarray(rq.delta_rule(q, k, v, g, beta))
    # t0: S = k v^T = [[2, 4], [0, 0]]; o = S^T q = [2, 4]
    np.testing.assert_allclose(o[0, 0], [2., 4.], rtol=1e-6)
    # t1: S <- S / 2 = [[1, 2], [0, 0]]; r = S^T k = [1, 2];
    # S += k (0.5 (v - r))^T = [[1 + 2.5, 2 + 3], [0, 0]]; o = S^T q = 0
    np.testing.assert_allclose(o[1, 0], [0., 0.], atol=1e-6)
    o2 = np.asarray(rq.delta_rule(jnp.asarray([[[1., 0.]], [[1., 0.]]]),
                                  k, v, g, beta))
    np.testing.assert_allclose(o2[1, 0], [3.5, 5.], rtol=1e-6)


def test_reference_router_takes_the_k_largest_and_renormalises():
    cfg = dict(tiny_qwen3next.TINY_Q3N_CFG, hidden_size=2, num_experts=4,
               num_experts_per_tok=2)
    lp = {"router/w": jnp.asarray([[1., 2., 3., 0.], [0., 0., 0., 5.]])}
    choice, w, margin = rq.route(jnp.asarray([[1., 0.], [0., 1.]]), lp, cfg)
    assert sorted(np.asarray(choice[0])) == [1, 2]
    e = np.exp([3., 2.])
    np.testing.assert_allclose(np.sort(np.asarray(w[0]))[::-1],
                               e / e.sum(), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(margin), [1., 0.], atol=1e-6)
    assert int(choice[1][0]) == 3


def test_reference_convolution_is_causal_with_the_last_tap_current():
    cfg = dict(tiny_qwen3next.TINY_Q3N_CFG, num_hidden_layers=1)
    lp = {k: jnp.asarray(v, jnp.float32) for k, v in
          rq.seeded_weights(cfg, 2, jnp.float32)["layers"][0].items()}
    u = jnp.asarray(np.random.default_rng(0).normal(size=(6, 64)),
                    jnp.float32)
    full = rq.linear_attention(u, lp, cfg)
    # a position's output does not move when later positions change
    u2 = u.at[4:].set(0.0)
    part = rq.linear_attention(u2, lp, cfg)
    np.testing.assert_allclose(np.asarray(full[:4]), np.asarray(part[:4]),
                               atol=1e-6)
    assert float(jnp.abs(full[4:] - part[4:]).max()) > 1e-3


# ---------------------------------------------------------- work, by hand
def test_the_layers_matmul_parameters_are_the_issues():
    assert work.linear_layer_params(CFG) == 2048 * 12288 + 2048 * 64 \
        + 4096 * 2048 == 33_685_504
    assert work.full_layer_params(CFG) == 2048 * 8192 + 2 * 2048 * 512 \
        + 4096 * 2048 == 27_262_976
    assert work.moe_fixed_params(CFG) == 2048 * 512 + 3 * 2048 * 512 + 2048
    assert work.delta_rule_flops_per_token(CFG) == 7 * 32 * 128 * 128
    assert work.layer_counts(CFG) == (9, 3)
    assert work.held_rows_per_token(CFG) == 1.25      # 10 x 64 / 512


def test_forward_flops_count_this_chips_share_of_the_experts():
    fixed = 9 * (2 * 33_685_504 + 3_670_016) + 3 * 2 * 27_262_976 \
        + 12 * 2 * 4_196_352
    one = work.forward_flops_per_token(CFG, 100.0)
    assert one == fixed + 3 * 4 * 100 * 4096 \
        + 12 * 1.25 * 2 * 3 * 2048 * 512 + 2 * 151_936 * 2048
    # the counter: 2 of a token's 10 rows went to held experts (ids 0..63)
    routed = [[2] * 64 + [0] * 384 + [128 * 8 // 64] * 64] * 12
    assert sum(routed[0]) == 128 + 1024        # 1152 rows = 115.2 tokens
    rows = work.held_rows_per_token(CFG, routed)
    assert rows == pytest.approx(10 * 128 / 1152)
    ev = [("decode", 100.0), ("chunk", 256, 10, True)]
    want = fixed + 3 * 4 * 100 * 4096 + 2 * 151_936 * 2048 \
        + 10 * (fixed + 3 * 4 * 261.5 * 4096) + 2 * 151_936 * 2048 \
        + 11 * 12 * rows * 2 * 3 * 2048 * 512
    assert work.serve_window_flops(CFG, ev, routed=routed) \
        == pytest.approx(want)


def test_the_kernels_work_by_hand():
    ctx = {"cfg": CFG, "counters": {},
           "serve": {"traced_decode_context_tokens": 50_000,
                     "traced_decode_tokens": [192, 0, 180],
                     "traced_chunks": [(0, 256), (256, 40)]}}
    f, b = work.gdn_step(ctx, 18)
    assert f == 372 * 9 * 7 * 32 * 128 * 128
    # the state there and back, and q, k, v, sq, u rows of 128 floats
    assert b == 372 * 9 * (2 * 2_097_152 + 32 * 7 * 128 * 4)
    f, b = work.gdn_chunk(ctx, 18)
    assert f == 296 * 9 * 7 * 32 * 128 * 128
    assert b == 9 * (2 * 2 * 2_097_152 + 296 * 32 * 4 * 128 * 4)
    f, b = work.moe_gemm(ctx, 96)
    rows = (372 + 296) * 12 * 1.25
    assert f == 2 * 3 * 2048 * 512 * rows
    assert b == 4 * 12 * 64 * 3 * 2048 * 512 * 2 \
        + rows * (2048 + 1024 + 512 + 2048) * 2
    f, b = work.gqa_decode(ctx, 6)
    assert f == 4 * 50_000 * 3 * 4096
    assert b == 50_000 * 3 * 2048           # 2 KB a token and page layer


# ------------------------------------------------------------- the traffic
def test_the_mix_is_the_zaya_cells_lengths_at_192_slots():
    assert MIX["kind"] == "serve_model"
    assert MIX["model"] == {"reference": "reference_qwen3next",
                            "work": "work_qwen3next"}
    assert MIX["engine"] == {"slots": 192, "max_len": 2048,
                             "chunk_len": 256, "page_len": 128}
    assert MIX["scheduler"] == {"max_queue": 256}
    for k in ("prompt", "output", "block", "blocks", "max_total", "feed",
              "rate_per_s", "preroll", "trace_seconds", "pairing_seed",
              "order_seed"):
        assert MIX[k] == ZAYA_MIX[k], k
    assert MIX["check"]["rule"] in ("widest_gap", "off_best_share")
    V = int(CFG["vocab_size"])
    a = traffic.schedule(MIX, 2147483999, V)
    z = traffic.schedule(ZAYA_MIX, 2147483999, V)
    shape = lambda s: [(len(r["prompt"]), r["max_new_tokens"],   # noqa: E731
                        r["due"]) for r in s]
    assert len(a) == 2560 and shape(a) == shape(z)
    assert all(max(r["prompt"]) < V for r in a[:50])


# ------------------------------------------------- the cell in BENCHMARK.json
def test_the_cell_reports_the_rate_and_its_own_layers():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]
    assert CELL not in e2e["itl_p95_ms"]["workloads"]
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "qwen3-next-80b-a3b", "serve.backlog.qwen3next", 1)
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "qwen3-next-80b-a3b")
    assert entry["reduced"] == ["num_hidden_layers", "num_experts"]
    mine = {m["name"] for m in BENCH["per_layer"]
            if CELL in m.get("workloads", ())}
    assert mine == {"gdn_step_roofline", "gdn_chunk_roofline",
                    "gdn_share_pct", "moe_gemm_roofline.q3n",
                    "moe_gemm_share_pct.q3n", "gqa_decode_roofline.q3n",
                    "decode_prog_ms_p50.q3n", "device_idle_serve_pct.q3n",
                    "beat_launch_ms_p50.q3n", "beat_readback_ms_p50.q3n",
                    "moe_load_cv_pct.q3n", "mfu_serve_pct",
                    "ttft_p90_backlog_ms"}
    for m in BENCH["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["moves"] == "serve_tokens_per_s"
            spec = readers._spec(m["name"])
            assert readers._by_name(spec["kind"], readers.KINDS)
            if "work_fn" in spec and m["name"] != "mfu_serve_pct":
                assert spec["work_fn"].startswith("work_qwen3next:") \
                    == (m["name"].split(".")[0] in (
                        "gdn_step_roofline", "gdn_chunk_roofline",
                        "moe_gemm_roofline", "gqa_decode_roofline"))
                assert readers._by_name(spec["work_fn"], None)


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_configuration_is_the_catalogs_row_less_depth_and_experts():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    for k, v in row["config"].items():
        if k == "num_hidden_layers":
            assert (v, CFG["published"][k], CFG[k]) == (48, 48, 12)
        elif k == "num_experts":
            assert (v, CFG["published"][k], CFG[k]) == (512, 512, 64)
        else:
            assert CFG[k] == v, k
    assert CFG["source"] == row["source_url"]
    assert "32 chips" in CFG["deployment"]
    assert set(CFG["changed"]) == {"num_hidden_layers", "num_experts"}
    assert len(CFG["assumed"]) >= 7 and "precision" in CFG
    assert rq.routed_experts(CFG) == 512


def test_a_program_that_cannot_build_the_configuration_ends_at_once():
    from benchmarks.lib import serve_model

    with pytest.raises(SystemExit, match="cannot build the configuration"):
        serve_model.build_model({"config": "x"}, {"model_type": "mamba"})


# ------------------------------------------------ the control and the fault
@pytest.mark.parametrize("seed", [1, 2])
def test_float8_is_not_correct_and_bfloat16_is(seed, capfd):
    tr = tiny_qwen3next.serve_traffic()
    assert tiny_qwen3next.run_serve(seed, 1.5, 0, traffic=tr, control="fp8")
    err = capfd.readouterr().err
    m = re.search(r"control fp8: its first tokens' widest gap (\S+), share "
                  r"off the reference's best (\S+) \(program's served "
                  r"tokens: (\S+), (\S+)\)", err)
    control, program = float(m.group(2)), float(m.group(4).rstrip(")"))
    assert program <= tr["check"]["limits"]["off_best_share"] < control


def test_an_altered_token_is_not_correct(capsys):
    assert tiny_qwen3next.run_serve(3, 1.5, 0, fault="token_altered") \
        is False
    got = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert not got["compared"]["served_tokens_off_best_share"]["ok"]


def test_a_traced_toy_run_reports_the_cells_host_metrics(capsys):
    assert tiny_qwen3next.run_serve(9, 1.0, 1) is True
    got = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert {"compiles_in_window", "moe_load_cv_pct.q3n", "mfu_serve_pct",
            "ttft_p90_backlog_ms", "beat_launch_ms_p50.q3n",
            "beat_readback_ms_p50.q3n"} <= set(got["metrics"])
    assert got["metrics"]["compiles_in_window"]["value"] == 0
    assert got["gauges"]["serving.moe.experts_held"] == 16
    assert got["gauges"]["serving.moe.experts_per_token"] == 4
    assert got["gauges"]["serving.kv.page_layers"] == 2
