"""The ``ling_v3`` configuration and its cell ``ling3.serve.longdoc`` (PR
36): the reference against cases written out by hand, the work functions
against counts done by hand, the traffic file's lengths as ISSUE 36
states them, what the cell reports, a planted fault read as not correct,
and the control - the reference in float8 put in the program's place -
failing ``correct`` where the bfloat16 path passes, at the toy size of
``tiny_ling3.py``."""

import json
import os
import re

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.checks import tiny_ling3
from benchmarks.lib import common, readers, traffic
from benchmarks.lib import reference_ling3 as rl
from benchmarks.lib import work_ling3 as work

BENCH = common.benchmark_json()
CELL = "ling3.serve.longdoc"
CFG = common.load_json(common.ROOT,
                       "benchmarks/configs/ling-3.0-flash-vl.json")
MIX = common.load_json(common.BENCH_DIR, "traffic",
                       "serve.longdoc.ling3.json")
B_MIX = common.load_json(common.BENCH_DIR, "traffic", "serve.backlog.json")


# --------------------------------------------------- the reference, by hand
def test_reference_delta_rule_decays_a_channel_by_hand():
    """One head, 2 x 2 state, two tokens, every number written out: the
    second token halves key channel 0 and keeps channel 1."""
    q = jnp.asarray([[[1., 1.]], [[1., 1.]]])
    k = jnp.asarray([[[1., 0.]], [[0., 1.]]])
    v = jnp.asarray([[[2., 4.]], [[6., 8.]]])
    g = jnp.log(jnp.asarray([[[1., 1.]], [[0.5, 1.]]]))
    beta = jnp.asarray([[1.0], [0.5]])
    o, S = rl.delta_rule(q, k, v, g, beta)
    # t0: S = k v^T = [[2, 4], [0, 0]]; o = S^T q = [2, 4]
    np.testing.assert_allclose(np.asarray(o[0, 0]), [2., 4.], rtol=1e-6)
    # t1: S <- diag(0.5, 1) S = [[1, 2], [0, 0]]; r = S^T k = [0, 0];
    # S += k (0.5 v)^T = [[1, 2], [3, 4]]; o = S^T q = [4, 6]
    np.testing.assert_allclose(np.asarray(S[0]), [[1., 2.], [3., 4.]],
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(o[1, 0]), [4., 6.], rtol=1e-6)


def test_reference_router_keeps_groups_then_takes_k_and_scales():
    cfg = dict(tiny_ling3.TINY_LING_CFG, hidden_size=8, num_experts=8,
               num_experts_per_tok=2, n_group=4, topk_group=2)
    z = np.full((8,), -20.0)
    z[[0, 1, 2, 6]] = [3.0, -1.0, 2.5, 2.0]
    lp = {"router/w": jnp.eye(8, dtype=jnp.float32),
          "router/bias": jnp.zeros((8,), jnp.float32)}
    choice, w, margin = rl.route(jnp.asarray(z, jnp.float32)[None], lp, cfg)
    # groups (0,1) (2,3) (4,5) (6,7): two-best sums 1.22, 0.92, 0, 0.88:
    # groups 0 and 1 stay, expert 6 is never eligible
    s = 1 / (1 + np.exp(-np.asarray([3.0, 2.5])))
    assert sorted(np.asarray(choice[0])) == [0, 2]
    np.testing.assert_allclose(np.sort(np.asarray(w[0]))[::-1],
                               2.5 * s / s.sum(), rtol=1e-6)
    np.testing.assert_allclose(float(margin[0]),
                               s[1] - 1 / (1 + np.exp(1.0)), rtol=1e-5)
    # a bias of +1 on expert 7 brings its group in instead of group 1;
    # the weights are the scores' own
    lp["router/bias"] = lp["router/bias"].at[7].set(1.0)
    choice, w, _ = rl.route(jnp.asarray(z, jnp.float32)[None], lp, cfg)
    assert sorted(np.asarray(choice[0])) == [0, 7]
    s = 1 / (1 + np.exp(-np.asarray([3.0, -20.0])))
    np.testing.assert_allclose(np.sort(np.asarray(w[0]))[::-1],
                               2.5 * s / s.sum(), rtol=1e-5)


def test_reference_latent_attention_is_causal_and_rotates_by_position():
    cfg = dict(tiny_ling3.TINY_LING_CFG)
    lp = {k: jnp.asarray(v, jnp.float32) for k, v in
          rl.seeded_weights(cfg, 2, jnp.float32)["layers"][5].items()}
    u = jnp.asarray(np.random.default_rng(0).normal(size=(6, 64)),
                    jnp.float32)
    full, _ = rl.mla(u, lp, cfg)
    part, _ = rl.mla(u.at[4:].set(0.0), lp, cfg)
    np.testing.assert_allclose(np.asarray(full[:4]), np.asarray(part[:4]),
                               atol=1e-6)
    assert float(jnp.abs(full[4:] - part[4:]).max()) > 1e-3
    # one head's key at position 3 is its key at position 0 rotated
    _, k0, _, _ = rl.mla_heads(u[:1], lp, cfg, jnp.asarray([0]))
    _, k3, _, _ = rl.mla_heads(u[:1], lp, cfg, jnp.asarray([3]))
    np.testing.assert_allclose(np.asarray(k0[..., :16]),
                               np.asarray(k3[..., :16]), atol=1e-6)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(k0[0, 0, 16:])),
        np.linalg.norm(np.asarray(k3[0, 0, 16:])), rtol=1e-5)
    assert float(jnp.abs(k0[..., 16:] - k3[..., 16:]).max()) > 1e-3


def test_the_selection_bias_is_balanced_as_training_leaves_it():
    """Seeded weights with the bias as drawn load some experts many times
    the mean; balanced over seeded tokens the loads of FRESH tokens are
    even to within their own sampling, and the share of rows that falls
    to a quarter of the experts is a quarter."""
    cfg = dict(tiny_ling3.TINY_LING_CFG, num_experts=64, n_group=8,
               topk_group=4, num_experts_per_tok=8)
    toks = jnp.asarray(np.random.default_rng(9).integers(0, 256, 1024))

    def loads(balance):
        p = rl.seeded_weights(cfg, 3, jnp.float32, balance_tokens=balance)
        own = np.asarray(rl.hidden_states(p, cfg, toks)[1])[2:]
        return np.stack([np.bincount(o.ravel(), minlength=64) for o in own])

    drawn, balanced = loads(0), loads(None)
    cv = lambda c: (c.std(1) / c.mean(1))                      # noqa: E731
    assert cv(drawn).min() > 0.7 and cv(balanced).max() < 0.45
    share = balanced[:, :16].sum(1) / balanced.sum(1)
    assert np.abs(share - 0.25).max() < 0.04


# ---------------------------------------------------------- work, by hand
def test_the_layers_matmul_parameters_are_the_issues():
    assert work.kda_layer_params(CFG) == 2560 * 16384 + 2560 * 64 \
        + 4096 * 2560 == 52_592_640
    assert work.mla_layer_params(CFG) == 2560 * 6144 + 2560 * 576 \
        + 2560 * 32 + 4096 * 2560 + 512 * 8192 == 31_965_184
    assert work.moe_fixed_params(CFG) == 2560 * 512 + 3 * 2560 * 768
    assert work.delta_rule_flops_per_token(CFG) == 7 * 32 * 128 * 128
    assert work.attention_flops_per_position(CFG, True) == 2 * 32 * 1088
    assert work.attention_flops_per_position(CFG, False) == 2 * 32 * 320
    assert work.held_rows_per_token(CFG) == 2.0       # 8 x 128 / 512


def test_forward_flops_count_this_chips_share_of_the_experts():
    fixed = 5 * (2 * 52_592_640 + 3_670_016) + 2 * 31_965_184 \
        + 2 * 2 * 3 * 2560 * 6144 + 4 * 2 * 7_208_960
    one = work.forward_flops_per_token(CFG, 100.0, True)
    assert one == fixed + 100 * 2 * 32 * 1088 \
        + 4 * 2.0 * 2 * 3 * 2560 * 768 + 2 * 157_184 * 2560
    # the counter: 1 of a token's 8 rows went to held experts (ids 0..127)
    routed = [[1] * 128 + [0] * 256 + [7] * 128] * 4
    rows = work.held_rows_per_token(CFG, routed)
    assert rows == pytest.approx(1.0)
    ev = [("decode", 100.0), ("chunk", 1024, 10, True)]
    want = fixed + 100 * 2 * 32 * 1088 + 2 * 157_184 * 2560 \
        + 10 * (fixed + 1029.5 * 2 * 32 * 320) + 2 * 157_184 * 2560 \
        + 11 * 4 * rows * 2 * 3 * 2560 * 768
    assert work.serve_window_flops(CFG, ev, routed=routed) \
        == pytest.approx(want)


def test_the_kernels_work_by_hand():
    ctx = {"cfg": CFG, "counters": {}, "traffic": MIX,
           "serve": {"traced_decode_context_tokens": 280_000,
                     "traced_decode_tokens": [20, 0, 18],
                     "traced_chunks": [(0, 1024), (1024, 40)]}}
    f, b = work.kda_step(ctx, 10)
    assert f == 38 * 5 * 7 * 32 * 128 * 128
    assert b == 38 * 5 * (2 * 2_097_152 + 32 * 7 * 128 * 4)
    f, b = work.kda_chunk(ctx, 10)
    assert f == 1064 * 5 * 7 * 32 * 128 * 128
    assert b == 5 * (2 * 2 * 2_097_152 + 1064 * 32 * 5 * 128 * 4)
    f, b = work.mla_decode(ctx, 2)
    assert f == 280_000 * 2 * 32 * 1088
    # 1,152 B a cached token; a row's page written back; q in, o out, row
    assert b == 280_000 * 1152 + 38 * (128 * 1152 + 32 * (1152 + 2048)
                                       + 1152)
    f, b = work.mla_prefill(ctx, 2)
    assert f == (1024 * 512.5 + 40 * 1044.5) * 2 * 32 * 320
    assert b == (1024 + 1064) * 1152 + 1064 * 32 * (1152 + 2048)
    f, b = work.moe_gemm(ctx, 32)
    rows = (38 + 1064) * 4 * 2.0
    assert f == 2 * 3 * 2560 * 768 * rows
    assert b == 4 * 4 * 128 * 3 * 2560 * 768 * 2 \
        + rows * (2560 + 1536 + 768 + 2560) * 2
    # every share of a roofline stays a share: operations an absorbed
    # chunk kernel really spends are 3.4 times what is counted here
    assert work.attention_flops_per_position(CFG, True) \
        / work.attention_flops_per_position(CFG, False) == 3.4


# ------------------------------------------------------------- the traffic
def test_the_mix_is_the_issues_letter_for_letter():
    assert MIX["kind"] == "serve_model"
    assert MIX["model"] == {"reference": "reference_ling3",
                            "work": "work_ling3"}
    assert MIX["engine"] == {"slots": 32, "max_len": 33792,
                             "chunk_len": 1024, "page_len": 128}
    assert MIX["scheduler"] == {"max_queue": 64, "chunk_budget": 1}
    assert MIX["prompt"] == {"median": 12288, "sigma": 0.6, "min": 2048,
                             "max": 32768}
    assert MIX["output"] == {"median": 256, "sigma": 0.7, "min": 32,
                             "max": 1024}
    assert (MIX["max_total"], MIX["block"], MIX["blocks"]) == (33792, 64, 40)
    assert (MIX["feed"], MIX["rate_per_s"]) == ("as_queue_has_room", 0)
    assert MIX["preroll"] == {"until": "slots_used"}
    assert MIX["trace_seconds"] == 10
    for k in ("pairing_seed", "order_seed", "block", "blocks"):
        assert MIX[k] == B_MIX[k], k            # paired as cell B's
    assert MIX["check"]["sample"] == 4
    assert MIX["check"]["rule"] in ("widest_gap", "off_best_share")
    V = int(CFG["vocab_size"])
    sched = traffic.schedule(MIX, 2147483999, V)
    assert len(sched) == 2560
    lens = [(len(r["prompt"]), r["max_new_tokens"]) for r in sched[:64]]
    assert min(p for p, _ in lens) >= 2048 and max(p for p, _ in lens) \
        == 32768
    assert all(32 <= o <= 1024 and p + o <= 33792 for p, o in lens)
    assert 12000 < np.median([p for p, _ in lens]) < 12600
    assert all(r["due"] == 0.0 for r in sched)
    assert all(max(r["prompt"]) < V for r in sched[:8])


# ------------------------------------------------- the cell in BENCHMARK.json
def test_the_cell_reports_the_rate_and_its_own_layers():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]
    assert CELL not in e2e["itl_p95_ms"]["workloads"]
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ling-3.0-flash-vl", "serve.longdoc.ling3", 1)
    assert BENCH["workloads"][-1] is cell           # appended, not inserted
    entry = BENCH["configs"][-1]
    assert entry["name"] == "ling-3.0-flash-vl"
    assert entry["reduced"] == ["num_hidden_layers", "num_experts"]
    assert entry["why"].startswith("drawn:")
    mine = [m["name"] for m in BENCH["per_layer"]
            if CELL in m.get("workloads", ())]
    assert set(mine) == {
        "kda_step_roofline", "kda_chunk_roofline", "mla_decode_roofline",
        "mla_prefill_roofline", "kda_share_pct", "mla_share_pct",
        "moe_gemm_roofline.ling", "moe_gemm_share_pct.ling",
        "moe_load_cv_pct.ling", "decode_prog_ms_p50.ling",
        "chunk_prog_ms_p50.ling", "device_idle_serve_pct.ling",
        "beat_launch_ms_p50.ling", "beat_readback_ms_p50.ling",
        "mfu_serve_pct", "ttft_p90_backlog_ms"}
    own_work = {"kda_step_roofline", "kda_chunk_roofline",
                "mla_decode_roofline", "mla_prefill_roofline",
                "moe_gemm_roofline.ling"}
    for m in BENCH["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["moves"] == "serve_tokens_per_s"
            spec = readers._spec(m["name"])
            assert readers._by_name(spec["kind"], readers.KINDS)
            if "work_fn" in spec:
                assert spec["work_fn"].startswith("work_ling3:") \
                    == (m["name"] in own_work)
                assert readers._by_name(spec["work_fn"], None)


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_configuration_is_the_catalogs_row_less_depth_and_experts():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ling-3.0-flash-VL")
    for k, v in row["config"].items():
        if k == "num_hidden_layers":
            assert (v, CFG["published"][k], CFG[k]) == (42, 42, 6)
        elif k == "num_experts":
            assert (v, CFG["published"][k], CFG[k]) == (512, 512, 128)
        else:
            assert CFG[k] == v, k
    assert CFG["source"] == row["source_url"]
    assert "28 chips" in CFG["deployment"]
    assert set(CFG["changed"]) == {"num_hidden_layers", "num_experts"}
    assert len(CFG["assumed"]) >= 12 and "precision" in CFG
    assert rl.routed_experts(CFG) == 512
    # one whole period: 5 linear layers to 1 latent, 2 dense to 4 expert
    kinds = [(rl.is_latent(CFG, i), rl.is_dense(CFG, i)) for i in range(6)]
    assert kinds == [(False, True)] * 2 + [(False, False)] * 3 \
        + [(True, False)]


# ------------------------------------------------ the control and the fault
@pytest.mark.parametrize("seed", [1, 2])
def test_float8_is_not_correct_and_bfloat16_is(seed, capfd):
    tr = tiny_ling3.serve_traffic()
    assert tiny_ling3.run_serve(seed, 1.5, 0, traffic=tr, control="fp8")
    err = capfd.readouterr().err
    m = re.search(r"control fp8: its first tokens' widest gap (\S+), share "
                  r"off the reference's best (\S+) \(program's served "
                  r"tokens: (\S+), (\S+)\)", err)
    control, program = float(m.group(2)), float(m.group(4).rstrip(")"))
    assert program <= tr["check"]["limits"]["off_best_share"] < control, \
        (program, control)


def test_an_altered_token_is_not_correct(capsys):
    assert tiny_ling3.run_serve(3, 1.5, 0, fault="token_altered") is False
    got = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert not got["compared"]["served_tokens_off_best_share"]["ok"]


def test_a_traced_toy_run_reports_the_cells_host_metrics(capsys):
    assert tiny_ling3.run_serve(9, 1.0, 1) is True
    got = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert {"compiles_in_window", "moe_load_cv_pct.ling", "mfu_serve_pct",
            "ttft_p90_backlog_ms", "beat_launch_ms_p50.ling",
            "beat_readback_ms_p50.ling"} <= set(got["metrics"])
    assert got["metrics"]["compiles_in_window"]["value"] == 0
    assert got["gauges"]["serving.moe.experts_held"] == 16
    assert got["gauges"]["serving.moe.experts_per_token"] == 4
    assert got["gauges"]["serving.kv.page_layers"] == 1
    assert got["gauges"]["serving.kv.bytes_per_token"] == 96   # 48 x 2 B
