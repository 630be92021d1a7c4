"""The ``serve_zaya`` kind (PR 30): the reference against a case written
out by hand, the work functions against counts done by hand, the traffic
file's lengths and digest, what the cell reports, and the control - the
reference in float8 put in the program's place - failing ``correct`` where
the bfloat16 path passes, at the toy size of ``tiny_zaya.py``."""

import hashlib
import json
import os
import re

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.checks import tiny_zaya
from benchmarks.lib import common, readers, traffic, work_zaya
from benchmarks.lib import reference_zaya as rz

BENCH = common.benchmark_json()
CELL = "zaya1.serve.backlog96"
CFG = common.load_json(common.ROOT, "benchmarks/configs/zaya1-8b.json")
MIX = common.load_json(common.BENCH_DIR, "traffic", "serve.backlog96.json")


# --------------------------------------------------- the reference, by hand
def test_reference_convolutions_qk_mean_and_value_shift_by_hand():
    """One head of 2 channels, 3 positions, every number written out."""
    z = jnp.asarray([[[1., 2.]], [[3., 4.]], [[5., 6.]]])        # [3, 1, 2]
    w0 = jnp.asarray([[0.5, 1.0], [0.25, 2.0]])     # [c, (prev, current)]
    b0 = jnp.asarray([0.1, 0.2])
    w1 = jnp.asarray([[[[1., 0.], [0., 1.]],          # prev tap: identity
                       [[0., 1.], [1., 0.]]]])        # current tap: swap
    b1 = jnp.asarray([[10., 20.]])
    c1, c2 = rz.causal_convs(z, w0, b0, w1, b1)
    want_c1 = np.array([[1 * 1.0 + 0.1, 2 * 2.0 + 0.2],
                        [0.5 * 1 + 3 + 0.1, 0.25 * 2 + 8 + 0.2],
                        [0.5 * 3 + 5 + 0.1, 0.25 * 4 + 12 + 0.2]])
    np.testing.assert_allclose(np.asarray(c1[:, 0]), want_c1, rtol=1e-6)
    prev = np.vstack([[0, 0], want_c1[:-1]])
    want_c2 = prev + want_c1[:, ::-1] + np.array([10., 20.])
    np.testing.assert_allclose(np.asarray(c2[:, 0]), want_c2, rtol=1e-6)
    # q-k mean: 2 query heads share 1 K/V head
    qt = jnp.asarray([[[2., 4.], [6., 8.]]])                     # [1, 2, 2]
    kt = jnp.asarray([[[10., 20.]]])
    m_q, m_k = rz.qk_mean(qt, kt)
    np.testing.assert_allclose(np.asarray(m_q[0]), [[6, 12], [8, 14]])
    np.testing.assert_allclose(np.asarray(m_k[0]), [[7, 13]])
    # the shift: zeros before position 0
    np.testing.assert_allclose(np.asarray(rz.shift(z))[:, 0],
                               [[0, 0], [1, 2], [3, 4]])


def test_reference_attention_reads_the_previous_tokens_values_in_head_1():
    """With one query group per K/V head made to attend only to its own
    position (keys far apart), head 0's output is ``u_t Wv1`` and head
    1's is ``u_{t-1} Wv2``: the value shift."""
    cfg = dict(tiny_zaya.TINY_ZAYA_CFG, num_hidden_layers=1)
    lp = {k: jnp.asarray(v, jnp.float32) for k, v in
          rz.seeded_weights(cfg, 2, jnp.float32)["layers"][0].items()}
    S, H, d = 5, 64, 16
    u = jnp.asarray(np.random.default_rng(0).normal(size=(S, H)),
                    jnp.float32)
    # read the values the sublayer would attend over
    v1 = u @ lp["attn/wv1"]
    v2 = rz.shift(u @ lp["attn/wv2"])
    assert np.allclose(np.asarray(v2[0]), 0)
    assert np.allclose(np.asarray(v2[1:]),
                       np.asarray((u @ lp["attn/wv2"])[:-1]), atol=1e-6)
    # position 0 attends itself alone: out = [v1_0 x G heads ; 0] Wo
    out = rz.attention_sublayer(u, lp, cfg)
    o0 = jnp.concatenate([jnp.tile(v1[0], 2), jnp.zeros(2 * d)])
    np.testing.assert_allclose(np.asarray(out[0]),
                               np.asarray(o0 @ lp["attn/wo"]), atol=1e-5)


# ---------------------------------------------------------- work, by hand
def test_the_layers_matmul_parameters_are_the_issues():
    # 5.24 M attention + 0.33 M second convolution + 0.66 M router + one
    # expert of 3 x 2048 x 2048
    attn = 2048 * (8 + 2 + 2) * 128 + 1024 * 2048
    conv = 10 * 2 * 128 * 128
    router = 2048 * 256 + 2 * 256 * 256 + 256 * 16
    assert (attn, conv, router) == (5_242_880, 327_680, 659_456)
    assert work_zaya.layer_matmul_params(CFG) == attn + conv + router \
        + 3 * 2048 * 2048 == 18_812_928


def test_forward_flops_count_the_latent_and_the_head():
    one = work_zaya.forward_flops_per_token(CFG, 100.0)
    assert one == 20 * (2 * 18_812_928 + 4 * 100 * 1024) \
        + 2 * 262_272 * 2048
    ev = [("decode", 100.0), ("chunk", 0, 256, False), ("chunk", 256, 10, True)]
    want = one + 256 * 20 * (2 * 18_812_928 + 4 * 128.5 * 1024) \
        + 10 * 20 * (2 * 18_812_928 + 4 * 261.5 * 1024) + 2 * 262_272 * 2048
    assert work_zaya.serve_window_flops(CFG, ev) == pytest.approx(want)


def test_the_kernels_work_by_hand():
    ctx = {"cfg": CFG, "serve": {"traced_decode_context_tokens": 50_000,
                                 "traced_decode_tokens": [96, 0, 90],
                                 "traced_chunks": [(0, 256), (256, 40)]}}
    f, b = work_zaya.moe_gemm(ctx, 160)
    tokens, programs = 96 + 90 + 256 + 40, 4
    assert f == 2 * 3 * 2048 * 2048 * tokens * 20
    assert b == 20 * (programs * 16 * 3 * 2048 * 2048 * 2
                      + tokens * (2048 + 4096 + 2048 + 2048) * 2)
    f, b = work_zaya.gqa_decode(ctx, 40)
    assert f == 4 * 50_000 * 20 * 1024
    assert b == 50_000 * 20 * 1024          # 1 KB a token and layer


def test_the_load_reader_is_a_coefficient_of_variation():
    even = [[6] * 16] * 20
    assert work_zaya.load_cv({"counters": {"k": even}}, {"key": "k"}) == 0
    skew = [[12, 0] * 8] * 20               # std = mean
    assert work_zaya.load_cv({"counters": {"k": skew}},
                             {"key": "k"}) == pytest.approx(100.0)
    assert work_zaya.load_cv({"counters": {}}, {"key": "k"}) is None
    assert work_zaya.op_share({}, {"pattern": "x"}) is None


# ------------------------------------------------------------- the traffic
FIRST_640 = "d529bb22e7011055340cf254e9ac23d9e5a49ff680fe26f45830ca1c750b0787"


def test_the_mix_is_the_issues_letter_for_letter():
    assert MIX["engine"] == {"slots": 96, "max_len": 2048, "chunk_len": 256,
                             "page_len": 128}
    assert MIX["scheduler"] == {"max_queue": 128}
    assert MIX["prompt"] == {"median": 256, "sigma": 0.8, "min": 32,
                             "max": 1024}
    assert MIX["output"] == {"median": 384, "sigma": 0.6, "min": 64,
                             "max": 1024}
    assert (MIX["block"], MIX["blocks"], MIX["max_total"]) == (64, 40, 2048)
    assert MIX["feed"] == "as_queue_has_room" and MIX["rate_per_s"] == 0
    assert MIX["preroll"] == {"until": "slots_used"}
    assert MIX["trace_seconds"] == 10


def test_every_seed_offers_the_same_lengths_and_the_digest_holds():
    V = int(CFG["vocab_size"])
    a = traffic.schedule(MIX, 2147483999, V)
    b = traffic.schedule(MIX, 7, V)
    shape = lambda s: [(len(r["prompt"]), r["max_new_tokens"],   # noqa: E731
                        r["due"]) for r in s]
    assert len(a) == 2560 and shape(a) == shape(b)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    assert a == traffic.schedule(MIX, 2147483999, V)
    assert all(r["due"] == 0 for r in a)
    for p, o in traffic.length_multiset(MIX):
        assert 32 <= p <= 1024 and 64 <= o <= 1024 and p + o <= 2048
    short = traffic.schedule(dict(MIX, blocks=10), 2147483999, V)
    assert a[:640] == short
    assert hashlib.sha256(json.dumps(short).encode()).hexdigest() \
        == FIRST_640


# ------------------------------------------------- the cell in BENCHMARK.json
def test_the_cell_reports_the_rate_and_not_the_gap():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]
    assert e2e["serve_tokens_per_s"]["bound"] == 0.04
    assert CELL not in e2e["itl_p95_ms"]["workloads"]
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "zaya1-8b", "serve.backlog96", 1)
    entry = next(c for c in BENCH["configs"] if c["name"] == "zaya1-8b")
    assert entry["reduced"] == ["num_hidden_layers"]
    mine = {m["name"] for m in BENCH["per_layer"]
            if CELL in m.get("workloads", ())}
    assert mine == {"moe_gemm_roofline", "gqa_decode_roofline",
                    "moe_gemm_share_pct", "moe_load_cv_pct", "mfu_serve_pct",
                    "ttft_p90_backlog_ms", "decode_prog_ms_p50.zaya",
                    "device_idle_serve_pct.zaya", "beat_launch_ms_p50.zaya",
                    "beat_readback_ms_p50.zaya"}
    for m in BENCH["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["moves"] == "serve_tokens_per_s"
            spec = readers._spec(m["name"])
            assert readers._by_name(spec["kind"], readers.KINDS)
            if "work_fn" in spec:
                assert readers._by_name(spec["work_fn"], None)


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_configuration_is_the_catalogs_row_less_its_depth():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "ZAYA1-8B")
    for k, v in row["config"].items():
        if k == "num_hidden_layers":
            assert (v, CFG["published"][k], CFG[k]) == (40, 40, 20)
        else:
            assert CFG[k] == v, k
    assert CFG["source"] == row["source_url"]
    assert "two pipeline stages of 20 layers" in CFG["deployment"]
    assert len(CFG["assumed"]) >= 7


# ------------------------------------------------------------- the control
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float8_is_not_correct_and_bfloat16_is(seed, capfd):
    tr = tiny_zaya.serve_traffic()
    assert tiny_zaya.run_serve(seed, 1.5, 0, traffic=tr, control="fp8")
    err = capfd.readouterr().err
    m = re.search(r"control fp8: share of its first tokens off the "
                  r"reference's best (\S+) \(program's served tokens: (\S+)\)",
                  err)
    control, program = float(m.group(1)), float(m.group(2))
    assert program <= tr["check"]["limits"]["off_best_share"] < control


def test_a_traced_toy_run_reports_the_cells_host_metrics(capsys):
    assert tiny_zaya.run_serve(9, 1.0, 1) is True
    got = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert {"compiles_in_window", "moe_load_cv_pct", "mfu_serve_pct",
            "ttft_p90_backlog_ms", "beat_launch_ms_p50.zaya",
            "beat_readback_ms_p50.zaya"} <= set(got["metrics"])
    assert got["metrics"]["compiles_in_window"]["value"] == 0
    assert "itl_p95_ms" not in got["metrics"]
    assert got["gauges"]["serving.moe.experts_held"] == 4
