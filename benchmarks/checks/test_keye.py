"""The ``KeyeVL2`` configuration and its cell ``keye.serve.longctx``: the
reference's selection against ``lax.top_k``, the work functions against
counts done by hand, the traffic file's lengths as the cell specifies
them, what the cell reports, a planted fault read as not correct, and
the control - the reference in float8 put in the program's place -
failing ``correct`` where the bfloat16 path passes, at the toy size of
``tiny_keye.py``."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.checks import tiny_keye
from benchmarks.lib import common, readers, traffic
from benchmarks.lib import reference_keye as rk
from benchmarks.lib import work_keye as work

BENCH = common.benchmark_json()
CELL = "keye.serve.longctx"
CFG = common.load_json(common.ROOT,
                       "benchmarks/configs/keye-vl-2.0-30b-a3b.json")
MIX = common.load_json(common.BENCH_DIR, "traffic",
                       "serve.longctx.keye.json")
B_MIX = common.load_json(common.BENCH_DIR, "traffic", "serve.backlog.json")


# --------------------------------------------------- the reference, by hand
def test_the_references_selection_is_lax_top_k():
    """The bisection over the scores' order keeps what ``lax.top_k``
    keeps, ties at the lower position, every live position below k."""
    s = jax.random.normal(jax.random.PRNGKey(1), (6, 300))
    s = s.at[0].set(jnp.round(s[0]))                 # many ties
    pos = jnp.asarray([299, 299, 150, 40, 31, 32], jnp.int32)
    s = jnp.where(jnp.arange(300)[None] <= pos[:, None], s, -jnp.inf)
    got = np.asarray(rk.top_mask(s, pos, 32))
    _, idx = jax.lax.top_k(s, 32)
    want = np.zeros_like(got)
    np.put_along_axis(want, np.asarray(idx), True, 1)
    want &= np.arange(300)[None] <= np.asarray(pos)[:, None]
    assert (got == want).all()


def test_the_index_score_by_hand():
    """Two heads of 2, one key: ``sum_j w_j ReLU(q_j . k / sqrt(2))``."""
    qi = jnp.asarray([[[1.0, 2.0], [-3.0, 1.0]]])            # [1, 2, 2]
    w = jnp.asarray([[0.5, 2.0]])
    ki = jnp.asarray([[2.0, 0.0]])
    got = float(rk.index_scores(qi, w, ki, jnp.asarray([0]))[0, 0])
    assert abs(got - 0.5 * 2.0 / np.sqrt(2.0)) < 1e-6


# ---------------------------------------------------- the work, by hand
def test_the_kernels_work_by_hand():
    ctx = {"cfg": CFG, "counters": {}, "traffic": MIX,
           "serve": {"traced_decode_context_tokens": 240_000,
                     "traced_decode_tokens": [7, 0, 5],
                     "traced_chunks": [(0, 1024), (2048, 40)]}}
    L = 8
    f, b = work.dsa_index(ctx, 3)
    live = 240_000 + (1024 * 1025 / 2) + (40 * 2048 + 40 * 41 / 2)
    assert f == L * live * 2 * 16 * 64
    q = 16 * (64 * 2 + 4)
    assert b == L * (240_000 * (128 + 8) + 12 * q + (1024 + 2088) * 128
                     + (live - 240_000) * 8 + 1064 * q)
    f, b = work.dsa_attend(ctx, 3)
    kept = 12 * 2048 + 1024 * 1025 / 2 + 40 * 2048
    assert f == L * kept * 4 * 32 * 128
    io = 32 * 128 * 6
    assert b == L * (12 * 2048 * 2048 + 12 * io + (1024 + 2088) * 2048
                     + 1064 * io)
    # a chunk's sums, below and past topk
    assert work._sums(0, 4, 2) == (10.0, 1 + 2 + 2 + 2)
    assert work._sums(10, 2, 2048) == (11 + 12, 11 + 12)


class _Trace:
    """Two decode programs and a chunk program on the program line, the
    second decode cut by the window's end."""

    def __init__(self, progs, calls):
        self.progs, self.calls = progs, calls

    def module_events(self, pattern):
        return [(a, b) for a, b, name in self.progs if pattern in name]

    def op_events(self, pattern):
        return list(self.calls)


def test_the_grouped_gemm_reader_counts_whole_decode_programs():
    """Only the decode programs whose 16 calls all lie in the window are
    read, their bytes the resident schedule's: all 16 held experts'
    weights and the 8 x 8 rows in and out of both calls a layer."""
    calls = [(10.0 + 0.001 * i, 10.0 + 0.001 * i + 0.0005) for i in range(16)]
    calls += [(20.0 + 0.001 * i, 20.0 + 0.001 * i + 0.004) for i in range(16)]
    calls += [(30.0 + 0.001 * i, 30.0 + 0.001 * i + 0.0005) for i in range(9)]
    tr = _Trace([(9.9, 10.1, "jit__state_decode_impl"),
                 (19.9, 20.1, "jit__state_chunk_impl"),
                 (29.9, 30.1, "jit__state_decode_impl")], calls)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = {"cfg": CFG, "counters": {}, "traffic": MIX, "trace": tr,
           "peaks": peaks, "serve": {"traced_decode_tokens": [8, 6],
                                     "traced_chunks": []}}
    got = work.moe_gemm_decode_roofline(ctx, {"pattern": "moe_grouped_gemm",
                                              "program": "decode"})
    layer = 16 * 3 * 2048 * 768 * 2 + 64 * (2048 + 1536 + 768 + 2048) * 2
    assert work.resident_moe_bytes(CFG, 64) == layer
    assert work.resident_moe_bytes(CFG, 8192) is None     # a chunk streams
    want = 100.0 * 8 * layer / peaks["hbm_bytes_per_s"] / (16 * 0.0005)
    assert abs(got - want) < 1e-9 * want
    assert "1 decode programs, 16 of 41 calls" in ctx["notes"][0]
    ctx["trace"] = _Trace([], calls)
    assert work.moe_gemm_decode_roofline(
        ctx, {"pattern": "moe_grouped_gemm", "program": "decode"}) is None


def test_a_window_counts_the_selected_attention_and_every_scored_position():
    events = [("decode", 30_000), ("chunk", 4096, 1024, True)]
    f = work.serve_window_flops(CFG, events)
    per = 2.0 * work.layer_params(CFG) + 1.0 * 2 * 3 * 2048 * 768
    live, kept = work._sums(4096, 1024, 2048)
    want = 8 * (1025 * per + (30_000 + live) * 2048
                + (2048 + kept) * 16384) + 2 * 2 * 151936 * 2048
    assert abs(f - want) < 1e-6 * want


# ------------------------------------------------------------- the traffic
def test_the_mix_is_the_cells_letter_for_letter():
    assert MIX["kind"] == "serve_model"
    assert MIX["model"] == {"reference": "reference_keye",
                            "work": "work_keye"}
    assert MIX["engine"] == {"slots": 8, "max_len": 65536,
                             "chunk_len": 1024, "page_len": 128}
    assert MIX["scheduler"] == {"max_queue": 16, "chunk_budget": 1}
    assert MIX["output"] == {"median": 256, "sigma": 0.7, "min": 32,
                             "max": 2048}
    assert MIX["prompt"]["sigma"] == 0.5 and MIX["prompt"]["max"] == 61440
    assert MIX["prompt"]["min"] == 8192
    assert MIX["prompt"]["median"] in (32768, 24576)
    assert (MIX["max_total"], MIX["block"], MIX["blocks"]) == (65536, 64, 40)
    assert (MIX["feed"], MIX["rate_per_s"]) == ("as_queue_has_room", 0)
    assert MIX["preroll"] == {"until": "slots_used"}
    for k in ("pairing_seed", "order_seed", "block", "blocks"):
        assert MIX[k] == B_MIX[k], k            # paired as cell B's
    assert MIX["check"]["rule"] == "off_best_share"
    lens = traffic.length_multiset(MIX)
    assert len(lens) == 64
    assert min(p for p, _ in lens) >= 8192 and max(p for p, _ in lens) \
        <= 61440
    assert all(32 <= o <= 2048 and p + o <= 65536 for p, o in lens)


# ------------------------------------------------- the cell in BENCHMARK.json
def test_the_cell_reports_the_rate_and_its_own_layers():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "keye-vl-2.0-30b-a3b", "serve.longctx.keye", 1)
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == "keye-vl-2.0-30b-a3b")
    assert entry["reduced"] == ["num_hidden_layers", "num_experts"]
    mine = {m["name"] for m in BENCH["per_layer"]
            if CELL in m.get("workloads", ())}
    assert mine == {
        "dsa_index_roofline", "dsa_attend_roofline", "dsa_share_pct",
        "moe_gemm_roofline.keye", "moe_load_cv_pct.keye",
        "decode_prog_ms_p50.keye", "chunk_prog_ms_p50.keye",
        "device_idle_serve_pct.keye", "mfu_serve_pct",
        "ttft_p90_backlog_ms", "idle_in_launch_pct.keye",
        "idle_in_readback_pct.keye", "idle_in_host_pct.keye",
        "beat_launch_ms_p50.keye", "beat_readback_ms_p50.keye",
        "completion_lag_us_p50.keye", "moe_gemm_share_pct.keye"}
    for m in BENCH["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["moves"] == "serve_tokens_per_s"
            spec = readers._spec(m["name"])
            assert readers._by_name(spec["kind"], readers.KINDS)
            if "work_fn" in spec:
                assert readers._by_name(spec["work_fn"], None)


#: a JSON-lines catalog of published model configurations, one row a model
CATALOG = os.environ.get("MODEL_CATALOG", "")


def test_the_configuration_is_the_catalogs_row_less_depth_and_experts():
    if not os.path.exists(CATALOG):
        pytest.skip("MODEL_CATALOG names no catalog file")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Keye-VL-2.0-30B-A3B")
    for k, v in row["config"].items():
        if k == "num_hidden_layers":
            assert (v, CFG["published"][k], CFG[k]) == (48, 48, 8)
        elif k == "num_experts":
            assert (v, CFG["published"][k], CFG[k]) == (128, 128, 16)
        else:
            assert CFG[k] == v, k
    assert CFG["source"] == row["source_url"]
    assert "48 chips" in CFG["deployment"]
    assert set(CFG["changed"]) == {"num_hidden_layers", "num_experts"}
    assert rk.routed_experts(CFG) == 128


# ------------------------------------------------ the control and the fault
@pytest.mark.parametrize("seed", [1, 2])
def test_float8_is_not_correct_and_bfloat16_is(seed, capfd):
    tr = tiny_keye.serve_traffic()
    assert tiny_keye.run_serve(seed, 1.5, 0, traffic=tr, control="fp8")
    err = capfd.readouterr().err
    m = re.search(r"control fp8: its first tokens' widest gap (\S+), share "
                  r"off the reference's best (\S+) \(program's served "
                  r"tokens: (\S+), (\S+)\)", err)
    control, program = float(m.group(2)), float(m.group(4).rstrip(")"))
    assert program <= tr["check"]["limits"]["off_best_share"] < control, \
        (program, control)


def test_an_altered_token_is_not_correct(capsys):
    assert tiny_keye.run_serve(3, 1.5, 0, fault="token_altered") is False
    got = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert not got["compared"]["served_tokens_off_best_share"]["ok"]


def test_a_traced_toy_run_reports_the_cells_host_metrics(capsys):
    assert tiny_keye.run_serve(9, 1.0, 1) is True
    got = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert {"compiles_in_window", "moe_load_cv_pct.keye", "mfu_serve_pct",
            "ttft_p90_backlog_ms"} <= set(got["metrics"])
    assert got["metrics"]["compiles_in_window"]["value"] == 0
    assert got["gauges"]["serving.moe.experts_held"] == 16
    assert got["gauges"]["serving.moe.experts_per_token"] == 8
    assert got["gauges"]["serving.kv.page_layers"] == 4
    # K and V rows of 2 x 128 and a 64-wide index key, bf16, 4 layers
    assert got["gauges"]["serving.kv.bytes_per_token"] == 4 * 576 * 2
