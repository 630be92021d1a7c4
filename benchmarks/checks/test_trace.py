"""The reduction from a profiler trace to metrics, on traces kept beside
it: one written by hand, whose answers are known exactly, and one
recorded on the chip in cell A (two training steps, trimmed by
``trim_trace.py``), whose answers were read once and must come out the
same every time."""

import os

import pytest

from benchmarks.lib import readers, trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_names_are_cleaned():
    assert trace.clean("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), "
                       "kind=kLoop") == "fusion"
    assert trace.clean("jit_step_fn(123456789)") == "jit_step_fn"
    assert trace.clean("flash_attention_bwd_dkv.23 = (bf16[2]) "
                       "custom-call()") == "flash_attention_bwd_dkv"
    assert trace.fusion_label(
        "%fusion.1 = bf16[50304,768]{1,0:T(8,128)(2,1)} fusion(f32[2]{0} "
        "%a), kind=kOutput, calls=%fc") == "fusion:Output bf16[50304,768]"


def test_hand_written_trace_has_its_known_answers():
    tr = trace.load(os.path.join(DATA, "hand_written.txtpb.gz"))
    assert tr.window_s == pytest.approx(10e-3)
    # ops [0,2] and [1,3] overlap: busy is the union, 3 + 1 + 0.5 ms
    assert tr.busy_s() == pytest.approx(4.5e-3)
    assert tr.idle_share_busiest() == pytest.approx(0.55)
    fwd = tr.op_events("flash_attention_fwd")
    assert len(fwd) == 2
    assert sum(e - s for s, e in fwd) == pytest.approx(3e-3)
    assert [round((e - s) * 1e3, 6) for s, e in
            tr.module_events("decode")] == [1.5]
    assert tr.module_names() == ["jit__decode_step", "jit_step_fn"]
    bd = tr.breakdown(default_host="elsewhere")
    assert dict(map(tuple, bd["device_ops"])) == pytest.approx({
        "flash_attention_fwd": 3e-3,
        "fusion:Output f32[16384,50304]": 2e-3, "copy": 0.5e-3})
    # the gap 3..5 ms falls in bench.step, the gap 6.5..10 ms in no span
    assert dict(map(tuple, bd["idle_gaps"])) == pytest.approx({
        "bench.step": 2e-3, "elsewhere": 3.5e-3})
    # a reader over it: the same every time, and silent where nothing
    # is there to read
    ctx = {"trace": tr}
    assert readers.idle_share(ctx, {}) == pytest.approx(55.0)
    assert readers.program_time(ctx, {"pattern": "decode", "stat": "p50"}
                                ) == pytest.approx(1.5)
    assert readers.program_time(ctx, {"pattern": "chunk", "stat": "p50"}
                                ) is None
    assert readers.kernel_roofline(
        ctx, {"patterns": ["paged_decode_attention"],
              "work_fn": "paged_decode"}) is None


def test_recorded_cell_a_trace_reads_the_same_every_time():
    """Two training steps of ``gpt2s.train.b16s1024`` recorded on a TPU
    v5e (PR 24, seed 51) and trimmed to 0.35 s: the readings below were
    taken from it once; the reduction has to give them again."""
    from benchmarks.lib import common

    path = os.path.join(DATA, "cellA_2steps.txtpb.gz")
    a, b = trace.load(path), trace.load(path)
    for tr in (a, b):
        assert tr.window_s == pytest.approx(0.35)
        assert tr.busy_s() == pytest.approx(0.33422715, rel=1e-6)
        assert tr.idle_share_busiest() == pytest.approx(0.04506529,
                                                        rel=1e-6)
        fwd = tr.op_events("flash_attention_fwd")
        assert len(fwd) == 36
        assert sum(e - s for s, e in fwd) == pytest.approx(0.030757117,
                                                           rel=1e-6)
        assert len(tr.op_events("flash_attention_bwd_dq")) == 24
        assert len(tr.op_events("flash_attention_bwd_dkv")) == 24
        steps = tr.module_events("step_fn")
        assert [round((e - s) * 1e3, 2) for s, e in steps] == [149.23,
                                                               149.25]
    assert a.breakdown() == b.breakdown()
    assert a.breakdown()["device_ops"][0][0] == \
        "fusion:Output bf16[16,1024,768]"
    ctx = {"trace": a, "peaks": common.peaks_table()["kinds"]["TPU v5 lite"],
           "cfg": common.load_json(common.BENCH_DIR, "configs",
                                   "gpt2-small.json"),
           "traffic": common.load_json(common.BENCH_DIR, "traffic",
                                       "train.b16s1024.json")}
    spec = common.load_json(common.BENCH_DIR, "metrics",
                            "flash_fwd_roofline.json")
    assert readers.kernel_roofline(ctx, spec) == pytest.approx(15.3109,
                                                               rel=1e-4)
    spec = common.load_json(common.BENCH_DIR, "metrics",
                            "flash_bwd_roofline.json")
    assert readers.kernel_roofline(ctx, spec) == pytest.approx(14.8756,
                                                               rel=1e-4)


def test_recorded_cell_b_trace_reads_the_same_every_time():
    """One second of ``gpt2l.serve.backlog`` recorded on a TPU v5e (PR 24,
    seed 42, 24 slots): five decode programs and two chunk programs."""
    path = os.path.join(DATA, "cellB_1s.txtpb.gz")
    a, b = trace.load(path), trace.load(path)
    for tr in (a, b):
        assert tr.window_s == pytest.approx(1.0)
        assert tr.busy_s() == pytest.approx(0.957388362, rel=1e-6)
        assert tr.idle_share_busiest() == pytest.approx(0.042611638,
                                                        rel=1e-6)
        assert [round((e - s) * 1e3, 2) for s, e in
                tr.module_events("decode")] == [140.92, 140.91, 140.9,
                                                140.91, 140.91]
        assert [round((e - s) * 1e3, 2) for s, e in
                tr.module_events("chunk")] == [60.72, 57.77]
        dec = tr.op_events("paged_decode_attention")
        assert len(dec) == 216                       # 36 layers x 6 calls
        assert sum(e - s for s, e in dec) == pytest.approx(0.213533957,
                                                           rel=1e-6)
        assert len(tr.op_events("paged_prefill_attention")) == 72
    assert a.breakdown() == b.breakdown()
    assert a.breakdown()["device_ops"][0][0] == "copy"
    assert a.breakdown(default_host="x")["idle_gaps"][0][0] == "bench.step"
    ctx = {"trace": a}
    assert readers.program_time(ctx, {"pattern": "decode", "stat": "p50"}
                                ) == pytest.approx(140.912, rel=1e-5)
