"""``BENCHMARK.json`` against the harness (PR 29): every per-layer metric
finds a reader file, each cell it lists reports the end-to-end metric it
moves, and the two serving cells report the token gap's tail under their
own names and bounds."""

import json

import pytest

from benchmarks.checks import tiny_serve
from benchmarks.lib import common, readers

BENCH = common.benchmark_json()
BACKLOG, CHAT = "gpt2l.serve.backlog", "gpt2l.serve.chat"


def _reports(metric, cell):
    cells = metric.get("workloads")
    return cells is None or cell in cells


def test_every_per_layer_metric_has_a_reader_and_moves_what_its_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = [w["name"] for w in BENCH["workloads"]]
    for m in BENCH["per_layer"]:
        spec = readers._spec(m["name"])
        assert readers._by_name(spec["kind"], readers.KINDS)
        for cell in m.get("workloads", cells):
            assert _reports(e2e[m["moves"]], cell), (m["name"], cell)


def test_a_variant_reads_the_file_of_its_quantity():
    assert (readers._spec("decode_prog_ms_p50.chat")
            == readers._spec("decode_prog_ms_p50"))
    with pytest.raises(FileNotFoundError):
        readers._spec("no_such_metric.chat")


def test_the_serving_cells_report_the_gap_under_their_own_names():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["itl_p95_ms"]["workloads"] == [BACKLOG]
    assert e2e["itl_p95_chat_ms"]["workloads"] == [CHAT]
    assert e2e["itl_p95_ms"]["bound"] < e2e["itl_p95_chat_ms"]["bound"] <= 0.1


@pytest.mark.parametrize("trace", [0, 1])
def test_a_cell_like_chat_reports_the_chat_names(trace, capsys):
    assert tiny_serve.run_serve(seed=84, seconds=1.0, trace=trace) is True
    got = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    names = set(got["metrics"])
    if trace == 0:
        assert names == {"itl_p95_chat_ms", "setup_s"}
        return
    # the host's readers find something on the CPU too; the device
    # trace's program line is the chip's
    assert {"beat_host_ms_p50.chat", "beat_launch_ms_p50.chat",
            "beat_readback_ms_p50.chat", "gen_late_ms_p95"} <= names
    assert not any(_reports(m, BACKLOG) and not _reports(m, CHAT)
                   and m["name"] in names for m in BENCH["per_layer"])
