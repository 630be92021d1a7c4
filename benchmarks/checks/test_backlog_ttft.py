"""``ttft_p90_backlog_ms`` is timed from the request's admission to a
slot (PR 29): in a backlog every request is due at t = 0, so a first
token timed from when it was due reads the pre-roll and the window, not
the system. And a run says how much of its schedule it used and what
the engine's own counters say a beat spent in the runtime's calls."""

import argparse
import json

from benchmarks.checks import tiny, tiny_serve
from benchmarks.lib import serve

BACKLOG = "gpt2l.serve.backlog"


def _run(capsys, trace):
    tr = tiny_serve.serve_traffic()
    tr.update(feed="as_queue_has_room", rate_per_s=0,
              preroll={"until": "slots_used"})
    args = argparse.Namespace(seed=83, seconds=2.0, trace=trace,
                              workload="tiny.backlog")
    ok = serve.run({"name": "tiny.backlog", "chips": 1},
                   dict(tiny.TINY_SERVE_CFG), tr, args,
                   tiny.bench_with("tiny.backlog", BACKLOG),
                   device_check=False)
    out = capsys.readouterr()
    return ok, json.loads(out.out.strip().splitlines()[-1]), out.err


def test_backlog_first_tokens_are_timed_from_admission(capsys):
    ok, line, err = _run(capsys, 1)
    assert ok is True
    from_admission = line["metrics"]["ttft_p90_backlog_ms"]["value"]
    from_due = float(err.split("first tokens from when due: median ")[1]
                     .split(" ms")[0])
    # every request was due when the pre-roll began: the median of the
    # window's first tokens from then is seconds, from admission a few
    # beats
    assert 0 < from_admission < from_due
    assert from_admission < 1000.0


def test_a_run_says_how_much_of_the_schedule_it_used(capsys):
    ok, line, _ = _run(capsys, 0)
    use = line["schedule"]
    assert ok is True and use["submitted_in_window"] > 0
    assert use["left"] > 0
    per_beat = line["engine_ms_per_beat"]
    assert sorted(per_beat) == ["launch", "readback", "upload"]
    assert all(v > 0 for v in per_beat.values())
    assert list(line)[-1] == "compared"
