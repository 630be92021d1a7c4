"""The rest of a run with the timed path broken underneath (the look for
a chip skipped): ``correct`` has to come out false, once for each fault
the cells can have."""

import json

import pytest

from benchmarks.checks import tiny, tiny_serve


def _last(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_broken_training_step_is_not_correct(fault, capsys):
    ok = tiny.run_train(seed=77, seconds=0.5, trace=0, fault=fault)
    line = _last(capsys)
    assert ok is False and line["correct"] is False
    failed = [k for k, v in line["compared"].items() if not v["ok"]]
    assert failed, line["compared"]


def test_altered_token_is_not_correct(capsys):
    ok = tiny_serve.run_serve(seed=78, seconds=2.0, trace=0,
                              fault="token_altered")
    line = _last(capsys)
    assert ok is False and line["correct"] is False
    assert not line["compared"]["served_logit_gap_widest"]["ok"]


def test_sound_serving_run_is_correct(capsys):
    ok = tiny_serve.run_serve(seed=79, seconds=2.0, trace=0)
    line = _last(capsys)
    assert ok is True and line["correct"] is True
    assert line["failed"] == 0
