"""The control has to come out as not correct: the plain reference put in
the program's place and computed in float8_e4m3fn, the nearest precision
below the bfloat16 the configurations state. On the chip it is read at the
cells' own sizes by ``calibrate_train.py`` and ``calibrate_serve.py``
(PERF.md has the readings); here at a size a test run can hold, on three
seeds, by the same comparison ``correct`` makes."""

import re

import pytest

from benchmarks.checks import calibrate_train, tiny, tiny_serve
from benchmarks.lib import common


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float8_training_is_not_correct(seed):
    traffic = tiny.train_traffic()
    got = calibrate_train.readings(dict(tiny.TINY_CFG), traffic, seed)
    limits = traffic["check"]["limits"]
    numbers = {k: (got["control_fp8"][k], limits[lim]) for k, lim in (
        ("grad_norm_worst_leaf_gap", "grad_norm_gap"),
        ("param_change_worst_leaf_gap", "param_change_gap"))}
    compared, ok = common.compare(numbers)
    assert not ok, compared
    # and the planted fault, by the number that is its to catch
    assert got["fault_half_batch"]["grad_norm_worst_leaf_gap"] \
        > 10 * limits["grad_norm_gap"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float8_serving_is_not_correct(seed, capfd):
    traffic = tiny_serve.serve_traffic()
    assert tiny_serve.run_serve(seed, 2.0, 0, traffic=traffic,
                                control="fp8")
    m = re.search(r"control fp8: widest gap of its first tokens (\S+) "
                  r"\(program's served tokens: (\S+)\)",
                  capfd.readouterr().err)
    control, program = float(m.group(1)), float(m.group(2))
    limit = traffic["check"]["limits"]["served_logit_gap"]
    assert program <= limit < control
