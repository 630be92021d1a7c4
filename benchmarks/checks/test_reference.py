"""The plain reference against the program at a toy size on the CPU: the
weights it makes from the seed are the recipe's own, bit for bit; its
losses, first gradient and three-step change agree with the recipe's amp
O2 run to bfloat16 rounding (through the whole harness: ``correct``)."""

import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.checks import tiny
from benchmarks.lib import reference_lm


def test_recipe_init_is_the_programs_own():
    from apex_tpu.models.transformer_lm import create_lm

    cfg = tiny.TINY_CFG
    model = create_lm("tiny", vocab_size=cfg["vocab_size"],
                      max_seq_len=cfg["n_positions"], dtype=jnp.bfloat16)
    params = model.init(jax.random.PRNGKey(11),
                        jnp.zeros((2, cfg["n_positions"]), jnp.int32),
                        train=False)["params"]
    mine = reference_lm.program_tree(reference_lm.recipe_init(cfg, 11))
    flat_a = jax.tree_util.tree_flatten_with_path(params)[0]
    flat_b = dict((jax.tree_util.keystr(p), x) for p, x in
                  jax.tree_util.tree_flatten_with_path(mine)[0])
    assert len(flat_a) == len(flat_b) == 4 + 12 * cfg["n_layer"]
    for path, x in flat_a:
        np.testing.assert_array_equal(np.asarray(x),
                                      np.asarray(flat_b[
                                          jax.tree_util.keystr(path)]))


def test_a_run_of_the_harness_is_correct(capsys, monkeypatch):
    monkeypatch.setenv("BENCH_WATCHDOG", "1")    # the diagnosis thread too
    assert tiny.run_train(seed=2147483999, seconds=1.0, trace=0)
    got = capsys.readouterr()
    assert "watchdog: wake-ups 50 ms late or more" in got.err
    assert "longest turn of the recipe's loop" in got.err
    line = json.loads(got.out.strip().splitlines()[-1])
    assert line["correct"] is True
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert list(line)[-1] == "compared"
    assert line["metrics"]["train_items_per_s_chip"]["value"] > 0
