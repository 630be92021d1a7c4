"""BERT-LAMB recipe's --data-parallel path (the reference's multi-GPU
BERT-LAMB shape: apex DDP + FusedLAMB, here one grad psum over 'data').
"""

import importlib.util
import os

import numpy as np
import pytest

# Heavy multi-device CPU-emulation tier: inert at the seed (shard_map
# import errors) until the apex_tpu.utils.compat shim made this file
# runnable on the hermetic jax, but too costly for the tier-1 wall-time
# budget. Deselect from the fast tier; run with -m slow.
pytestmark = pytest.mark.slow

_RECIPE = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir,
                       "examples", "bert_lamb", "main_amp.py")


@pytest.fixture(scope="module")
def bl():
    spec = importlib.util.spec_from_file_location("bert_lamb_recipe",
                                                  _RECIPE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


BASE = ["--bert-model", "tiny", "--train_batch_size", "8",
        "--max_seq_length", "32", "--max_predictions_per_seq", "4",
        "--max_steps", "4"]


def test_ddp_trains(bl, eight_devices):
    m = bl.main(BASE + ["--data-parallel", "4"])
    assert np.isfinite(float(m["loss"]))
    assert not bool(m["found_inf"])


def test_batch_divisibility_rejected(bl, eight_devices):
    with pytest.raises(SystemExit, match="divide"):
        bl.main(BASE + ["--data-parallel", "3"])
