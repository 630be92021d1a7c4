"""The engine's greedy tokens against a recompute-from-scratch oracle.

The oracle shares no code with ``apex_tpu.serving``: it is the model's
PLAIN forward (no cache, no pages, no engine program) over the whole
sequence so far, once per emitted token - what the contiguous engine
stood in for, and stronger, since that engine shared the model's cache
modes, the sampling and the host bookkeeping with the one under test.

Prompt lengths sweep what the paged pool and the chunked ingest can get
wrong: below / at / straddling a page, at / straddling a chunk,
several chunks, and ``max_len - 1`` (one decode step fills the cache).
Three engines, each held as its own tests hold it:

- the GPT-2 block, float32 (policy O0): token for token;
- the GPT-2 block on the int8 KV tier: a token match rate against the
  float32 oracle, teacher-forced along the served stream
  (``test_kv_quant``'s rule and threshold);
- ZAYA, float32: every served token within ``LOGIT_TOL`` of the best
  logit of the float32 reference (``benchmarks/lib/reference_zaya.py``)
  GIVEN the expert choices the plain forward made
  (``test_zaya_model``'s rule: a near-tie between two experts is the
  model's, not the engine's).

CPU, tiny sizes, Pallas in interpret mode; one engine and one jitted
oracle per model for the whole module.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.amp.policy import resolve_policy
from apex_tpu.models import build_lm
from apex_tpu.models.transformer_lm import TransformerLM
from apex_tpu.serving import Engine, KVQuantConfig
from benchmarks.checks.tiny_zaya import TINY_ZAYA_CFG
from benchmarks.lib import reference_zaya as rz

pytestmark = pytest.mark.serving

O0 = resolve_policy("O0", verbose=False)

# GPT-2 block: pages of 8, chunks of 16 (two pages), 64 positions
VOCAB, PAGE, CHUNK, MAX_LEN = 101, 8, 16, 64
LENGTHS = {"below_a_page": 5, "a_page": 8, "straddling_a_page": 11,
           "a_chunk": 16, "straddling_a_chunk": 21, "three_chunks": 40,
           "max_len_less_one": 63}
MATCH_THRESHOLD = 0.95      # test_kv_quant's

# ZAYA: pages of 128, chunks of 256, 512 positions
Z_PAGE, Z_CHUNK, Z_MAX_LEN = 128, 256, 512
Z_LENGTHS = {"below_a_page": 100, "a_page": 128, "straddling_a_page": 150,
             "a_chunk": 256, "straddling_a_chunk": 300,
             "a_chunk_and_a_page": 384, "max_len_less_one": 511}
LOGIT_TOL = 1e-4            # test_zaya_serving's


def _prompt(seed, n, vocab):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


def _serve(eng, prompt, n_new):
    """Greedy tokens of ``prompt`` in slot 0: chunked prefill, then
    decode steps until ``n_new`` tokens or a full cache."""
    out = [eng.prefill_chunked(0, prompt)]
    last = np.zeros(eng.slots, np.int32)
    act = np.zeros(eng.slots, bool)
    act[0] = True
    temps = np.zeros(eng.slots, np.float32)
    while len(out) < n_new and len(prompt) + len(out) <= eng.max_len:
        last[0] = out[-1]
        out.append(int(eng.decode_step(last, act, temps)[0]))
    eng.release_slot(0)
    return out


# ------------------------------------------------------------ GPT-2 block
@pytest.fixture(scope="module")
def gpt2():
    m = TransformerLM(vocab_size=VOCAB, hidden=32, num_layers=2,
                      num_heads=4, max_seq_len=MAX_LEN)
    params = m.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32),
                    train=False)["params"]

    @jax.jit
    def logits(seq):                 # [MAX_LEN] padded -> [MAX_LEN, V]
        return m.apply({"params": params}, seq[None], train=False)[0]

    def next_token(tokens):
        """argmax of the plain forward at the last of ``tokens``
        (right-padding cannot reach it: attention is causal)."""
        seq = np.zeros(MAX_LEN, np.int32)
        seq[:len(tokens)] = tokens
        return int(jnp.argmax(logits(jnp.asarray(seq))[len(tokens) - 1]))

    return m, params, next_token


def _gpt2_engine(gpt2, **kw):
    m, params, _ = gpt2
    return Engine(m, params, slots=2, max_len=MAX_LEN, prefill_len=63,
                  chunk_len=CHUNK, page_len=PAGE, policy=O0, seed=3, **kw)


@pytest.fixture(scope="module")
def gpt2_engine(gpt2):
    return _gpt2_engine(gpt2)


@pytest.fixture(scope="module")
def gpt2_int8_engine(gpt2):
    return _gpt2_engine(gpt2, kv_quant=KVQuantConfig())


@pytest.mark.parametrize("case", sorted(LENGTHS))
def test_gpt2_greedy_tokens_equal_a_from_scratch_greedy_loop(
        gpt2, gpt2_engine, case):
    _, _, next_token = gpt2
    n = LENGTHS[case]
    prompt = _prompt(n, n, VOCAB)
    served = _serve(gpt2_engine, prompt, 8)
    assert len(served) == min(8, MAX_LEN - n + 1)
    want, seq = [], list(prompt)
    for _ in served:                 # the oracle's own greedy loop
        want.append(next_token(seq))
        seq.append(want[-1])
    assert served == want, f"prompt of {n}: engine left the oracle"
    assert gpt2_engine.compiled_programs == 2


@pytest.mark.parametrize("case", sorted(LENGTHS))
def test_gpt2_int8_kv_tier_matches_the_from_scratch_oracle(
        gpt2, gpt2_int8_engine, case):
    """int8 K/V moves logits by the quantisation step, so near-ties
    flip: each served token is held to the oracle's choice GIVEN the
    served stream before it, and the share that agrees to the
    threshold."""
    _, _, next_token = gpt2
    n = LENGTHS[case]
    prompt = _prompt(100 + n, n, VOCAB)
    served = _serve(gpt2_int8_engine, prompt, 24)
    assert len(served) == min(24, MAX_LEN - n + 1)
    agree = [tok == next_token(prompt + served[:i])
             for i, tok in enumerate(served)]
    rate = sum(agree) / len(agree)
    assert rate >= MATCH_THRESHOLD, \
        f"prompt of {n}: token match rate {rate:.3f} over {len(agree)}"


# ------------------------------------------------------------------- ZAYA
@pytest.fixture(scope="module")
def zaya():
    weights = rz.seeded_weights(TINY_ZAYA_CFG, 3, jnp.float32)
    m = build_lm(TINY_ZAYA_CFG, dtype=jnp.float32)
    tree = rz.program_tree(weights)
    eng = Engine(m, tree, slots=2, max_len=Z_MAX_LEN, chunk_len=Z_CHUNK,
                 page_len=Z_PAGE, policy=O0)

    @jax.jit
    def choices(seq):                # the plain forward's expert choices
        _, aux = m.apply({"params": tree}, seq[None], train=False,
                         mutable=["intermediates"])
        return jnp.stack([c[0] for c in
                          aux["intermediates"]["expert_choice"]])

    return weights, eng, choices


@pytest.mark.parametrize("case", sorted(Z_LENGTHS))
def test_zaya_served_tokens_are_the_references_given_the_programs_choices(
        zaya, case):
    weights, eng, choices = zaya
    n = Z_LENGTHS[case]
    prompt = _prompt(n, n, 256)
    served = _serve(eng, prompt, 4)
    assert len(served) == min(4, Z_MAX_LEN - n + 1)
    seq = np.zeros(Z_MAX_LEN, np.int32)      # the last token is only predicted
    seq[:n + len(served) - 1] = prompt + served[:-1]
    seq = jnp.asarray(seq)
    h, _, _ = rz.hidden_states(weights, TINY_ZAYA_CFG, seq,
                               choices=np.asarray(choices(seq)))
    ref = np.asarray(rz.logits_of(weights, h))           # [S, V]
    for j, tok in enumerate(served):     # token j is predicted at n - 1 + j
        row = ref[n - 1 + j]
        assert row.max() - row[tok] < LOGIT_TOL, \
            f"prompt of {n}: served token {j} lies " \
            f"{row.max() - row[tok]:.2e} below the reference's best"
    assert eng.compiled_programs == 2
