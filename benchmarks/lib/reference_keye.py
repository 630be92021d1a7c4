"""The plain reference of Keye-VL-2.0-30B-A3B's language model (Kwai), as
the benchmark's yardstick.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the forward pass over one
whole sequence, no kernel, no cache, no batching; the indexer's scores as
plain products against every earlier position, the selection an exact
k-th largest found by bisection over the scores' order and the ties kept
from the lowest position (the order ``lax.top_k`` keeps, checked against
it in the tests), the attention a softmax over every earlier position
with the unselected masked out, the experts a loop with masked dense
products. It imports nothing of ``apex_tpu`` and takes no array the
program has made: weights come from the seed here (``seeded_weights``)
and are handed TO the engine under the program's parameter paths
(``program_tree``). The rounding helpers, the rotary and the expert loop
are ``reference_zaya``'s and ``reference_ling3``'s, the parameter paths
and the untied head's readings ``reference_qwen3next``'s, by import.

So that a request of 64k positions fits, a layer first makes the keys,
values and index keys of every position, then takes the queries in
BLOCKS of ``BLOCK``, each against the positions up to its end rounded up
to ``KEY_UNIT`` (the rest masked): the same sums in the same float32,
never a ``[S, S]`` array and never a head's ``[block, S]`` array beside
another's.

The sizes are the catalog row's ``config``. With ``rms(x; w) = x /
sqrt(mean(x^2) + eps) * w`` and ``t`` a position, a layer is:

- ``u = rms(x; w_a)``;
- attention projections: ``q = rms_h(u W_q; w_q)`` (32 heads of 128),
  ``k = rms_h(u W_k; w_k)``, ``v = u W_v`` (4 heads of 128); rotary
  (half-split pairs, absolute position, theta ``rope_theta``) over the
  whole head of ``q`` and ``k`` - the text's three ``mrope`` position ids
  are equal, so ``mrope_section`` [16, 24, 24] is 1-D rotary over all 64
  frequency pairs;
- the indexer (``sa_config``): ``qI_t = rot_half(u_t W_qI)`` (16 heads of
  64, rotary on the first half of a head), ``kI_s = rot_half(LN(u_s
  W_kI))`` (64, one for all heads, ``LN`` with gain and bias), ``w_t =
  (u_t W_w) / sqrt(16)``; ``I_{t,s} = sum_j w_{t,j} ReLU(qI_{t,j} . kI_s
  / sqrt(64))`` for ``s <= t``;
- the selection: ``S_t`` the 2,048 largest ``I_{t,s}`` over ``s <= t``,
  ties to the lower ``s``; every ``s <= t`` where ``t < 2,048``;
- sparse attention: ``o_{t,h} = sum_{s in S_t} softmax_s(q_{t,h} .
  k_{s,h // 8} / sqrt(128)) v_{s,h // 8}``, one selection for all heads;
  ``x += o W_o``;
- experts: ``u = rms(x; w_m)``, ``p = softmax(u W_r)`` over all 128, the
  top 8 renormalised over the 8, ``x += sum_{e in top8 and held} p^_e
  (silu(u Wg^e) * (u Wu^e)) Wd^e``; no shared expert;

and ``logits = rms(x_L; w_f) W_head`` with ``W_head`` its own matrix.
What the configuration does not fix is listed under ``assumed`` in the
configuration's file.

``lowp="fp8"`` is the control: both operands of every matrix product with
a weight rounded to float8_e4m3fn, the nearest precision below the
bfloat16 the configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .reference_ling3 import experts, rms
from .reference_qwen3next import (head_readings, logits_of,  # noqa: F401
                                  program_tree)
from .reference_zaya import _LOWP, HI, _freeze, _mm, _thaw, rotary

BLOCK = 1024                # queries a step of the blocked forward takes
KEY_UNIT = 16384            # a step's keys: its end rounded up to this
ROUTER_SCALE = 2.0          # seeded_weights: see there, and `assumed`


def _frozen(cfg):
    """``_freeze`` of what the reference reads (``rope_scaling`` holds a
    list and says nothing the reference needs: 1-D rotary)."""
    return _freeze({k: v for k, v in cfg.items() if k != "rope_scaling"})


def routed_experts(cfg) -> int:
    """The router's width: the published count where the file's
    ``num_experts`` is the chip's share."""
    return int(cfg.get("published", {}).get("num_experts",
                                            cfg["num_experts"]))


def _sizes(cfg):
    g = lambda k: int(cfg[k])                                   # noqa: E731
    sa = cfg["sa_config"]
    return dict(H=g("hidden_size"), nq=g("num_attention_heads"),
                nk=g("num_key_value_heads"), d=g("head_dim"),
                nh=int(sa["indexer_num_heads"]),
                di=int(sa["indexer_head_dim"]), topk=int(sa["topk"]),
                E=routed_experts(cfg), G=g("num_experts"),
                k=g("num_experts_per_tok"), F=g("moe_intermediate_size"),
                theta=float(cfg["rope_theta"]),
                eps=float(cfg["rms_norm_eps"]))


def layer_shapes(cfg):
    """Every parameter of a layer under the program's path, and its
    shape. ``experts/*`` are stacked over the experts HELD
    (``num_experts``, ids ``0 .. num_experts - 1``)."""
    s = _sizes(cfg)
    H, d = s["H"], s["d"]
    return {"attn_norm/scale": (H,),
            "attn/wq": (H, s["nq"] * d), "attn/wk": (H, s["nk"] * d),
            "attn/wv": (H, s["nk"] * d), "attn/q_norm": (d,),
            "attn/k_norm": (d,), "attn/wo": (s["nq"] * d, H),
            "indexer/wq": (H, s["nh"] * s["di"]),
            "indexer/wk": (H, s["di"]), "indexer/k_norm": (s["di"],),
            "indexer/k_bias": (s["di"],), "indexer/ww": (H, s["nh"]),
            "moe_norm/scale": (H,), "router/w": (H, s["E"]),
            "experts/w_gate_up": (s["G"], H, 2 * s["F"]),
            "experts/w_down": (s["G"], s["F"], H)}


# ---------------------------------------------------------------- weights

def _draw_spec(name, shape):
    """(centre, scale) of the normal a leaf is drawn from. Every learned
    gain is perturbed away from its initial value, so that a path that
    left one out would show."""
    leaf = name.split("/")[-1]
    if leaf in ("scale", "q_norm", "k_norm"):                # gains
        return 1.0, 0.05
    if leaf == "k_bias":
        return 0.0, 0.05
    scale = 1.0 / np.sqrt(shape[-2])            # a matrix: 1 / sqrt(fan_in)
    if name == "router/w":
        scale *= ROUTER_SCALE
    return 0.0, scale


@functools.partial(jax.jit, static_argnums=(0, 1))
def _seeded_layer(cfg_items, dtype, key):
    shapes = layer_shapes(_thaw(cfg_items))
    ks = jax.random.split(key, len(shapes))
    out = {}
    for k, (n, shape) in zip(ks, shapes.items()):
        centre, scale = _draw_spec(n, shape)
        out[n] = (centre + scale * jax.random.normal(
            k, shape, jnp.float32)).astype(dtype)
    return out


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _seeded_top(V, H, dtype, key):
    k0, k1, k2 = jax.random.split(key, 3)
    return {"wte/embedding": (0.02 * jax.random.normal(
                k0, (V, H), jnp.float32)).astype(dtype),
            "norm_f/scale": (1.0 + 0.05 * jax.random.normal(
                k1, (H,), jnp.float32)).astype(dtype),
            "head/kernel": (jax.random.normal(k2, (H, V), jnp.float32)
                            / np.sqrt(H)).astype(dtype)}


def seeded_weights(cfg, seed: int, dtype=jnp.bfloat16):
    """The benchmark's own weights for the serving cell, made on the
    device layer by layer, in ``dtype``, the type they are served in; the
    reference reads the same values widened to float32.
    ``{"wte/embedding", "norm_f/scale", "head/kernel", "layers": [{leaf:
    array}]}``.

    Scales (assumed): the embedding normal(0.02); every matrix normal(1 /
    sqrt(fan_in)), the untied head included, so that the logits are of
    order 1 and the indexer's scores of order 1 too (``qI . kI / 8`` about
    a unit normal a head, the weights about a quarter); the router
    ``ROUTER_SCALE`` = 2 times wider (``reference_qwen3next``'s: the eight
    chosen weigh unevenly, as a trained router's do); norm gains
    normal(1, 0.05) and the index key's LayerNorm bias normal(0, 0.05)."""
    key = jax.random.PRNGKey(seed % (2**31 - 1))
    frozen = _frozen(cfg)
    L = int(cfg["num_hidden_layers"])
    V, H = int(cfg["vocab_size"]), int(cfg["hidden_size"])
    p = _seeded_top(V, H, dtype, jax.random.fold_in(key, L))
    p["layers"] = [_seeded_layer(frozen, dtype, jax.random.fold_in(key, i))
                   for i in range(L)]
    return p


# ---------------------------------------------------------------- forward

def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def keys_of(u, lp, cfg, pos, lowp=None):
    """``(k [n, nk, d], v [n, nk, d], kI [n, di])`` of the normed rows
    ``u [n, H]`` at positions ``pos [n]``."""
    s = _sizes(cfg)
    n = u.shape[0]
    k = _mm(u, lp["attn/wk"], lowp).reshape(n, s["nk"], s["d"])
    k = rotary(rms(k, lp["attn/k_norm"], s["eps"]), pos, s["theta"], s["d"])
    v = _mm(u, lp["attn/wv"], lowp).reshape(n, s["nk"], s["d"])
    ki = _layer_norm(_mm(u, lp["indexer/wk"], lowp), lp["indexer/k_norm"],
                     lp["indexer/k_bias"], s["eps"])
    ki = rotary(ki[:, None], pos, s["theta"], s["di"] // 2)[:, 0]
    return k, v, ki


def queries_of(u, lp, cfg, pos, lowp=None):
    """``(q [n, nq, d], qI [n, nh, di], w [n, nh])``."""
    s = _sizes(cfg)
    n = u.shape[0]
    q = _mm(u, lp["attn/wq"], lowp).reshape(n, s["nq"], s["d"])
    q = rotary(rms(q, lp["attn/q_norm"], s["eps"]), pos, s["theta"], s["d"])
    qi = _mm(u, lp["indexer/wq"], lowp).reshape(n, s["nh"], s["di"])
    qi = rotary(qi, pos, s["theta"], s["di"] // 2)
    return q, qi, _mm(u, lp["indexer/ww"], lowp) / np.sqrt(s["nh"])


def index_scores(qi, w, ki, pos):
    """``I [n, m]`` of queries at ``pos [n]`` against the index keys ``ki
    [m, di]`` of positions ``0 .. m - 1``; ``-inf`` past each query."""
    di = ki.shape[1]

    def head(acc, j):
        r = jnp.maximum(jnp.einsum("nd,md->nm", qi[:, j], ki, precision=HI)
                        / np.sqrt(di), 0.0)
        return acc + w[:, j][:, None] * r, None

    acc = jax.lax.scan(head, jnp.zeros((qi.shape[0], ki.shape[0])),
                       jnp.arange(qi.shape[1]))[0]
    cols = jnp.arange(ki.shape[0])[None]
    return jnp.where(cols <= pos[:, None], acc, -jnp.inf)


def _key(x):
    """float32 -> int32 of the same order (``-0.0`` as ``+0.0``)."""
    b = jax.lax.bitcast_convert_type(x + 0.0, jnp.int32)
    return b ^ ((b >> 31) & 0x7FFFFFFF)


def top_mask(scores, pos, k: int):
    """``[n, m]`` bool: the ``k`` largest of each row over ``s <= pos``,
    ties to the lower position; every ``s <= pos`` where that is ``k`` or
    fewer. The k-th largest by bisection over the order key (the largest
    ``T`` with at least ``k`` keys ``>= T``), then the ties at it kept in
    position order."""
    key = _key(scores)
    cols = jnp.arange(scores.shape[1])[None]
    live = cols <= pos[:, None]
    key = jnp.where(live, key, jnp.iinfo(jnp.int32).min)

    def at_least(t):
        return jnp.sum(key >= t[:, None], axis=1) >= k

    t = jnp.where(at_least(jnp.zeros_like(pos)), 0,
                  jnp.iinfo(jnp.int32).min).astype(jnp.int32)

    def bit(i, t):
        cand = t | jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(at_least(cand), cand, t)

    t = jax.lax.fori_loop(0, 31, bit, t)
    above = key > t[:, None]
    need = k - jnp.sum(above, axis=1)
    tie = key == t[:, None]
    keep = above | (tie & (jnp.cumsum(tie, axis=1) <= need[:, None]))
    return jnp.where((pos + 1 <= k)[:, None], live, keep & live)


def route(u, lp, cfg, lowp=None):
    """-> (each token's ``k`` experts ``[n, k]``, their weights ``[n, k]``
    renormalised over the ``k``, the margin ``[n]`` of the k-th
    probability above the next: how far the choice is from a tie)."""
    kk = int(cfg["num_experts_per_tok"])
    p = jax.nn.softmax(_mm(u, lp["router/w"], lowp), -1)
    top, choice = jax.lax.top_k(p, kk + 1)
    w = top[:, :kk]
    return choice[:, :kk], w / jnp.sum(w, -1, keepdims=True), \
        top[:, kk - 1] - top[:, kk]


def weights_of(u, choice, lp, cfg, lowp=None):
    """The reference's weights for experts chosen elsewhere (tests)."""
    p = jax.nn.softmax(_mm(u, lp["router/w"], lowp), -1)
    w = jnp.take_along_axis(p, choice, 1)
    return w / jnp.sum(w, -1, keepdims=True)


def sparse_attention(q, k, v, keep):
    """``q [n, nq, d]`` against ``k, v [m, nk, d]`` under ``keep [n, m]``
    -> ``[n, nq * d]``, a head at a time."""
    n, nq, d = q.shape
    G = nq // k.shape[1]

    def head(_, h):
        s = jnp.einsum("nd,md->nm", q[:, h], k[:, h // G], precision=HI) \
            / np.sqrt(d)
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
        return None, jnp.einsum("nm,md->nd", p, v[:, h // G], precision=HI)

    o = jax.lax.scan(head, None, jnp.arange(nq))[1]          # [nq, n, d]
    return jnp.moveaxis(o, 0, 1).reshape(n, nq * d)


def _f32(lp):
    """A layer's leaves widened to float32 - the stacked experts apart,
    which :func:`~.reference_ling3.experts` widens one at a time."""
    return {k: v if k.startswith("experts/") else jnp.asarray(v, jnp.float32)
            for k, v in lp.items()}


@functools.partial(jax.jit, static_argnums=(2, 3))
def _keys_jit(x, lp, cfg_items, lowp, pos):
    lp, cfg = _f32(lp), _thaw(cfg_items)
    u = rms(x, lp["attn_norm/scale"], float(cfg["rms_norm_eps"]))
    return keys_of(u, lp, cfg, pos, _LOWP[lowp])


@functools.partial(jax.jit, static_argnums=(2, 3))
def _block_jit(x, lp, cfg_items, lowp, pos, k, v, ki, keep, choice):
    """One block of queries through one layer: ``(x out, the kept
    positions [n, m], the scores [n, m], the reference's own expert choice
    and its margin)``; ``keep``/``choice`` (tests) replace the reference's
    own where given."""
    lp, cfg = _f32(lp), _thaw(cfg_items)
    lowp = _LOWP[lowp]
    eps = float(cfg["rms_norm_eps"])
    u = rms(x, lp["attn_norm/scale"], eps)
    q, qi, w = queries_of(u, lp, cfg, pos, lowp)
    scores = index_scores(qi, w, ki, pos)
    own = top_mask(scores, pos, int(cfg["sa_config"]["topk"]))
    o = sparse_attention(q, k, v, own if keep is None else keep)
    x = x + _mm(o, lp["attn/wo"], lowp)
    u = rms(x, lp["moe_norm/scale"], eps)
    mine, weights, margin = route(u, lp, cfg, lowp)
    if choice is not None:
        weights = weights_of(u, choice, lp, cfg, lowp)
    y = experts(u, mine if choice is None else choice, weights, lp, cfg,
                lowp)
    return x + y, own, scores, mine, margin


def hidden_states(p, cfg, tokens, lowp=None, choices=None, selections=None,
                  block=BLOCK, with_scores=False):
    """Final-norm output ``[S, H]`` for ``tokens [S]`` (``S`` a multiple of
    ``block``, or less than it), layer by layer, a block of queries at a
    time. Also the reference's own expert choices ``[L, S, k]`` and their
    margins ``[L, S]``; with ``with_scores`` (tests, small sizes) the
    reference's own selections ``[L, S, S]`` and scores ``[L, S, S]``
    too. ``choices [L, S, k]`` and ``selections [L, S, S]`` (tests): the
    experts and positions USED are those."""
    S = int(tokens.shape[0])
    bs = min(block, S)
    if S % bs:
        raise ValueError(f"{S} positions are not whole blocks of {bs}")
    frozen = _frozen(cfg)
    pos_all = jnp.arange(S, dtype=jnp.int32)
    own, margins, sels, scs = [], [], [], []
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(p["wte/embedding"], jnp.float32)[tokens]
        for i, lp in enumerate(p["layers"]):
            kv = [_keys_jit(x[lo:lo + bs], lp, frozen, lowp,
                            pos_all[lo:lo + bs]) for lo in range(0, S, bs)]
            K, V, KI = (jnp.concatenate(t) for t in zip(*kv))
            xs, ch, mg = [], [], []
            for lo in range(0, S, bs):
                m = S if with_scores else min(
                    S, -(-(lo + bs) // KEY_UNIT) * KEY_UNIT)
                keep = None if selections is None \
                    else jnp.asarray(selections[i][lo:lo + bs, :m])
                choice = None if choices is None \
                    else jnp.asarray(choices[i][lo:lo + bs])
                xb, sel, sc, c, mgn = _block_jit(
                    x[lo:lo + bs], lp, frozen, lowp, pos_all[lo:lo + bs],
                    K[:m], V[:m], KI[:m], keep, choice)
                xs.append(xb)
                ch.append(c)
                mg.append(mgn)
                if with_scores:
                    sels.append(sel)
                    scs.append(sc)
            x = jnp.concatenate(xs)
            own.append(jnp.concatenate(ch))
            margins.append(jnp.concatenate(mg))
        x = rms(x, jnp.asarray(p["norm_f/scale"], jnp.float32),
                float(cfg["rms_norm_eps"]))
    out = (x, jnp.stack(own), jnp.stack(margins))
    if with_scores:
        L = len(p["layers"])
        out += (jnp.concatenate(sels).reshape(L, S, S),
                jnp.concatenate(scs).reshape(L, S, S))
    return out


# ---------------------------------------------------------------- serving

def served_token_gaps(p, cfg, prompt, output, lowp=None, pad_to=None,
                      reach=0, block=BLOCK):
    """``(gaps of the served tokens, gaps of the control's tokens, tie
    margins)``, numpy arrays of length ``len(output)``: output token j is
    predicted at position ``len(prompt) - 1 + j`` of prompt + output; its
    gap is how far its float32 logit lies below the reference's best
    there, its tie margin the least margin, over the layers, of the
    reference's k-th expert against the next and at the ``reach``
    positions before it. With ``lowp`` the second array is the gap of the
    token the lower precision puts first.

    The sequence is padded to whole blocks of ``block`` (to a multiple of
    128 where it is shorter than one), so that the requests of a run meet
    few compiled shapes; ``pad_to`` is not needed (a block's keys are
    rounded to ``KEY_UNIT``) and is accepted for the harness's call. The
    head is read at the served positions only."""
    del pad_to
    n, m = len(prompt), len(output)
    seq = np.asarray(list(prompt) + list(output), np.int32)
    unit = block if len(seq) > block else 128
    S = -(-len(seq) // unit) * unit
    pad = np.zeros((S,), np.int32)
    pad[:len(seq)] = seq
    tokens = jnp.asarray(pad)
    M = -(-m // 128) * 128
    at_rows = np.minimum(n - 1 + np.arange(M), S - 1)
    nxt = np.zeros((M,), np.int32)
    nxt[:m] = np.asarray(output, np.int32)
    nxt = jnp.asarray(nxt)
    h, _, margins = hidden_states(p, cfg, tokens, block=block)
    h = h[at_rows]
    best, _, at = head_readings(p, h, nxt)
    served = np.asarray(best - at)[:m]
    least = np.asarray(jnp.min(margins, 0))
    near = least
    for k in range(1, reach + 1):
        near = np.minimum(near, np.concatenate(
            [np.full((k,), np.inf, least.dtype), least[:-k]]))
    ties = near[at_rows][:m]
    ctrl = np.zeros_like(served)
    if lowp is not None:
        hl, _, _ = hidden_states(p, cfg, tokens, lowp, block=block)
        _, pick, _ = head_readings(p, hl[at_rows], nxt, lowp)
        _, _, at_pick = head_readings(p, h, pick)
        ctrl = np.asarray(best - at_pick)[:m]
    return served, ctrl, ties
