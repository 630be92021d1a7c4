"""The plain reference of Qwen3-Next (Qwen), as the benchmark's yardstick.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the forward pass over one
whole sequence, no kernel, no cache, no batching; the convolution as
explicit shifted sums, the delta rule as a plain ``lax.scan`` over time,
the experts as a loop with masked dense products. It imports nothing of
``apex_tpu`` and takes no array the program has made: weights come from
the seed here (``seeded_weights``) and are handed TO the engine under the
program's parameter paths (``program_tree``: names are the interface to
the system under test). The rounding helpers and the rotary are
``reference_zaya``'s, by import.

The sizes are the published ``config.json``'s; the structure follows the
public ``qwen3_next`` modelling code, set down without the network. What
the configuration does not fix is listed under ``assumed`` in the
configuration's file and marked "assumed" here.

With ``rms(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)`` (zero-centred),
a layer is ``h = x + Mixer(rms(x))``, ``x' = h + MoE(rms(h))``; layer
``l`` is full attention where ``(l + 1) % full_attention_interval == 0``,
else linear; ``logits = rms(x_L) W_head`` with ``W_head`` its own matrix.

*full attention* - ``[q | g] = u W_q`` split per head into a query and a
gate of ``d`` each, ``k = u W_k``, ``v = u W_v``; ``q, k`` RMS-normed per
head; rotary on the first ``partial_rotary_factor`` of each head
(half-split pairs, absolute position); causal softmax attention at
``1/sqrt(d)``, query head ``h`` reading K/V head ``h // G``; ``o *
sigmoid(g)``; ``o W_o``.

*gated delta-rule linear attention* - ``[q~ | k~ | v~ | z] = u W_qkvz``
(columns in this order: assumed layout), ``[b | a] = u W_ba``; ``c =
silu(conv([q~ | k~ | v~]))``, depthwise and causal over time, zeros before
position 0; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
dt_bias)`` per value head; ``q, k`` L2-normalised per head (eps 1e-6), ``q
/ sqrt(dk)``, each key head serving ``nv / nk`` value heads; per value
head, from ``S = 0``: ``S <- exp(g_t) S; r = S^T k_t; S <- S + k_t (beta_t
(v_t - r))^T; o_t = S^T q_t``; ``y = rms_plain(o_t; w_n) silu(z)`` per
head (``rms_plain`` multiplies by ``w``); ``y W_out``.

*experts* - ``p = softmax(u W_r)`` over ALL the routed experts; the ``k``
largest, weights ``p_e / sum of the k``; ``y = sum_e w_e (silu(u Wg^e) *
(u Wu^e)) Wd^e`` over the experts HELD (the chip's share; the weights
stay normalised over all ``k``), ``+ sigmoid(u w_sg) (silu(u Wg^s) * (u
Wu^s)) Wd^s``. No token is dropped.

``lowp="fp8"`` is the control: both operands of every matrix product with
a weight rounded to float8_e4m3fn, the nearest precision below the
bfloat16 the configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .reference_zaya import _LOWP, HI, _freeze, _mm, _thaw, rotary, shift

ROUTER_SCALE = 2.0          # seeded_weights: see there, and `assumed`


def routed_experts(cfg) -> int:
    """The router's width: the published count where the file's
    ``num_experts`` is the chip's share."""
    return int(cfg.get("published", {}).get("num_experts",
                                            cfg["num_experts"]))


def is_full(cfg, layer: int) -> bool:
    return (layer + 1) % int(cfg["full_attention_interval"]) == 0


def layer_shapes(cfg, full: bool):
    """Every per-layer parameter of a layer of that kind under the
    program's path, and its shape. ``experts/*`` are stacked over the
    experts HELD (``num_experts``, ids ``0 .. num_experts - 1``)."""
    g = lambda k: int(cfg[k])                                   # noqa: E731
    H, F, Fs = g("hidden_size"), g("moe_intermediate_size"), \
        g("shared_expert_intermediate_size")
    if full:
        nq, nk, d = g("num_attention_heads"), g("num_key_value_heads"), \
            g("head_dim")
        mixer = {"attn/wq": (H, nq * 2 * d), "attn/wk": (H, nk * d),
                 "attn/wv": (H, nk * d), "attn/q_norm": (d,),
                 "attn/k_norm": (d,), "attn/wo": (nq * d, H)}
    else:
        nk, nv = g("linear_num_key_heads"), g("linear_num_value_heads")
        dk, dv = g("linear_key_head_dim"), g("linear_value_head_dim")
        C = 2 * nk * dk + nv * dv
        mixer = {"gdn/w_qkvz": (H, C + nv * dv), "gdn/w_ba": (H, 2 * nv),
                 "gdn/conv_w": (C, g("linear_conv_kernel_dim")),
                 "gdn/a_log": (nv,), "gdn/dt_bias": (nv,),
                 "gdn/norm": (dv,), "gdn/w_out": (nv * dv, H)}
    return {"attn_norm/scale": (H,), **mixer, "moe_norm/scale": (H,),
            "router/w": (H, routed_experts(cfg)),
            "experts/w_gate_up": (g("num_experts"), H, 2 * F),
            "experts/w_down": (g("num_experts"), F, H),
            "shared/w_gate_up": (H, 2 * Fs), "shared/w_down": (Fs, H),
            "shared/w_gate": (H,)}


# ---------------------------------------------------------------- weights

def _draw_spec(name, shape):
    """(centre, scale) of the normal a leaf is drawn from. Every learned
    scale is perturbed away from its initial value, so that a path that
    left one out would show."""
    leaf = name.split("/")[-1]
    if leaf in ("scale", "q_norm", "k_norm"):       # zero-centred (1 + w)
        return 0.0, 0.05
    if leaf == "norm":                              # plain
        return 1.0, 0.05
    if leaf == "conv_w":
        return None, None               # drawn column by column below
    if leaf == "a_log":                 # exp(A_log) about 0.9 .. 2.3
        return 0.35, 0.5
    if leaf == "dt_bias":               # softplus(a + dt_bias) about 0.03
        return -4.0, 0.5
    if name == "router/w":
        return 0.0, ROUTER_SCALE / np.sqrt(shape[0])
    if leaf == "w_gate":
        return 0.0, 1.0 / np.sqrt(shape[0])
    return 0.0, 1.0 / np.sqrt(shape[-2])     # a matrix: 1 / sqrt(fan_in)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _seeded_layer(cfg_items, full, dtype, key):
    shapes = layer_shapes(_thaw(cfg_items), full)
    ks = jax.random.split(key, len(shapes))
    out = {}
    for k, (n, shape) in zip(ks, shapes.items()):
        centre, scale = _draw_spec(n, shape)
        noise = jax.random.normal(k, shape, jnp.float32)
        if centre is None:          # earlier taps about 0.3, the current 1
            taps = jnp.concatenate([jnp.full(shape[:-1] + (shape[-1] - 1,),
                                             0.3),
                                    jnp.ones(shape[:-1] + (1,))], -1)
            out[n] = (taps + 0.1 * noise).astype(dtype)
        else:
            out[n] = (centre + scale * noise).astype(dtype)
    return out


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _seeded_top(V, H, dtype, key):
    k0, k1, k2 = jax.random.split(key, 3)
    return {"wte/embedding": (0.02 * jax.random.normal(
                k0, (V, H), jnp.float32)).astype(dtype),
            "norm_f/scale": (0.05 * jax.random.normal(
                k1, (H,), jnp.float32)).astype(dtype),
            "head/kernel": (jax.random.normal(k2, (H, V), jnp.float32)
                            / np.sqrt(H)).astype(dtype)}


def seeded_weights(cfg, seed: int, dtype=jnp.bfloat16):
    """The benchmark's own weights for the serving cell, made on the
    device layer by layer, in ``dtype``, the type they are served in; the
    reference reads the same values widened to float32.
    ``{"wte/embedding", "norm_f/scale", "head/kernel", "layers": [{leaf:
    array}]}``.

    Scales: the embedding normal(0.02); every matrix normal(1 /
    sqrt(fan_in)), the untied head included, so the logits are of order 1
    and a served token is one that rounding can change; norm gains
    perturbed away from their initial values. Three draws are chosen so
    that the mechanisms are not degenerate at random weights (assumed):
    the router ``ROUTER_SCALE`` = 2 times wider than 1 / sqrt(fan_in)
    (at 1 the ten chosen of 512 weigh within a factor 2.7 of each other;
    at 2 the first has about a third of the renormalised mass and the
    tenth a twentieth); ``A_log`` normal(0.35, 0.5) and ``dt_bias``
    normal(-4, 0.5), so that with ``a = u w_a`` of order 1 a head decays
    by ``exp(g)`` of about 0.9 to 0.99 a token - a memory of tens of
    tokens, neither none nor unbounded; the convolution's current tap
    about 1 and the three before it about 0.3."""
    key = jax.random.PRNGKey(seed % (2**31 - 1))
    frozen = _freeze(cfg)
    L = int(cfg["num_hidden_layers"])
    V, H = int(cfg["vocab_size"]), int(cfg["hidden_size"])
    p = _seeded_top(V, H, dtype, jax.random.fold_in(key, L))
    p["layers"] = [_seeded_layer(frozen, is_full(cfg, i), dtype,
                                 jax.random.fold_in(key, i))
                   for i in range(L)]
    return p


def program_tree(p):
    """The same arrays under the parameter paths the program's
    ``Qwen3NextLM`` uses: ``layer_<i>/<module>/<leaf>``. Nothing is
    copied."""
    tree = {"wte": {"embedding": p["wte/embedding"]},
            "norm_f": {"scale": p["norm_f/scale"]},
            "head": {"kernel": p["head/kernel"]}}
    for i, lp in enumerate(p["layers"]):
        blk = {}
        for n, v in lp.items():
            mod, leaf = n.split("/")
            blk.setdefault(mod, {})[leaf] = v
        tree[f"layer_{i}"] = blk
    return tree


# ---------------------------------------------------------------- forward

def rms(x, w, eps, centred=True):
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return y * ((1.0 + w) if centred else w)


def delta_rule(q, k, v, g, beta):
    """The recurrence over one sequence: ``q, k [S, nv, dk]``, ``v [S, nv,
    dv]``, ``g, beta [S, nv]`` -> ``o [S, nv, dv]``, from ``S = 0``."""
    def step(S, x):
        qt, kt, vt, gt, bt = x
        S = jnp.exp(gt)[:, None, None] * S
        r = jnp.einsum("hkv,hk->hv", S, kt, precision=HI)
        S = S + kt[:, :, None] * (bt[:, None] * (vt - r))[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt, precision=HI)
    S0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    return jax.lax.scan(step, S0, (q, k, v, g, beta))[1]


def linear_attention(u, lp, cfg, lowp=None):
    """``u [S, H]`` (normed) -> ``[S, H]``."""
    g_ = lambda k: int(cfg[k])                                  # noqa: E731
    nk, nv = g_("linear_num_key_heads"), g_("linear_num_value_heads")
    dk, dv = g_("linear_key_head_dim"), g_("linear_value_head_dim")
    K = g_("linear_conv_kernel_dim")
    S = u.shape[0]
    kw, vw = nk * dk, nv * dv
    qkvz = _mm(u, lp["gdn/w_qkvz"], lowp)
    x, z = qkvz[:, :2 * kw + vw], qkvz[:, 2 * kw + vw:]
    ba = _mm(u, lp["gdn/w_ba"], lowp)
    beta = jax.nn.sigmoid(ba[:, :nv])
    g = -jnp.exp(lp["gdn/a_log"]) * jax.nn.softplus(
        ba[:, nv:] + lp["gdn/dt_bias"])
    w = lp["gdn/conv_w"]                        # [C, K], tap K - 1 current
    c = jax.nn.silu(sum(w[:, j] * shift(x, K - 1 - j) for j in range(K)))
    unit = lambda t: t * jax.lax.rsqrt(                          # noqa: E731
        jnp.sum(jnp.square(t), -1, keepdims=True) + 1e-6)
    q = unit(c[:, :kw].reshape(S, nk, dk)) / np.sqrt(dk)
    k = unit(c[:, kw:2 * kw].reshape(S, nk, dk))
    v = c[:, 2 * kw:].reshape(S, nv, dv)
    o = delta_rule(jnp.repeat(q, nv // nk, 1), jnp.repeat(k, nv // nk, 1),
                   v, g, beta)
    y = rms(o, lp["gdn/norm"], float(cfg["rms_norm_eps"]), centred=False) \
        * jax.nn.silu(z.reshape(S, nv, dv))
    return _mm(y.reshape(S, vw), lp["gdn/w_out"], lowp)


def full_attention(u, lp, cfg, lowp=None):
    """``u [S, H]`` (normed) -> ``[S, H]``."""
    nq, nk, d = int(cfg["num_attention_heads"]), \
        int(cfg["num_key_value_heads"]), int(cfg["head_dim"])
    eps = float(cfg["rms_norm_eps"])
    S = u.shape[0]
    qg = _mm(u, lp["attn/wq"], lowp).reshape(S, nq, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = _mm(u, lp["attn/wk"], lowp).reshape(S, nk, d)
    v = _mm(u, lp["attn/wv"], lowp).reshape(S, nk, d)
    q, k = rms(q, lp["attn/q_norm"], eps), rms(k, lp["attn/k_norm"], eps)
    pos = jnp.arange(S)
    rot = int(d * float(cfg["partial_rotary_factor"]))
    theta = float(cfg["rope_theta"])
    q, k = rotary(q, pos, theta, rot), rotary(k, pos, theta, rot)
    G = nq // nk
    sc = jnp.einsum("qjgd,kjd->jgqk", q.reshape(S, nk, G, d), k,
                    precision=HI) / np.sqrt(d)
    sc = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], sc,
                   -jnp.inf)
    o = jnp.einsum("jgqk,kjd->qjgd", jax.nn.softmax(sc, -1), v,
                   precision=HI).reshape(S, nq, d)
    return _mm((o * jax.nn.sigmoid(gate)).reshape(S, nq * d),
               lp["attn/wo"], lowp)


def route(u, lp, cfg, lowp=None):
    """-> (each token's ``k`` experts ``[S, k]``, their renormalised
    weights ``[S, k]``, the margin ``[S]`` by which the k-th router logit
    lies above the (k + 1)-th: how far the choice is from a tie)."""
    kk = int(cfg["num_experts_per_tok"])
    logits = _mm(u, lp["router/w"], lowp)
    p = jax.nn.softmax(logits, -1)
    top, choice = jax.lax.top_k(p, kk)
    best = jax.lax.top_k(logits, kk + 1)[0]
    return choice, top / jnp.sum(top, -1, keepdims=True), \
        best[:, kk - 1] - best[:, kk]


def experts(u, choice, weights, lp, cfg, lowp=None, held=None):
    """The routed sum as a loop (a ``lax.scan``, one expert a turn, in
    order) over the experts in ``held`` (default: all that ``lp`` holds,
    ids ``0 .. num_experts - 1``; an id indexes the stacked weights), each
    a dense product over every token, masked and weighted."""
    F = int(cfg["moe_intermediate_size"])
    ids = jnp.arange(int(cfg["num_experts"])) if held is None \
        else jnp.asarray(held)

    def one(y, e):
        gu = _mm(u, lp["experts/w_gate_up"][e], lowp)
        h = jax.nn.silu(gu[:, :F]) * gu[:, F:]
        w = jnp.sum(jnp.where(choice == e, weights, 0.0), -1)[:, None]
        return y + w * _mm(h, lp["experts/w_down"][e], lowp), None

    return jax.lax.scan(one, jnp.zeros_like(u), ids)[0]


def shared_expert(u, lp, cfg, lowp=None):
    Fs = int(cfg["shared_expert_intermediate_size"])
    gu = _mm(u, lp["shared/w_gate_up"], lowp)
    h = jax.nn.silu(gu[:, :Fs]) * gu[:, Fs:]
    gate = jax.nn.sigmoid(_mm(u, lp["shared/w_gate"][:, None], lowp))
    return gate * _mm(h, lp["shared/w_down"], lowp)


def layer(x, lp, cfg, full, lowp=None, choice=None, held=None,
          shared=True):
    """One layer over ``x [S, H]``. ``choice [S, k]`` (tests): the experts
    to use instead of the reference's own, with the reference's weights
    for them. Returns ``(x, the reference's own choice, its margin)``."""
    eps = float(cfg["rms_norm_eps"])
    u = rms(x, lp["attn_norm/scale"], eps)
    x = x + (full_attention if full else linear_attention)(u, lp, cfg, lowp)
    u = rms(x, lp["moe_norm/scale"], eps)
    own, weights, margin = route(u, lp, cfg, lowp)
    if choice is not None:
        p = jax.nn.softmax(_mm(u, lp["router/w"], lowp), -1)
        top = jnp.take_along_axis(p, choice, -1)
        weights = top / jnp.sum(top, -1, keepdims=True)
    used = own if choice is None else choice
    y = experts(u, used, weights, lp, cfg, lowp, held)
    if shared:
        y = y + shared_expert(u, lp, cfg, lowp)
    return x + y, own, margin


def _f32(lp):
    return jax.tree_util.tree_map(lambda t: jnp.asarray(t, jnp.float32), lp)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer_jit(x, lp, cfg_items, full, lowp, choice):
    return layer(x, _f32(lp), _thaw(cfg_items), full, _LOWP[lowp], choice)


def hidden_states(p, cfg, tokens, lowp=None, choices=None):
    """Final-norm output ``[S, H]`` for ``tokens [S]``, layer by layer (a
    layer's float32 weights are made from ``p`` one layer at a time, so
    the whole model is never held twice). Also the reference's own
    expert choices ``[L, S, k]`` and their margins ``[L, S]`` - with
    ``choices [L, S, k]`` (tests) the experts USED are those."""
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(p["wte/embedding"], jnp.float32)[tokens]
        own, margins = [], []
        frozen = _freeze(cfg)
        for i, lp in enumerate(p["layers"]):
            x, ch, mg = _layer_jit(
                x, lp, frozen, is_full(cfg, i), lowp,
                None if choices is None else jnp.asarray(choices[i]))
            own.append(ch)
            margins.append(mg)
        x = rms(x, jnp.asarray(p["norm_f/scale"], jnp.float32),
                float(cfg["rms_norm_eps"]))
    return x, jnp.stack(own), jnp.stack(margins)


@functools.partial(jax.jit, static_argnums=(3,))
def _best_and_at(hidden, head, ids, lowp):
    with jax.default_matmul_precision("highest"):
        lg = _mm(hidden, jnp.asarray(head, jnp.float32), _LOWP[lowp])
    loc = jnp.take_along_axis(lg, jnp.clip(ids, 0, lg.shape[1] - 1)[:, None],
                              1)[:, 0]
    inside = (ids >= 0) & (ids < lg.shape[1])
    return (jnp.max(lg, -1), jnp.argmax(lg, -1),
            jnp.where(inside, loc, -jnp.inf))


def head_readings(p, hidden, ids, lowp=None, block=32768):
    """Over the untied head in blocks of ``block`` vocabulary columns:
    the best logit, its token, and the logit of ``ids`` at every position
    - the ``[S, V]`` logits are never held whole."""
    head = p["head/kernel"]
    V = head.shape[1]
    best = jnp.full((hidden.shape[0],), -jnp.inf)
    arg = jnp.zeros((hidden.shape[0],), jnp.int32)
    at = jnp.full((hidden.shape[0],), -jnp.inf)
    for lo in range(0, V, block):
        b, a, t = _best_and_at(hidden, head[:, lo:lo + block], ids - lo,
                               lowp)
        arg = jnp.where(b > best, a.astype(jnp.int32) + lo, arg)
        best = jnp.maximum(best, b)
        at = jnp.maximum(at, t)
    return best, arg, at


def logits_of(p, hidden):
    """All the logits ``[S, V]`` (tests, small sizes)."""
    with jax.default_matmul_precision("highest"):
        return _mm(hidden, jnp.asarray(p["head/kernel"], jnp.float32), None)


# ---------------------------------------------------------------- serving

def served_token_gaps(p, cfg, prompt, output, lowp=None, pad_to=None,
                      reach=0):
    """``(gaps of the served tokens, gaps of the control's tokens, tie
    margins)``, numpy arrays of length ``len(output)``: output token j is
    predicted at position ``len(prompt) - 1 + j`` of prompt + output; its
    gap is how far its float32 logit lies below the reference's best
    there, its tie margin the least margin, over the layers, of the
    reference's k-th against its (k + 1)-th expert at that position and
    at the ``reach`` positions before it. With ``lowp`` the second array
    is the gap of the token the lower precision puts first."""
    n, m = len(prompt), len(output)
    seq = np.asarray(list(prompt) + list(output), np.int32)
    S = len(seq) if pad_to is None else max(pad_to, len(seq))
    S = -(-S // 128) * 128                  # few distinct shapes to compile
    pad = np.zeros((S,), np.int32)
    pad[:len(seq)] = seq
    tokens = jnp.asarray(pad)
    nxt = jnp.concatenate([tokens[1:], tokens[:1]])
    h, _, margins = hidden_states(p, cfg, tokens)
    best, _, at = head_readings(p, h, nxt)
    sl = slice(n - 1, n - 1 + m)
    served = np.asarray(best - at)[sl]
    least = np.asarray(jnp.min(margins, 0))        # over the layers
    near = least
    for k in range(1, reach + 1):        # and over positions t-k..t
        near = np.minimum(near, np.concatenate(
            [np.full((k,), np.inf, least.dtype), least[:-k]]))
    ties = near[sl]
    ctrl = np.zeros_like(served)
    if lowp is not None:
        hl, _, _ = hidden_states(p, cfg, tokens, lowp)
        _, pick, _ = head_readings(p, hl, nxt, lowp)
        _, _, at_pick = head_readings(p, h, pick)
        ctrl = np.asarray(best - at_pick)[sl]
    return served, ctrl, ties
