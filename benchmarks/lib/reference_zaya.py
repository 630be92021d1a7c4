"""The plain reference of ZAYA1 (Zyphra), as the benchmark's yardstick.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the forward pass over one
whole sequence, no kernel, no cache, no batching; the convolutions as
explicit shifted sums, the experts as a loop over all of them with masked
dense products. It imports nothing of ``apex_tpu`` and takes no array the
program has made: weights come from the seed here (``seeded_weights``)
and are handed TO the engine under the program's parameter paths
(``program_tree``: names are the interface to the system under test).

The sizes are the published ``config.json``'s. The structure follows
"Compressed Convolutional Attention" (arXiv:2510.04476, the CCGQA form)
and the ZAYA1 report (arXiv:2511.17127), set down without the network;
what neither fixes is listed under ``assumed`` in the configuration's file
and marked "assumed" here: each is a possible departure from Zyphra's
checkpoint, which this reference has never read.

Per layer, with ``u = rmsnorm(x)``, ``d`` the head size, ``G`` query heads
per key/value head:

*attention* - ``q~ = u Wq``, ``k~ = u Wk``; ``z = [q~ ; k~]`` as heads of
``d``; a depthwise causal convolution over time of kernel ``cca_time0``
then one of kernel ``cca_time1`` grouped by head (zeros before position
0, a bias on each: assumed); the q-k mean of the values BEFORE the
convolutions added back (``m_q[h] = (q~[h] + k~[h // G]) / 2``, ``m_k[j]``
the mean of its group's ``m_q``); per head L2 normalisation to
``sqrt(d)``, keys times ``beta_j = exp(tau_j)`` (assumed form); rotary on
the first ``partial_rotary_factor`` of each head (half-split pairs), by
absolute position; values ``[u_t Wv1 ; u_{t-1} Wv2]`` (K/V head 0 the
current token's, head 1 the previous token's); causal softmax attention
at ``1/sqrt(d)``, query head ``h`` reading K/V head ``h // G``; ``o Wo``.

*experts* - router state ``r = u Wd + bd + gamma * r_prev`` (the previous
layer's state, zero before the first layer held; ``gamma`` per channel:
assumed); ``logits = gelu(gelu(r W1 + b1) W2 + b2) W3`` (three layers,
exact GELU: assumed); ``p = softmax(logits)``; ``e = argmax(p + c)`` with
``c`` the balancing bias (takes part in the choice only);
``y = p_e * (silu(u Wg^e) * (u Wu^e)) Wd^e``. No token is dropped.

*residual* - each sublayer ``f``: ``x <- (x + b_r) * s_r + (f(rmsnorm(x))
+ b_h) * s_h`` (form and order: assumed).

``lowp="fp8"`` is the control: both operands of every matrix product
rounded to float8_e4m3fn, the nearest precision below the bfloat16 the
configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
ROUTER_OUT_SCALE = 4.0      # seeded_weights: see there, and `assumed`


def sizes(cfg):
    """(hidden, layers, q heads, kv heads, head size, experts, expert
    width, router width, vocabulary)."""
    return (int(cfg["hidden_size"]), int(cfg["num_hidden_layers"]),
            int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
            int(cfg["head_dim"]), int(cfg["num_experts"]),
            int(cfg["moe_intermediate_size"]), int(cfg["router_hidden_size"]),
            int(cfg["vocab_size"]))


def layer_shapes(cfg):
    """Every per-layer parameter under the program's path, and its shape."""
    H, _, nq, nk, d, E, F, R, _ = sizes(cfg)
    t0, t1 = int(cfg["cca_time0"]), int(cfg["cca_time1"])
    nz = nq + nk
    res = {f"{s}/{n}": (H,) for s in ("attn_res", "moe_res")
           for n in ("s_r", "b_r", "s_h", "b_h")}
    return {
        "attn_norm/scale": (H,),
        "attn/wq": (H, nq * d), "attn/wk": (H, nk * d),
        "attn/wv1": (H, d), "attn/wv2": (H, d), "attn/wo": (nq * d, H),
        "attn/conv0_w": (nz * d, t0), "attn/conv0_b": (nz * d,),
        "attn/conv1_w": (nz, t1, d, d), "attn/conv1_b": (nz, d),
        "attn/tau": (nk,),
        "moe_norm/scale": (H,),
        "router/wd": (H, R), "router/bd": (R,), "router/gamma": (R,),
        "router/w1": (R, R), "router/b1": (R,),
        "router/w2": (R, R), "router/b2": (R,),
        "router/w3": (R, E), "router/bias_c": (E,),
        "experts/w_gate_up": (E, H, 2 * F), "experts/w_down": (E, F, H),
        **res,
    }


LAYER_LEAVES = tuple(layer_shapes({
    "hidden_size": 8, "num_hidden_layers": 1, "num_attention_heads": 2,
    "num_key_value_heads": 1, "head_dim": 4, "num_experts": 2,
    "moe_intermediate_size": 8, "router_hidden_size": 4, "vocab_size": 8,
    "cca_time0": 2, "cca_time1": 2}))
TOP_LEAVES = ("wte/embedding", "norm_f/scale")


# ---------------------------------------------------------------- weights

def _draw_spec(name, shape):
    """(centre, scale) of the normal a leaf is drawn from. Every learned
    scale and bias is perturbed away from 1 and 0, so that a path that
    left one out would show."""
    leaf = name.split("/")[-1]
    if leaf in ("scale", "s_r", "s_h"):
        return 1.0, 0.05
    if leaf in ("b_r", "b_h", "bd", "b1", "b2", "conv0_b", "conv1_b"):
        return 0.0, 0.02
    if leaf == "conv0_w":               # a[c, 0] z_{t-1} + a[c, 1] z_t
        return None, None               # drawn column by column below
    if leaf == "conv1_w":               # two d x d maps per head
        return 0.0, 1.0 / np.sqrt(shape[1] * shape[2])
    if leaf == "tau":
        return 0.0, 0.1
    if leaf == "gamma":
        return 0.5, 0.1
    if leaf == "bias_c":
        return 0.0, 0.1
    if leaf == "w3":
        return 0.0, ROUTER_OUT_SCALE / np.sqrt(shape[-2])
    return 0.0, 1.0 / np.sqrt(shape[-2])     # a matrix: 1 / sqrt(fan_in)


def _freeze(cfg):
    """A configuration as a hashable static argument (numbers, strings and
    nested groups; lists are left out)."""
    def fz(v):
        return tuple(sorted((k, fz(x)) for k, x in v.items())) \
            if isinstance(v, dict) else v
    return tuple(sorted((k, fz(v)) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, dict))))


def _thaw(items):
    return {k: (_thaw(v) if isinstance(v, tuple) else v) for k, v in items}


@functools.partial(jax.jit, static_argnums=(0, 1))
def _seeded_layer(cfg_items, dtype, key):
    shapes = layer_shapes(_thaw(cfg_items))
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
    k0, k1 = jax.random.split(key)
    return {"wte/embedding": (0.02 * jax.random.normal(
                k0, (V, H), jnp.float32)).astype(dtype),
            "norm_f/scale": (1.0 + 0.05 * jax.random.normal(
                k1, (H,), jnp.float32)).astype(dtype)}


BALANCE_TOKENS = 2048


def seeded_weights(cfg, seed: int, dtype=jnp.bfloat16,
                   balance_tokens: int = BALANCE_TOKENS):
    """The benchmark's own weights for the serving cell, made on the
    device layer by layer (a layer's float32 draw is 0.8 GB at the
    published widths; the whole model's would not fit), in ``dtype``, the
    type they are served in; the reference reads the same values widened
    to float32. ``{"wte/embedding", "norm_f/scale", "layers": [{leaf:
    array}]}``.

    Scales: the embedding normal(0.02); every matrix normal(1 /
    sqrt(fan_in)), so that each sublayer writes about as much into the
    residual stream as it reads (``reference_lm.seeded_weights`` says why
    a served token must be one that rounding can change). The router's
    output layer is drawn ``ROUTER_OUT_SCALE`` = 4 times wider: at 1 /
    sqrt(fan_in) the 16 probabilities lie within a few hundredths of 1/16
    of each other, every token sits on a tie and ``p_e`` scales every
    expert's output by a sixteenth; at 4 the best expert has about half
    the mass. Norm gains, residual scales and biases, convolution taps and
    biases, ``tau`` and ``gamma`` are all drawn away from 1 and 0.

    The balancing bias is drawn normal(0.1) and then BALANCED, layer by
    layer, as training leaves it (the report's bias takes part in the
    choice only and is moved until the experts' loads are even): one
    sequence of ``balance_tokens`` seeded tokens goes through the layers
    in float32, and each layer's bias is set by :func:`balancing_bias`
    over it before the sequence goes on. At random weights and a bias
    near 0 one expert of a layer drew half the served tokens and others
    none (chip, PR 30), which no deployment's router does. 0 leaves the
    bias as drawn."""
    key = jax.random.PRNGKey(seed % (2**31 - 1))
    frozen = _freeze(cfg)
    L = int(cfg["num_hidden_layers"])
    V, H = int(cfg["vocab_size"]), int(cfg["hidden_size"])
    p = _seeded_top(V, H, dtype, jax.random.fold_in(key, L))
    p["layers"] = [_seeded_layer(frozen, dtype, jax.random.fold_in(key, i))
                   for i in range(L)]
    if balance_tokens:
        tokens = jax.random.randint(jax.random.fold_in(key, L + 1),
                                    (balance_tokens,), 0, V)
        with jax.default_matmul_precision("highest"):
            x = jnp.asarray(p["wte/embedding"], jnp.float32)[tokens]
            r = jnp.zeros((balance_tokens, int(cfg["router_hidden_size"])),
                          jnp.float32)
            for lp in p["layers"]:
                lp["router/bias_c"] = _balanced_bias_jit(
                    x, r, lp, frozen).astype(dtype)
                x, r, _, _ = _layer_jit(x, r, lp, frozen, None, None)
    return p


def program_tree(p):
    """The same arrays under the parameter paths the program's ``ZayaLM``
    uses: ``layer_<i>/<module>/<leaf>``. Nothing is copied."""
    tree = {"wte": {"embedding": p["wte/embedding"]},
            "norm_f": {"scale": p["norm_f/scale"]}}
    for i, lp in enumerate(p["layers"]):
        blk = {}
        for n, v in lp.items():
            mod, leaf = n.split("/")
            blk.setdefault(mod, {})[leaf] = v
        tree[f"layer_{i}"] = blk
    return tree


# ---------------------------------------------------------------- forward

def _fp8(x, axis):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _bf16(x, axis):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


_LOWP = {None: None, "fp8": _fp8, "bf16": _bf16}


def _mm(x, w, lowp):
    """``x [..., K] @ w [K, N]``."""
    if lowp is not None:
        x, w = lowp(x, -1), lowp(w, 0)
    return jnp.einsum("...k,kn->...n", x, w, precision=HI)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def shift(x, n=1):
    """``x_{t-n}`` along the leading (time) axis, zeros before time 0."""
    return jnp.concatenate([jnp.zeros_like(x[:n]), x[:-n]], 0) if n else x


def causal_convs(z, w0, b0, w1, b1):
    """``z [S, heads, d]`` through the depthwise convolution (``w0 [heads
    * d, t0]``, tap ``t0 - 1`` on the current position) and the one
    grouped by head (``w1 [heads, t1, d, d]``), as shifted sums."""
    S, nz, d = z.shape
    t0, t1 = w0.shape[-1], w1.shape[1]
    a = w0.reshape(nz, d, t0)
    c1 = b0.reshape(nz, d) + sum(a[..., j] * shift(z, t0 - 1 - j)
                                 for j in range(t0))
    c2 = b1 + sum(jnp.einsum("shd,hde->she", shift(c1, t1 - 1 - j), w1[:, j],
                             precision=HI) for j in range(t1))
    return c1, c2


def qk_mean(qt, kt):
    """``qt [S, nq, d]``, ``kt [S, nk, d]`` -> (m_q, m_k)."""
    nq, nk = qt.shape[1], kt.shape[1]
    m_q = 0.5 * (qt + jnp.repeat(kt, nq // nk, axis=1))
    m_k = m_q.reshape(qt.shape[0], nk, nq // nk, -1).mean(2)
    return m_q, m_k


def rotary(x, pos, theta, rot):
    """Half-split rotary on the first ``rot`` of the last axis of ``x [S,
    heads, d]`` at absolute positions ``pos [S]``."""
    half = rot // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rot)
    ang = pos.astype(jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], -1)


def attention_sublayer(u, lp, cfg, lowp=None):
    """``u [S, H]`` (normed) -> ``[S, H]``."""
    _, _, nq, nk, d, _, _, _, _ = sizes(cfg)
    S = u.shape[0]
    qt = _mm(u, lp["attn/wq"], lowp).reshape(S, nq, d)
    kt = _mm(u, lp["attn/wk"], lowp).reshape(S, nk, d)
    _, c2 = causal_convs(jnp.concatenate([qt, kt], 1), lp["attn/conv0_w"],
                         lp["attn/conv0_b"], lp["attn/conv1_w"],
                         lp["attn/conv1_b"])
    m_q, m_k = qk_mean(qt, kt)
    q, k = c2[:, :nq] + m_q, c2[:, nq:] + m_k
    norm = lambda t: t * (np.sqrt(d) * jax.lax.rsqrt(            # noqa: E731
        jnp.sum(jnp.square(t), -1, keepdims=True)))
    q, k = norm(q), norm(k) * jnp.exp(lp["attn/tau"])[None, :, None]
    pos = jnp.arange(S)
    theta = float(cfg["rope_parameters"]["hybrid"]["rope_theta"])
    rot = int(d * float(cfg["partial_rotary_factor"]))
    q, k = rotary(q, pos, theta, rot), rotary(k, pos, theta, rot)
    v = jnp.stack([_mm(u, lp["attn/wv1"], lowp),
                   shift(_mm(u, lp["attn/wv2"], lowp))], 1)   # [S, 2, d]
    if nk != 2:
        raise ValueError("the value shift is written for two K/V heads")
    G = nq // nk
    sc = jnp.einsum("qjgd,kjd->jgqk", q.reshape(S, nk, G, d), k,
                    precision=HI) / np.sqrt(d)
    sc = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], sc,
                   -jnp.inf)
    o = jnp.einsum("jgqk,kjd->qjgd", jax.nn.softmax(sc, -1), v,
                   precision=HI)
    return _mm(o.reshape(S, nq * d), lp["attn/wo"], lowp)


def router(u, r_prev, lp, lowp=None):
    """-> (router state ``r [S, R]``, probabilities ``p [S, E]``)."""
    r = _mm(u, lp["router/wd"], lowp) + lp["router/bd"] \
        + lp["router/gamma"] * r_prev
    h = jax.nn.gelu(_mm(r, lp["router/w1"], lowp) + lp["router/b1"],
                    approximate=False)
    h = jax.nn.gelu(_mm(h, lp["router/w2"], lowp) + lp["router/b2"],
                    approximate=False)
    return r, jax.nn.softmax(_mm(h, lp["router/w3"], lowp), -1)


def choose(p, bias_c):
    """The expert of each token and the float32 margin of the choice:
    how far the best ``p + c`` lies above the next."""
    top = jax.lax.top_k(p + bias_c, 2)[0]
    return jnp.argmax(p + bias_c, -1), top[:, 0] - top[:, 1]


def experts(u, p, choice, lp, cfg, lowp=None, held=None):
    """``p_e * (silu(u Wg^e) * (u Wu^e)) Wd^e`` as a loop over the experts
    (all of them, or those in ``held``: the part of the result that a
    chip holding them gives), each a dense product over every token,
    masked."""
    E, F = int(cfg["num_experts"]), int(cfg["moe_intermediate_size"])
    y = jnp.zeros_like(u)
    for e in (range(E) if held is None else held):
        gu = _mm(u, lp["experts/w_gate_up"][e], lowp)
        h = jax.nn.silu(gu[:, :F]) * gu[:, F:]
        w = jnp.where(choice == e, p[:, e], 0.0)[:, None]
        y = y + w * _mm(h, lp["experts/w_down"][e], lowp)
    return y


def _residual(x, fx, lp, which):
    g = lambda n: lp[f"{which}/{n}"]                              # noqa: E731
    return (x + g("b_r")) * g("s_r") + (fx + g("b_h")) * g("s_h")


def balancing_bias(p, c0, iters=300):
    """The balancing bias as training leaves it: from ``c0``, lowered for
    the experts that ``argmax(p + c)`` loads above ``1 / E`` of the
    tokens ``p [T, E]`` and raised for those below, in ``iters`` steps
    of falling size, until the tokens spread evenly."""
    E = p.shape[1]

    def step(i, c):
        load = jnp.mean(jax.nn.one_hot(jnp.argmax(p + c, -1), E,
                                       dtype=jnp.float32), 0)
        return c - (0.5 * 0.985 ** i) * (load - 1.0 / E)
    return jax.lax.fori_loop(0, iters, step, c0)


def _to_router(x, r_prev, lp, cfg, lowp=None):
    """A layer up to its router: ``(x after the attention sublayer, the
    normed input of the expert sublayer, router state, probabilities)``."""
    eps = float(cfg["rms_norm_eps"])
    u = rmsnorm(x, lp["attn_norm/scale"], eps)
    x = _residual(x, attention_sublayer(u, lp, cfg, lowp), lp, "attn_res")
    u = rmsnorm(x, lp["moe_norm/scale"], eps)
    r, p = router(u, r_prev, lp, lowp)
    return x, u, r, p


def layer(x, r_prev, lp, cfg, lowp=None, choice=None):
    """One layer over ``x [S, H]``. ``choice [S]`` (tests): the experts to
    use instead of the reference's own. Returns ``(x, r, the reference's
    own choice, its margin)``; the experts used are ``choice`` where
    given."""
    x, u, r, p = _to_router(x, r_prev, lp, cfg, lowp)
    own, margin = choose(p, lp["router/bias_c"])
    used = own if choice is None else choice
    x = _residual(x, experts(u, p, used, lp, cfg, lowp), lp, "moe_res")
    return x, r, own, margin


def _f32(lp):
    return jax.tree_util.tree_map(lambda t: jnp.asarray(t, jnp.float32), lp)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer_jit(x, r, lp, cfg_items, lowp, choice):
    return layer(x, r, _f32(lp), _thaw(cfg_items), _LOWP[lowp], choice)


@functools.partial(jax.jit, static_argnums=(3,))
def _balanced_bias_jit(x, r, lp, cfg_items):
    lp = _f32(lp)
    p = _to_router(x, r, lp, _thaw(cfg_items))[3]
    return balancing_bias(p, lp["router/bias_c"])


def hidden_states(p, cfg, tokens, lowp=None, choices=None):
    """Final-norm output ``[S, H]`` for ``tokens [S]``, layer by layer (a
    layer's float32 weights are made from ``p`` one layer at a time, so
    the whole model is never held twice). Also the reference's own
    expert choices ``[L, S]`` and their margins ``[L, S]`` - with
    ``choices [L, S]`` (tests) the experts USED are those, and the
    reference's own are what it would have picked at each layer given
    them upstream."""
    H, L = int(cfg["hidden_size"]), int(cfg["num_hidden_layers"])
    R = int(cfg["router_hidden_size"])
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(p["wte/embedding"], jnp.float32)[tokens]
        r = jnp.zeros((tokens.shape[0], R), jnp.float32)
        own, margins = [], []
        frozen = _freeze(cfg)
        for i in range(L):
            x, r, ch, mg = _layer_jit(
                x, r, p["layers"][i], frozen, lowp,
                None if choices is None else jnp.asarray(choices[i]))
            own.append(ch)
            margins.append(mg)
        x = rmsnorm(x, jnp.asarray(p["norm_f/scale"], jnp.float32),
                    float(cfg["rms_norm_eps"]))
    return x, jnp.stack(own), jnp.stack(margins)


@functools.partial(jax.jit, static_argnums=(3,))
def _best_and_at(hidden, emb, ids, lowp):
    with jax.default_matmul_precision("highest"):
        lg = _mm(hidden, jnp.asarray(emb, jnp.float32).T, _LOWP[lowp])
    loc = jnp.take_along_axis(lg, jnp.clip(ids, 0, lg.shape[1] - 1)[:, None],
                              1)[:, 0]
    inside = (ids >= 0) & (ids < lg.shape[1])
    return (jnp.max(lg, -1), jnp.argmax(lg, -1),
            jnp.where(inside, loc, -jnp.inf))


def head_readings(p, hidden, ids, lowp=None, block=32768):
    """Over the tied head in blocks of ``block`` vocabulary rows: the
    best logit, its token, and the logit of ``ids`` at every position -
    the ``[S, V]`` logits are never held whole."""
    emb = p["wte/embedding"]
    V = emb.shape[0]
    best = jnp.full((hidden.shape[0],), -jnp.inf)
    arg = jnp.zeros((hidden.shape[0],), jnp.int32)
    at = jnp.full((hidden.shape[0],), -jnp.inf)
    for lo in range(0, V, block):
        b, a, t = _best_and_at(hidden, emb[lo:lo + block], ids - lo, lowp)
        arg = jnp.where(b > best, a.astype(jnp.int32) + lo, arg)
        best = jnp.maximum(best, b)
        at = jnp.maximum(at, t)
    return best, arg, at


def logits_of(p, hidden):
    """All the logits ``[S, V]`` (tests, small sizes)."""
    with jax.default_matmul_precision("highest"):
        return _mm(hidden, jnp.asarray(p["wte/embedding"], jnp.float32).T,
                   None)


# ---------------------------------------------------------------- serving

def served_token_gaps(p, cfg, prompt, output, lowp=None, pad_to=None,
                      reach=0):
    """``(gaps of the served tokens, gaps of the control's tokens, tie
    margins)``, numpy arrays of length ``len(output)``: output token j is
    predicted at position ``len(prompt) - 1 + j`` of prompt + output; its
    gap is how far its float32 logit lies below the reference's best
    there, its tie margin the least margin, over the layers, of the
    reference's expert choice at that position and at the ``reach``
    positions before it (a token hands its convolution inputs and its
    shifted values to its successors, so a flipped expert there shows
    here undiluted). With ``lowp`` the second array is the gap of the
    token the lower precision puts first."""
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
