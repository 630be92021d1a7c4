"""The plain reference of Ling-3.0-flash's language model (inclusionAI), as
the benchmark's yardstick.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the forward pass over one
whole sequence, no kernel, no cache, no batching; the convolution as
explicit shifted sums, the Kimi delta rule as a plain ``lax.scan`` over
time, token by token, latent attention NOT absorbed (every head's key and
value expanded from the latent), the experts as a loop with masked dense
products. It imports nothing of ``apex_tpu`` and takes no array the
program has made: weights come from the seed here (``seeded_weights``)
and are handed TO the engine under the program's parameter paths
(``program_tree``). The rounding helpers and the rotary are
``reference_zaya``'s, the parameter paths and the untied head's readings
``reference_qwen3next``'s, by import.

So that a request of 32k positions fits, a sequence is taken in BLOCKS of
``BLOCK`` positions, layer by layer: a block hands the next the delta
rule's matrix and the convolution's last inputs, and reads the keys and
values of the blocks before it, a block of keys at a time - the same sums
in the same float32, only never a ``[S, S]`` array.

The sizes are the catalog row's ``config``; the equations are Kimi Linear's
(arXiv:2510.26692) for the linear layers, DeepSeek-V2's (arXiv:2405.04434)
for the latent ones and DeepSeek-V3's router. What the configuration does
not fix is listed under ``assumed`` in the configuration's file and marked
"assumed" here.

With ``rms(x; w) = x / sqrt(mean(x^2) + eps) * w``, a layer is ``h = x +
Mixer(rms(x))``, ``x' = h + MLP(rms(h))``; layer ``l`` is latent attention
where ``(l + 1) % layer_group_size == 0`` (assumed: the family's rule),
else Kimi delta attention; the first ``first_k_dense_replace`` layers
have a dense SwiGLU MLP, the rest the expert block; ``logits = rms(x_L)
W_head`` with ``W_head`` its own matrix (assumed untied).

*Kimi delta attention* - ``[q~ | k~ | v~] = u W_qkv`` (flat, assumed
layout), ``c = silu(conv(.))`` depthwise and causal over time, zeros
before position 0; ``nh`` heads of ``dk = dv = head_dim``; ``q, k``
L2-normalised a head (eps 1e-6), ``q / sqrt(dk)``; ``beta = sigmoid(u
W_b)`` a head; log-decay a head AND key channel ``g = kda_lower_bound
sigmoid(exp(A_log) (u W_f + dt_bias))`` (assumed form of the safe gate);
per head from ``S = 0``: ``S <- diag(exp(g_t)) S; r = S^T k_t; S <- S +
k_t (beta_t (v_t - r))^T; o_t = S^T q_t``; ``y = rms(o_t; w_n) sigmoid(u
W_g)[head]``; ``y W_o``.

*latent attention* - ``[q_n | q_r] = u W_q`` a head (``d_n | d_r``), ``q_n
<- rms(q_n; w_q)`` (assumed: ``use_qk_norm`` is this norm and the
latent's; a norm of ``k_n`` a head would stand between the latent and the
key and forbid the absorbed decode the deployment runs); ``[c~ | k~_r] =
u W_kva``, ``c = rms(c~; w_c)``; rotary (half-split pairs, absolute
position, theta ``rope_theta``) on all ``d_r`` of ``q_r`` and of ``k_r``,
one ``k_r`` for all heads; ``[k_n | v] = c W_kvb`` a head; causal softmax
of ``(q_n . k_n + q_r . k_r) / sqrt(d_n + d_r)``; ``o * sigmoid(u
W_g)[head]``; ``o W_o``.

*experts* - ``s = sigmoid(u W_r)`` over ALL the routed experts; selection
on ``s + b``: ``n_group`` contiguous groups, a group's score the sum of
its two best (assumed), the ``topk_group`` best groups kept, the ``k``
best experts among them; weights ``s`` of the chosen (no ``b``) over
their sum, times ``routed_scaling_factor``; ``y = sum_e w_e (silu(u Wg^e)
* (u Wu^e)) Wd^e`` over the experts HELD (the chip's share), ``+
(silu(u Wg^s) * (u Wu^s)) Wd^s``. No token is dropped, nothing clamped.

``lowp="fp8"`` is the control: both operands of every matrix product with
a weight rounded to float8_e4m3fn, the nearest precision below the
bfloat16 the configuration states.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .reference_qwen3next import (head_readings, logits_of,  # noqa: F401
                                  program_tree)
from .reference_zaya import _LOWP, HI, _freeze, _mm, _thaw, rotary, shift

BLOCK = 2048                # positions a step of the blocked forward takes
BALANCE_TOKENS = 2048       # seeded_weights: see there, and `assumed`


def routed_experts(cfg) -> int:
    """The router's width: the published count where the file's
    ``num_experts`` is the chip's share."""
    return int(cfg.get("published", {}).get("num_experts",
                                            cfg["num_experts"]))


def is_latent(cfg, layer: int) -> bool:
    return (layer + 1) % int(cfg["layer_group_size"]) == 0


def is_dense(cfg, layer: int) -> bool:
    return layer < int(cfg["first_k_dense_replace"])


def layer_shapes(cfg, layer: int):
    """Every parameter of layer ``layer`` under the program's path, and
    its shape. ``experts/*`` are stacked over the experts HELD
    (``num_experts``, ids ``0 .. num_experts - 1``)."""
    g = lambda k: int(cfg[k])                                   # noqa: E731
    H, nh = g("hidden_size"), g("num_attention_heads")
    if is_latent(cfg, layer):
        dn, dr, dv, r = g("qk_nope_head_dim"), g("qk_rope_head_dim"), \
            g("v_head_dim"), g("kv_lora_rank")
        mixer = {"mla/w_q": (H, nh * (dn + dr)), "mla/w_kva": (H, r + dr),
                 "mla/kv_norm": (r,), "mla/q_norm": (dn,),
                 "mla/w_kvb": (r, nh * (dn + dv)), "mla/w_g": (H, nh),
                 "mla/w_o": (nh * dv, H)}
    else:
        W = nh * g("head_dim")
        mixer = {"kda/w_qkv": (H, 3 * W), "kda/w_f": (H, W),
                 "kda/w_bg": (H, 2 * nh),
                 "kda/conv_w": (3 * W, g("short_conv_kernel_size")),
                 "kda/a_log": (nh,), "kda/dt_bias": (W,),
                 "kda/norm": (g("head_dim"),), "kda/w_o": (W, H)}
    if is_dense(cfg, layer):
        F = g("intermediate_size")
        mlp = {"mlp/w_gate_up": (H, 2 * F), "mlp/w_down": (F, H)}
    else:
        F, Fs = g("moe_intermediate_size"), \
            g("moe_shared_expert_intermediate_size")
        mlp = {"router/w": (H, routed_experts(cfg)),
               "router/bias": (routed_experts(cfg),),
               "experts/w_gate_up": (g("num_experts"), H, 2 * F),
               "experts/w_down": (g("num_experts"), F, H),
               "shared/w_gate_up": (H, 2 * Fs), "shared/w_down": (Fs, H)}
    return {"attn_norm/scale": (H,), **mixer, "mlp_norm/scale": (H,), **mlp}


# ---------------------------------------------------------------- weights

def _draw_spec(name, shape):
    """(centre, scale) of the normal a leaf is drawn from. Every learned
    scale is perturbed away from its initial value, so that a path that
    left one out would show."""
    leaf = name.split("/")[-1]
    if leaf in ("scale", "norm", "kv_norm", "q_norm"):      # plain gains
        return 1.0, 0.05
    if leaf == "conv_w":
        return None, None               # drawn column by column below
    if leaf == "a_log":                 # exp(A_log) about 0.7 .. 1.3
        return 0.0, 0.3
    if leaf == "dt_bias":               # sigmoid(. - 5) about 0.007
        return -5.0, 0.5
    if name == "router/bias":           # takes part in the choice only
        return 0.0, 0.02
    return 0.0, 1.0 / np.sqrt(shape[-2])     # a matrix: 1 / sqrt(fan_in)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _seeded_layer(cfg_items, layer, dtype, key):
    shapes = layer_shapes(_thaw(cfg_items), layer)
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
            "norm_f/scale": (1.0 + 0.05 * jax.random.normal(
                k1, (H,), jnp.float32)).astype(dtype),
            "head/kernel": (jax.random.normal(k2, (H, V), jnp.float32)
                            / np.sqrt(H)).astype(dtype)}


def seeded_weights(cfg, seed: int, dtype=jnp.bfloat16, balance_tokens=None):
    """The benchmark's own weights for the serving cell, made on the
    device layer by layer, in ``dtype``, the type they are served in; the
    reference reads the same values widened to float32.
    ``{"wte/embedding", "norm_f/scale", "head/kernel", "layers": [{leaf:
    array}]}``.

    Scales (assumed): the embedding normal(0.02); every matrix normal(1 /
    sqrt(fan_in)), the untied head and the router included (a sigmoid
    router's chosen scores saturate at random weights whatever the scale:
    the eight weights lie within some ten percent of each other, and what
    the mechanism decides is WHICH eight); the selection bias normal(0,
    0.02), wide enough to change the eight of most tokens; norm gains
    normal(1, 0.05); ``A_log`` normal(0, 0.3) and ``dt_bias`` normal(-5,
    0.5), so that with ``u W_f`` of order 1 a key channel decays by
    ``exp(g)`` of about 0.9 to 0.99 a token - a memory of tens of tokens,
    each channel its own; the convolution's current tap about 1 and the
    three before it about 0.3.

    The selection bias is then BALANCED, layer by layer, as training
    leaves it (DeepSeek-V3's balancing moves it until the experts' loads
    are even; it takes part in the choice only): one sequence of
    ``balance_tokens`` seeded tokens (default ``min(BALANCE_TOKENS, 16 x
    experts)``) goes through the layers in float32, and each expert
    layer's bias is set by :func:`balancing_bias` over it before the
    sequence goes on. At random weights and the bias as drawn one expert
    of 512 drew 3.6% of a window's rows and others one row (chip, PR 36:
    a coefficient of variation of 177%), which no deployment's router
    does, and the share of rows that falls to the experts HELD moved with
    the seed. 0 leaves the bias as drawn."""
    key = jax.random.PRNGKey(seed % (2**31 - 1))
    frozen = _freeze(cfg)
    L = int(cfg["num_hidden_layers"])
    V, H = int(cfg["vocab_size"]), int(cfg["hidden_size"])
    p = _seeded_top(V, H, dtype, jax.random.fold_in(key, L))
    p["layers"] = [_seeded_layer(frozen, i, dtype, jax.random.fold_in(key, i))
                   for i in range(L)]
    if balance_tokens is None:
        balance_tokens = min(BALANCE_TOKENS, 16 * routed_experts(cfg))
    if balance_tokens:
        tokens = jax.random.randint(jax.random.fold_in(key, L + 1),
                                    (balance_tokens,), 0, V)
        with jax.default_matmul_precision("highest"):
            x = jnp.asarray(p["wte/embedding"], jnp.float32)[tokens]
            for i, lp in enumerate(p["layers"]):
                kinds = (is_latent(cfg, i), is_dense(cfg, i))
                carry = _first_carry(cfg, i, balance_tokens)
                if kinds[1]:
                    x = _layer_jit(x, lp, frozen, *kinds, None, carry,
                                   None)[0]
                else:
                    x, bias = _balanced_layer_jit(x, lp, frozen, kinds[0],
                                                  carry)
                    lp["router/bias"] = bias.astype(dtype)
    return p


# ---------------------------------------------------------------- forward

def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def delta_rule(q, k, v, g, beta, S0=None):
    """The recurrence over one stretch, token by token: ``q, k, g [S, nh,
    dk]``, ``v [S, nh, dv]``, ``beta [S, nh]`` -> ``(o [S, nh, dv], the
    matrix the last token leaves)``, from ``S0`` (zeros)."""
    def step(S, x):
        qt, kt, vt, gt, bt = x
        S = jnp.exp(gt)[:, :, None] * S
        r = jnp.einsum("hkv,hk->hv", S, kt, precision=HI)
        S = S + kt[:, :, None] * (bt[:, None] * (vt - r))[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, qt, precision=HI)
    if S0 is None:
        S0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    S, o = jax.lax.scan(step, S0, (q, k, v, g, beta))
    return o, S


def kda(u, lp, cfg, carry=None, lowp=None):
    """``u [S, H]`` (normed) -> ``([S, H], carry)``. ``carry`` = (the
    matrix ``[nh, dk, dv]``, the convolution's last ``K - 1`` inputs) of
    the stretch before (None: the sequence starts here)."""
    g_ = lambda k: int(cfg[k])                                  # noqa: E731
    nh, d, K = g_("num_attention_heads"), g_("head_dim"), \
        g_("short_conv_kernel_size")
    S, W = u.shape[0], nh * d
    x = _mm(u, lp["kda/w_qkv"], lowp)
    S0, tail = carry if carry is not None else (
        None, jnp.zeros((K - 1, 3 * W), jnp.float32))
    xs = jnp.concatenate([tail, x], 0)
    w = lp["kda/conv_w"]                        # [C, K], tap K - 1 current
    c = jax.nn.silu(sum(w[:, j] * shift(xs, K - 1 - j)
                        for j in range(K))[K - 1:])
    unit = lambda t: t * jax.lax.rsqrt(                          # noqa: E731
        jnp.sum(jnp.square(t), -1, keepdims=True) + 1e-6)
    q = unit(c[:, :W].reshape(S, nh, d)) / np.sqrt(d)
    k = unit(c[:, W:2 * W].reshape(S, nh, d))
    v = c[:, 2 * W:].reshape(S, nh, d)
    bg = _mm(u, lp["kda/w_bg"], lowp)
    beta, gate = jax.nn.sigmoid(bg[:, :nh]), jax.nn.sigmoid(bg[:, nh:])
    f = _mm(u, lp["kda/w_f"], lowp).reshape(S, nh, d)
    g = float(cfg["kda_lower_bound"]) * jax.nn.sigmoid(
        jnp.exp(lp["kda/a_log"])[:, None]
        * (f + lp["kda/dt_bias"].reshape(nh, d)))
    o, ST = delta_rule(q, k, v, g, beta, S0)
    y = rms(o, lp["kda/norm"], float(cfg["rms_norm_eps"])) * gate[..., None]
    return _mm(y.reshape(S, W), lp["kda/w_o"], lowp), (ST, xs[S:])


def mla_heads(u, lp, cfg, pos, lowp=None):
    """``u [S, H]`` at absolute positions ``pos [S]`` -> every head's
    query ``[S, nh, d_n + d_r]``, key (the same width; the rotary part one
    for all heads), value ``[S, nh, d_v]`` and gate ``[S, nh]``."""
    g_ = lambda k: int(cfg[k])                                  # noqa: E731
    nh, dn, dr = g_("num_attention_heads"), g_("qk_nope_head_dim"), \
        g_("qk_rope_head_dim")
    dv, r = g_("v_head_dim"), g_("kv_lora_rank")
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    S = u.shape[0]
    q = _mm(u, lp["mla/w_q"], lowp).reshape(S, nh, dn + dr)
    q_n = rms(q[..., :dn], lp["mla/q_norm"], eps)
    q_r = rotary(q[..., dn:], pos, theta, dr)
    kva = _mm(u, lp["mla/w_kva"], lowp)
    c = rms(kva[:, :r], lp["mla/kv_norm"], eps)
    k_r = rotary(kva[:, None, r:], pos, theta, dr)            # [S, 1, dr]
    kv = _mm(c, lp["mla/w_kvb"], lowp).reshape(S, nh, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_r, (S, nh, dr))],
                        -1)
    return (jnp.concatenate([q_n, q_r], -1), k, kv[..., dn:],
            jax.nn.sigmoid(_mm(u, lp["mla/w_g"], lowp)))


def causal_attention(q, k_all, v_all, pos0, n_blocks):
    """Causal softmax attention of the queries ``q [Sb, nh, d]`` at
    positions ``pos0 ..`` over the keys and values ``k_all, v_all [cap,
    nh, .]`` at positions ``0 ..``, ``Sb`` keys at a time (the first
    ``n_blocks`` such blocks; later ones hold nothing yet): the running
    maximum, sum and weighted values of an exact softmax."""
    Sb, nh, d = q.shape
    rows = pos0 + jnp.arange(Sb)

    def one(j, carry):
        m, l, acc = carry
        kb = jax.lax.dynamic_slice_in_dim(k_all, j * Sb, Sb, 0)
        vb = jax.lax.dynamic_slice_in_dim(v_all, j * Sb, Sb, 0)
        s = jnp.einsum("qhd,khd->hqk", q, kb, precision=HI) / np.sqrt(d)
        cols = j * Sb + jnp.arange(Sb)
        s = jnp.where(cols[None, None, :] <= rows[None, :, None], s,
                      -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, -1))
        p = jnp.exp(s - m_new[..., None])
        a = jnp.exp(m - m_new)
        return (m_new, a * l + jnp.sum(p, -1),
                a[..., None] * acc + jnp.einsum("hqk,khd->hqd", p, vb,
                                                precision=HI))

    m0 = jnp.full((nh, Sb), -jnp.inf)
    # block 0 first: every row has its own position or an earlier one in
    # reach by then, so the running maximum is finite from there on
    m, l, acc = one(0, (m0, jnp.zeros((nh, Sb)),
                        jnp.zeros((nh, Sb, v_all.shape[-1]))))
    m, l, acc = jax.lax.fori_loop(1, n_blocks, one, (m, l, acc))
    return jnp.moveaxis(acc / l[..., None], 0, 1)            # [Sb, nh, dv]


def mla(u, lp, cfg, carry=None, lowp=None):
    """``u [S, H]`` (normed) -> ``([S, H], carry)``. ``carry`` = (keys,
    values ``[cap, nh, .]`` of the stretch before with room for this one,
    the position this stretch starts at) (None: the sequence is this
    stretch)."""
    S = u.shape[0]
    if carry is None:
        k_all = v_all = None
        pos0 = jnp.int32(0)
    else:
        k_all, v_all, pos0 = carry
    q, k, v, gate = mla_heads(u, lp, cfg, pos0 + jnp.arange(S), lowp)
    if k_all is None:
        k_all, v_all = k, v
    else:
        k_all = jax.lax.dynamic_update_slice_in_dim(k_all, k, pos0, 0)
        v_all = jax.lax.dynamic_update_slice_in_dim(v_all, v, pos0, 0)
    o = causal_attention(q, k_all, v_all, pos0, pos0 // S + 1)
    out = _mm((o * gate[..., None]).reshape(S, -1), lp["mla/w_o"], lowp)
    return out, (k_all, v_all, pos0 + S)


def select(sel, cfg, n):
    """The ``n`` best of the selection scores ``sel [S, E]`` among the
    kept groups: ``(scores [S, n], experts [S, n])``, best first."""
    E = routed_experts(cfg)
    ng, tg = int(cfg["n_group"]), int(cfg["topk_group"])
    S = sel.shape[0]
    group_score = jnp.sum(jax.lax.top_k(sel.reshape(S, ng, E // ng), 2)[0],
                          -1)
    kept = jax.lax.top_k(group_score, tg)[1]
    keep = jnp.any(kept[:, :, None] == jnp.arange(ng)[None, None, :], 1)
    return jax.lax.top_k(
        jnp.where(jnp.repeat(keep, E // ng, 1), sel, -jnp.inf), n)


def balancing_bias(s, b0, cfg, iters=400):
    """The selection bias as training leaves it: from ``b0``, lowered for
    the experts that the group-limited choice over ``s + b`` (``s [T, E]``
    the tokens' scores) loads above the mean and raised for those below,
    in ``iters`` steps of falling size, until the tokens spread evenly."""
    T, E = s.shape
    kk = int(cfg["num_experts_per_tok"])

    def step(i, b):
        choice = select(s + b, cfg, kk)[1]
        load = jnp.zeros((E,), jnp.float32).at[choice.reshape(-1)].add(
            E / (T * kk))                                   # mean 1
        return b - (0.02 * 0.99 ** i) * jnp.clip(load - 1.0, -1.0, 1.0)
    return jax.lax.fori_loop(0, iters, step, b0)


def route(u, lp, cfg, lowp=None):
    """-> (each token's ``k`` experts ``[S, k]``, their weights ``[S, k]``
    (normalised over the ``k``, times the scale), the margin ``[S]`` by
    which the k-th selection score lies above the best not chosen among
    the kept groups: how far the choice is from a tie)."""
    kk = int(cfg["num_experts_per_tok"])
    s = jax.nn.sigmoid(_mm(u, lp["router/w"], lowp))
    best, order = select(s + lp["router/bias"], cfg, kk + 1)
    choice = order[:, :kk]
    w = jnp.take_along_axis(s, choice, 1)
    return choice, w / jnp.sum(w, -1, keepdims=True) \
        * float(cfg["routed_scaling_factor"]), best[:, kk - 1] - best[:, kk]


def weights_of(u, choice, lp, cfg, lowp=None):
    """The reference's weights for experts chosen elsewhere (tests)."""
    s = jax.nn.sigmoid(_mm(u, lp["router/w"], lowp))
    w = jnp.take_along_axis(s, choice, 1)
    return w / jnp.sum(w, -1, keepdims=True) \
        * float(cfg["routed_scaling_factor"])


def swiglu(u, w_gate_up, w_down, lowp=None):
    F = w_down.shape[0]
    gu = _mm(u, w_gate_up, lowp)
    return _mm(jax.nn.silu(gu[:, :F]) * gu[:, F:], w_down, lowp)


def experts(u, choice, weights, lp, cfg, lowp=None, held=None):
    """The routed sum as a loop (a ``lax.scan``, one expert a turn, in
    order) over the experts in ``held`` (default: all that ``lp`` holds,
    ids ``0 .. num_experts - 1``; an id indexes the stacked weights), each
    a dense product over every token, masked and weighted. The stacked
    weights are widened to float32 an expert at a time."""
    ids = jnp.arange(int(cfg["num_experts"])) if held is None \
        else jnp.asarray(held)
    f32 = lambda t: jnp.asarray(t, jnp.float32)                 # noqa: E731

    def one(y, e):
        w = jnp.sum(jnp.where(choice == e, weights, 0.0), -1)[:, None]
        return y + w * swiglu(u, f32(lp["experts/w_gate_up"][e]),
                              f32(lp["experts/w_down"][e]), lowp), None

    return jax.lax.scan(one, jnp.zeros_like(u), ids)[0]


def layer(x, lp, cfg, index, carry=None, lowp=None, choice=None, held=None,
          shared=True):
    """Layer ``index`` over the stretch ``x [S, H]``: :func:`layer_of` of
    its kinds."""
    return layer_of(x, lp, cfg, is_latent(cfg, index), is_dense(cfg, index),
                    carry, lowp, choice, held, shared)


def _to_mlp(x, lp, cfg, latent, carry=None, lowp=None):
    """A layer up to its MLP: ``(x after the mixer, the MLP's normed
    input, carry)``."""
    eps = float(cfg["rms_norm_eps"])
    u = rms(x, lp["attn_norm/scale"], eps)
    out, carry = (mla if latent else kda)(u, lp, cfg, carry, lowp)
    x = x + out
    return x, rms(x, lp["mlp_norm/scale"], eps), carry


def layer_of(x, lp, cfg, latent, dense, carry=None, lowp=None, choice=None,
             held=None, shared=True):
    """One layer (``latent``: its mixer is latent attention, else Kimi
    delta attention; ``dense``: a dense MLP, else the expert block) over
    the stretch ``x [S, H]``. ``choice [S, k]`` (tests): the experts to
    use instead of the reference's own, with the reference's weights for
    them. Returns ``(x, carry, the reference's own choice, its margin)``
    (a dense layer: no choice, margin inf)."""
    x, u, carry = _to_mlp(x, lp, cfg, latent, carry, lowp)
    y, own, margin = _mlp(u, lp, cfg, dense, lowp, choice, held, shared)
    return x + y, carry, own, margin


def _mlp(u, lp, cfg, dense, lowp=None, choice=None, held=None, shared=True):
    """A layer's MLP over its normed input: ``(y, the reference's own
    choice, its margin)``."""
    if dense:
        kk = int(cfg["num_experts_per_tok"])
        return swiglu(u, lp["mlp/w_gate_up"], lp["mlp/w_down"], lowp), \
            jnp.zeros((u.shape[0], kk), jnp.int32), \
            jnp.full((u.shape[0],), jnp.inf)
    own, weights, margin = route(u, lp, cfg, lowp)
    if choice is not None:
        weights = weights_of(u, choice, lp, cfg, lowp)
    y = experts(u, own if choice is None else choice, weights, lp, cfg,
                lowp, held)
    if shared:
        y = y + swiglu(u, lp["shared/w_gate_up"], lp["shared/w_down"], lowp)
    return y, own, margin


def _f32(lp):
    """A layer's leaves widened to float32 - the stacked experts apart,
    which :func:`experts` widens one at a time."""
    return {k: v if k.startswith("experts/") else jnp.asarray(v, jnp.float32)
            for k, v in lp.items()}


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _layer_jit(x, lp, cfg_items, latent, dense, lowp, carry, choice):
    return layer_of(x, _f32(lp), _thaw(cfg_items), latent, dense, carry,
                    _LOWP[lowp], choice)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _balanced_layer_jit(x, lp, cfg_items, latent, carry):
    """An expert layer over ``x`` with its selection bias balanced on the
    way: ``(x out, the bias)``; the mixer runs once."""
    lp, cfg = _f32(lp), _thaw(cfg_items)
    x, u, _ = _to_mlp(x, lp, cfg, latent, carry)
    bias = balancing_bias(jax.nn.sigmoid(_mm(u, lp["router/w"], None)),
                          lp["router/bias"], cfg)
    return x + _mlp(u, dict(lp, **{"router/bias": bias}), cfg, False)[0], bias


def _first_carry(cfg, index, cap):
    g = lambda k: int(cfg[k])                                   # noqa: E731
    nh = g("num_attention_heads")
    if is_latent(cfg, index):
        d = g("qk_nope_head_dim") + g("qk_rope_head_dim")
        return (jnp.zeros((cap, nh, d), jnp.float32),
                jnp.zeros((cap, nh, g("v_head_dim")), jnp.float32),
                jnp.int32(0))
    d = g("head_dim")
    return (jnp.zeros((nh, d, d), jnp.float32),
            jnp.zeros((g("short_conv_kernel_size") - 1, 3 * nh * d),
                      jnp.float32))


def hidden_states(p, cfg, tokens, lowp=None, choices=None, block=BLOCK,
                  cap=None):
    """Final-norm output ``[S, H]`` for ``tokens [S]``, layer by layer (a
    layer's float32 weights are made from ``p`` one layer at a time) and,
    within a layer, ``block`` positions at a time (``S`` a multiple of it,
    or less than it). Also the reference's own expert choices ``[L, S,
    k]`` and their margins ``[L, S]`` (a dense layer: zeros, inf) - with
    ``choices [L, S, k]`` (tests) the experts USED are those. ``cap``: the
    positions the latent layers' keys are given room for (default ``S``;
    one value for every request of a run compiles once)."""
    S = int(tokens.shape[0])
    bs = min(block, S)
    if S % bs:
        raise ValueError(f"{S} positions are not whole blocks of {bs}")
    cap = S if cap is None else -(-max(cap, S) // bs) * bs
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(p["wte/embedding"], jnp.float32)[tokens]
        own, margins = [], []
        frozen = _freeze(cfg)
        for i, lp in enumerate(p["layers"]):
            carry = _first_carry(cfg, i, cap)
            xs, ch, mg = [], [], []
            for lo in range(0, S, bs):
                xb, carry, c, m = _layer_jit(
                    x[lo:lo + bs], lp, frozen, is_latent(cfg, i),
                    is_dense(cfg, i), lowp, carry,
                    None if choices is None
                    else jnp.asarray(choices[i][lo:lo + bs]))
                xs.append(xb)
                ch.append(c)
                mg.append(m)
            x = jnp.concatenate(xs)
            own.append(jnp.concatenate(ch))
            margins.append(jnp.concatenate(mg))
        x = rms(x, jnp.asarray(p["norm_f/scale"], jnp.float32),
                float(cfg["rms_norm_eps"]))
    return x, jnp.stack(own), jnp.stack(margins)


# ---------------------------------------------------------------- serving

def served_token_gaps(p, cfg, prompt, output, lowp=None, pad_to=None,
                      reach=0, block=BLOCK):
    """``(gaps of the served tokens, gaps of the control's tokens, tie
    margins)``, numpy arrays of length ``len(output)``: output token j is
    predicted at position ``len(prompt) - 1 + j`` of prompt + output; its
    gap is how far its float32 logit lies below the reference's best
    there, its tie margin the least margin, over the layers, of the
    reference's k-th expert against the best not chosen at that position
    and at the ``reach`` positions before it. With ``lowp`` the second
    array is the gap of the token the lower precision puts first.

    The sequence is padded to whole blocks of ``block`` (to a multiple of
    128 where it is shorter than one) and ``pad_to`` is the room the
    latent layers' keys are given, so that every request of a run meets
    the same compiled shapes; the head is read at the served positions
    only."""
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
    h, _, margins = hidden_states(p, cfg, tokens, block=block, cap=pad_to)
    h = h[at_rows]
    best, _, at = head_readings(p, h, nxt)
    served = np.asarray(best - at)[:m]
    least = np.asarray(jnp.min(margins, 0))        # over the layers
    near = least
    for k in range(1, reach + 1):        # and over positions t-k..t
        near = np.minimum(near, np.concatenate(
            [np.full((k,), np.inf, least.dtype), least[:-k]]))
    ties = near[at_rows][:m]
    ctrl = np.zeros_like(served)
    if lowp is not None:
        hl, _, _ = hidden_states(p, cfg, tokens, lowp, block=block,
                                 cap=pad_to)
        _, pick, _ = head_readings(p, hl[at_rows], nxt, lowp)
        _, _, at_pick = head_readings(p, h, pick)
        ctrl = np.asarray(best - at_pick)[:m]
    return served, ctrl, ties
