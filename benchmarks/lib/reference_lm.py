"""The plain reference of the GPT-2 family, as the benchmark's yardstick.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``: pre-LN blocks, learned positions, tied head, tanh
GELU (OpenAI's GPT-2), mean cross-entropy, AdamW with decoupled decay.
No kernel, no cache, no batching tricks. It imports nothing of
``apex_tpu`` or ``examples`` and takes no array the program has made:
weights come from the seed here (``recipe_init`` follows the recipe's
initialisers key by key; ``seeded_weights`` is the benchmark's own
generator for the serving cells, whose output is handed TO the engine).

Layout: blocks stacked on a leading layer axis and scanned, so a
36-layer model compiles as fast as a 1-layer one. A "leaf" in the
comparisons is one layer's slice of one stacked array, named like the
program's parameter path (``block_3/attn/qkv/kernel``) - names only.

``lowp="fp8"`` is the control: both operands of every GEMM rounded to
float8_e4m3fn (per-row scale on activations, per-output-channel scale
on weights, straight-through gradient) - the nearest precision below
the bfloat16 the configurations state.
"""

from __future__ import annotations

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
BLOCK_LEAVES = ("ln_attn/scale", "ln_attn/bias", "attn/qkv/kernel",
                "attn/qkv/bias", "attn/proj/kernel", "attn/proj/bias",
                "ln_mlp/scale", "ln_mlp/bias", "mlp_in/kernel",
                "mlp_in/bias", "mlp_out/kernel", "mlp_out/bias")
TOP_LEAVES = ("wte/embedding", "wpe", "ln_f/scale", "ln_f/bias")


def sizes(cfg):
    return (int(cfg["n_embd"]), int(cfg["n_layer"]), int(cfg["n_head"]),
            int(cfg["n_positions"]), int(cfg["vocab_size"]))


def block_shapes(cfg):
    H = int(cfg["n_embd"])
    return {"ln_attn/scale": (H,), "ln_attn/bias": (H,),
            "attn/qkv/kernel": (H, 3 * H), "attn/qkv/bias": (3 * H,),
            "attn/proj/kernel": (H, H), "attn/proj/bias": (H,),
            "ln_mlp/scale": (H,), "ln_mlp/bias": (H,),
            "mlp_in/kernel": (H, 4 * H), "mlp_in/bias": (4 * H,),
            "mlp_out/kernel": (4 * H, H), "mlp_out/bias": (H,)}


# ---------------------------------------------------------------- weights

def _fold_path(key, path):
    """flax.linen's key for a parameter: the root 'params' key with the
    SHA-1 of (scope names..., counter) folded in (flax.core.scope
    ._fold_in_static, separator fix off as this installation has it)."""
    m = hashlib.sha1()
    for x in path:
        m.update(x.encode() if isinstance(x, str)
                 else x.to_bytes((x.bit_length() + 7) // 8, "big"))
    return jax.random.fold_in(
        key, jnp.uint32(int.from_bytes(m.digest()[:4], "big")))


def recipe_init(cfg, seed: int):
    """The float32 weights ``examples/lm/main_amp.py --seed`` starts from,
    made here from the seed alone: flax's default initialisers (Dense:
    LeCun-normal kernel, zero bias; Embed: fan-in normal over the feature
    axis; positions normal(0.02); layer norms one/zero) under flax's
    per-parameter keys. Checked against the program at a small size in
    ``benchmarks/checks/test_reference.py``."""
    H, L, _, S, V = sizes(cfg)
    root = jax.random.PRNGKey(seed)
    lecun = jax.nn.initializers.lecun_normal()
    embed = jax.nn.initializers.variance_scaling(1.0, "fan_in", "normal",
                                                 out_axis=0)
    p = {"wte/embedding": embed(_fold_path(root, ("wte", 1)), (V, H),
                                jnp.float32),
         "wpe": jax.nn.initializers.normal(0.02)(_fold_path(root, (1,)),
                                                 (S, H), jnp.float32),
         "ln_f/scale": jnp.ones((H,)), "ln_f/bias": jnp.zeros((H,))}
    shapes = block_shapes(cfg)
    blocks = {n: [] for n in BLOCK_LEAVES}
    for i in range(L):
        for n in BLOCK_LEAVES:
            if n.endswith("kernel"):
                scope = (f"block_{i}",) + tuple(n.split("/")[:-1]) + (1,)
                blocks[n].append(lecun(_fold_path(root, scope), shapes[n],
                                       jnp.float32))
            elif n.endswith("scale"):
                blocks[n].append(jnp.ones(shapes[n]))
            else:
                blocks[n].append(jnp.zeros(shapes[n]))
    p["blocks"] = {n: jnp.stack(v) for n, v in blocks.items()}
    return p


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _seeded(H, L, S, V, dtype, key):
    std = 0.02
    shapes = block_shapes({"n_embd": H})
    ks = jax.random.split(key, 4 + len(BLOCK_LEAVES))

    def draw(k, shape, scale, centre=0.0):
        x = centre + scale * jax.random.normal(k, shape, jnp.float32)
        return x.astype(dtype)

    p = {"wte/embedding": draw(ks[0], (V, H), std),
         "wpe": draw(ks[1], (S, H), std),
         "ln_f/scale": draw(ks[2], (H,), 0.05, 1.0),
         "ln_f/bias": draw(ks[3], (H,), std)}
    blocks = {}
    for j, n in enumerate(BLOCK_LEAVES):
        shape = (L,) + shapes[n]
        if n.endswith("scale"):
            blocks[n] = draw(ks[4 + j], shape, 0.05, 1.0)
        elif n.endswith("kernel"):
            blocks[n] = draw(ks[4 + j], shape, 1.0 / np.sqrt(shape[-2]))
        else:
            blocks[n] = draw(ks[4 + j], shape, std)
    p["blocks"] = blocks
    return p


def seeded_weights(cfg, seed: int, dtype=jnp.bfloat16):
    """The benchmark's own weights for a serving cell, on the device in
    one jitted call, in ``dtype``, the type they are served in; the
    reference reads the same values widened to float32.

    Scales: embeddings and positions normal(0.02) as GPT-2's; every block
    kernel normal(1 / sqrt(fan_in)), so that each block writes about as
    much into the residual stream as it reads and the last hidden state
    no longer remembers the input token. (At GPT-2's own 0.02 the tied
    head scores the input token some 15 logit units above every other: a
    greedy stream then repeats one token, no rounding can change it, and
    a comparison of served tokens could never fail a lower precision.)
    Logits come out about normal(0.7) over the vocabulary, the best a few
    tenths above the next. Biases and layer-norm gains are drawn too, so
    that a path that drops one of them shows."""
    H, L, _, S, V = sizes(cfg)
    return _seeded(H, L, S, V, dtype, jax.random.PRNGKey(seed % (2**31 - 1)))


@jax.jit
def _unstack(p):
    tree = {"wte": {"embedding": p["wte/embedding"]}, "wpe": p["wpe"],
            "ln_f": {"scale": p["ln_f/scale"], "bias": p["ln_f/bias"]}}
    L = p["blocks"]["ln_attn/scale"].shape[0]
    for i in range(L):
        blk = {}
        for n in BLOCK_LEAVES:
            node = blk
            parts = n.split("/")
            for part in parts[:-1]:
                node = node.setdefault(part, {})
            node[parts[-1]] = p["blocks"][n][i]
        tree[f"block_{i}"] = blk
    return tree


def program_tree(p):
    """The same values under the parameter paths the program's
    ``TransformerLM`` uses (names are the interface to the system under
    test; nothing else of it is known here)."""
    return _unstack(p)


def leaf_names(cfg):
    L = int(cfg["n_layer"])
    return list(TOP_LEAVES) + [f"block_{i}/{n}" for i in range(L)
                               for n in BLOCK_LEAVES]


def leaf_norms(p):
    """Euclidean norm of every leaf, in ``leaf_names`` order."""
    out = [jnp.sqrt(jnp.sum(jnp.square(p[n].astype(jnp.float32))))
           for n in TOP_LEAVES]
    per = [jnp.sqrt(jnp.sum(jnp.square(
        p["blocks"][n].astype(jnp.float32)).reshape(
            p["blocks"][n].shape[0], -1), axis=1)) for n in BLOCK_LEAVES]
    return jnp.concatenate([jnp.stack(out),
                            jnp.stack(per, axis=1).reshape(-1)])


# ---------------------------------------------------------------- forward

def _fp8(x, axis):
    """Round to float8_e4m3fn under a scale that puts the largest
    magnitude along ``axis`` at 448; straight-through gradient."""
    s = jax.lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30)
        / 448.0)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def _bf16(x, axis):
    return x + jax.lax.stop_gradient(
        x.astype(jnp.bfloat16).astype(jnp.float32) - x)


_LOWP = {None: None, "fp8": _fp8, "bf16": _bf16}


def _gemm(x, w, lowp):
    """``x [..., K] @ w [K, N]``."""
    if lowp is not None:
        x = lowp(x, -1)
        w = lowp(w, 0)
    return jnp.einsum("...k,kn->...n", x, w, precision=HI)


def _ln(x, s, b, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * s + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def _block(x, bp, n_head, lowp):
    B, S, H = x.shape
    d = H // n_head
    h = _ln(x, bp["ln_attn/scale"], bp["ln_attn/bias"])
    qkv = _gemm(h, bp["attn/qkv/kernel"], lowp) + bp["attn/qkv/bias"]
    qkv = qkv.reshape(B, S, 3, n_head, d)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / np.sqrt(d)
    mask = jnp.tril(jnp.ones((S, S), bool))
    sc = jnp.where(mask[None, None], sc, -jnp.inf)
    att = jax.nn.softmax(sc, axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", att, v, precision=HI)
    x = x + _gemm(ctx.reshape(B, S, H), bp["attn/proj/kernel"], lowp) \
        + bp["attn/proj/bias"]
    h = _ln(x, bp["ln_mlp/scale"], bp["ln_mlp/bias"])
    h = _gelu_tanh(_gemm(h, bp["mlp_in/kernel"], lowp) + bp["mlp_in/bias"])
    return x + _gemm(h, bp["mlp_out/kernel"], lowp) + bp["mlp_out/bias"]


def hidden_states(p, tokens, n_head, lowp=None):
    """Final-layer-norm output ``[B, S, H]`` for ``tokens [B, S]``."""
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    S = tokens.shape[1]
    x = f32(p["wte/embedding"])[tokens] + f32(p["wpe"])[:S][None]
    fn = _LOWP[lowp]

    def body(x, bp):
        return jax.checkpoint(
            lambda x, bp: _block(x, bp, n_head, fn))(x, bp), None

    x, _ = jax.lax.scan(body, x, jax.tree_util.tree_map(f32, p["blocks"]))
    return _ln(x, f32(p["ln_f/scale"]), f32(p["ln_f/bias"]))


def logits_of(p, hidden, lowp=None):
    w = jnp.asarray(p["wte/embedding"], jnp.float32).T
    return _gemm(hidden, w, _LOWP[lowp])


# ---------------------------------------------------------------- serving

@functools.partial(jax.jit, static_argnums=(2, 3))
def _served_gaps(p, seq, n_head, lowp, first, n_out):
    """For one padded sequence ``seq [S]`` (prompt then served tokens):
    the reference's logits at every position, and per position the gap
    by which the NEXT token of ``seq`` lies below the reference's best
    (positions outside ``[first, first + n_out)`` read 0). With ``lowp``
    also the gap of the token that the lower precision puts first."""
    h = hidden_states(p, seq[None], n_head)[0]
    ref = logits_of(p, h)                              # [S, V]
    best = jnp.max(ref, axis=-1)
    nxt = jnp.concatenate([seq[1:], seq[:1]])
    pos = jnp.arange(seq.shape[0])
    live = (pos >= first) & (pos < first + n_out)
    served = jnp.where(
        live, best - jnp.take_along_axis(ref, nxt[:, None], 1)[:, 0], 0.0)
    ctrl = jnp.zeros_like(served)
    if lowp is not None:
        hl = hidden_states(p, seq[None], n_head, lowp)[0]
        pick = jnp.argmax(logits_of(p, hl, lowp), axis=-1)
        ctrl = jnp.where(
            live, best - jnp.take_along_axis(ref, pick[:, None], 1)[:, 0],
            0.0)
    return served, ctrl


def served_token_gaps(p, cfg, prompt, output, lowp=None):
    """``(gaps of the served tokens, gaps of the control's tokens)`` as
    numpy arrays of length ``len(output)``. Output token j is predicted
    at position ``len(prompt) - 1 + j`` of prompt + output."""
    _, _, n_head, S, _ = sizes(cfg)
    n, m = len(prompt), len(output)
    seq = np.zeros((S,), np.int32)
    seq[:n + m] = np.asarray(list(prompt) + list(output), np.int32)[:S]
    served, ctrl = _served_gaps(p, jnp.asarray(seq), n_head, lowp,
                                n - 1, min(m, S - n))
    served, ctrl = np.asarray(served), np.asarray(ctrl)
    return served[n - 1:n - 1 + m], ctrl[n - 1:n - 1 + m]


# --------------------------------------------------------------- training

def _block_loss(p, tokens, n_head, lowp, denom):
    """Sum of the next-token cross-entropies of ``tokens [b, S+1]`` over
    ``denom`` (the whole batch's token count)."""
    h = hidden_states(p, tokens[:, :-1], n_head, lowp)
    logits = logits_of(p, h, lowp)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.sum(nll) / denom


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _grad_block(p, tokens, n_head, lowp, denom):
    return jax.value_and_grad(_block_loss)(p, tokens, n_head, lowp, denom)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adamw(p, m, v, g, step, lr, b1, b2, eps, wd):
    """apex FusedAdam, adam_w_mode: bias-corrected moments, decay added to
    the update, applied to every leaf."""
    bc1 = 1.0 - b1 ** step
    bc2 = 1.0 - b2 ** step

    def leaf(p, m, v, g):
        m2 = b1 * m + (1.0 - b1) * g
        v2 = b2 * v + (1.0 - b2) * g * g
        upd = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + eps) + wd * p
        return p - lr * upd, m2, v2

    out = jax.tree_util.tree_map(leaf, p, m, v, g)
    pick = lambda i: jax.tree_util.tree_map(          # noqa: E731
        lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2)


def stack_named(named, cfg):
    """Named leaves (``block_3/attn/qkv/kernel`` -> array) into this
    module's stacked layout."""
    L = int(cfg["n_layer"])
    p = {n: jnp.asarray(named[n], jnp.float32) for n in TOP_LEAVES}
    p["blocks"] = {n: jnp.stack([jnp.asarray(named[f"block_{i}/{n}"],
                                             jnp.float32)
                                 for i in range(L)]) for n in BLOCK_LEAVES}
    return p


@jax.jit
def _masked_change(p, p0, g1):
    """Per-leaf norm of ``p - p0`` over the elements whose first
    reference gradient is not nought to rounding: at least a thousandth
    of the median leaf's root-mean-square gradient. Under Adam an element
    below that moves by round-off alone (a key's bias under softmax, here
    a third of the fused qkv bias)."""
    def rms(t):
        flat = [jnp.sqrt(jnp.mean(jnp.square(t[n]))) for n in TOP_LEAVES]
        per = [jnp.sqrt(jnp.mean(jnp.square(t["blocks"][n]).reshape(
            t["blocks"][n].shape[0], -1), axis=1)) for n in BLOCK_LEAVES]
        return jnp.concatenate([jnp.stack(flat),
                                jnp.stack(per, axis=1).reshape(-1)])

    thr = 1e-3 * jnp.median(rms(g1))
    d = jax.tree_util.tree_map(
        lambda a, b, g: jnp.where(jnp.abs(g) >= thr, a - b, 0.0), p, p0, g1)
    return leaf_norms(d)


def train_steps(cfg, p0, batches, *, lr, weight_decay, rows_per_block=4,
                lowp=None, faults=(), program_final=None):
    """Follow ``len(batches)`` optimizer steps from ``p0``. Returns the
    losses, the per-leaf norms of the first gradient, and the per-leaf
    norms of the parameters' change over all the steps (see
    ``_masked_change``); with ``program_final`` (the program's parameters
    after the same steps, stacked) also the program's change, under the
    same mask.

    ``faults`` plants what a broken program would do, for reading how far
    each moves the numbers: ``"half_batch"`` (second half of the rows
    left out, the mean taken over the rest)."""
    _, _, n_head, _, _ = sizes(cfg)
    p0 = jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), p0)
    p = jax.tree_util.tree_map(jnp.array, p0)
    m = jax.tree_util.tree_map(jnp.zeros_like, p)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, g1 = [], None
    for step, batch in enumerate(batches, start=1):
        batch = np.asarray(batch)
        if "half_batch" in faults:
            batch = batch[:batch.shape[0] // 2]
        denom = float(batch.shape[0] * (batch.shape[1] - 1))
        loss, grads = 0.0, None
        for lo in range(0, batch.shape[0], rows_per_block):
            l, g = _grad_block(p, jnp.asarray(batch[lo:lo + rows_per_block]),
                               n_head, lowp, denom)
            loss = loss + l
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
        if g1 is None:
            g1 = grads
        losses.append(float(loss))
        p, m, v = _adamw(p, m, v, grads, float(step), lr, 0.9, 0.999, 1e-8,
                         weight_decay)
    out = {"losses": losses, "grad_norms": np.asarray(leaf_norms(g1)),
           "change_norms": np.asarray(_masked_change(p, p0, g1)),
           "final": p}
    if program_final is not None:
        out["program_change_norms"] = np.asarray(
            _masked_change(program_final, p0, g1))
    return out
