"""Operations and bytes of the ``ling_v3`` configuration's kernels and of
its whole step AS THIS CHIP COMPUTES THEM, from the configuration's shapes
and the program's own counter of tokens routed (as ``work_qwen3next.py``
for ``qwen3_next``). Kept with the benchmark so that no PR that claims a
gain can move them.

Conventions are ``flops.py``'s: a multiply-add is 2 operations, causal
attention counted once, nothing recomputed, activations and cache 2 bytes,
the recurrent state 4. A kernel's work is what its RESULT needs, not what
its form spends: the chunked delta rule's extra products and the absorbed
prefill's wider products are their price, not their work.

Per token (Ling-3.0-flash; M = 10^6 weights in a matrix product):

- a Kimi delta layer outside the MLP: ``W_qkv`` 31.46 M + ``W_f`` 10.49 M
  + ``W_bg`` 0.16 M + ``W_o`` 10.49 M, and the delta rule itself ``7 dk
  dv`` operations a head (3.67 M operations a token);
- a latent layer: ``W_q`` 15.73 M + ``W_kva`` 1.47 M + ``W_g`` 0.08 M +
  ``W_o`` 10.49 M, ``W_kvb`` once a token whichever side it is applied on
  (4.19 M), and attention: a DECODE token against the latent itself,
  ``2 (2 r + d_r)`` operations a cached position and head (the absorbed
  form is the cheapest there is for one query); a PROMPT token ``2 (d_n +
  d_r + d_v)`` a position and head, the expanded form's count, which is
  what its result needs (the absorbed chunk kernel spends 3.4 times that);
- a dense MLP 47.19 M; an expert layer the router 1.31 M, the shared expert
  5.90 M, and 5.898 M for each (token, expert) row routed to an expert
  HELD here - 2 of a token's 8 on average with 128 of 512 held, counted
  from the program's ``[layers, experts]`` counter where the driver read
  it.
"""

from __future__ import annotations

from .reference_ling3 import routed_experts


def _s(cfg):
    g = lambda k: int(cfg[k])                                   # noqa: E731
    L, every, dense = g("num_hidden_layers"), g("layer_group_size"), \
        g("first_k_dense_replace")
    return dict(
        H=g("hidden_size"), L=L, latent=L // every, kda=L - L // every,
        dense=dense, moe=L - dense, nh=g("num_attention_heads"),
        d=g("head_dim"), r=g("kv_lora_rank"), dn=g("qk_nope_head_dim"),
        dr=g("qk_rope_head_dim"), dv=g("v_head_dim"),
        Fd=g("intermediate_size"), E=routed_experts(cfg),
        G=g("num_experts"), k=g("num_experts_per_tok"),
        F=g("moe_intermediate_size"),
        Fs=g("moe_shared_expert_intermediate_size"), V=g("vocab_size"))


def kda_layer_params(cfg):
    """Weights in a matrix product for one token in a Kimi delta layer,
    outside its MLP."""
    s = _s(cfg)
    W = s["nh"] * s["d"]
    return s["H"] * 4 * W + s["H"] * 2 * s["nh"] + W * s["H"]


def mla_layer_params(cfg):
    """... in a latent layer: the projections, and ``W_kvb`` once."""
    s = _s(cfg)
    return s["H"] * s["nh"] * (s["dn"] + s["dr"]) \
        + s["H"] * (s["r"] + s["dr"]) + s["H"] * s["nh"] \
        + s["nh"] * s["dv"] * s["H"] \
        + s["r"] * s["nh"] * (s["dn"] + s["dv"])


def moe_fixed_params(cfg):
    """Router and shared expert: every token, every expert layer."""
    s = _s(cfg)
    return s["H"] * s["E"] + 3 * s["H"] * s["Fs"]


def delta_rule_flops_per_token(cfg):
    """``7 dk dv`` a head and Kimi delta layer."""
    s = _s(cfg)
    return 7.0 * s["nh"] * s["d"] * s["d"]


def attention_flops_per_position(cfg, decode: bool):
    """Operations one query token spends on one cached position in a
    latent layer, all heads (module docstring)."""
    s = _s(cfg)
    per_head = 2 * s["r"] + s["dr"] if decode \
        else s["dn"] + s["dr"] + s["dv"]
    return 2.0 * s["nh"] * per_head


def held_rows_per_token(cfg, routed=None):
    """(token, expert) rows a token sends to the experts held here in
    ONE expert layer, on average: from the ``[layers, experts]`` counter
    (held ids are the first ``num_experts``; every token is counted ``k``
    times a layer) or, without one, ``k G / E``."""
    s = _s(cfg)
    if routed:
        all_rows = sum(sum(row) for row in routed)
        if all_rows > 0:
            return s["k"] * sum(sum(row[:s["G"]]) for row in routed) \
                / all_rows
    return s["k"] * s["G"] / s["E"]


def forward_flops_per_token(cfg, context: float, decode: bool,
                            head: bool = True, held_rows: float = None):
    """One token's forward pass on this chip, attending ``context``
    positions in the latent layers."""
    s = _s(cfg)
    if held_rows is None:
        held_rows = held_rows_per_token(cfg)
    f = s["kda"] * (2.0 * kda_layer_params(cfg)
                    + delta_rule_flops_per_token(cfg)) \
        + s["latent"] * (2.0 * mla_layer_params(cfg) + context
                         * attention_flops_per_position(cfg, decode)) \
        + s["dense"] * 2.0 * 3 * s["H"] * s["Fd"] \
        + s["moe"] * (2.0 * moe_fixed_params(cfg)
                      + held_rows * 2.0 * 3 * s["H"] * s["F"])
    if head:
        f += 2.0 * s["V"] * s["H"]
    return f


def serve_window_flops(cfg, events, routed=None):
    """As ``flops.serve_window_flops``: ``("decode", context)`` per output
    token, ``("chunk", offset, n, is_last)`` per prompt chunk; the head
    counts only where a token is sampled. ``routed``: the window's
    ``[layers, experts]`` counter."""
    s = _s(cfg)
    rows = held_rows_per_token(cfg, routed)
    total = 0.0
    for ev in events:
        if ev[0] == "decode":
            total += forward_flops_per_token(cfg, ev[1], True,
                                             held_rows=rows)
        else:
            _, o, n, last = ev
            total += n * forward_flops_per_token(
                cfg, o + (n + 1) / 2.0, False, head=False, held_rows=rows)
            if last:
                total += 2.0 * s["V"] * s["H"]
    return total


# ------------------------------------------------- kernels' work functions

def _programs(ctx):
    sv = ctx["serve"]
    return [n for n in sv["traced_decode_tokens"] if n], \
        [(o, n) for o, n in sv["traced_chunks"]]


def kda_step(ctx, n_events):
    """``kda_step`` over the traced window: a call a decode program and
    Kimi delta layer. Each DECODING row's state crosses HBM twice (``nh dk
    dv`` float32 each way) beside its four columns a head in (k, a q, a
    beta k, a: ``4 dk``), ``beta v`` in and its two output rows; ``7 dk
    dv`` operations a head."""
    s = _s(ctx["cfg"])
    rows = float(sum(_programs(ctx)[0]))
    state = s["nh"] * s["d"] * s["d"] * 4.0
    io = s["nh"] * (4 * s["d"] + 3 * s["d"]) * 4.0
    return rows * s["kda"] * delta_rule_flops_per_token(ctx["cfg"]), \
        rows * s["kda"] * (2 * state + io)


def kda_chunk(ctx, n_events):
    """``kda_chunk`` over the traced window: a call a chunk program and
    Kimi delta layer. The recurrence's own ``7 dk dv`` operations a real
    token and head; bytes q, k, v and the running log-decay in (float32)
    and o out for the real tokens, and one state in and out."""
    s = _s(ctx["cfg"])
    chunks = _programs(ctx)[1]
    tokens = float(sum(n for _, n in chunks))
    state = s["nh"] * s["d"] * s["d"] * 4.0
    io = s["nh"] * 5 * s["d"] * 4.0
    return tokens * s["kda"] * delta_rule_flops_per_token(ctx["cfg"]), \
        s["kda"] * (len(chunks) * 2 * state + tokens * io)


def mla_decode(ctx, n_events):
    """``mla_decode_attention`` on the latent layers: the rows of the LIVE
    context once for all heads - ``r + d_r`` values a token and page layer
    (1,152 B) - the page written back a decoding row, the queries in and
    the results out; ``2 (2 r + d_r)`` operations a cached position and
    head."""
    s = _s(ctx["cfg"])
    live = float(ctx["serve"]["traced_decode_context_tokens"])
    rows = float(sum(_programs(ctx)[0]))
    row = (s["r"] + s["dr"]) * 2.0
    page = float(ctx["traffic"]["engine"]["page_len"]) * row
    io = s["nh"] * ((s["r"] + s["dr"]) * 2.0 + s["r"] * 4.0) + row
    return live * s["latent"] * attention_flops_per_position(
        ctx["cfg"], True), \
        s["latent"] * (live * row + rows * (page + io))


def mla_prefill(ctx, n_events):
    """``mla_prefill_attention`` on the latent layers: a prompt token at
    offset ``o + i`` attends ``o + i + 1`` positions, ``2 (d_n + d_r +
    d_v)`` operations each a head (what the result needs: module
    docstring); bytes the chunk's reachable rows once, the queries in and
    the results out."""
    s = _s(ctx["cfg"])
    row = (s["r"] + s["dr"]) * 2.0
    flops = byts = 0.0
    for o, n in _programs(ctx)[1]:
        flops += n * (o + (n + 1) / 2.0) \
            * attention_flops_per_position(ctx["cfg"], False)
        byts += (o + n) * row \
            + n * s["nh"] * ((s["r"] + s["dr"]) * 2.0 + s["r"] * 4.0)
    return s["latent"] * flops, s["latent"] * byts


def moe_gemm(ctx, n_events):
    """The grouped GEMMs of the traced window: every program runs the
    expert sublayer once an EXPERT layer, as two kernel calls a block of
    rows. Per program and layer: the weights of the G experts HELD once
    (a program streams every one of them whatever is routed) and the
    rows' activations in and out of both calls; ``2 x 3 H F`` operations a
    row routed to a held expert."""
    s = _s(ctx["cfg"])
    dec, chunks = _programs(ctx)
    progs = dec + [n for _, n in chunks]
    rows = float(sum(progs)) * s["moe"] * held_rows_per_token(
        ctx["cfg"], ctx["counters"].get("moe_tokens_per_expert"))
    flops = 2.0 * 3 * s["H"] * s["F"] * rows
    byts = len(progs) * s["moe"] * s["G"] * 3 * s["H"] * s["F"] * 2.0 \
        + rows * (s["H"] + 2 * s["F"] + s["F"] + s["H"]) * 2.0
    return flops, byts
