"""Operations and bytes of the ``qwen3_next`` configuration's kernels and of
its whole step AS THIS CHIP COMPUTES THEM, from the configuration's shapes
and the program's own counter of tokens routed (as ``work_zaya.py`` for
the ``zaya`` configuration). Kept with the benchmark so that no PR that
claims a gain can move them.

Conventions are ``flops.py``'s: a multiply-add is 2 operations, causal
attention counted once, nothing recomputed, activations and cache 2 bytes,
the recurrent state 4.

Per token (Qwen3-Next-80B-A3B; M = 10^6 weights in a matrix product):

- a linear layer outside the experts: ``W_qkvz`` 25.17 M + ``W_ba`` 0.13 M
  + ``W_out`` 8.39 M, and the delta rule itself ``7 dk dv`` operations a
  value head (decay, the read ``S^T k``, the rank-one write, the read
  ``S^T q``: 3.67 M operations a token);
- a full layer: ``W_q`` 16.78 M + ``W_k, W_v`` 2.10 M + ``W_o`` 8.39 M, and
  ``4 x context x nq d`` operations of attention;
- every layer: the router 1.05 M, the shared expert 3.15 M, and 3.146 M
  for each (token, expert) row routed to an expert HELD here - 1.25 of a
  token's 10 on average with 64 of 512 held, counted from the program's
  ``[layers, experts]`` counter where the driver read it.
"""

from __future__ import annotations

from .reference_qwen3next import routed_experts


def _s(cfg):
    g = lambda k: int(cfg[k])                                   # noqa: E731
    return dict(
        H=g("hidden_size"), L=g("num_hidden_layers"),
        every=g("full_attention_interval"), nq=g("num_attention_heads"),
        nkv=g("num_key_value_heads"), d=g("head_dim"),
        nk=g("linear_num_key_heads"), nv=g("linear_num_value_heads"),
        dk=g("linear_key_head_dim"), dv=g("linear_value_head_dim"),
        E=routed_experts(cfg), G=g("num_experts"),
        k=g("num_experts_per_tok"), F=g("moe_intermediate_size"),
        Fs=g("shared_expert_intermediate_size"), V=g("vocab_size"))


def layer_counts(cfg):
    """(linear layers, full layers)."""
    s = _s(cfg)
    full = s["L"] // s["every"]
    return s["L"] - full, full


def linear_layer_params(cfg):
    """Weights in a matrix product for one token in a linear layer,
    outside the expert sublayer."""
    s = _s(cfg)
    C = 2 * s["nk"] * s["dk"] + s["nv"] * s["dv"]
    return s["H"] * (C + s["nv"] * s["dv"]) + s["H"] * 2 * s["nv"] \
        + s["nv"] * s["dv"] * s["H"]


def full_layer_params(cfg):
    s = _s(cfg)
    return s["H"] * s["nq"] * 2 * s["d"] + 2 * s["H"] * s["nkv"] * s["d"] \
        + s["nq"] * s["d"] * s["H"]


def moe_fixed_params(cfg):
    """Router and shared expert: every token, every layer."""
    s = _s(cfg)
    return s["H"] * s["E"] + 3 * s["H"] * s["Fs"] + s["H"]


def delta_rule_flops_per_token(cfg):
    """``7 dk dv`` a value head and linear layer."""
    s = _s(cfg)
    return 7.0 * s["nv"] * s["dk"] * s["dv"]


def held_rows_per_token(cfg, routed=None):
    """(token, expert) rows a token sends to the experts held here in
    ONE layer, on average: from the ``[layers, experts]`` counter (held
    ids are the first ``num_experts``; every token is counted ``k`` times
    a layer) or, without one, ``k G / E``."""
    s = _s(cfg)
    if routed:
        all_rows = sum(sum(row) for row in routed)
        if all_rows > 0:
            return s["k"] * sum(sum(row[:s["G"]]) for row in routed) \
                / all_rows
    return s["k"] * s["G"] / s["E"]


def forward_flops_per_token(cfg, context: float, head: bool = True,
                            held_rows: float = None):
    """One token's forward pass on this chip, attending ``context``
    positions in the full layers; ``held_rows`` its rows routed to held
    experts, a layer (default: :func:`held_rows_per_token`'s ``k G /
    E``)."""
    s = _s(cfg)
    lin, full = layer_counts(cfg)
    if held_rows is None:
        held_rows = held_rows_per_token(cfg)
    f = lin * (2.0 * linear_layer_params(cfg)
               + delta_rule_flops_per_token(cfg)) \
        + full * (2.0 * full_layer_params(cfg)
                  + 4.0 * context * s["nq"] * s["d"]) \
        + s["L"] * 2.0 * moe_fixed_params(cfg) \
        + s["L"] * held_rows * 2.0 * 3 * s["H"] * s["F"]
    if head:
        f += 2.0 * s["V"] * s["H"]
    return f


def serve_window_flops(cfg, events, routed=None):
    """As ``flops.serve_window_flops``: ``("decode", context)`` per output
    token, ``("chunk", offset, n, is_last)`` per prompt chunk; the head
    counts only where a token is sampled. ``routed``: the window's
    ``[layers, experts]`` counter, so that routed-expert work is what was
    routed to the experts held here and not 10 a token."""
    s = _s(cfg)
    rows = held_rows_per_token(cfg, routed)
    total = 0.0
    for ev in events:
        if ev[0] == "decode":
            total += forward_flops_per_token(cfg, ev[1], held_rows=rows)
        else:
            _, o, n, last = ev
            total += n * forward_flops_per_token(
                cfg, o + (n + 1) / 2.0, head=False, held_rows=rows)
            if last:
                total += 2.0 * s["V"] * s["H"]
    return total


# ------------------------------------------------- kernels' work functions

def _programs(ctx):
    sv = ctx["serve"]
    return [n for n in sv["traced_decode_tokens"] if n], \
        [n for _, n in sv["traced_chunks"]]


def gdn_step(ctx, n_events):
    """``gated_delta_step`` over the traced window: a call a decode
    program and linear layer. Each DECODING row's state crosses HBM twice
    (read, written: ``nv dk dv`` float32 each way) beside its q, k, v in
    and its two output rows; ``7 dk dv`` operations a head."""
    s = _s(ctx["cfg"])
    lin, _ = layer_counts(ctx["cfg"])
    rows = float(sum(_programs(ctx)[0]))
    state = s["nv"] * s["dk"] * s["dv"] * 4.0
    io = s["nv"] * (4 * s["dk"] + 3 * s["dv"]) * 4.0
    return rows * lin * delta_rule_flops_per_token(ctx["cfg"]), \
        rows * lin * (2 * state + io)


def gdn_chunk(ctx, n_events):
    """``gated_delta_chunk`` over the traced window: a call a chunk
    program and linear layer. The recurrence's own ``7 dk dv`` operations
    a real token and head (the chunked form's extra products are its
    price, not its work); bytes q, k, v in and o out for the real tokens
    and one state in and out. The reader takes the larger of the two
    times."""
    s = _s(ctx["cfg"])
    lin, _ = layer_counts(ctx["cfg"])
    chunks = _programs(ctx)[1]
    tokens = float(sum(chunks))
    state = s["nv"] * s["dk"] * s["dv"] * 4.0
    io = s["nv"] * (2 * s["dk"] + 2 * s["dv"]) * 4.0
    return tokens * lin * delta_rule_flops_per_token(ctx["cfg"]), \
        lin * (len(chunks) * 2 * state + tokens * io)


def moe_gemm(ctx, n_events):
    """The grouped GEMMs of the traced window: every program runs the
    expert sublayer once a layer, as two kernel calls a block of rows.
    Per program and layer: the weights of the G experts HELD once (a beat
    reaches every one of them) and the rows' activations in and out of
    both calls; ``2 x 3 H F`` operations a row routed to a held expert -
    the window's own share of rows where the counter was read."""
    s = _s(ctx["cfg"])
    dec, chunks = _programs(ctx)
    progs = dec + chunks
    rows = float(sum(progs)) * s["L"] * held_rows_per_token(
        ctx["cfg"], ctx["counters"].get("moe_tokens_per_expert"))
    flops = 2.0 * 3 * s["H"] * s["F"] * rows
    byts = len(progs) * s["L"] * s["G"] * 3 * s["H"] * s["F"] * 2.0 \
        + rows * (s["H"] + 2 * s["F"] + s["F"] + s["H"]) * 2.0
    return flops, byts


def gqa_decode(ctx, n_events):
    """The paged decode kernel on the full-attention layers: the K and V
    of the LIVE context once for all the query heads of a group - ``2 nkv
    d`` values a token and page layer (2 KB) - and 4 operations per
    cached position and query channel."""
    s = _s(ctx["cfg"])
    _, full = layer_counts(ctx["cfg"])
    live = float(ctx["serve"]["traced_decode_context_tokens"])
    return 4.0 * live * full * s["nq"] * s["d"], \
        live * full * 2 * s["nkv"] * s["d"] * 2.0
