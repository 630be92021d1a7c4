"""Operations and bytes of the ``KeyeVL2`` configuration's kernels and of
its whole step AS THIS CHIP COMPUTES THEM, from the configuration's shapes
and the program's own counter of tokens routed (as ``work_ling3.py`` for
``ling_v3``). Kept with the benchmark so that no PR that claims a gain can
move them.

Conventions are ``flops.py``'s: a multiply-add is 2 operations, nothing
recomputed, activations, cache and index keys 2 bytes. A kernel's work is
what its RESULT needs, not what its form spends: attention is counted on
the SELECTED positions, ``min(t + 1, topk)`` a query (the chunk kernel's
masked form spends the whole causal count), the indexer on every causal
position.

Per token (Keye-VL-2.0-30B-A3B; M = 10^6 weights in a matrix product):

- a layer outside its experts: ``W_q`` 8.39 M + ``W_k`` 1.05 M + ``W_v``
  1.05 M + ``W_o`` 8.39 M, the indexer's ``W_qI`` 2.10 M + ``W_kI`` 0.13 M
  + ``W_w`` 0.03 M, the router 0.26 M;
- the indexer ``2 nh di`` = 2,048 operations a causal position;
- sparse attention ``4 nq d`` = 16,384 operations a selected position;
- 4.72 M weights (``2 x 4.72 M`` operations) for each (token, expert) row
  routed to an expert HELD here - 1 of a token's 8 on average with 16 of
  128 held, counted from the program's ``[layers, experts]`` counter where
  the benchmark reads it.
"""

from __future__ import annotations

from .reference_keye import routed_experts


def _s(cfg):
    g = lambda k: int(cfg[k])                                   # noqa: E731
    sa = cfg["sa_config"]
    return dict(H=g("hidden_size"), L=g("num_hidden_layers"),
                nq=g("num_attention_heads"), nk=g("num_key_value_heads"),
                d=g("head_dim"), nh=int(sa["indexer_num_heads"]),
                di=int(sa["indexer_head_dim"]), topk=int(sa["topk"]),
                E=routed_experts(cfg), G=g("num_experts"),
                k=g("num_experts_per_tok"), F=g("moe_intermediate_size"),
                V=g("vocab_size"))


def layer_params(cfg):
    """Weights in a matrix product for one token in a layer, outside its
    experts: attention, the indexer and the router."""
    s = _s(cfg)
    H, d = s["H"], s["d"]
    return H * s["nq"] * d * 2 + H * s["nk"] * d * 2 \
        + H * s["nh"] * s["di"] + H * s["di"] + H * s["nh"] + H * s["E"]


def index_flops_per_position(cfg):
    s = _s(cfg)
    return 2.0 * s["nh"] * s["di"]


def attend_flops_per_position(cfg):
    s = _s(cfg)
    return 4.0 * s["nq"] * s["d"]


def held_rows_per_token(cfg, routed=None):
    """(token, expert) rows a token sends to the experts held here in ONE
    layer, on average: from the ``[layers, experts]`` counter (held ids
    are the first ``num_experts``) or, without one, ``k G / E``."""
    s = _s(cfg)
    if routed:
        all_rows = sum(sum(row) for row in routed)
        if all_rows > 0:
            return s["k"] * sum(sum(row[:s["G"]]) for row in routed) \
                / all_rows
    return s["k"] * s["G"] / s["E"]


def _sums(o, n, topk):
    """Over the queries at positions ``o .. o + n - 1``: the causal
    positions ``sum (t + 1)`` and the selected ``sum min(t + 1, topk)``."""
    live = n * o + n * (n + 1) / 2.0
    lo, hi = o + 1, o + n               # t + 1 runs over lo .. hi
    below = max(0, min(hi, topk) - lo + 1)
    kept = (lo + lo + below - 1) * below / 2.0 + (n - below) * topk
    return live, kept


def forward_flops(cfg, live, kept, tokens, head, held_rows):
    """The forward pass of ``tokens`` tokens on this chip, whose queries
    score ``live`` causal positions and attend ``kept`` in every layer."""
    s = _s(cfg)
    f = s["L"] * (tokens * (2.0 * layer_params(cfg)
                            + held_rows * 2.0 * 3 * s["H"] * s["F"])
                  + live * index_flops_per_position(cfg)
                  + kept * attend_flops_per_position(cfg))
    return f + head * 2.0 * s["V"] * s["H"]


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
            total += forward_flops(cfg, ev[1], min(ev[1], s["topk"]), 1, 1,
                                   rows)
        else:
            _, o, n, last = ev
            live, kept = _sums(o, n, s["topk"])
            total += forward_flops(cfg, live, kept, n, 1 if last else 0,
                                   rows)
    return total


# ------------------------------------------------- kernels' work functions

def _programs(ctx):
    sv = ctx["serve"]
    return [n for n in sv["traced_decode_tokens"] if n], \
        [(o, n) for o, n in sv["traced_chunks"]]


def _decode_kept(ctx):
    """Positions the traced window's decoding rows kept: ``min(context,
    topk)`` a row, from the window's sum of contexts over its rows (exact
    where every decoding row attends ``topk`` positions or more, as in
    the cell, whose prompts start at 8,192)."""
    s = _s(ctx["cfg"])
    live = float(ctx["serve"]["traced_decode_context_tokens"])
    rows = float(sum(_programs(ctx)[0]))
    return live, (min(live / rows, s["topk"]) * rows if rows else 0.0), rows


def dsa_index(ctx, n_events):
    """``dsa_index`` and ``dsa_select`` over the traced window, a call of
    each a program and layer: ``2 nh di`` operations a causal position
    scored; bytes the index keys of the live context read once a decoding
    row or once a chunk (``di`` values, 128 B a position), the queries and
    weights in, the scores out and read back by the selection (4 B a
    position each way)."""
    s = _s(ctx["cfg"])
    live, _, rows = _decode_kept(ctx)
    key = s["di"] * 2.0
    q = s["nh"] * (s["di"] * 2.0 + 4.0)
    flops = live * index_flops_per_position(ctx["cfg"])
    byts = live * (key + 8.0) + rows * q
    for o, n in _programs(ctx)[1]:
        c_live, _ = _sums(o, n, s["topk"])
        flops += c_live * index_flops_per_position(ctx["cfg"])
        byts += (o + n) * key + c_live * 8.0 + n * q
    return s["L"] * flops, s["L"] * byts


def dsa_attend(ctx, n_events):
    """``dsa_attend`` (decode) and ``dsa_attend_chunk`` over the traced
    window, a call a program and layer: ``4 nq d`` operations a SELECTED
    position; bytes a decoding row's selected K/V rows (``2 nk d`` values,
    2,048 B a position) and a chunk's live context's K/V read once (the
    least any kernel can move), the queries in and the results out."""
    s = _s(ctx["cfg"])
    _, kept, rows = _decode_kept(ctx)
    kv = 2.0 * s["nk"] * s["d"] * 2.0
    io = s["nq"] * s["d"] * (2.0 + 4.0)
    flops = kept * attend_flops_per_position(ctx["cfg"])
    byts = kept * kv + rows * io
    for o, n in _programs(ctx)[1]:
        _, c_kept = _sums(o, n, s["topk"])
        flops += c_kept * attend_flops_per_position(ctx["cfg"])
        byts += (o + n) * kv + n * io
    return s["L"] * flops, s["L"] * byts


# the grouped GEMM's tiling (``kernels/grouped_gemm.py``, default tiles):
# 128-row tiles, weight tiles [<= 2048, <= 512], and the rule that keeps a
# call's rows resident in VMEM
GMM_ROWS, GMM_BLOCK_K, GMM_BLOCK_N = 128, 2048, 512
GMM_RESIDENT_ROWS, GMM_RESIDENT_OUT = 1 << 20, 1 << 19


def resident_moe_bytes(cfg, rows):
    """Bytes the grouped GEMM moves in one layer of a program whose
    ``rows`` sorted (token, expert) rows stay RESIDENT in VMEM (a decode
    program's ``slots x k``): every held expert's weights, whatever is
    routed, and the rows in and the result out once, for its gate-up call
    (``[G, H, 2F]``) and its down call (``[G, F, H]``). None where the
    rows are streamed instead (a chunk's): that schedule reads only the
    experts a row reaches, which the program does not record a program
    at a time."""
    s = _s(cfg)
    H, F = s["H"], s["F"]
    tm = GMM_ROWS if rows > GMM_ROWS else -(-rows // 16) * 16
    Mp = -(-rows // tm) * tm
    if Mp * min(GMM_BLOCK_K, H) > GMM_RESIDENT_ROWS \
            or Mp * min(GMM_BLOCK_N, 2 * F) > GMM_RESIDENT_OUT:
        return None
    return s["G"] * 3 * H * F * 2.0 \
        + Mp * ((H + 2 * F) + (F + H)) * 2.0


def moe_gemm_decode_roofline(ctx, p):
    """Reader (``work_keye:moe_gemm_decode_roofline``): the grouped GEMM's
    calls inside the traced window's DECODE programs (``p["program"]`` on
    the program line; the calls ``p["pattern"]``), a program counted where
    all ``2 L`` of its calls lie in the window: the least time the chip
    could take for them - bytes by :func:`resident_moe_bytes`, ``2 x 3 H
    F`` operations a row routed to a held expert - over their device
    time, in percent. The chunk programs' calls are left out: what they
    move depends on each program's routing."""
    tr = ctx.get("trace")
    if tr is None:
        return None
    import bisect

    s = _s(ctx["cfg"])
    slots = int(ctx["traffic"]["engine"]["slots"])
    byts = resident_moe_bytes(ctx["cfg"], slots * s["k"])
    calls = sorted(tr.op_events(p["pattern"]))
    starts = [a for a, _ in calls]
    t, n = 0.0, 0
    for a, b in tr.module_events(p["program"]):
        inside = [(x, y) for x, y in calls[bisect.bisect_left(starts, a):
                                           bisect.bisect_right(starts, b)]
                  if y <= b]
        if len(inside) == 2 * s["L"]:
            t += sum(y - x for x, y in inside)
            n += 1
    if byts is None or not n or t <= 0:
        return None
    dec = _programs(ctx)[0]
    rows = (sum(dec) / len(dec) if dec else slots) * held_rows_per_token(
        ctx["cfg"], ctx["counters"].get("moe_tokens_per_expert"))
    t_f = n * s["L"] * 2.0 * 3 * s["H"] * s["F"] * rows \
        / ctx["peaks"]["bf16_flops"]
    t_b = n * s["L"] * byts / ctx["peaks"]["hbm_bytes_per_s"]
    ctx.setdefault("notes", []).append(
        f"work_keye:moe_gemm_decode_roofline: {n} decode programs, "
        f"{2 * s['L'] * n} of {len(calls)} calls, {t:.4f}s on device of "
        f"{sum(y - x for x, y in calls):.4f}s; bound by "
        f"{'compute' if t_f >= t_b else 'memory'} ({t_f:.5f}s vs "
        f"{t_b:.5f}s)")
    return 100.0 * max(t_f, t_b) / t
