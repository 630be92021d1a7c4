"""Operations and bytes of the ``zaya`` configuration's kernels and of its
whole step, from the configuration's shapes alone (as ``flops.py`` for the
GPT-2 configurations), and the two reader kinds its cell adds. Kept with
the benchmark so that no PR that claims a gain can move them.

Conventions are ``flops.py``'s: a multiply-add is 2 operations, causal
attention counted once, nothing recomputed, activations and cache 2 bytes.

Per layer and token (ZAYA1-8B: 18.81 M): the attention projections
``H x (nq + nk + 2) d + nq d x H`` (5.24 M), the convolution grouped by
head ``(nq + nk) x t1 x d x d`` (0.33 M; the depthwise one is no matrix
product), the router ``H R + 2 R^2 + R E`` (0.66 M) and ONE expert
``3 H F`` (12.58 M: top-1). Attention reads the latent: ``4 x context x
nq d`` operations a layer, and per cached token a layer ``2 x nk d``
values = 1 KB in bfloat16.
"""

from __future__ import annotations

import statistics

from .reference_zaya import sizes as _sizes


def layer_matmul_params(cfg):
    """Weights that take part in a matrix product for ONE token in one
    layer (top-1: one expert of the layer's E)."""
    H, _, nq, nk, d, E, F, R, _ = _sizes(cfg)
    attn = H * (nq + nk + 2) * d + nq * d * H
    conv = (nq + nk) * int(cfg["cca_time1"]) * d * d
    router = H * R + 2 * R * R + R * E
    return attn + conv + router + 3 * H * F


def forward_flops_per_token(cfg, context: float, head: bool = True):
    """One token's forward pass attending ``context`` positions."""
    H, L, nq, _, d, _, _, _, V = _sizes(cfg)
    f = L * (2.0 * layer_matmul_params(cfg) + 4.0 * context * nq * d)
    if head:
        f += 2.0 * V * H
    return f


def serve_window_flops(cfg, events):
    """As ``flops.serve_window_flops``: ``("decode", context)`` per output
    token, ``("chunk", offset, n, is_last)`` per prompt chunk; the head
    counts only where a token is sampled."""
    H, _, _, _, _, _, _, _, V = _sizes(cfg)
    total = 0.0
    for ev in events:
        if ev[0] == "decode":
            total += forward_flops_per_token(cfg, ev[1])
        else:
            _, o, n, last = ev
            total += n * forward_flops_per_token(cfg, o + (n + 1) / 2.0,
                                                 head=False)
            if last:
                total += 2.0 * V * H
    return total


def moe_gemm(ctx, n_events):
    """The grouped GEMMs of the traced window: every program (a decode
    beat, a prompt chunk) runs the expert sublayer once a layer, as two
    kernel calls (gate and up fused, then down). Per program and layer:
    ``2 x 3 H F`` operations for each of its tokens; bytes the weights of
    all E experts once (a beat of 96 tokens reaches every expert) and the
    tokens' activations in and out of both calls."""
    H, L, _, _, _, E, F, _, _ = _sizes(ctx["cfg"])
    s = ctx["serve"]
    progs = [n for n in s["traced_decode_tokens"] if n] \
        + [n for _, n in s["traced_chunks"]]
    tokens = float(sum(progs))
    flops = 2.0 * 3 * H * F * tokens * L
    byts = (len(progs) * E * 3 * H * F * 2.0
            + tokens * (H + 2 * F + F + H) * 2.0) * L
    return flops, byts


def gqa_decode(ctx, n_events):
    """The paged decode kernel under grouped heads: each call reads the
    K and V of the LIVE context once for all the query heads of a group -
    ``2 nk d`` values a token and layer - and does 4 operations per
    cached position and query channel."""
    _, L, nq, nk, d, _, _, _, _ = _sizes(ctx["cfg"])
    live = float(ctx["serve"]["traced_decode_context_tokens"])
    return 4.0 * live * L * nq * d, live * L * 2 * nk * d * 2.0


# ------------------------------------------------------------ reader kinds

def op_share(ctx, p):
    """Percent of the traced window's BUSY device time spent in the
    operations whose name matches ``pattern``."""
    tr = ctx.get("trace")
    if tr is None:
        return None
    evs = tr.op_events(p["pattern"])
    busy = tr.busy_by_device()
    if not evs or not busy:
        return None
    dev = sorted(tr.ops)[0]
    if busy[dev] <= 0:
        return None
    return 100.0 * sum(e - s for s, e in evs) / busy[dev]


def load_cv(ctx, p):
    """Coefficient of variation, in percent, of the tokens each expert
    was routed over the window - the standard deviation over a layer's
    experts over their mean, averaged over the layers - from the
    program's ``[layers, experts]`` counter read as the window opened and
    closed. Nothing to read where the program has no such counter."""
    counts = ctx["counters"].get(p["key"])
    if not counts:
        return None
    cvs = [statistics.pstdev(row) / statistics.fmean(row)
           for row in counts if sum(row) > 0]
    return 100.0 * statistics.fmean(cvs) if cvs else None
