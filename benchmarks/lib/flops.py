"""Operations and bytes the algorithm needs, from the configuration's
shapes alone - never from what the program ran. Kept with the benchmark
so that no PR that claims a gain can move them.

Conventions: a multiply-add is 2 operations; causal attention is counted
once (the masked half is not work); nothing recomputed is counted;
activations and the KV cache are 2-byte (bfloat16) unless a function says
otherwise.
"""

from __future__ import annotations


def _sizes(cfg):
    H, L, nh = int(cfg["n_embd"]), int(cfg["n_layer"]), int(cfg["n_head"])
    return H, L, nh, H // nh, int(cfg["vocab_size"])


def block_matmul_params(cfg):
    """Weights that take part in a matrix product, without the head:
    qkv 3H^2 + proj H^2 + mlp 8H^2 per layer."""
    H, L, _, _, _ = _sizes(cfg)
    return 12 * H * H * L


def lm_forward_flops_per_token(cfg, context: float, head: bool = True):
    """One token's forward pass attending ``context`` positions."""
    H, L, _, _, V = _sizes(cfg)
    f = 2.0 * block_matmul_params(cfg) + 4.0 * context * H * L
    if head:
        f += 2.0 * V * H
    return f


def lm_train_flops_per_token(cfg, traffic):
    """Forward + backward (3x forward) of a sequence of ``seq_len``
    tokens, per token; a causal position attends (seq_len + 1) / 2 on
    average."""
    S = int(traffic["seq_len"])
    return 3.0 * lm_forward_flops_per_token(cfg, (S + 1) / 2.0)


def flash_fwd(ctx, n_events):
    """Per call q,k,v,o [B, h, S, d]: QK^T and PV over the causal half;
    reads q,k,v and writes o once."""
    cfg, tr = ctx["cfg"], ctx["traffic"]
    H, _, nh, d, _ = _sizes(cfg)
    B, S = int(tr["batch"]), int(tr["seq_len"])
    flops = 2 * (2.0 * B * nh * S * S * d) / 2.0
    byts = 4.0 * B * nh * S * d * 2
    return n_events * flops, n_events * byts


def flash_bwd(ctx, n_events):
    """dq and dkv kernels of one layer together (``n_events`` counts the
    pairs): S, dP, dV, dK, dQ - five products over the causal half; reads
    q,k,v,o,do and writes dq,dk,dv."""
    cfg, tr = ctx["cfg"], ctx["traffic"]
    H, _, nh, d, _ = _sizes(cfg)
    B, S = int(tr["batch"]), int(tr["seq_len"])
    flops = 5 * (2.0 * B * nh * S * S * d) / 2.0
    byts = 8.0 * B * nh * S * d * 2
    return n_events * flops, n_events * byts


def paged_decode(ctx, n_events):
    """All decode-attention calls of the traced window: each reads the K
    and V of the LIVE context of every running request (not the pool),
    once per layer, and does 4 operations per cached element."""
    cfg = ctx["cfg"]
    H, L, _, _, _ = _sizes(cfg)
    live = float(ctx["serve"]["traced_decode_context_tokens"])
    byts = live * L * 2 * H * 2
    return 4.0 * live * L * H, byts


def paged_prefill(ctx, n_events):
    """All chunk-prefill attention calls of the traced window: a chunk of
    n tokens at offset o attends o + (n + 1) / 2 positions on average and
    reads o + n cached positions of K and V once per layer."""
    cfg = ctx["cfg"]
    H, L, _, _, _ = _sizes(cfg)
    flops = byts = 0.0
    for o, n in ctx["serve"]["traced_chunks"]:
        flops += 4.0 * n * (o + (n + 1) / 2.0) * H * L
        byts += ((o + n) * 2 * H + 2 * n * H) * 2.0 * L
    return flops, byts


def serve_window_flops(cfg, events):
    """Model operations of every prompt and output token processed:
    ``events`` are ``("decode", context)`` per output token and
    ``("chunk", offset, n, is_last)`` per prompt chunk. The head counts
    only where a token is sampled."""
    total = 0.0
    for ev in events:
        if ev[0] == "decode":
            total += lm_forward_flops_per_token(cfg, ev[1])
        else:
            _, o, n, last = ev
            total += n * lm_forward_flops_per_token(
                cfg, o + (n + 1) / 2.0, head=False)
            if last:
                H, _, _, _, V = _sizes(cfg)
                total += 2.0 * V * H
    return total
