"""Expert parallelism: mixture-of-experts with an ``expert`` mesh axis.

The reference has NO expert parallelism (SURVEY §3.3 — "EP: absent from
apex; leave extension point in mesh design"). This module fills that
extension point the TPU-native way — the GShard/Switch formulation whose
dispatch/combine are einsums (MXU work, XLA-fusable) and whose only
communication is one ``all_to_all`` pair over the ``expert`` axis (ICI).

Design (top-1 switch routing, Fedus et al. 2021; GShard dispatch algebra,
Lepikhin et al. 2020):

- every shard routes its local tokens over ALL ``num_experts`` experts;
- dispatch tensor [tokens, E, C] scatters tokens into per-expert capacity
  slots; tokens over capacity are dropped (their combine weight is 0 and the
  residual path carries them — standard switch behavior);
- ``all_to_all`` sends each expert's slots to the shard that owns it, local
  expert MLPs run on [E_local, shards*C, H], and the inverse ``all_to_all``
  brings results home for the weighted combine.

Single-shard (no mesh axis) degenerates to the same math without the
all_to_alls, so the layer is testable on one device and parity-testable
against its sharded self.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from apex_tpu.comm import AXIS_EXPERT

__all__ = ["MoEMLP", "top1_routing", "top2_routing", "router_z_loss",
           "dropless_top1_experts", "dropless_topk_experts",
           "group_limited_sigmoid_topk"]


def _scatter_to_slots(mask, pos, gate, capacity):
    """(dispatch, combine) [T,E,C] for one routing choice: ``mask`` [T,E]
    marks each token's expert, ``pos`` [T,E] its queue position there (only
    the masked entry meaningful), ``gate`` [T] its combine weight. Tokens at
    pos >= capacity are dropped (dispatch row zero)."""
    keep = (pos < capacity).astype(jnp.float32) * mask         # [T, E]
    p = jnp.sum(pos * mask, axis=-1).astype(jnp.int32)         # [T]
    dispatch = keep[:, :, None] * jax.nn.one_hot(p, capacity)[:, None, :]
    return dispatch, dispatch * gate[:, None, None]


def top1_routing(router_logits, num_experts: int, capacity: int):
    """Switch top-1 router → (dispatch [T,E,C], combine [T,E,C], aux_loss).

    aux_loss is the switch load-balancing loss (mean_prob · mean_assignment
    · E), reference formulation from the Switch paper.
    """
    T = router_logits.shape[0]
    probs = jax.nn.softmax(jnp.asarray(router_logits, jnp.float32), axis=-1)
    expert_index = jnp.argmax(probs, axis=-1)                  # [T]
    expert_mask = jax.nn.one_hot(expert_index, num_experts)    # [T, E]

    # position of each token within its expert's queue (prefix count)
    position_in_expert = (jnp.cumsum(expert_mask, axis=0) - 1.0) * expert_mask
    gate = jnp.sum(probs * expert_mask, axis=-1)               # [T]
    dispatch, combine = _scatter_to_slots(expert_mask, position_in_expert,
                                          gate, capacity)

    # load-balancing aux loss
    density = jnp.mean(expert_mask, axis=0)                    # [E]
    density_proxy = jnp.mean(probs, axis=0)                    # [E]
    aux = jnp.sum(density * density_proxy) * num_experts
    return dispatch, combine, aux


def router_z_loss(router_logits):
    """ST-MoE router z-loss (Zoph et al. 2022): mean(logsumexp(logits)²).
    Keeps router logits small so the fp32 softmax stays well-conditioned.
    When adding it to the objective yourself, ~1e-3 is the paper's weight;
    through ``MoEMLP(router_z_weight=...)`` it is folded into the returned
    aux and therefore ALSO scaled by the caller's aux weight — see the
    ``router_z_weight`` field doc."""
    lse = jax.nn.logsumexp(jnp.asarray(router_logits, jnp.float32), axis=-1)
    return jnp.mean(lse ** 2)


def top2_routing(router_logits, num_experts: int, capacity: int):
    """GShard top-2 router → (dispatch [T,E,C], combine [T,E,C], aux_loss).

    Each token goes to its two highest-probability experts with combine
    weights renormalized over the pair (GShard, Lepikhin et al. 2020).
    Capacity is filled by all first choices before any second choice (the
    GShard ordering: second choices are the first dropped under pressure).
    aux_loss uses the FIRST-choice assignment density, the standard
    formulation shared with switch.
    """
    T = router_logits.shape[0]
    probs = jax.nn.softmax(jnp.asarray(router_logits, jnp.float32), axis=-1)

    idx1 = jnp.argmax(probs, axis=-1)                          # [T]
    mask1 = jax.nn.one_hot(idx1, num_experts)                  # [T, E]
    probs_wo1 = probs * (1.0 - mask1)
    idx2 = jnp.argmax(probs_wo1, axis=-1)                      # [T]
    mask2 = jax.nn.one_hot(idx2, num_experts)

    p1 = jnp.sum(probs * mask1, axis=-1)                       # [T]
    # from the top-1-masked probs: a saturated softmax (p1 == 1 exactly)
    # leaves probs_wo1 all-zero and argmax would alias expert 0 — p2 == 0
    # then zeroes mask2 so no phantom second choice is dispatched and w1
    # renormalizes to 1
    p2 = jnp.sum(probs_wo1 * mask2, axis=-1)
    mask2 = mask2 * (p2 > 0.0).astype(jnp.float32)[:, None]
    denom = jnp.maximum(p1 + p2, 1e-9)
    w1, w2 = p1 / denom, p2 / denom

    # queue positions: every first choice precedes every second choice
    pos1 = (jnp.cumsum(mask1, axis=0) - 1.0) * mask1           # [T, E]
    count1 = jnp.sum(mask1, axis=0)                            # [E]
    pos2 = ((jnp.cumsum(mask2, axis=0) - 1.0) + count1[None, :]) * mask2

    d1, c1 = _scatter_to_slots(mask1, pos1, w1, capacity)
    d2, c2 = _scatter_to_slots(mask2, pos2, w2, capacity)
    # a slot is owned by exactly one (token, choice): positions are disjoint
    dispatch = d1 + d2
    combine = c1 + c2

    density = jnp.mean(mask1, axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * num_experts
    return dispatch, combine, aux


class MoEMLP(nn.Module):
    """Expert-parallel MLP block.

    ``num_experts`` total experts; inside ``shard_map`` over ``axis_name``
    each shard holds ``num_experts // axis_size`` of them. Outside a mesh
    (``axis_name=None`` or unbound) all experts are local — identical math.

    ``__call__(x[T, H]) -> (y[T, H], aux_loss)``; callers add
    ``aux_weight * aux_loss`` to their objective.
    """

    hidden: int
    intermediate: int
    num_experts: int
    capacity_factor: float = 1.25
    router_top_k: int = 1          # 1 = switch, 2 = GShard top-2
    # ST-MoE z-loss weight RELATIVE to the load-balancing term: the layer
    # returns aux = lb_aux + router_z_weight * z_loss and the caller scales
    # the whole thing by its aux weight. For an objective weighting of
    # aux_weight=1e-2 on lb and the paper's 1e-3 on z, set
    # router_z_weight=0.1.
    router_z_weight: float = 0.0
    axis_name: Optional[str] = AXIS_EXPERT
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    def _axis_size(self) -> int:
        if self.axis_name is None:
            return 1
        try:
            return int(lax.psum(1, self.axis_name))
        except NameError:  # axis not bound: single-shard math
            return 1

    @nn.compact
    def __call__(self, x):
        T, H = x.shape
        E = self.num_experts
        ep = self._axis_size()
        if E % ep:
            raise ValueError(f"num_experts={E} not divisible by expert-"
                             f"parallel size {ep}")
        e_local = E // ep
        if self.router_top_k not in (1, 2):
            raise ValueError(
                f"router_top_k must be 1 (switch) or 2 (GShard top-2), "
                f"got {self.router_top_k}")
        # capacity per expert per shard (scaled by top_k: each token takes
        # router_top_k slots on average), padded to a multiple of 4 sublanes
        C = max(4, int(self.capacity_factor * self.router_top_k * T / E
                       + 0.5))
        C = (C + 3) // 4 * 4

        router = nn.Dense(E, dtype=jnp.float32,
                          param_dtype=self.param_dtype, name="router")
        logits = router(jnp.asarray(x, jnp.float32))
        routing = top1_routing if self.router_top_k == 1 else top2_routing
        dispatch, combine, aux = routing(logits, E, C)
        if self.router_z_weight:
            aux = aux + self.router_z_weight * router_z_loss(logits)
        dispatch = jnp.asarray(dispatch, x.dtype)

        # scatter tokens into expert slots: [E, C, H]
        slots = jnp.einsum("tec,th->ech", dispatch, x,
                           preferred_element_type=jnp.float32)
        slots = jnp.asarray(slots, x.dtype)

        if ep > 1:
            # [E, C, H] → [ep, e_local, C, H] —a2a→ local experts' slots
            # from every shard: [ep, e_local, C, H] → [e_local, ep*C, H]
            slots = slots.reshape(ep, e_local, C, H)
            slots = lax.all_to_all(slots, self.axis_name, split_axis=0,
                                   concat_axis=0, tiled=False)
            slots = jnp.moveaxis(slots, 0, 1).reshape(e_local, ep * C, H)
        else:
            slots = slots.reshape(e_local, C, H)

        # local expert MLPs, batched over the expert dim (one big MXU GEMM)
        w1 = self.param("w1", nn.initializers.normal(stddev=0.02),
                        (e_local, H, self.intermediate), self.param_dtype)
        b1 = self.param("b1", nn.initializers.zeros,
                        (e_local, self.intermediate), self.param_dtype)
        w2 = self.param("w2", nn.initializers.normal(stddev=0.02),
                        (e_local, self.intermediate, H), self.param_dtype)
        b2 = self.param("b2", nn.initializers.zeros,
                        (e_local, H), self.param_dtype)
        h = jnp.einsum("esh,ehi->esi", slots, jnp.asarray(w1, slots.dtype),
                       preferred_element_type=jnp.float32)
        h = jax.nn.gelu(h + b1[:, None, :], approximate=False)
        h = jnp.asarray(h, slots.dtype)
        out = jnp.einsum("esi,eih->esh", h, jnp.asarray(w2, slots.dtype),
                         preferred_element_type=jnp.float32)
        out = jnp.asarray(out + b2[:, None, :], x.dtype)

        if ep > 1:
            out = out.reshape(e_local, ep, C, H)
            out = jnp.moveaxis(out, 1, 0)              # [ep, e_local, C, H]
            out = lax.all_to_all(out, self.axis_name, split_axis=0,
                                 concat_axis=0, tiled=False)
            out = out.reshape(E, C, H)
        else:
            out = out.reshape(E, C, H)

        y = jnp.einsum("tec,ech->th", jnp.asarray(combine, jnp.float32),
                       jnp.asarray(out, jnp.float32),
                       preferred_element_type=jnp.float32)
        return jnp.asarray(y, x.dtype), aux


# ------------------------------------------------- serving: drop nothing
def dropless_top1_experts(u, gate, choice, w_gate_up, w_down, *,
                          num_experts: int, experts_held=None,
                          out_dtype=None):
    """The serving tier's expert layer: top-1, no capacity, no token
    dropped, one grouped GEMM over the experts HELD here.

    ``u`` ``[T, H]`` the (normed) tokens; ``choice`` ``[T]`` int32 each
    token's expert among ALL ``num_experts`` (the router runs over the
    published count whatever is held); ``gate`` ``[T]`` fp32 the chosen
    expert's probability; ``w_gate_up`` ``[G, H, 2F]`` (gate columns
    first) and ``w_down`` ``[G, F, H]`` the weights of the ``G`` experts
    in ``experts_held`` (a static tuple of expert ids in the order the
    weights are stacked; None = all of them). Returns ``(y [T, H],
    tokens_per_expert [num_experts] int32)`` with ``y[t] = gate[t] *
    (silu(u Wg) * (u Wu)) Wd`` under expert ``choice[t]`` where that
    expert is held and zero where it lives on another chip — the part of
    the layer's result this chip gives; the parts of all the shares add
    up to the whole layer's.

    Tokens are sorted by expert (``moe.sort``), run through
    :func:`~apex_tpu.kernels.grouped_gemm.grouped_gemm` for gate and up
    as ONE fused ``[G, H, 2F]`` operand and again for down
    (``moe.gemm``), scaled and unsorted (``moe.combine``). Any
    imbalance, an expert with no token included, costs nothing but the
    empty expert's weight stream. The training module above
    (:class:`MoEMLP`) keeps its capacity-and-drop dispatch."""
    from apex_tpu.kernels.grouped_gemm import group_ranges, grouped_gemm

    T, H = u.shape
    F = w_down.shape[1]
    out_dtype = out_dtype or u.dtype
    with jax.named_scope("moe.sort"):
        order = jnp.argsort(choice, stable=True)
        inverse = jnp.zeros((T,), jnp.int32).at[order].set(
            jnp.arange(T, dtype=jnp.int32))
        sizes, starts, ends = group_ranges(choice, num_experts,
                                           experts_held)
        xs = u[order]
    with jax.named_scope("moe.gemm"):
        gu = grouped_gemm(xs, w_gate_up, starts, ends)
        h = jax.nn.silu(jnp.asarray(gu[:, :F], jnp.float32)) \
            * jnp.asarray(gu[:, F:], jnp.float32)
        ys = grouped_gemm(jnp.asarray(h, xs.dtype), w_down, starts, ends,
                          out_dtype=jnp.float32)
    with jax.named_scope("moe.combine"):
        y = ys[inverse] * jnp.asarray(gate, jnp.float32)[:, None]
    return jnp.asarray(y, out_dtype), sizes


TOPK_BLOCK_ROWS = 512


def group_limited_sigmoid_topk(logits, bias, *, k: int, n_group: int,
                               topk_group: int, scale: float = 1.0):
    """The choosing of a sigmoid router limited to groups (DeepSeek-V3's):
    ``logits [T, E]`` float32 -> ``(weights [T, k] float32, choices [T, k]
    int32)``, what :func:`dropless_topk_experts` takes.

    Scores ``s = sigmoid(logits)``. The SELECTION runs on ``s + bias``
    (``bias [E]``, a balancing buffer that takes part in the choice only):
    the ``E`` experts lie in ``n_group`` contiguous groups, a group's
    score is the sum of its two best ``s + bias``, the ``topk_group`` best
    groups stay, and the ``k`` best experts among them are chosen. The
    WEIGHTS are ``s`` of the chosen, without the bias, normalised over
    the ``k`` and times ``scale``. Ties go to the lower index at every
    step (``lax.top_k``)."""
    T, E = logits.shape
    s = jax.nn.sigmoid(jnp.asarray(logits, jnp.float32))
    sel = s + jnp.asarray(bias, jnp.float32)
    groups = sel.reshape(T, n_group, E // n_group)
    group_score = jnp.sum(lax.top_k(groups, 2)[0], -1)         # [T, n_group]
    kept = lax.top_k(group_score, topk_group)[1]               # [T, topk]
    keep = jnp.zeros((T, n_group), bool).at[
        jnp.arange(T)[:, None], kept].set(True)
    sel = jnp.where(jnp.repeat(keep, E // n_group, axis=1), sel, -jnp.inf)
    choices = lax.top_k(sel, k)[1].astype(jnp.int32)
    w = jnp.take_along_axis(s, choices, -1)
    return w / jnp.sum(w, -1, keepdims=True) * scale, choices


def dropless_topk_experts(u, weights, choices, w_gate_up, w_down, *,
                          num_experts: int, experts_held=None,
                          out_dtype=None, block_rows: int = TOPK_BLOCK_ROWS):
    """The drop-nothing layer for ``k`` experts a token, over the experts
    HELD here: ``choices`` ``[T, k]`` int32 each token's experts among ALL
    ``num_experts`` (distinct), ``weights`` ``[T, k]`` fp32 what each
    contributes (normalised by the caller over all ``k``, held or not);
    the other operands as :func:`dropless_top1_experts`. Returns ``y [T,
    H]``: the sum over a token's HELD experts of ``weights * (silu(u Wg)
    * (u Wu)) Wd`` - this chip's part of the layer; what the experts on
    other chips add is theirs to give, and the parts of all the shares
    add up to the whole layer's.

    The ``T x k`` (token, expert) rows are sorted with the held experts
    first, in the order their weights are stacked (``moe.sort``), so the
    rows this chip works on are the first ``n_held`` of the order and
    nothing is gathered for a row routed elsewhere. They go through the
    grouped GEMMs in blocks of ``block_rows`` (``moe.gemm``): one block
    where ``T x k`` fits one (the whole of a top-1 layer), else as many
    as ``n_held`` needs - with a chip's share of 1/8 of the experts that
    is one block in all but pathological routings, and every block
    streams the held weights once. Each block's rows are weighted and
    summed back into their tokens by a one-hot product (``moe.combine``).
    No capacity, no token dropped, whatever the routing."""
    from apex_tpu.kernels.grouped_gemm import grouped_gemm

    T, H = u.shape
    k = choices.shape[1]
    F = w_down.shape[1]
    R = T * k
    out_dtype = out_dtype or u.dtype
    G = num_experts if experts_held is None else len(experts_held)
    with jax.named_scope("moe.sort"):
        flat = choices.reshape(-1)                     # row p = t * k + j
        if experts_held is None:
            key = flat
        else:
            rank = np.full((num_experts,), G, np.int32)
            rank[list(experts_held)] = np.arange(G, dtype=np.int32)
            key = jnp.asarray(rank)[flat]              # not held: G, last
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        sizes = jnp.zeros((G + 1,), jnp.int32).at[key].add(1)[:G]
        ends = jnp.cumsum(sizes)
        starts = ends - sizes
        n_held = ends[-1]
    mb = min(int(block_rows), -(-R // 16) * 16)
    n_blocks = -(-R // mb)
    order = jnp.pad(order, (0, n_blocks * mb - R))
    wflat = jnp.asarray(weights, jnp.float32).reshape(-1)
    tokens = jnp.arange(T, dtype=jnp.int32)

    def block(i, y):
        lo = i * mb
        rows = jax.lax.dynamic_slice_in_dim(order, lo, mb)
        tok = rows // k
        s = jnp.clip(starts - lo, 0, mb)
        e = jnp.clip(ends - lo, 0, mb)
        with jax.named_scope("moe.gemm"):
            xs = u[tok]
            gu = grouped_gemm(xs, w_gate_up, s, e)
            h = jax.nn.silu(jnp.asarray(gu[:, :F], jnp.float32)) \
                * jnp.asarray(gu[:, F:], jnp.float32)
            ys = grouped_gemm(jnp.asarray(h, xs.dtype), w_down, s, e,
                              out_dtype=jnp.float32)
        with jax.named_scope("moe.combine"):
            live = lo + jnp.arange(mb, dtype=jnp.int32) < n_held
            wt = jnp.where(live, wflat[rows], 0.0)
            onehot = jnp.where(tok[None, :] == tokens[:, None], wt[None, :],
                               0.0)                              # [T, mb]
            return y + jnp.dot(onehot, ys, precision=lax.Precision.HIGHEST)

    y = jnp.zeros((T, H), jnp.float32)
    if n_blocks == 1:
        y = block(0, y)
    else:
        y = lax.fori_loop(0, (n_held + mb - 1) // mb, block, y)
    return jnp.asarray(y, out_dtype)
