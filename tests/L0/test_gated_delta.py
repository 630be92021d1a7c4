"""The gated delta rule's two kernels (kernels/gated_delta.py) against
the recurrence itself (a ``lax.scan`` over time), in interpret mode on the
CPU at the published head size (128 x 128) and a few heads.

Every case runs for both shapes of the decay: a scalar a head (``g [..,
H]``: the gated delta rule, kernels ``gated_delta_step`` /
``gated_delta_chunk``) and a vector a head (``g [.., H, dk]``: Kimi delta
attention, the same step body as ``kda_step`` and the sibling
``kda_chunk``).

Everything is float32 and the kernels do the recurrence's sums in another
order (the chunked form solves a triangular system a sub-chunk): outputs
of order 0.1 and states of order 1 agree to 2e-6; a state the kernel must
not touch is compared bitwise.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.kernels import gated_delta as gd

B, T, H, DK, DV = 3, 128, 8, 128, 128
TOL = 2e-6


@pytest.fixture(scope="module", params=["decay_a_head", "decay_a_channel"])
def case(request):
    rng = np.random.default_rng(0)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731,E501
    f = lambda x: jnp.asarray(x, jnp.float32)                       # noqa: E731
    q = f(unit(rng.normal(size=(B, T, H, DK))) / np.sqrt(DK))
    k = f(unit(rng.normal(size=(B, T, H, DK))))
    v = f(rng.normal(size=(B, T, H, DV)))
    g = f(-0.1 * np.abs(rng.normal(size=(B, T, H))))
    if request.param == "decay_a_channel":
        g = f(-0.1 * np.abs(rng.normal(size=(B, T, H, DK))))
    beta = f(rng.uniform(0.1, 0.9, size=(B, T, H)))
    s0 = f(0.1 * rng.normal(size=(B, H, DK, DV)))
    o, sT = jax.jit(gd.gated_delta_recurrence)(q, k, v, g, beta, s0)
    # two layers of state: the kernels work on layer 1, layer 0 is zeros
    state = jnp.stack([jnp.zeros_like(s0), s0])
    return dict(q=q, k=k, v=v, g=g, beta=beta, s0=s0, o=o, sT=sT,
                state=state, channel=request.param == "decay_a_channel")


def _err(a, b):
    return float(jnp.max(jnp.abs(a - b)))


def test_the_chunked_form_is_the_recurrence(case):
    c = case
    o, sT = jax.jit(gd.gated_delta_chunk_reference)(
        c["q"], c["k"], c["v"], c["g"], c["beta"], c["s0"])
    assert _err(o, c["o"]) < TOL and _err(sT, c["sT"]) < TOL
    # a length that is no multiple of the sub-chunk pads with positions
    # that write nothing
    o2, s2 = gd.gated_delta_chunk_reference(
        *(c[n][:, :100] for n in ("q", "k", "v", "g", "beta")), c["s0"])
    o3, s3 = gd.gated_delta_recurrence(
        *(c[n][:, :100] for n in ("q", "k", "v", "g", "beta")), c["s0"])
    assert _err(o2, o3) < TOL and _err(s2, s3) < TOL


_chunk = jax.jit(lambda st, slot, fresh, q, k, v, g, b:
                 gd.gated_delta_chunk(st, 1, slot, fresh, q, k, v, g, b))


@pytest.mark.parametrize("fresh", [False, True])
def test_chunk_kernel_from_and_to_a_slots_state_in_place(case, fresh):
    c = case
    slot = 1
    o, st = _chunk(c["state"], slot, fresh,
                   *(c[n][slot] for n in ("q", "k", "v", "g", "beta")))
    if fresh:       # an admitted request starts from zeros, whatever lay
        want_o, want_s = gd.gated_delta_recurrence(
            *(c[n][slot:slot + 1] for n in ("q", "k", "v", "g", "beta")),
            jnp.zeros_like(c["s0"][:1]))
        want_o, want_s = want_o[0], want_s[0]
    else:
        want_o, want_s = c["o"][slot], c["sT"][slot]
    assert _err(o, want_o) < TOL and _err(st[1, slot], want_s) < TOL
    # the other slots and the other layer: bitwise as they were
    keep = np.array([0, 2])
    assert np.array_equal(np.asarray(st[1, keep]),
                          np.asarray(c["state"][1, keep]))
    assert not np.asarray(st[0]).any()


def test_a_padded_chunk_leaves_its_last_valid_positions_state(case):
    c = case
    n = 88
    g = c["g"][0].at[n:].set(0.0)
    beta = c["beta"][0].at[n:].set(0.0)
    o, st = _chunk(c["state"], 0, False, c["q"][0], c["k"][0], c["v"][0],
                   g, beta)
    want_o, want_s = gd.gated_delta_recurrence(
        *(c[m][:1, :n] for m in ("q", "k", "v", "g", "beta")), c["s0"][:1])
    assert _err(o[:n], want_o[0]) < TOL and _err(st[1, 0], want_s[0]) < TOL


@pytest.mark.parametrize("heads_a_step", [8, 4])
def test_step_kernel_moves_active_rows_only_in_place(case, heads_a_step,
                                                     monkeypatch):
    c = case
    if heads_a_step != H:           # more than one block of heads a slot
        monkeypatch.setattr(gd, "_step_heads", lambda *a: heads_a_step)
    active = np.array([True, False, True])
    step = jax.jit(lambda st, *a: gd.gated_delta_step(st, 1, *a))
    o, st = step(c["state"], *(c[n][:, 0] for n in
                               ("q", "k", "v", "g", "beta")), active)
    want_o, want_s = gd.gated_delta_recurrence(
        *(c[n][:, :1] for n in ("q", "k", "v", "g", "beta")), c["s0"])
    assert _err(o[active], want_o[active, 0]) < TOL
    assert _err(st[1][active], want_s[active]) < TOL
    # the masked row (a slot mid-prefill rides the batch) and the other
    # layer: bitwise as they were
    assert np.array_equal(np.asarray(st[1, 1]), np.asarray(c["state"][1, 1]))
    assert not np.asarray(st[0]).any()
    # and the jnp form of the step agrees
    o2, st2 = gd.gated_delta_step_reference(
        c["state"], 1, *(c[n][:, 0] for n in ("q", "k", "v", "g", "beta")),
        active)
    assert _err(o2[active], o[active]) < TOL and _err(st2, st) < TOL


def test_shapes_the_tiling_does_not_take_fall_back_to_the_jnp_form():
    rng = np.random.default_rng(1)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32) * 0.3  # noqa: E731,E501
    state = f(1, 2, 2, 16, 16)
    q, k, v = f(2, 2, 16), f(2, 2, 16), f(2, 2, 16)
    beta = jax.nn.sigmoid(f(2, 2))
    for g in (-jnp.abs(f(2, 2)), -jnp.abs(f(2, 2, 16))):
        o, st = gd.gated_delta_step(state, 0, q, k, v, g, beta,
                                    np.array([True, True]))
        want_o, want_s = gd.gated_delta_recurrence(
            q[:, None], k[:, None], v[:, None], g[:, None], beta[:, None],
            state[0])
        assert _err(o, want_o[:, 0]) < TOL and _err(st[0], want_s) < TOL


def test_a_decay_a_channel_that_is_constant_is_the_decay_a_head(case):
    """The scalar rule through the per-channel kernels: ``g [.., H]``
    broadcast over ``dk`` gives what the scalar kernels give (which are
    untouched: the scalar entry points run the code they ran)."""
    c = case
    if c["channel"]:
        pytest.skip("the scalar case's own check")
    wide = jnp.broadcast_to(c["g"][..., None], c["g"].shape + (DK,))
    args = lambda g, t: tuple(c[n][t] if n != "g" else g[t]  # noqa: E731
                              for n in ("q", "k", "v", "g", "beta"))
    o1, s1 = _chunk(c["state"], 2, False, *args(c["g"], 2))
    o2, s2 = _chunk(c["state"], 2, False, *args(wide, 2))
    assert _err(o1, o2) < TOL and _err(s1, s2) < TOL
    step = jax.jit(lambda st, *a: gd.gated_delta_step(st, 1, *a))
    act = np.array([True, True, True])
    o1, s1 = step(c["state"], *args(c["g"], (slice(None), 0)), act)
    o2, s2 = step(c["state"], *args(wide, (slice(None), 0)), act)
    assert _err(o1, o2) < TOL and _err(s1, s2) < TOL


@pytest.mark.parametrize("form", ["kernel", "jnp"])
def test_the_lower_bound_over_a_whole_sub_chunk_stays_finite(case, form):
    """A log-decay of -5 a token on every channel of the first sub-chunk
    (e^-320 across it: one reference row a sub-chunk would need e^+320)
    and mixed decays after it: finite, and the recurrence's numbers."""
    c = case
    if not c["channel"]:
        pytest.skip("the scalar rule's decays are a matrix of differences")
    rng = np.random.default_rng(5)
    g = jnp.asarray(-5.0 * rng.uniform(0, 1, size=(T, H, DK)), jnp.float32)
    g = g.at[:gd.SUB].set(-5.0)
    q, k, v, beta = (c[n][0] for n in ("q", "k", "v", "beta"))
    want_o, want_s = gd.gated_delta_recurrence(
        q[None], k[None], v[None], g[None], beta[None], c["s0"][:1])
    if form == "kernel":
        o, st = _chunk(c["state"], 0, False, q, k, v, g, beta)
        s = st[1, 0]
    else:
        o, s = gd.gated_delta_chunk_reference(
            q[None], k[None], v[None], g[None], beta[None], c["s0"][:1])
        o, s = o[0], s[0]
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(s).all())
    assert _err(o, want_o[0]) < TOL and _err(s, want_s[0]) < 5 * TOL
