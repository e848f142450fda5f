"""`ops.ssm`: the chunked state-space product and the convolution against
the token-by-token recurrence written out here in NumPy float64, a
right-padded chunk, and the decode step's kernel (interpret mode on the
CPU) against its composite, with an idle lane left bit for bit as it was
and the state leaf aliased."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex1_tpu.ops import force_impl
from apex1_tpu.ops.ssm import (causal_conv, pack_state, ssd_chunk, ssm_step,
                               unpack_state)


def _inputs(seed, B, S, H, P, N, published=False):
    """x, dt, A, B, C, D, state. ``published``: A in -1 ... -16 and dt in
    0.001 ... 0.1, as Mamba-2 initialises them: a token's decay is near 1
    and the state remembers hundreds of tokens."""
    ks = jax.random.split(jax.random.key(seed), 8)
    x = jax.random.normal(ks[0], (B, S, H, P))
    if published:
        dt = jnp.exp(jax.random.uniform(ks[1], (B, S, H), minval=np.log(
            1e-3), maxval=np.log(1e-1)))
        A = -jax.random.uniform(ks[2], (H,), minval=1.0, maxval=16.0)
    else:
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
        A = -jnp.exp(0.02 * jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    D = jax.random.normal(ks[5], (H,))
    state = jax.random.normal(ks[6], (B, H, P, N))
    return x, dt, A, Bm, Cm, D, state


def _recurrence(x, dt, A, Bm, Cm, D, state):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t; y_t = S_t C_t + D x_t,
    one token at a time, float64."""
    x, dt, A, Bm, Cm, D, s = (np.asarray(a, np.float64)
                              for a in (x, dt, A, Bm, Cm, D, state))
    ys = []
    for t in range(x.shape[1]):
        s = (np.exp(dt[:, t] * A)[:, :, None, None] * s
             + (dt[:, t, :, None] * x[:, t])[..., None]
             * Bm[:, t, None, None, :])
        ys.append(np.einsum("bhpn,bn->bhp", s, Cm[:, t])
                  + D[:, None] * x[:, t])
    return np.stack(ys, 1), s


@pytest.mark.parametrize("n_chunks", [1, 2, 5])
def test_chunked_product_is_the_recurrence(n_chunks):
    Q = 16
    args = _inputs(n_chunks, 2, n_chunks * Q, 4, 8, 16)
    want_y, want_s = _recurrence(*args)
    y, s = ssd_chunk(*args, chunk=Q)
    np.testing.assert_allclose(y, want_y, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s, want_s, rtol=1e-4, atol=1e-4)


def test_chunk_width_that_does_not_divide_the_run():
    args = _inputs(7, 2, 37, 4, 8, 16)
    want_y, want_s = _recurrence(*args)
    y, s = ssd_chunk(*args, chunk=16)
    assert y.shape == want_y.shape
    np.testing.assert_allclose(y, want_y, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s, want_s, rtol=1e-4, atol=1e-4)


def test_published_initialisation_carries_the_state_over_512_tokens():
    """Decays near 1 (0.85-0.99 a token where the seeded weights give
    0.2-0.75): the state is a sum over tens to hundreds of tokens, ten
    chunks hand it on, and the last chunk's output and the final state
    still match the recurrence. The carry matters here: a last chunk that
    starts from an empty state is off by a large share of the output."""
    args = _inputs(11, 1, 640, 4, 8, 16, published=True)
    want_y, want_s = _recurrence(*args)
    y, s = ssd_chunk(*args, chunk=64)
    scale = np.abs(want_y).max()
    assert np.abs(np.asarray(y) - want_y).max() < 2e-4 * scale
    np.testing.assert_allclose(s, want_s, rtol=2e-4,
                               atol=2e-4 * np.abs(want_s).max())
    x, dt, A, Bm, Cm, D, state = args
    cut = 576
    y_cut, _ = ssd_chunk(x[:, cut:], dt[:, cut:], A, Bm[:, cut:],
                         Cm[:, cut:], D, jnp.zeros_like(state), chunk=64)
    assert np.abs(np.asarray(y_cut[:, :8]) - want_y[:, cut:cut + 8]).max() \
        > 0.05 * scale


def test_padded_chunk_leaves_both_states_where_the_real_tokens_did():
    B, S, H, P, N, K, n_real = 2, 16, 4, 8, 16, 4, 11
    x, dt, A, Bm, Cm, D, state = _inputs(3, B, S, H, P, N)
    real = (x[:, :n_real], dt[:, :n_real], A, Bm[:, :n_real],
            Cm[:, :n_real], D, state)
    want_y, want_s = ssd_chunk(*real, chunk=S)
    y, s = ssd_chunk(x, dt, A, Bm, Cm, D, state, jnp.int32(n_real), chunk=S)
    np.testing.assert_allclose(y[:, :n_real], want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s, want_s, rtol=1e-6, atol=1e-6)
    # without `n_real` the padding decays and feeds the state
    _, wrong = ssd_chunk(x, dt, A, Bm, Cm, D, state, chunk=S)
    assert np.abs(np.asarray(wrong) - np.asarray(want_s)).max() > 0.1

    ks = jax.random.split(jax.random.key(9), 4)
    u = jax.random.normal(ks[0], (B, S, 24))
    w = jax.random.normal(ks[1], (K, 24))
    b = jax.random.normal(ks[2], (24,))
    conv_state = jax.random.normal(ks[3], (B, K - 1, 24))
    want_y, want_c = causal_conv(u[:, :n_real], w, b, conv_state)
    y, c = causal_conv(u, w, b, conv_state, jnp.int32(n_real))
    np.testing.assert_allclose(y[:, :n_real], want_y, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(c, want_c)
    np.testing.assert_array_equal(c, u[:, n_real - K + 1:n_real])
    # fewer real tokens than the state holds: the old inputs move up
    _, c = causal_conv(u, w, b, conv_state, jnp.int32(1))
    np.testing.assert_array_equal(
        c, jnp.concatenate([conv_state[:, 1:], u[:, :1]], axis=1))


def test_causal_conv_is_the_depthwise_convolution_with_its_last_tap_now():
    ks = jax.random.split(jax.random.key(4), 3)
    u = np.asarray(jax.random.normal(ks[0], (1, 9, 5)))
    w = np.asarray(jax.random.normal(ks[1], (4, 5)))
    b = np.asarray(jax.random.normal(ks[2], (5,)))
    y, _ = causal_conv(jnp.asarray(u), jnp.asarray(w), jnp.asarray(b),
                       jnp.zeros((1, 3, 5)))
    for t in range(9):
        want = b + sum(w[3 - j] * u[0, t - j] for j in range(4) if t >= j)
        np.testing.assert_allclose(y[0, t], want, rtol=1e-5, atol=1e-5)


def test_stored_form_round_trips():
    state = _inputs(0, 3, 1, 16, 16, 32)[-1]
    packed = pack_state(state)
    assert packed.shape == (3, 2, 32, 128)     # 8 heads of 16 a row
    np.testing.assert_array_equal(unpack_state(packed, 16), state)
    wide = _inputs(0, 3, 1, 4, 128, 16)[-1]
    assert pack_state(wide).shape == (3, 4, 16, 128)


@pytest.mark.parametrize("idx", [[3, -1, 0, 7, -1], [-1, -1, 2, -1, -1],
                                 [4, 4, 4, 4, 4], [-1, 5, -1, -1, 9],
                                 [-1, -1, -1, -1, -1]])
def test_step_kernel_is_the_composite_and_leaves_idle_lanes(idx):
    """Interpret mode: the kernel's hand-over from one live lane to the
    next (two buffers, fetches a lane ahead) for lanes live at the ends,
    in the middle, alone, all and none."""
    B, H, P, N = 5, 16, 16, 32
    x, dt, A, Bm, Cm, D, state = _inputs(5, B, 1, H, P, N)
    one = (x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], D)
    pool = pack_state(state)
    idx = jnp.asarray(idx, jnp.int32)
    want_y, want_s = ssm_step(*one, pool, idx)              # composite
    with force_impl("pallas"):
        y, s = ssm_step(*one, pool, idx)
    live = np.asarray(idx) >= 0
    np.testing.assert_allclose(np.asarray(y)[live], np.asarray(want_y)[live],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s, want_s, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(s)[~live],
                                  np.asarray(pool)[~live])
    # and the composite is the recurrence
    ref_y, ref_s = _recurrence(x, dt, A, Bm, Cm, D, state)
    np.testing.assert_allclose(np.asarray(want_y)[live], ref_y[live, 0],
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(unpack_state(want_s, P))[live], ref_s[live],
        rtol=1e-4, atol=1e-4)


def test_step_kernel_aliases_the_state_leaf_and_is_named():
    B, H, P, N = 4, 16, 16, 32
    x, dt, A, Bm, Cm, D, state = _inputs(6, B, 1, H, P, N)
    with force_impl("pallas"):
        jaxpr = jax.make_jaxpr(lambda s, i: ssm_step(
            x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], D, s, i))(
                pack_state(state), jnp.zeros((B,), jnp.int32))

    def calls(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for v in eqn.params.values():
                sub = getattr(v, "jaxpr", v)
                if hasattr(sub, "eqns"):
                    yield from calls(sub)

    (call,) = list(calls(jaxpr.jaxpr))
    assert "apex1_ssm_step" in str(call.params["name"]) \
        or "apex1_ssm_step" in str(call)
    # operand 7 (after the three scalar operands and the lane's four small
    # ones) is the state leaf, and it is output 1
    assert tuple(call.params["input_output_aliases"]) == ((7, 1),)
    assert call.invars[7].aval.shape == (B, 2, N, 128)


def test_scalar_index_and_cpu_take_the_composite():
    B, H, P, N = 2, 16, 16, 32
    x, dt, A, Bm, Cm, D, state = _inputs(8, B, 1, H, P, N)
    args = (x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], D, pack_state(state))
    for idx in (0, jnp.zeros((B,), jnp.int32)):
        assert "pallas_call" not in str(jax.make_jaxpr(
            lambda s: ssm_step(*args[:-1], s, idx))(args[-1]))
    with force_impl("pallas"):
        assert "pallas_call" not in str(jax.make_jaxpr(
            lambda s: ssm_step(*args[:-1], s, 0))(args[-1]))
