"""The gated delta rule (ops/delta_rule.py) on the CPU in float32: the
chunked form against the recurrence written out position by position, and
what a cache entry that no position indexes asks of ``window`` and
``step``: padding moves nothing, a window in two calls is the window in
one, a step is a window of one."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import delta_rule as dr

H, DK, DV, TAPS = 3, 4, 10, 4
C = H * (2 * DK + DV)


def rule_inputs(b, t, seed=0, beta=None, g=None):
    rng = np.random.RandomState(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.randn(b, t, H, DK)) * DK ** -0.5
    k = unit(rng.randn(b, t, H, DK))
    v = rng.randn(b, t, H, DV)
    g = -0.3 * np.abs(rng.randn(b, t, H)) if g is None \
        else np.full((b, t, H), g)
    beta = 2 / (1 + np.exp(-rng.randn(b, t, H))) if beta is None \
        else np.full((b, t, H), beta)
    return [x.astype(np.float32) for x in (q, k, v, g, beta)]


def by_hand(q, k, v, g, beta, state):
    """The recurrence, position after position, in float64."""
    state = np.asarray(state, np.float64).copy()
    out = np.zeros(v.shape)
    for t in range(q.shape[1]):
        state = np.exp(g[:, t])[..., None, None] * state
        u = beta[:, t][..., None] * (
            v[:, t] - np.einsum("bhkv,bhk->bhv", state, k[:, t]))
        state = state + k[:, t][..., None] * u[..., None, :]
        out[:, t] = np.einsum("bhkv,bhk->bhv", state, q[:, t])
    return out, state


def close(got, want, tol=1e-4):
    return np.abs(np.asarray(got) - want).max() \
        <= tol * max(1.0, np.abs(want).max())


@pytest.mark.parametrize("t, chunk", [(64, 64), (128, 64), (150, 64),
                                      (37, 16), (5, 8), (1, 64), (96, 32)])
def test_the_chunked_rule_is_the_recurrence(t, chunk):
    """Chunks that do and do not divide the window, a window shorter than
    a chunk, a state carried in."""
    xs = rule_inputs(2, t, seed=t)
    state0 = np.random.RandomState(1).randn(2, H, DK, DV).astype(np.float32)
    want_o, want_s = by_hand(*xs, state0)
    o, s = dr.chunk_rule(*map(jnp.asarray, xs), jnp.asarray(state0),
                         chunk=chunk)
    assert o.shape == (2, t, H, DV) and s.dtype == jnp.float32
    assert close(o, want_o) and close(s, want_s)


def test_the_carried_loop_has_a_step_a_chunk_not_a_position():
    xs = [jax.ShapeDtypeStruct(x.shape, x.dtype)
          for x in rule_inputs(1, 2048)]
    state = jax.ShapeDtypeStruct((1, H, DK, DV), jnp.float32)
    text = jax.jit(dr.chunk_rule).lower(*xs, state).as_text()
    loops = [int(n) for n in __import__("re").findall(
        r"stablehlo.constant dense<(\d+)> : tensor<i32>", text)]
    assert 2048 // dr.CHUNK == 32 and 32 in loops and 2048 not in loops
    assert text.count("stablehlo.while") == 1


@pytest.mark.parametrize("n", [1, 2, 8, 64])
def test_the_unit_lower_inverse(n):
    """Entries of the size ``beta k_i . k_j`` has (larger ones make the
    system itself ill-conditioned, whoever solves it)."""
    rng = np.random.RandomState(n)
    a = np.tril(0.2 * rng.randn(2, 3, n, n), -1).astype(np.float32)
    inv = dr._unit_lower_inverse(jnp.asarray(a))
    assert close(inv, np.linalg.inv(np.eye(n) + a.astype(np.float64)))


def test_write_strength_near_two_and_decay_near_one_stay_finite():
    """The hardest head: every key the same, ``beta`` 1.999 (the state's
    transition has an eigenvalue of -0.999 along it), a decay of 0.9999:
    4,096 positions, as the recurrence. A sum of powers of the chunk's
    matrix would overflow float32 here."""
    q, k, v, g, beta = rule_inputs(1, 4096, seed=3, beta=1.999, g=-1e-4)
    k = np.broadcast_to(k[:, :1], k.shape).copy()
    q = k * DK ** -0.5
    state0 = np.zeros((1, H, DK, DV), np.float32)
    want_o, want_s = by_hand(q, k, v, g, beta, state0)
    o, s = dr.chunk_rule(*map(jnp.asarray, (q, k, v, g, beta, state0)))
    assert np.isfinite(np.asarray(o)).all()
    # float32 against float64 over 4,096 sign flips: 6e-4 and 1e-3
    assert close(o, want_o, 5e-3) and close(s, want_s, 5e-3)


# -- the mixer's recurrent part: window and step ---------------------------

def layer_params(seed=5):
    rng = np.random.RandomState(seed)
    return {"ConvW": jnp.asarray(rng.randn(TAPS, C), jnp.float32) * 0.5,
            "ALog": jnp.log(jnp.linspace(1.0, 16.0, H)),
            "DtBias": jnp.asarray(rng.randn(H), jnp.float32) - 2.0,
            "GNorm": jnp.ones((DV,), jnp.float32)}


def z_of(b, t, seed=2):
    return jnp.asarray(np.random.RandomState(seed).randn(b, t, C + 2 * H),
                       jnp.float32)


def zeros(b):
    return (jnp.zeros((b, H, DK, DV), jnp.float32),
            jnp.zeros((b, TAPS - 1, C), jnp.float32))


def test_the_sizes_are_read_off_the_parameters():
    assert dr._sizes(layer_params()) == (H, DK, DV)


def test_window_is_the_convolution_the_gates_and_the_recurrence():
    p, z = layer_params(), z_of(2, 21)
    o, state, tail = dr.window(p, z, *zeros(2), jnp.asarray([21, 21]), 1e-6)
    x, ab = np.asarray(z[..., :C]), np.asarray(z[..., C:])
    full = np.concatenate([np.zeros((2, TAPS - 1, C)), x], axis=1)
    c = sum(full[:, j:j + 21] * np.asarray(p["ConvW"])[j]
            for j in range(TAPS))
    c = c / (1 + np.exp(-c))

    def l2(y):
        return y / np.sqrt((y * y).sum(-1, keepdims=True) + 1e-6)

    q = l2(c[..., :H * DK].reshape(2, 21, H, DK)) * DK ** -0.5
    k = l2(c[..., H * DK:2 * H * DK].reshape(2, 21, H, DK))
    v = c[..., 2 * H * DK:].reshape(2, 21, H, DV)
    g = -np.exp(np.asarray(p["ALog"])) * np.log1p(
        np.exp(ab[..., :H] + np.asarray(p["DtBias"])))
    beta = 2 / (1 + np.exp(-ab[..., H:]))
    want_o, want_s = by_hand(q, k, v, g, beta, np.zeros((2, H, DK, DV)))
    assert close(o, want_o.reshape(2, 21, -1)) and close(state, want_s)
    assert np.allclose(tail, x[:, -(TAPS - 1):])


@pytest.mark.parametrize("lens", [1, 2, 3, 7, 10])
def test_padding_neither_moves_the_state_nor_enters_the_tail(lens):
    """A row of ``lens`` real positions in a window of 10 leaves the state
    and the tail of the same row alone in a window of ``lens``."""
    p, z = layer_params(), z_of(1, 10)
    o, state, tail = dr.window(p, z, *zeros(1), jnp.asarray([lens]), 1e-6)
    o2, state2, tail2 = dr.window(p, z[:, :lens], *zeros(1),
                                  jnp.asarray([lens]), 1e-6)
    assert np.allclose(state, state2, rtol=1e-5, atol=1e-7)
    assert np.allclose(tail, tail2)
    assert np.allclose(o[:, :lens], o2, rtol=1e-5, atol=1e-7)


def test_rows_of_unequal_lens_are_the_rows_alone():
    p, z = layer_params(), z_of(3, 70)
    lens = jnp.asarray([70, 1, 33])
    o, state, tail = dr.window(p, z, *zeros(3), lens, 1e-6)
    for r, n in enumerate((70, 1, 33)):
        o1, s1, t1 = dr.window(p, z[r:r + 1, :n], *zeros(1),
                               jnp.asarray([n]), 1e-6)
        assert close(o[r, :n], np.asarray(o1[0]))
        assert close(state[r], np.asarray(s1[0]))
        assert np.allclose(tail[r], t1[0])


@pytest.mark.parametrize("cut", [1, 2, 5, 64, 100])
def test_a_window_in_two_calls_is_the_window_in_one(cut):
    """State and tail carried across the cut, wherever it falls in a chunk
    of the rule or in the convolution's reach."""
    p, z = layer_params(), z_of(2, 130)
    whole = jnp.asarray([130, 130])
    o, state, tail = dr.window(p, z, *zeros(2), whole, 1e-6)
    o1, s1, t1 = dr.window(p, z[:, :cut], *zeros(2),
                           jnp.asarray([cut, cut]), 1e-6)
    o2, s2, t2 = dr.window(p, z[:, cut:], s1, t1, whole - cut, 1e-6)
    assert close(jnp.concatenate([o1, o2], axis=1), np.asarray(o))
    assert close(s2, np.asarray(state))
    assert np.allclose(t2, tail)


def test_a_step_is_a_window_of_one():
    p, z = layer_params(), z_of(2, 9)
    _, state, tail = dr.window(p, z[:, :8], *zeros(2), jnp.asarray([8, 8]),
                               1e-6)
    o_w, s_w, t_w = dr.window(p, z[:, 8:], state, tail, jnp.asarray([1, 1]),
                              1e-6)
    # the rows' states as the entries of a one-layer pool, all held
    # and their tails flat, as the tail pool stores an entry
    o_s, s_s, t_s = dr.step(p, z[:, 8], state[None], 0, jnp.ones((2,), bool),
                            tail.reshape(2, -1), 1e-6)
    assert np.allclose(o_s, o_w[:, 0], rtol=1e-5, atol=1e-7)
    assert np.allclose(s_s[0], s_w, rtol=1e-5, atol=1e-7)
    assert np.allclose(t_s.reshape(t_w.shape), t_w)


def test_the_state_is_float32_whatever_the_models_type():
    p = {k: v.astype(jnp.bfloat16) if k in ("ConvW", "GNorm") else v
         for k, v in layer_params().items()}
    z = z_of(1, 5).astype(jnp.bfloat16)
    state0, tail0 = zeros(1)
    o, state, tail = dr.window(p, z, state0, tail0.astype(jnp.bfloat16),
                               jnp.asarray([5]), 1e-6)
    assert (o.dtype, state.dtype, tail.dtype) == (
        jnp.bfloat16, jnp.float32, jnp.bfloat16)
    o, state, tail = dr.step(p, z[:, 0], state0[None], 0,
                             jnp.ones((1,), bool),
                             tail0.astype(jnp.bfloat16).reshape(1, -1), 1e-6)
    assert (o.dtype, state.dtype, tail.dtype) == (
        jnp.bfloat16, jnp.float32, jnp.bfloat16)


def test_the_scopes_a_trace_names():
    p, z = layer_params(), z_of(1, 5)
    text = jax.jit(dr.window, static_argnums=5).lower(
        p, z, *zeros(1), jnp.asarray([5]), 1e-6).as_text(debug_info=True)
    assert "delta/conv" in text and "delta/chunk" in text
    state0, tail0 = zeros(1)
    text = jax.jit(dr.step, static_argnums=6).lower(
        p, z[:, 0], state0[None], 0, jnp.ones((1,), bool),
        tail0.reshape(1, -1), 1e-6).as_text(debug_info=True)
    assert "delta/conv" in text and "delta/step" in text
