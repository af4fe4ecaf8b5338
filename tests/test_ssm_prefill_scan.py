"""The state-space prefill scan as one kernel (ops/ssm.py ``scan_window``:
the Pallas kernel ``ssm_state_scan`` through the interpreter hook) against
the sequential scan by hand in float64 and against the carried ``lax.scan``
it stands in for, at tile-sized widths (Jamba2's 16 states a channel).

What the chip's compiler makes of it (one custom call a run of state
layers in each of Jamba2's prefill programs, no loop over positions) is
tests/test_paged_decode_on_the_chips_compiler.py's; the fallback's own
cases and an engine's logits are tests/test_hybrid_ssm.py's.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import pallas_attention as pa
from paddle_tpu.ops import ssm

N = 16
BLOCK = 32      # positions a block of the kernel in these tests


def inputs(t, b=1, width=256, seed=0, c_type=jnp.float32, n=N):
    """(dt, c, bm, cm, a, d, state0): steps and decays in Jamba2's range."""
    r = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(r.randn(*s), jnp.float32)
    dt = jnp.asarray(np.exp(r.uniform(np.log(1e-3), np.log(0.1),
                                      (b, t, width))), jnp.float32)
    return (dt, f(b, t, width).astype(c_type), f(b, t, n), f(b, t, n),
            -jnp.exp(f(n, width) * 0.5), f(width), f(b, n, width))


def by_hand(dt, c, bm, cm, a, d, state):
    """tests/test_hybrid_ssm.py ``_scan_by_hand``'s equations, float64."""
    dt, c, bm, cm, a, d, state = (
        np.asarray(x.astype(jnp.float32), np.float64)
        for x in (dt, c, bm, cm, a, d, state))
    ys = np.zeros(c.shape)
    for t in range(c.shape[1]):
        state = np.exp(dt[:, t, None, :] * a) * state \
            + (dt[:, t] * c[:, t])[:, None, :] * bm[:, t, :, None]
        ys[:, t] = (state * cm[:, t, :, None]).sum(1) + d * c[:, t]
    return ys, state


@pytest.fixture
def kernel(monkeypatch):
    """``scan_window`` with the hook on and blocks of BLOCK positions x
    128 channels, in a function of its own a call (jit's cache knows
    nothing of the hook)."""
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(ssm, "SCAN_KERNEL_POSITIONS", BLOCK)
    monkeypatch.setattr(ssm, "SCAN_KERNEL_CHANNELS", 128)

    def run(*args):
        assert ssm.scan_in_kernel(args[0].shape[2], args[4].shape[0],
                                  args[6].dtype)
        return jax.jit(lambda *a: ssm.scan_window(*a))(*args)

    return run


def test_the_gate_reads_the_backend_the_widths_and_the_states_type(
        monkeypatch):
    assert not ssm.scan_in_kernel(5120, 16, "float32")          # a CPU
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    assert ssm.scan_in_kernel(5120, 16, "float32")
    assert ssm.scan_in_kernel(128, 8, jnp.float32)
    for width, n in ((48, 4), (192, 16), (128, 12), (5, 3)):
        assert not ssm.scan_in_kernel(width, n, "float32"), (width, n)
    assert not ssm.scan_in_kernel(5120, 16, "bfloat16")


def test_a_shape_that_fails_the_gate_takes_the_carried_scan(monkeypatch):
    """HYBRID_SSM_TINY's widths (48 channels, 4 states) with the hook on:
    no kernel in the program, a loop over the positions in its place."""
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    narrow = str(jax.make_jaxpr(lambda *a: ssm.scan_window(*a))(
        *inputs(12, width=48, n=4)))
    assert "pallas_call" not in narrow and "scan[" in narrow
    wide = str(jax.make_jaxpr(lambda *a: ssm.scan_window(*a))(*inputs(12)))
    # its own loop over a block's positions is inside the kernel
    assert wide.index("pallas_call") < wide.index("scan[")
    assert "ssm_state_scan" in wide


@pytest.mark.parametrize("width", [128, 384], ids=["1tile", "3tiles"])
@pytest.mark.parametrize("t", [1, 7, BLOCK, BLOCK + 1, 3 * BLOCK])
def test_the_kernel_is_the_sequential_scan(t, width, kernel):
    """Lengths that do and do not divide the block of positions, one
    channel tile and several: against float64 by hand to float32's
    rounding, and against the carried scan to the last bits of the
    ``exp`` and the order of a sum over 16."""
    args = inputs(t, width=width, seed=t)
    y, state = kernel(*args)
    assert y.shape == args[1].shape and y.dtype == args[1].dtype
    assert state.shape == args[6].shape and state.dtype == jnp.float32
    want_y, want_state = by_hand(*args)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(state, want_state, rtol=1e-5, atol=2e-5)
    ref_y, ref_state = ssm.scan_window(*args)        # the lax.scan: a CPU
    np.testing.assert_allclose(state, ref_state, rtol=2e-6, atol=1e-6)
    np.testing.assert_allclose(y, ref_y, rtol=1e-5, atol=1e-5)


def test_a_16_bit_input_leaves_in_its_own_type(kernel):
    """``c`` in bfloat16, as the cell's: ``y`` leaves the kernel in that
    type (``window`` cast it there at once; the rounding is the same, one
    place earlier), the state stays float32."""
    args = inputs(BLOCK + 5, c_type=jnp.bfloat16, seed=3)
    y, state = kernel(*args)
    assert y.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    want_y, want_state = by_hand(*args)
    np.testing.assert_allclose(state, want_state, rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(y, np.float64), want_y,
                               rtol=1e-2, atol=1e-2)
    ref_y, _ = ssm.scan_window(*args)
    assert ref_y.dtype == jnp.bfloat16


def test_rows_are_independent(kernel):
    """Three rows at once, and each of them alone: the same bits."""
    args = inputs(BLOCK + 9, b=3, seed=5)
    y, state = kernel(*args)
    for row in range(3):
        one = tuple(x[row:row + 1] if x.ndim == 3 else x for x in args)
        y1, s1 = kernel(*one)
        assert np.array_equal(y[row], y1[0]), row
        assert np.array_equal(state[row], s1[0]), row


@pytest.mark.parametrize("cut", [1, 16, BLOCK, BLOCK + 7])
def test_a_window_in_two_calls_is_the_window_in_one(cut, kernel):
    """The state is all that crosses a chunk boundary (``state0``: the
    chunk program's path). To the last bits of the ``exp``: a window of
    another length is another program, which the CPU's compiler may round
    otherwise."""
    args = inputs(2 * BLOCK + 3, b=2, seed=cut)
    y, state = kernel(*args)
    head = tuple(x[:, :cut] for x in args[:4])
    rest = tuple(x[:, cut:] for x in args[:4])
    y1, s1 = kernel(*head, *args[4:])
    y2, s2 = kernel(*rest, *args[4:6], s1)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], axis=1), y,
                               rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(s2, state, rtol=2e-6, atol=1e-6)


def test_a_state_handed_in_is_where_the_window_starts(kernel):
    """``state0`` is the kernel's start at every channel tile and row:
    twice the same window from the state the first left, against one
    window of twice the positions, by hand."""
    args = inputs(BLOCK, b=2, width=256, seed=21)
    _, s1 = kernel(*args)
    y2, s2 = kernel(*args[:6], s1)
    twice = tuple(jnp.concatenate([x, x], axis=1) for x in args[:4])
    want_y, want_state = by_hand(*twice, *args[4:])
    np.testing.assert_allclose(y2, want_y[:, BLOCK:], rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(s2, want_state, rtol=1e-5, atol=2e-5)


def test_a_position_with_no_step_leaves_the_state_bit_for_bit(kernel):
    """``window`` hands a padded position ``dt = 0``: exp(0) = 1 and the
    input is 0 x c x B, so whatever finite values the padding holds, the
    state's bits stay, the padding's own ``y`` is all that reads them, and
    a NaN in what ``y`` alone reads (the output map) stays in that ``y``.
    The mask stays outside the kernel: a NaN in ``c`` or in the INPUT map
    there is 0 x NaN in ``scan_step``'s equations, in the kernel as in
    the carried scan."""
    t, real = BLOCK + 8, 11
    dt, c, bm, cm, a, d, state0 = inputs(t, seed=9)
    pad = np.arange(t) >= real
    dt = jnp.where(pad[None, :, None], 0.0, dt)
    c = jnp.where(pad[None, :, None], 1e30, c)
    bm = jnp.where(pad[None, :, None], -1e8, bm)
    cm = cm.at[:, real + 2].set(jnp.nan)
    y, state = kernel(dt, c, bm, cm, a, d, state0)
    y_real, s_real = kernel(dt[:, :real], c[:, :real], bm[:, :real],
                            cm[:, :real], a, d, state0)
    assert np.array_equal(np.asarray(state).view(np.uint32),
                          np.asarray(s_real).view(np.uint32))
    assert np.array_equal(y[:, :real], y_real)
    assert np.isnan(np.asarray(y[:, real + 2])).all()
    assert np.isfinite(np.asarray(y[:, real + 3:])).all()
    # and the equations' own answer to a NaN input there, in both forms
    spoiled = c.at[:, real + 1, 7].set(jnp.nan)
    for scan in (kernel, ssm.scan_window):
        _, s = scan(dt, spoiled, bm, cm, a, d, state0)
        s = np.asarray(s)
        assert np.isnan(s[0, :, 7]).all()
        assert np.isfinite(np.delete(s, 7, axis=2)).all()
