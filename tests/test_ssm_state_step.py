"""The state-space decode step over a layer's entries where they lie in
the pool (ops/ssm.py ``step_entries``): the Pallas kernel ``ssm_state_step``
through the interpreter hook against ``scan_step``, the jax.numpy step it
stands in for, at tile-sized widths (Jamba2's: 16 states a channel, 5,120
channels, 129 entries a layer: a ragged last block of entries).

What the chip's compiler makes of it (the kernel once a run of layers, the
pool aliased through the decode program, no copy or temporary of the slab)
is tests/test_paged_decode_on_the_chips_compiler.py's; an engine's logits
and its counter under the hook are tests/test_hybrid_ssm.py's.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import pallas_attention as pa
from paddle_tpu.ops import ssm

N = 16


def entries(n, width, layers=2, seed=0, c_type=jnp.bfloat16):
    """Random inputs of ``n`` entries: (dt, c, bm, cm, held, a, d, pool),
    steps and decays in Jamba2's range, every fifth entry not held."""
    r = np.random.RandomState(seed)
    return (jnp.asarray(r.uniform(0.001, 0.1, (n, width)), jnp.float32),
            jnp.asarray(r.randn(n, width), c_type),
            jnp.asarray(r.randn(n, N), jnp.float32),
            jnp.asarray(r.randn(n, N), jnp.float32),
            jnp.asarray(np.arange(n) % 5 != 2),
            -jnp.exp(jnp.asarray(r.randn(N, width) * 0.5, jnp.float32)),
            jnp.asarray(r.randn(width), jnp.float32),
            jnp.asarray(r.randn(layers, n, N, width), jnp.float32))


def through_the_kernel(monkeypatch, *args, layer=1):
    """``step_entries`` with the hook on, in a function of its own (jit's
    cache knows nothing of the hook)."""
    with monkeypatch.context() as m:
        m.setattr(pa, "_FORCE_INTERPRET", True)
        assert ssm.step_in_kernel(args[-1].shape, args[-1].dtype)
        return jax.jit(lambda *a: ssm.step_entries(*a, jnp.int32(layer)))(
            *args)


def test_the_gate_reads_the_backend_and_the_pools_shape_and_type(
        monkeypatch):
    shape = (26, 129, 16, 5120)
    assert not ssm.step_in_kernel(shape, "float32")          # a CPU
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    assert ssm.step_in_kernel(shape, "float32")
    assert ssm.step_in_kernel((4, 4, 8, 128), jnp.float32)
    for narrow in ((4, 4, 4, 48), (4, 4, 16, 192), (4, 4, 12, 128),
                   (4, 16, 128), (12, 9, 30, 96, 192)):
        assert not ssm.step_in_kernel(narrow, "float32"), narrow
    assert not ssm.step_in_kernel(shape, "bfloat16")


@pytest.mark.parametrize("n, width, block", [
    (8, 256, None), (9, 256, None), (129, 256, None), (8, 5120, None),
    (9, 5120, None), (129, 5120, None), (9, 256, 8), (129, 256, 64)])
def test_the_kernel_is_scan_step_on_every_held_entry(n, width, block,
                                                     monkeypatch):
    """Against ``scan_step`` on the entries' own slab: the states to the
    last bits of the ``exp``, the outputs to the order of a sum over 16;
    ``block``: entries a block (8: float32 ``c``, a ragged block of one
    entry at 9; 129 is ragged at every size), the default where None."""
    if block:
        monkeypatch.setattr(ssm, "STEP_BLOCK_ENTRIES", block)
    args = entries(n, width, layers=2 if width == 256 else 1, seed=n,
                   c_type=jnp.float32 if block == 8 else jnp.bfloat16)
    dt, c, bm, cm, held, a, d, pool = args
    layer = pool.shape[0] - 1
    y, out = through_the_kernel(monkeypatch, *args, layer=layer)
    want_y, want = ssm.scan_step(dt, c, bm, cm, a, d, pool[layer])
    held = np.asarray(held)
    assert y.dtype == out.dtype == jnp.float32 and out.shape == pool.shape
    np.testing.assert_allclose(np.asarray(out[layer])[held],
                               np.asarray(want)[held], rtol=2e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(y)[held], np.asarray(want_y)[held],
                               rtol=1e-5, atol=1e-5)
    # what is not held, and every other layer, as it was
    assert np.array_equal(np.asarray(out[layer])[~held],
                          np.asarray(pool[layer])[~held])
    assert np.array_equal(np.asarray(out[:layer]), np.asarray(pool[:layer]))


@pytest.mark.parametrize("hook", [False, True], ids=["jnp", "kernel"])
def test_an_entry_not_held_keeps_its_bits_nan_among_them(hook, monkeypatch):
    """A NaN entry (``spoil_entry``), an infinite one and one of
    denormals, none of them held, beside held ones: the same bits after
    the step, and nothing of them in a held entry's state or output."""
    dt, c, bm, cm, _, a, d, pool = entries(9, 256, seed=4)
    odd = np.asarray(pool).copy()
    odd[1, 2] = np.nan
    odd[1, 5] = np.inf
    odd[1, 7] = 1e-42
    odd[1, 2, 3, 7] = np.float32(np.frombuffer(
        np.uint32(0x7fc12345).tobytes(), np.float32)[0])   # a NaN's payload
    held = np.ones((9,), bool)
    held[[2, 5, 7]] = False
    args = (dt, c, bm, cm, jnp.asarray(held), a, d, jnp.asarray(odd))
    y, out = through_the_kernel(monkeypatch, *args) if hook \
        else ssm.step_entries(*args, 1)
    out = np.asarray(out)
    assert np.array_equal(out[1, ~held].view(np.uint32),
                          odd[1, ~held].view(np.uint32))
    assert np.isfinite(out[1, held]).all() and np.isfinite(
        np.asarray(y)[held]).all()
    assert not np.array_equal(out[1, held], odd[1, held])


def test_an_entrys_result_is_the_same_bits_alone_and_among_128_others(
        monkeypatch):
    """Entry 77 of 129 all held, and the same entry with nothing else held
    and other inputs beside it (a request alone in the engine's pool, the
    other slots free): the same state and the same output, bit for bit. (A
    pool of another size is another program, which the CPU's compiler may
    round otherwise: not compared.)"""
    dt, c, bm, cm, _, a, d, pool = entries(129, 256, layers=1, seed=7)
    y, out = through_the_kernel(monkeypatch, dt, c, bm, cm,
                                jnp.ones((129,), bool), a, d, pool, layer=0)
    at = 77
    only = jnp.arange(129) == at
    others = entries(129, 256, layers=1, seed=8)

    def mine(x, other):
        return jnp.where(only.reshape((-1,) + (1,) * (x.ndim - 1)), x,
                         other.astype(x.dtype))

    y1, out1 = through_the_kernel(
        monkeypatch, mine(dt, others[0]), mine(c, others[1]),
        mine(bm, others[2]), mine(cm, others[3]), only, a, d, pool, layer=0)
    assert np.array_equal(np.asarray(out1[0, at]), np.asarray(out[0, at]))
    assert np.array_equal(np.asarray(y1[at]), np.asarray(y[at]))
    assert not np.array_equal(np.asarray(out[0, at]), np.asarray(pool[0, at]))
    rest = np.asarray(~only)
    assert np.array_equal(np.asarray(out1[0])[rest], np.asarray(pool[0])[rest])


def test_four_steps_with_the_pool_carried_are_four_steps_of_the_reference(
        monkeypatch):
    """A dispatch's steps over a run of layers: the pool rides the carry of
    two nested loops, aliased into and out of the kernel each time."""
    n, width, layers, steps = 9, 256, 3, 4
    _, _, _, _, held, a, d, pool = entries(n, width, layers=layers, seed=3)
    r = np.random.RandomState(5)
    dt = jnp.asarray(r.uniform(0.001, 0.1, (steps, layers, n, width)),
                     jnp.float32)
    c = jnp.asarray(r.randn(steps, layers, n, width), jnp.bfloat16)
    bm, cm = (jnp.asarray(r.randn(steps, layers, n, N), jnp.float32)
              for _ in range(2))

    def dispatch(pool):
        def a_step(pool, xs):
            def a_layer(pool, lyr_xs):
                lyr, (dt, c, bm, cm) = lyr_xs
                y, pool = ssm.step_entries(dt, c, bm, cm, held, a, d, pool,
                                           lyr)
                return pool, y
            return jax.lax.scan(a_layer, pool, (jnp.arange(layers), xs))
        return jax.lax.scan(a_step, pool, (dt, c, bm, cm))

    with monkeypatch.context() as m:
        m.setattr(pa, "_FORCE_INTERPRET", True)
        out, ys = jax.jit(dispatch)(pool)
    want = np.asarray(pool).copy()
    keep = np.asarray(held)
    for s in range(steps):
        for lyr in range(layers):
            y, state = ssm.scan_step(dt[s, lyr], c[s, lyr], bm[s, lyr],
                                     cm[s, lyr], a, d, jnp.asarray(want[lyr]))
            want[lyr][keep] = np.asarray(state)[keep]
            np.testing.assert_allclose(
                np.asarray(ys[s, lyr])[keep], np.asarray(y)[keep],
                rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(out)[:, ~keep],
                          np.asarray(pool)[:, ~keep])
