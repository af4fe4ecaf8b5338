"""The delta rule's decode step over a layer's entries where they lie in the
pool (ops/delta_rule.py ``step_entries``): the Pallas kernel
``delta_state_step`` through the interpreter hook against ``rule_step``, the
jax.numpy step it stands in for, at tile-sized states (Ling's: 128 x 128 a
head, a decay a channel; and 8 x 128, the smallest the gate admits) and at
sizes the interpreter can afford: 9 and 19 entries of 4 heads, a ragged last
block of entries; 16 heads, two blocks of heads.

What the chip's compiler makes of it (the kernel once a run of layers, the
pool aliased through the decode program, no copy or temporary of the slab)
is tests/test_paged_decode_on_the_chips_compiler.py's; an engine's logits
and its counter under the hook are tests/test_kda_latent_moe.py's.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import delta_rule as dr
from paddle_tpu.ops import pallas_attention as pa


def entries(n, heads, dk, dv, channel, layers=2, seed=0, beta_max=2.0,
            floor=-5.0):
    """Random inputs of ``n`` entries: (q, k, v, g, beta, held, pool) as
    ``_heads`` and ``gates`` give them (queries and keys normed a head, the
    gates through ``gates`` itself with Kimi's lower bound or with none),
    every fifth entry not held."""
    r = np.random.RandomState(seed)
    p = {"ALog": jnp.asarray(r.randn(heads) * 0.3, jnp.float32),
         "DtBias": jnp.asarray(
             r.randn(heads * dk if channel else heads) * 0.3, jnp.float32)}
    ab = jnp.asarray(r.randn(n, (heads * dk if channel else heads) + heads),
                     jnp.float32)
    g, beta = dr.gates(p, ab, beta_max=beta_max, floor=floor)
    q, k = (dr._l2(jnp.asarray(r.randn(n, heads, dk), jnp.float32))
            for _ in range(2))
    return (q * dk ** -0.5, k,
            jnp.asarray(r.randn(n, heads, dv), jnp.float32), g, beta,
            jnp.asarray(np.arange(n) % 5 != 2),
            jnp.asarray(r.randn(layers, n, heads, dk, dv), jnp.float32))


def through_the_kernel(monkeypatch, *args, layer=1):
    """``step_entries`` with the hook on, in a function of its own (jit's
    cache knows nothing of the hook)."""
    with monkeypatch.context() as m:
        m.setattr(pa, "_FORCE_INTERPRET", True)
        assert dr.step_in_kernel(args[-1].shape, args[-1].dtype)
        return jax.jit(lambda *a: dr.step_entries(*a, jnp.int32(layer)))(
            *args)


def test_the_gate_reads_the_backend_and_the_pools_shape_and_type(
        monkeypatch):
    shape = (5, 257, 32, 128, 128)
    assert not dr.step_in_kernel(shape, "float32")           # a CPU
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    assert dr.step_in_kernel(shape, "float32")
    assert dr.step_in_kernel((2, 9, 4, 8, 128), jnp.float32)
    for narrow in ((12, 9, 30, 96, 192), (5, 4, 2, 4, 128), (5, 4, 2, 4, 6),
                   (5, 257, 32, 12, 128), (257, 32, 128, 128),
                   (26, 129, 16, 5120)):
        assert not dr.step_in_kernel(narrow, "float32"), narrow
    assert not dr.step_in_kernel(shape, "bfloat16")


@pytest.mark.parametrize("n, heads, dk, channel, gate, block", [
    (9, 4, 128, True, (2.0, -5.0), None), (9, 4, 128, False, (2.0, None), None),
    (19, 4, 8, True, (1.0, -5.0), None), (19, 4, 8, False, (2.0, None), None),
    (9, 4, 8, True, (2.0, None), 2), (5, 16, 8, True, (1.0, -5.0), None),
    (5, 16, 8, False, (2.0, -5.0), 1)])
def test_the_kernel_is_rule_step_on_every_held_entry(
        n, heads, dk, channel, gate, block, monkeypatch):
    """Against ``rule_step`` on the entries' own slab, for a decay a channel
    and a decay a head, ``beta_max`` / ``floor`` as ``gates`` gives them:
    the states to the order of one product's rounding, the outputs to the
    order of a sum over dk (the kernel takes both reductions off the
    decayed state). ``block``: entries a block, the default where None (4:
    9 and 19 entries are ragged at it; 5 entries of 16 heads: two blocks of
    heads, the columns rolled)."""
    if block:
        monkeypatch.setattr(dr, "STEP_BLOCK_ENTRIES", block)
    args = entries(n, heads, dk, 128, channel, seed=n + dk,
                   beta_max=gate[0], floor=gate[1])
    q, k, v, g, beta, held, pool = args
    assert g.ndim == (3 if channel else 2)
    layer = pool.shape[0] - 1
    o, out = through_the_kernel(monkeypatch, *args, layer=layer)
    want_o, want = dr.rule_step(q, k, v, g, beta, pool[layer])
    held = np.asarray(held)
    assert o.dtype == out.dtype == jnp.float32 and out.shape == pool.shape
    np.testing.assert_allclose(np.asarray(out[layer])[held],
                               np.asarray(want)[held], rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(np.asarray(o)[held], np.asarray(want_o)[held],
                               rtol=1e-5, atol=1e-5)
    # what is not held, and every other layer, as it was
    assert np.array_equal(np.asarray(out[layer])[~held],
                          np.asarray(pool[layer])[~held])
    assert np.array_equal(np.asarray(out[:layer]), np.asarray(pool[:layer]))


def a_layer(n, heads, dk, dv, channel, seed=0, d_conv=4):
    """A layer's parameters and a step's inputs as ``step`` takes them: (p,
    z [n, C + A + H], tail0 [n, (k - 1) C])."""
    r = np.random.RandomState(seed)
    c = heads * (2 * dk + dv)
    a = heads * dk if channel else heads
    p = {"ConvW": jnp.asarray(r.randn(d_conv, c) * 0.5, jnp.float32),
         "ALog": jnp.asarray(r.randn(heads) * 0.3, jnp.float32),
         "DtBias": jnp.asarray(r.randn(a) * 0.3, jnp.float32),
         "GNorm": jnp.ones((dv,), jnp.float32)}
    return (p, jnp.asarray(r.randn(n, c + a + heads), jnp.float32),
            jnp.asarray(r.randn(n, (d_conv - 1) * c), jnp.float32))


@pytest.mark.parametrize("channel, gate", [
    (True, dict(beta_max=1.0, floor=-5.0)), (False, {})],
    ids=["kda", "delta"])
def test_step_through_the_kernel_is_step_outside_it(channel, gate,
                                                    monkeypatch):
    """``step`` as the runner calls it, the hook on and off: the same
    outputs and states of the held entries, the same tails, and on both
    sides an entry not held keeps its bits: a NaN entry (``spoil_entry``),
    an infinite one and one of denormals beside held ones, and nothing of
    them in a held entry's state or output."""
    n, heads, dk, dv = 9, 4, 8, 128
    p, z, tail0 = a_layer(n, heads, dk, dv, channel, seed=4)
    odd = np.random.RandomState(5).randn(2, n, heads, dk, dv).astype(
        np.float32)
    odd[1, 2] = np.nan
    odd[1, 5] = np.inf
    odd[1, 7] = 1e-42
    odd[1, 2, 3, 3, 7] = np.float32(np.frombuffer(
        np.uint32(0x7fc12345).tobytes(), np.float32)[0])   # a NaN's payload
    held = np.ones((n,), bool)
    held[[2, 5, 7]] = False

    def run(hook):
        with monkeypatch.context() as m:
            m.setattr(pa, "_FORCE_INTERPRET", hook)
            assert dr.step_in_kernel(odd.shape, odd.dtype) is hook
            return jax.jit(lambda *a: dr.step(
                p, a[0], a[1], jnp.int32(1), a[2], a[3], 1e-6, scope="kda",
                **gate))(z, jnp.asarray(odd), jnp.asarray(held), tail0)

    (o, out, tail), (want_o, want, want_tail) = run(True), run(False)
    for got in (np.asarray(out), np.asarray(want)):
        assert np.array_equal(got[1, ~held].view(np.uint32),
                              odd[1, ~held].view(np.uint32))
        assert np.array_equal(got[0], odd[0])
        assert np.isfinite(got[1, held]).all()
        assert not np.array_equal(got[1, held], odd[1, held])
    assert np.isfinite(np.asarray(o)[held]).all()
    np.testing.assert_allclose(np.asarray(out)[1, held],
                               np.asarray(want)[1, held], rtol=2e-6,
                               atol=2e-6)
    np.testing.assert_allclose(np.asarray(o)[held], np.asarray(want_o)[held],
                               rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(tail), np.asarray(want_tail))


@pytest.mark.parametrize("channel", [True, False], ids=["channel", "head"])
def test_an_entrys_result_is_the_same_bits_alone_and_among_others(
        channel, monkeypatch):
    """Entry 13 of 19 all held, and the same entry with nothing else held
    and other inputs beside it (a request alone in the engine's pool, the
    other slots free): the same state and the same output, bit for bit."""
    n, at = 19, 13
    q, k, v, g, beta, _, pool = entries(n, 4, 8, 128, channel, layers=1,
                                        seed=7)
    o, out = through_the_kernel(monkeypatch, q, k, v, g, beta,
                                jnp.ones((n,), bool), pool, layer=0)
    only = jnp.arange(n) == at
    others = entries(n, 4, 8, 128, channel, layers=1, seed=8)

    def mine(x, other):
        return jnp.where(only.reshape((-1,) + (1,) * (x.ndim - 1)), x, other)

    o1, out1 = through_the_kernel(
        monkeypatch, *(mine(x, y) for x, y in zip((q, k, v, g, beta),
                                                  others)),
        only, pool, layer=0)
    assert np.array_equal(np.asarray(out1[0, at]), np.asarray(out[0, at]))
    assert np.array_equal(np.asarray(o1[at]), np.asarray(o[at]))
    assert not np.array_equal(np.asarray(out[0, at]), np.asarray(pool[0, at]))
    rest = np.asarray(~only)
    assert np.array_equal(np.asarray(out1[0])[rest], np.asarray(pool[0])[rest])


def test_four_steps_with_the_pool_carried_are_four_steps_of_the_reference(
        monkeypatch):
    """A dispatch's steps over a run of layers: the pool rides the carry of
    two nested loops, aliased into and out of the kernel each time."""
    n, heads, dk, dv, layers, steps = 9, 4, 8, 128, 3, 4
    _, _, _, _, _, held, pool = entries(n, heads, dk, dv, True, layers=layers,
                                        seed=3)
    xs = [entries(n, heads, dk, dv, True, layers=1, seed=10 + i)[:5]
          for i in range(steps * layers)]
    q, k, v, g, beta = (
        jnp.stack([x[i] for x in xs]).reshape(
            (steps, layers) + xs[0][i].shape) for i in range(5))

    def dispatch(pool):
        def a_step(pool, xs):
            def a_layer(pool, lyr_xs):
                lyr, (q, k, v, g, beta) = lyr_xs
                o, pool = dr.step_entries(q, k, v, g, beta, held, pool, lyr)
                return pool, o
            return jax.lax.scan(a_layer, pool, (jnp.arange(layers), xs))
        return jax.lax.scan(a_step, pool, (q, k, v, g, beta))

    with monkeypatch.context() as m:
        m.setattr(pa, "_FORCE_INTERPRET", True)
        out, os_ = jax.jit(dispatch)(pool)
    want = np.asarray(pool).copy()
    keep = np.asarray(held)
    for s in range(steps):
        for lyr in range(layers):
            o, state = dr.rule_step(q[s, lyr], k[s, lyr], v[s, lyr],
                                    g[s, lyr], beta[s, lyr],
                                    jnp.asarray(want[lyr]))
            want[lyr][keep] = np.asarray(state)[keep]
            np.testing.assert_allclose(
                np.asarray(os_[s, lyr])[keep], np.asarray(o)[keep],
                rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(out)[:, ~keep],
                          np.asarray(pool)[:, ~keep])


def test_outside_the_gate_step_is_the_jax_numpy_step(monkeypatch):
    """A pool the gate refuses whatever the backend (Olmo-Hybrid's widths
    cut down: dv a lane tile and a half), the hook on: the slice,
    ``rule_step``, the ``where`` and the set."""
    n, heads, dk, dv = 5, 2, 96, 192
    p, z, tail0 = a_layer(n, heads, dk, dv, False, seed=2)
    pool = jnp.asarray(np.random.RandomState(1).randn(3, n, heads, dk, dv),
                       jnp.float32)
    held = jnp.asarray(np.arange(n) != 3)
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    assert not dr.step_in_kernel(pool.shape, pool.dtype)
    o, out, _ = dr.step(p, z, pool, 2, held, tail0, 1e-6)
    c, _ = dr.ssm.conv_step(
        z[:, :-2 * heads], tail0.reshape(n, 3, -1), p["ConvW"],
        jnp.zeros((heads * (2 * dk + dv),), jnp.float32))
    want_o, want = dr.rule_step(*dr._heads(p, c),
                                *dr.gates(p, z[:, -2 * heads:]), pool[2])
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o).reshape(
        n, -1), rtol=1e-6, atol=1e-6)
    keep = np.asarray(held)
    np.testing.assert_allclose(np.asarray(out[2])[keep],
                               np.asarray(want)[keep], rtol=1e-6, atol=1e-6)
    assert np.array_equal(np.asarray(out[2])[~keep],
                          np.asarray(pool[2])[~keep])
    assert np.array_equal(np.asarray(out[:2]), np.asarray(pool[:2]))
