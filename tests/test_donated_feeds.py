"""Executor.run(donate_feeds=...): the feeds a caller names are given up
to the dispatch (XLA writes the fetch of their shape into their buffer),
the feeds beside them and the persistables the program only reads are
not, and with no feed named the executor builds the jit it always built.
serving/decode_engine.py hands its cache pools over this way."""
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid


@pytest.fixture
def doubled():
    """y = 2 x + w k and z = 3 v, with x, v of one shape and type."""
    main_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup_p):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        v = fluid.layers.data(name="v", shape=[8], dtype="float32")
        k = fluid.layers.data(name="k", shape=[8], dtype="float32")
        y = fluid.layers.scale(x, scale=2.0) + fluid.layers.fc(
            k, size=8, bias_attr=False)
        z = fluid.layers.scale(v, scale=3.0)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup_p)
    return exe, main_p.clone(for_test=True), [y.name, z.name]


def _feeds(seed=0):
    rng = np.random.RandomState(seed)
    return {n: rng.standard_normal((4, 8)).astype(np.float32)
            for n in ("x", "v", "k")}


def test_a_named_feed_is_consumed_and_nothing_else(doubled):
    exe, program, fetch = doubled
    host = _feeds()
    want = exe.run(program, feed=host, fetch_list=fetch)
    dev = {n: jnp.asarray(a) for n, a in host.items()}
    weights = [fluid.global_scope().find_var(n)
               for n, var in program.global_block().vars.items()
               if var.persistable]
    with warnings.catch_warnings():
        warnings.simplefilter("error")       # no donation left unused
        got = exe.run(program, feed=dev, fetch_list=fetch,
                      donate_feeds=("x", "v"))
    assert dev["x"].is_deleted() and dev["v"].is_deleted()
    assert not dev["k"].is_deleted()
    assert weights and not any(w.is_deleted() for w in weights)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_no_feed_named_is_the_jit_as_it_was(doubled):
    exe, program, fetch = doubled
    dev = {n: jnp.asarray(a) for n, a in _feeds(1).items()}
    exe.run(program, feed=dev, fetch_list=fetch)
    assert not any(a.is_deleted() for a in dev.values())
    mine = [k for k in exe.compile_cache_keys() if k[0] == program.uid]
    assert [k[-1] for k in mine] == [()]
    stats = exe.compiled_stats(program, feed=dev, fetch_list=fetch,
                               top_k=0)
    assert stats["aliased_bytes"] == 0
    # naming feeds is another executable beside it, not in place of it
    exe.run(program, feed=dict(dev), fetch_list=fetch, donate_feeds=("v",))
    assert sorted(k[-1] for k in exe.compile_cache_keys()
                  if k[0] == program.uid) == [(), ("v",)]
    assert dev["v"].is_deleted() and not dev["x"].is_deleted()


def test_feeds_of_one_shape_alias_in_the_order_named(doubled):
    """Named in fetch order, x goes to y and v to z: all of both aliased.
    (Matched the other way round XLA would have to copy one across.)"""
    exe, program, fetch = doubled
    dev = {n: jnp.asarray(a) for n, a in _feeds(2).items()}
    stats = exe.compiled_stats(program, feed=dev, fetch_list=fetch,
                               top_k=0, donate_feeds=("x", "v"))
    assert stats["aliased_bytes"] == dev["x"].nbytes + dev["v"].nbytes
    assert not any(a.is_deleted() for a in dev.values())   # lowered only


def test_a_name_the_feed_does_not_hold_is_refused(doubled):
    exe, program, fetch = doubled
    with pytest.raises(KeyError, match="donate_feeds"):
        exe.run(program, feed=_feeds(3), fetch_list=fetch,
                donate_feeds=("pool",))
