"""A prefill window's routed experts as one grouped kernel
(ops/moe.py ``moe_grouped_rows``), through the Pallas interpreter against
the sorted ``jax.lax.ragged_dot`` form it stands in for on the chip: the
same float32 sums in another order (1e-6), a row's result that of the row
alone, the work list that says which expert's blocks a step holds, the
gate's refusals, and the engine's count of the dispatches behind it."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.models.hybrid_moe import HYBRID_GATED_TINY
from paddle_tpu.ops import moe
from paddle_tpu.ops import pallas_attention as PA
from paddle_tpu.serving.decode_engine import DecodeConfig, DecodeEngine

from benchmark.builders.serve_blocks import make_weights
from benchmark.builders.serve_hybrid import engine_logits, stand_ins

from decode_forms import rel_l2

TILE = moe.GROUPED_ROW_TILE
MIXED = [70, 90, 0, 150, 40, 0, 30, 4]
# the rows of each of 8 groups, the stack's layer, the hidden width and
# its tile
CASES = {
    "without_layer": dict(sizes=MIXED),
    "with_layer": dict(sizes=MIXED, layer=2),
    "an_expert_nobody_reached": dict(sizes=[0, TILE, 0, 0, TILE + 2, 0, 0,
                                            0], layer=1),
    "a_group_of_one_row": dict(sizes=[100, 1, 100, 0, 1, 54, 0, 0]),
    "a_group_over_two_tiles": dict(sizes=[20, 2 * TILE + 44, 10, 0, 0, 0,
                                          0, 54], layer=1),
    "a_group_that_starts_off_a_tile": dict(sizes=[5, TILE, TILE, 0, 0, 0,
                                                  0, TILE - 5]),
    "rows_short_of_a_tile": dict(sizes=[70, 90, 0, 50, 0, 0, 0, 3],
                                 layer=0),
    "two_hidden_tiles": dict(sizes=MIXED, layer=1, f=256, hidden_tile=128),
}


def _weights(rng, layer, e, d, f):
    shape = (3,) if layer is not None else ()
    return [jnp.asarray(0.1 * rng.randn(*shape, e, *dims), jnp.float32)
            for dims in ((d, f), (d, f), (f, d))]


def _ragged(xs, sizes, w_gate, w_up, w_down):
    """The parent's form of the same rows: three grouped products."""
    g = jax.lax.ragged_dot(xs, w_gate, sizes)
    u = jax.lax.ragged_dot(xs, w_up, sizes)
    return jax.lax.ragged_dot((g * jax.nn.sigmoid(g)) * u, w_down, sizes)


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_grouped_kernel_is_the_three_ragged_products(case, monkeypatch):
    spec = dict(dict(layer=None, f=128, hidden_tile=None), **CASES[case])
    layer, f = spec["layer"], spec["f"]
    sizes = np.asarray(spec["sizes"], np.int32)
    rng = np.random.RandomState(11)
    d, p = 128, int(sizes.sum())
    xs = jnp.asarray(rng.randn(p, d), jnp.float32)
    w = _weights(rng, layer, sizes.size, d, f)
    want = _ragged(xs, jnp.asarray(sizes),
                   *(m if layer is None else m[layer] for m in w))

    # the work list: every expert reached and no other, in ascending
    # order, its blocks asked for ONCE (the steps behind the last item
    # ask for nothing new)
    n_tiles = -(-p // TILE)
    expert, tile, starts, n = (np.asarray(a) for a in moe._work_items(
        jnp.asarray(sizes), TILE, n_tiles))
    assert expert.size == n_tiles + sizes.size - 1 and n[0] <= expert.size
    assert sorted(set(expert[:n[0]])) == list(np.flatnonzero(sizes))
    assert np.count_nonzero(np.diff(expert)) + 1 == np.count_nonzero(sizes)
    assert (np.diff(tile) >= 0).all() and set(tile) == set(range(n_tiles))
    assert list(starts) == [0] + list(np.cumsum(sizes))
    spans = [(starts[e + 1] - 1) // TILE - starts[e] // TILE + 1
             for e in np.flatnonzero(sizes)]
    assert n[0] == sum(spans)
    if case == "a_group_over_two_tiles":
        assert max(spans) > 2
    if case == "a_group_that_starts_off_a_tile":
        assert all(starts[e] % TILE for e in (1, 2, 7))

    monkeypatch.setattr(PA, "_FORCE_INTERPRET", True)
    run = jax.jit(lambda xs, sizes, *w: moe.moe_grouped_rows(
        xs, sizes, *w, layer=layer, hidden_tile=spec["hidden_tile"]))
    got = run(xs, jnp.asarray(sizes), *w)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert rel_l2(got, want).max() < 1e-5
    # a row alone is the row among the others, bit for bit: the first and
    # the last row of every group
    for e in np.flatnonzero(sizes):
        for r in {starts[e], starts[e + 1] - 1}:
            alone = run(xs[r:r + 1], jnp.asarray(np.eye(
                sizes.size, dtype=np.int32)[e]), *w)
            assert np.array_equal(np.asarray(alone[0]),
                                  np.asarray(got[r])), (e, r)


@pytest.mark.parametrize("layer", [None, 1], ids=["without_layer",
                                                  "with_layer"])
def test_a_window_of_tokens_goes_through_the_kernel_and_back(layer,
                                                             monkeypatch):
    """``moe_apply_sorted`` over more rows than the few-rows kernel takes:
    the sort, the gather, the kernel and the un-sort against the form of a
    CPU, expert 5 reached by nobody."""
    rng = np.random.RandomState(7)
    t, k, e, d, f = 150, 3, 8, 128, 256
    x = jnp.asarray(rng.randn(t, d), jnp.float32)
    w = _weights(rng, layer, e, d, f)
    idx = jnp.asarray(np.stack([rng.choice([0, 1, 2, 3, 4, 6, 7], k,
                                           replace=False)
                                for _ in range(t)]), jnp.int32)
    gates = jnp.asarray(rng.rand(t, k), jnp.float32)
    assert not moe.grouped_rows_usable(t, w[0], w[2])
    want = moe.moe_apply_sorted(x, idx, gates, *w, layer=layer)
    monkeypatch.setattr(PA, "_FORCE_INTERPRET", True)
    assert moe.grouped_rows_usable(t, w[0], w[2])
    jaxpr = str(jax.make_jaxpr(lambda *a: moe.moe_apply_sorted(
        *a, layer=layer))(x, idx, gates, *w))
    assert "moe_grouped_rows" in jaxpr and "ragged_dot" not in jaxpr
    got = jax.jit(lambda *a: moe.moe_apply_sorted(*a, layer=layer))(
        x, idx, gates, *w)
    assert rel_l2(got, want).max() < 1e-5


def _abstract(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


LAGUNA = (_abstract((3, 256, 2048, 512)), _abstract((3, 256, 512, 2048)))
# what the gate refuses: (tokens, w_gate, w_down, held, the hook)
REFUSALS = {
    "a_share": (2048, *LAGUNA, (0, 256), True),
    "a_decode_steps_rows": (moe.FEW_ROWS, *LAGUNA, None, True),
    "a_cpu_without_the_hook": (2048, *LAGUNA, None, False),
    "a_model_width_off_the_lane_tile": (
        2048, _abstract((8, 2000, 512)), _abstract((8, 512, 2000)), None,
        True),
    "a_hidden_width_off_the_lane_tile": (
        2048, _abstract((8, 2048, 500)), _abstract((8, 500, 2048)), None,
        True),
    "two_types": (2048, LAGUNA[0], _abstract((3, 256, 512, 2048),
                                             jnp.float32), None, True),
    # DeepSeek-V3's 7,168 x 2,048: 176 MB twice over
    "two_experts_over_the_budget": (
        2048, _abstract((16, 7168, 2048)), _abstract((16, 2048, 7168)),
        None, True),
}


@pytest.mark.parametrize("why", sorted(REFUSALS))
def test_the_gate_refuses(why, monkeypatch):
    t, w_gate, w_down, held, hook = REFUSALS[why]
    monkeypatch.setattr(PA, "_FORCE_INTERPRET", True)
    assert moe.grouped_rows_usable(2048, *LAGUNA)
    assert moe.grouped_rows_usable(moe.FEW_ROWS + 1, *LAGUNA)
    assert moe.grouped_rows_usable(            # xing4's: 44 MB twice over
        2048, _abstract((64, 3584, 1024)), _abstract((64, 1024, 3584)))
    assert not moe.few_rows_usable(moe.FEW_ROWS + 1, *LAGUNA)
    monkeypatch.setattr(PA, "_FORCE_INTERPRET", hook)
    assert not moe.grouped_rows_usable(t, w_gate, w_down, held)


# Laguna-XS.2's mechanisms small, its model and hidden widths whole lane
# tiles: where the gate admits the kernel
WIDE = dataclasses.replace(HYBRID_GATED_TINY, name="hybrid-gated-wide",
                           dim=128, expert_hidden=128)
ENGINE = dict(max_batch=2, prompt_buckets=(144, 432), max_new_tokens=4,
              page_size=4, decode_block=2, chunk_size=144, prefill_batch=1,
              default_timeout_s=300.0)


def _scope():
    w = {k: v if k.endswith("norm") else v * 10
         for k, v in make_weights(WIDE, 3).items()}
    w.update(stand_ins(WIDE, WIDE.param_shapes()))
    scope = fluid.Scope()
    for name, value in w.items():
        scope.set(name, value)
    return scope


@pytest.mark.serving
@pytest.mark.parametrize("hook", [False, True], ids=["off", "on"])
def test_the_engine_counts_its_prefills_through_the_grouped_kernel(
        hook, monkeypatch):
    """``prefill_experts_in_kernel_total`` equals the whole-prompt plus the
    chunk dispatches of an engine built where the kernel runs (windows of
    144 tokens x 3 picks), and stays 0 where it does not (every CPU)."""
    monkeypatch.setattr(PA, "_FORCE_INTERPRET", hook)
    engine = DecodeEngine(WIDE, scope=_scope(), place=fluid.CPUPlace(),
                          config=DecodeConfig(**ENGINE))
    try:
        bundles = list(engine.programs.prefill.values()) \
            + [engine.programs.chunk]
        assert [b["experts_in_kernel"] for b in bundles] == [hook, hook]
        engine.warmup()
        rng = np.random.RandomState(3)
        requests = [engine.submit(
            rng.randint(0, WIDE.vocab_size, (n,)).astype(np.int64),
            max_new=4, timeout=300) for n in (140, 300)]
        assert all(len(r.result(300)) == 4 for r in requests)
        stats = engine.stats()
    finally:
        engine.close()
    assert stats["prefill_dispatch_total"] == 1
    assert stats["chunk_prefill_total"] == 3
    assert stats["prefill_experts_in_kernel_total"] == (4 if hook else 0)


def test_the_engines_logits_are_the_same_through_the_grouped_kernel(
        monkeypatch):
    """A prompt through the whole-prompt program and one through three
    chunks, then 2 decoded positions each: the same picks and the same
    logits to the order of the sums with the routed experts in
    ``ragged_dot`` (and, decoding, sorted) and through the two kernels."""
    scope = _scope()

    def probe():
        engine = DecodeEngine(WIDE, scope=scope, place=fluid.CPUPlace(),
                              config=DecodeConfig(**ENGINE),
                              auto_start=False)
        rng = np.random.RandomState(1)
        return [np.asarray(x) for n in (131, 300) for x in engine_logits(
            engine, rng.randint(0, WIDE.vocab_size, n), 2)]

    want = probe()
    monkeypatch.setattr(PA, "_FORCE_INTERPRET", True)
    for a, b in zip(probe(), want):
        if np.issubdtype(b.dtype, np.integer):
            assert np.array_equal(a, b)
        else:
            assert rel_l2(a, b).max() < 1e-4
