"""A prefill window's routed experts as one grouped kernel
(ops/moe.py ``moe_grouped_rows``), through the Pallas interpreter against
the sorted ``jax.lax.ragged_dot`` form it stands in for on the chip: the
same float32 sums in another order (1e-6), a row's result that of the row
alone, the work list that says which expert's blocks a step holds, the
gate's refusals, and the engine's count of the dispatches behind it; and a
SHARE's sorted rows through it (PR 63): the pairs of absent experts lie
behind the last held group, named by no item and written by nobody, and
every branch of ``moe_apply_sorted`` that a share takes masks them."""
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
    # a share: ``rows`` sorted rows of which only the groups' are held
    "a_share_with_rows_behind_the_last_group": dict(
        sizes=MIXED, rows=sum(MIXED) + 2 * TILE + 9, layer=2),
    "a_share_whose_last_group_ends_on_a_tile": dict(
        sizes=[TILE, 0, 0, 0, 0, 0, 0, TILE], rows=4 * TILE),
    "a_share_that_holds_no_pair": dict(sizes=[0] * 8, rows=2 * TILE + 44,
                                       layer=1),
    "a_share_whose_pairs_are_one_experts": dict(
        sizes=[0, 0, 0, 2 * TILE + 44, 0, 0, 0, 0], rows=3 * TILE + 1),
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
    d, held = 128, int(sizes.sum())
    p = spec.get("rows", held)
    xs = jnp.asarray(rng.randn(p, d), jnp.float32)
    w = _weights(rng, layer, sizes.size, d, f)
    want = _ragged(xs, jnp.asarray(sizes),
                   *(m if layer is None else m[layer] for m in w))[:held]

    # the work list: every expert reached and no other, in ascending
    # order, its blocks asked for ONCE (the steps behind the last item
    # ask for nothing new)
    n_tiles = -(-p // TILE)
    expert, tile, starts, n = (np.asarray(a) for a in moe._work_items(
        jnp.asarray(sizes), TILE, n_tiles))
    assert expert.size == n_tiles + sizes.size - 1 and n[0] <= expert.size
    assert sorted(set(expert[:n[0]])) == list(np.flatnonzero(sizes))
    # ... and where nobody reached any, the one block the pipeline fetches
    # before it looks
    assert np.count_nonzero(np.diff(expert)) + 1 == max(
        np.count_nonzero(sizes), 1)
    assert (0 <= expert).all() and (expert < sizes.size).all()
    # no item names a tile behind the last held row
    assert (np.diff(tile) >= 0).all() and set(tile) == set(range(max(
        -(-held // TILE), 1)))
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
    assert got.shape == (p, d) and got.dtype == jnp.float32
    # a row of no group is written by nobody: the interpreter hands the
    # kernel a buffer of NaN, the chip whatever lay there
    assert np.isnan(np.asarray(got[held:])).all()
    got = got[:held]
    assert not np.isnan(np.asarray(got)).any()
    if held:
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


# a share of the router through every branch ``moe_apply_sorted`` has for
# one: (tokens, picks, experts held, the router's width, the first one held,
# the stack's layer, what the rows pick: None = any of the router's)
SHARES = {
    # a quarter of the router over more pairs than one block of the
    # un-sort: every sorted row back to its place in token order
    "a_quarter_at_129_rows": dict(t=129, e=8, width=32, first=8),
    "a_quarter_at_256_rows_of_a_stack": dict(t=256, e=8, width=32, first=16,
                                             layer=1),
    "a_quarter_at_300_rows": dict(t=300, e=8, width=32, first=24),
    "a_quarter_first_in_the_router": dict(t=150, e=8, width=32, first=0,
                                          layer=2),
    "a_quarter_that_holds_no_pair": dict(t=150, e=8, width=32, first=8,
                                         picks=range(16, 32)),
    "a_quarter_whose_pairs_are_one_experts": dict(
        t=150, e=8, width=32, first=8, picks=[11]),
    # a sixteenth: the leading rows set aside (``few`` of them: 120 of 450),
    # or where the router sends more, all of them: both sides of the cond
    "a_sixteenth_within_the_rows_set_aside": dict(t=150, e=2, width=32,
                                                  first=4),
    "a_sixteenth_within_them_of_a_stack": dict(t=150, e=2, width=32,
                                               first=30, layer=0),
    "a_sixteenth_over_the_rows_set_aside": dict(
        t=150, e=2, width=32, first=4, picks=[3, 4, 5, 6]),
    "a_sixteenth_over_them_of_a_stack": dict(
        t=150, e=2, width=32, first=4, picks=[3, 4, 5, 6], layer=2),
    "a_sixteenth_that_holds_no_pair": dict(t=150, e=2, width=32, first=4,
                                           picks=range(6, 32)),
    "a_sixteenth_whose_pairs_are_one_experts": dict(
        t=150, e=2, width=32, first=4, picks=[5]),
    # an eighth over pairs within one block: ``leading(t * k)`` alone
    "an_eighth_at_one_block": dict(t=130, e=4, width=32, first=4, k=1),
}


@pytest.mark.parametrize("case", sorted(SHARES))
def test_a_shares_rows_go_through_the_kernel_and_back(case, monkeypatch):
    """``moe_apply_sorted`` with ``held`` over more rows than the few-rows
    kernel takes: the hook's form (the sort, the gather, the kernel, the
    branch's un-sort) against a CPU's (three ``ragged_dot``). The rows the
    kernel leaves unwritten are set to NaN behind it, so a branch that let
    one through to a token would show."""
    spec = dict(dict(k=3, layer=None, picks=None), **SHARES[case])
    t, k, e, width, first, layer = (spec[n] for n in (
        "t", "k", "e", "width", "first", "layer"))
    rng = np.random.RandomState(5)
    d = f = 128
    x = jnp.asarray(rng.randn(t, d), jnp.float32)
    w = _weights(rng, layer, e, d, f)
    picks = list(spec["picks"] or range(width))
    idx = jnp.asarray(np.stack([
        rng.choice(picks, k, replace=len(picks) < k) for _ in range(t)]),
        jnp.int32)
    gates = jnp.asarray(rng.rand(t, k), jnp.float32)
    n_held = int(((idx >= first) & (idx < first + e)).sum())
    few = -(-4 * t * k * e // width // 8) * 8
    if case.startswith("a_quarter"):
        assert few >= t * k > moe.UNSORT_BLOCK
    elif case.startswith("a_sixteenth"):
        assert few < t * k and (n_held > few) == ("over" in case or
                                                  "one_experts" in case)
    else:
        assert few < t * k <= moe.UNSORT_BLOCK
    if "no_pair" in case:
        assert n_held == 0
    if "one_experts" in case:
        assert n_held == t * k

    def apply(*a):
        return moe.moe_apply_sorted(*a[:-1], layer=a[-1], held=(first, width))

    args = (x, idx, gates, *w, None if layer is None else jnp.int32(layer))
    assert not moe.grouped_rows_usable(t, w[0], w[2], (first, width))
    want = jax.jit(apply)(*args)
    assert "ragged_dot" in str(jax.make_jaxpr(apply)(*args))

    monkeypatch.setattr(PA, "_FORCE_INTERPRET", True)
    assert moe.grouped_rows_usable(t, w[0], w[2], (first, width))
    kernel = moe.moe_grouped_rows

    def poisoned(xs, sizes, *a, **kw):
        out = kernel(xs, sizes, *a, **kw)
        return jnp.where((jnp.arange(out.shape[0]) < jnp.sum(sizes))[:, None],
                         out, jnp.nan)

    monkeypatch.setattr(moe, "moe_grouped_rows", poisoned)
    # a function of its own: a trace's cache goes by the function
    jaxpr = str(jax.make_jaxpr(lambda *a: apply(*a))(*args))
    assert "moe_grouped_rows" in jaxpr and "ragged_dot" not in jaxpr
    got = jax.jit(lambda *a: apply(*a))(*args)
    assert got.shape == want.shape and not np.isnan(np.asarray(got)).any()
    if n_held == 0:
        assert not np.asarray(got).any() and not np.asarray(want).any()
    else:
        # over the whole call: a token none of whose picks is held is a
        # row of zeros on both sides
        assert rel_l2(np.ravel(got), np.ravel(want)) < 1e-5
        assert np.array_equal(np.asarray(got).any(-1),
                              np.asarray(want).any(-1))


def _abstract(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


LAGUNA = (_abstract((3, 256, 2048, 512)), _abstract((3, 256, 512, 2048)))
# what the gate refuses: (tokens, w_gate, w_down, held, the hook)
REFUSALS = {
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
    # a share is refused for what a whole layer is: DeepSeek-V3's 16 of
    # 256 held, a stack of 4 layers; MiMo's 4,096 x 2,048, 100 MB
    "a_share_of_experts_over_the_budget": (
        2048, _abstract((4, 16, 7168, 2048)), _abstract((4, 16, 2048, 7168)),
        (0, 256), True),
    "another_share_of_experts_over_the_budget": (
        2048, _abstract((4, 16, 4096, 2048)), _abstract((4, 16, 2048, 4096)),
        (16, 256), True),
}
# what it admits: (tokens, w_gate, w_down, held)
LING = (_abstract((6, 128, 2560, 768)), _abstract((6, 128, 768, 2560)))
ADMISSIONS = {
    "every_expert_held": (2048, *LAGUNA, None),
    "a_share": (2048, *LAGUNA, (0, 1024)),
    # ling's 128 of 512, 23.6 MB twice over: a decode step of 256 rows, a
    # chunk of 2,048 tokens, the narrowest whole-prompt bucket
    "lings_share_at_a_decode_steps_rows": (256, *LING, (0, 512)),
    "lings_share_at_a_chunk": (2048, *LING, (0, 512)),
    "lings_share_one_row_over_the_few_rows_kernel": (
        moe.FEW_ROWS + 1, *LING, (0, 512)),
    "a_share_not_first_in_the_router": (192, *LING, (384, 512)),
}


@pytest.mark.parametrize("what", sorted(ADMISSIONS))
def test_the_gate_admits(what, monkeypatch):
    """A share is admitted as a whole layer is, by the same observables;
    on a CPU without the hook neither is, and at the few-rows kernel's
    rows the other gate answers."""
    t, w_gate, w_down, held = ADMISSIONS[what]
    assert not moe.grouped_rows_usable(t, w_gate, w_down, held)
    monkeypatch.setattr(PA, "_FORCE_INTERPRET", True)
    assert moe.grouped_rows_usable(t, w_gate, w_down, held)
    assert not moe.few_rows_usable(t, w_gate, w_down, held)
    assert not moe.grouped_rows_usable(moe.FEW_ROWS, w_gate, w_down, held)
    assert moe.few_rows_usable(moe.FEW_ROWS, w_gate, w_down, held)


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
