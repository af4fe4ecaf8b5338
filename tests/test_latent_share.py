"""One chip's share of an expert-parallel layer: a router over all the
experts, group-limited, a run of them held, the plain residual path, with
latent attention; through ops/moe.py, the paged programs and DecodeEngine,
against the plain reference of the same share
(benchmark/reference/latent_moe_share.py) at a small size in float32.
"""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.models.latent_moe import (LATENT_MOE_TINY,
                                          LATENT_SHARE_TINY as CFG)
from paddle_tpu.ops import moe
from paddle_tpu.ops import transformer_ops as T
from paddle_tpu.serving.decode_engine import DecodeConfig, DecodeEngine

from benchmark.reference import latent_moe_share as ref
import stored_width

REL_L2_F32 = 1e-4
PS, MP = 4, 8                      # page size, pages a row
ROUTED = CFG.n_layers - CFG.n_dense_layers
MODEL = dict(
    num_hidden_layers=CFG.n_layers,
    first_k_dense_replace=CFG.n_dense_layers,
    num_attention_heads=CFG.n_heads, qk_nope_head_dim=CFG.nope_dim,
    qk_rope_head_dim=CFG.rope_dim, v_head_dim=CFG.v_dim,
    kv_lora_rank=CFG.kv_rank, rms_norm_eps=CFG.norm_eps,
    rope_theta=CFG.rope_base,
    rope_scaling=dict(factor=CFG.rope_factor,
                      original_max_position_embeddings=CFG.rope_original_max,
                      beta_fast=CFG.rope_beta_fast,
                      beta_slow=CFG.rope_beta_slow,
                      mscale_all_dim=CFG.rope_mscale_all_dim),
    num_experts_per_tok=CFG.moe_top_k, n_group=CFG.n_group,
    topk_group=CFG.topk_group, routed_scaling_factor=CFG.route_scale,
    n_shared_experts=CFG.n_shared,
    experts_held=dict(first=CFG.experts_first, count=CFG.n_experts,
                      of=CFG.router_width))


def make_weights(cfg, seed=0):
    """Seeded float32 weights, every term alive: norms off 1, a selection
    bias of the size of the score gaps."""
    out = {}
    shapes = cfg.param_shapes()
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    for k, (name, (shape, dt)) in zip(keys, sorted(shapes.items())):
        x = jax.random.normal(k, shape)
        if name.endswith("norm"):
            x = 1.0 + 0.1 * x
        elif name.endswith("moe_bias"):
            x = 0.1 * x
        else:
            x = 0.2 * x
        out[name] = x.astype(dt)
    return out


W = make_weights(CFG)
REF_W = ref.from_stacked(W, CFG.n_dense_layers)


def rel_l2(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return np.linalg.norm(got - want, axis=-1) \
        / np.linalg.norm(want, axis=-1)


def op_inputs(cfg=CFG, w=W, **feeds):
    ins = {"Emb": [w["tok_emb"]], "FinalNorm": [w["final_norm"]],
           "LmHead": [w["lm_head"]]}
    for prefix, scope, n, routed in (
            ("Lead", "lead", cfg.n_dense_layers, False),
            ("", "blocks", cfg.n_layers - cfg.n_dense_layers, True)):
        for slot, (suffix, _, _) in cfg.layer_params(n, routed).items():
            ins[prefix + slot] = [w[f"{scope}.{suffix}"]]
    ins.update({k: [jnp.asarray(v)] for k, v in feeds.items()})
    return ins


def run_op(op, steps=1, **feeds):
    pools = feeds.pop("Pools")
    ins = op_inputs(**feeds)
    ins["Pools"] = list(pools)
    out = op(None, ins, dict(CFG.block_attrs(PS), steps=steps))
    return {k: v if k == "PoolsOut" else v[0] for k, v in out.items()}


def empty_pool(n_pages=40):
    return [jnp.zeros((CFG.n_layers, n_pages, PS, CFG.entry_dim),
                      jnp.float32)]


def reference_logits(seq, positions=None, model=MODEL, weights=REF_W):
    logits, margins, _ = ref.forward(weights, np.asarray(seq), model,
                                     positions)
    return np.asarray(logits), np.asarray(margins)


LENS = np.array([7, 3, 1, 5], np.int32)       # row 2 is an inactive slot
TABLE = np.zeros((4, MP), np.int32)
TABLE[0, :4] = [1, 2, 3, 4]
TABLE[1, :3] = [5, 6, 7]
TABLE[3, :4] = [8, 9, 10, 11]
RNG = np.random.RandomState(0)
PROMPTS = RNG.randint(0, CFG.vocab_size, (4, 8))


# -- the generalised configuration -----------------------------------------

def test_the_plain_path_has_no_hyper_connection_parameter():
    shapes = CFG.param_shapes()
    assert not [n for n in shapes if ".hc_" in n]
    assert shapes["blocks.moe_router"] == ([ROUTED, 32, 16], "float32")
    assert shapes["blocks.moe_bias"] == ([ROUTED, 16], "float32")
    assert shapes["blocks.moe_w_gate"][0] == [ROUTED, 4, 32, 16]
    attrs = CFG.block_attrs(PS)
    assert (attrs["residual"], attrs["n_group"], attrs["topk_group"],
            attrs["experts_first"]) == ("plain", 4, 2, 4)


def test_xing4s_block_is_unchanged_through_the_generalised_config(
        monkeypatch):
    cfg = LATENT_MOE_TINY
    assert (cfg.residual, cfg.router_width, cfg.experts_first, cfg.n_group,
            cfg.topk_group) == ("mhc", cfg.n_experts, 0, 1, 1)
    shapes = cfg.param_shapes()
    assert len([n for n in shapes if ".hc_" in n]) == 12
    assert shapes["blocks.moe_router"][0][-1] == cfg.n_experts
    attrs = cfg.block_attrs(PS)
    assert (attrs["residual"], attrs["n_group"], attrs["topk_group"],
            attrs["experts_first"]) == ("mhc", 1, 1, 0)
    # its expert layer is told of no share: the whole-length form, as ever
    seen = []
    keep = moe.moe_apply_sorted
    monkeypatch.setattr(moe, "moe_apply_sorted", lambda *a, **kw: (
        seen.append(kw.get("held")), keep(*a, **kw))[1])
    w = make_weights(cfg)
    run = T._block_runner(op_inputs(cfg, w), cfg.block_attrs(PS))
    assert run.embed(jnp.asarray(PROMPTS[:1, :3])).shape \
        == (1, 3, cfg.n_streams, cfg.dim)
    p = {slot: v[0] for slot, v in run.params.items()}
    out, (load, idx) = T._routed_ffn(
        run.kinds, p, jax.random.normal(jax.random.PRNGKey(0),
                                        (1, 5, cfg.dim)), None)
    assert seen == [None] and out.shape == (1, 5, cfg.dim)
    assert int(load.sum()) == 5 * cfg.moe_top_k == idx.size


@pytest.mark.parametrize("kw", [dict(experts_first=13),
                                dict(n_group=3), dict(topk_group=5)])
def test_a_share_that_is_no_share_of_the_router_is_refused(kw):
    with pytest.raises(ValueError, match=CFG.name):
        replace(CFG, **kw)


# -- the router -------------------------------------------------------------

def _todays_route(xt, wg, top_k, bias, scale):
    """moe_route's sigmoid path as it stood before groups (PR 30)."""
    logits = jnp.dot(xt.astype(jnp.float32), wg.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    idx = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)[1]
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, scale * gates / (
        jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)


def test_one_group_of_which_one_is_kept_is_todays_router_bit_for_bit():
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(keys[0], (50, 32))
    wg = 0.3 * jax.random.normal(keys[1], (32, 16))
    bias = 0.1 * jax.random.normal(keys[2], (16,))
    want = _todays_route(x, wg, 3, bias, 2.5)
    for got in (moe.moe_route(x, wg, 3, "sigmoid", bias, 2.5),
                moe.moe_route(x, wg, 3, "sigmoid", bias, 2.5, 1, 1)):
        assert np.array_equal(got[0], want[0])
        assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))


def test_group_limited_routing_on_a_case_written_out_by_hand():
    """8 experts in 4 groups of 2, the 2 best groups kept, 3 picked. The
    router is the identity, so a token's scores are sigmoid(x)."""
    logit = lambda p: float(np.log(p / (1 - p)))
    scores = [0.90, 0.10,        # group 0: sum 1.00
              0.60, 0.55,        # group 1: sum 1.15  <- kept
              0.80, 0.05,        # group 2: sum 0.85
              0.50, 0.70]        # group 3: sum 1.20  <- kept
    x = jnp.asarray([[logit(p) for p in scores]], jnp.float32)
    eye = jnp.eye(8, dtype=jnp.float32)
    idx, gates = moe.moe_route(x, eye, 3, "sigmoid", None, 2.0, 4, 2)
    # ungrouped, the three largest are experts 0, 4, 7; the groups of 0
    # and 4 are dropped, and the pick is 7, 2, 3
    plain, _ = moe.moe_route(x, eye, 3, "sigmoid", None, 2.0)
    assert sorted(np.asarray(plain)[0]) == [0, 4, 7]
    assert np.asarray(idx)[0].tolist() == [7, 2, 3]
    np.testing.assert_allclose(
        np.asarray(gates)[0],
        2.0 * np.array([0.70, 0.60, 0.55]) / (0.70 + 0.60 + 0.55),
        rtol=1e-5)
    # the bias steers the choice of groups and of experts, not the gates
    bias = jnp.asarray([0, 0.5, 0, 0, 0, 0, 0, 0], jnp.float32)
    idx, gates = moe.moe_route(x, eye, 3, "sigmoid", bias, 2.0, 4, 2)
    assert np.asarray(idx)[0].tolist() == [0, 7, 1]   # groups 0 and 3
    np.testing.assert_allclose(
        np.asarray(gates)[0],
        2.0 * np.array([0.90, 0.70, 0.10]) / (0.90 + 0.70 + 0.10),
        rtol=1e-5)
    # and the reference's router is the same function
    rw = {"l0.moe_router": eye, "l0.moe_bias": bias}
    rm = dict(num_experts_per_tok=3, routed_scaling_factor=2.0, n_group=4,
              topk_group=2)
    picked, g, margin, gap = ref.route(rw, 0, x, rm)
    assert np.asarray(picked)[0].tolist() == [0, 7, 1]
    np.testing.assert_allclose(np.asarray(g), np.asarray(gates), rtol=1e-6)
    assert float(gap[0]) == 0.0
    # selection scores 1.4 + 0.1 and 0.7 + 0.5 against 0.6 + 0.55: the
    # last group kept stands 0.05 over the best one dropped, and the last
    # expert picked (0.6) 0.1 over the next (0.5)
    assert float(margin[0]) == pytest.approx(0.05, abs=1e-5)


def test_the_references_gap_for_picks_not_its_own():
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(keys[0], (40, 32))
    rw = {"l0.moe_router": 0.3 * jax.random.normal(keys[1], (32, 16)),
          "l0.moe_bias": 0.1 * jax.random.normal(keys[2], (16,))}
    rm = dict(num_experts_per_tok=3, routed_scaling_factor=2.5, n_group=4,
              topk_group=2)
    picked, _, margin, gap = ref.route(rw, 0, x, rm)
    idx, _ = moe.moe_route(x, rw["l0.moe_router"], 3, "sigmoid",
                           rw["l0.moe_bias"], 2.5, 4, 2)
    assert np.array_equal(np.sort(picked, -1), np.sort(idx, -1))
    assert float(jnp.max(gap)) == 0.0 and float(jnp.min(margin)) > 0
    sel = np.asarray(jax.nn.sigmoid(x @ rw["l0.moe_router"])
                     + rw["l0.moe_bias"])
    own = np.asarray(picked)
    at = np.arange(40) == 5
    # the runner-up inside the kept groups in the last pick's place
    groups = set((own[5] // 4).tolist())
    inside = [e for e in np.argsort(-sel[5])
              if e // 4 in groups and e not in own[5]]
    if len(groups) == 2:
        forced = own.copy()
        forced[5, -1] = inside[0]
        _, _, _, gap2 = ref.route(rw, 0, x, rm, (at, forced))
        assert float(gap2[5]) == pytest.approx(
            sel[5, own[5, -1]] - sel[5, inside[0]], rel=1e-4)
        assert float(jnp.max(jnp.where(at, 0.0, gap2))) == 0.0
    # a pick from a group the reference dropped: the gap is at least how
    # far that group scores under the last group kept
    dropped = [g for g in range(4) if g not in groups]
    score = lambda g: np.sort(sel[5, 4 * g:4 * g + 4])[-2:].sum()
    forced = own.copy()
    forced[5, -1] = 4 * dropped[0] + int(np.argmax(
        sel[5, 4 * dropped[0]:4 * dropped[0] + 4]))
    _, _, _, gap3 = ref.route(rw, 0, x, rm, (at, forced))
    assert float(gap3[5]) >= min(score(g) for g in groups) \
        - score(dropped[0]) - 1e-6 > 0


# -- the expert layer of a share --------------------------------------------

def _layer_case(t=24, seed=3, skew=0.0):
    d, e, f, k = 32, 16, 16, 3
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(keys[0], (t, d))
    router = 0.3 * jax.random.normal(keys[1], (d, e))
    bias = 0.1 * jax.random.normal(keys[2], (e,))
    bias = bias.at[4:6].add(skew)
    w = [0.2 * jax.random.normal(kk, s) for kk, s in zip(
        keys[3:6], ((e, d, f), (e, d, f), (e, f, d)))]
    idx, gates = moe.moe_route(x, router, k, "sigmoid", bias, 2.5, 4, 2)
    return x, idx, gates, w


def _by_loop(x, idx, gates, w, first, n):
    """The held experts' part, expert by expert."""
    want = np.zeros(x.shape, np.float32)
    for j in range(first, first + n):
        weight = np.asarray(jnp.sum(jnp.where(idx == j, gates, 0.0), -1))
        want += weight[:, None] * np.asarray(
            ref.swiglu(x, w[0][j], w[1][j], w[2][j]))
    return want


def test_a_share_computes_its_own_experts_part_and_nothing_else():
    x, idx, gates, w = _layer_case()
    first, n = 4, 4
    mine = [m[first:first + n] for m in w]
    got = moe.moe_apply_sorted(x, idx, gates, *mine, held=(first, 16))
    np.testing.assert_allclose(np.asarray(got),
                               _by_loop(x, idx, gates, w, first, n),
                               rtol=1e-4, atol=1e-5)
    # pairs to absent experts: send them to OTHER absent experts, and
    # neither the output nor the held experts' load moves
    absent = (idx < first) | (idx >= first + n)
    elsewhere = jnp.where(absent, (idx + 8) % 16, idx)
    elsewhere = jnp.where((elsewhere >= first) & (elsewhere < first + n)
                          & absent, 15, elsewhere)
    assert not np.array_equal(np.asarray(idx), np.asarray(elsewhere))
    again = moe.moe_apply_sorted(x, elsewhere, gates, *mine,
                                 held=(first, 16))
    assert np.array_equal(np.asarray(got), np.asarray(again))
    load = moe.moe_load(idx, n, None, first)
    assert np.array_equal(load, moe.moe_load(elsewhere, n, None, first))
    assert 0 < int(load.sum()) == int((~absent).sum()) < idx.size


def test_the_short_form_and_the_whole_length_form_agree():
    """Experts 4 and 5 of 16 held, 96 tokens: the leading rows are 144 of
    the 288 sorted pairs. An even router stays under them; one that
    favours the held experts does not and takes the whole length; each
    equals the loop over the held experts."""
    few = -(-4 * 96 * 3 * 2 // 16 // 8) * 8
    assert few == 144
    for skew, over in ((0.0, False), (3.0, True)):
        x, idx, gates, w = _layer_case(t=96, skew=skew)
        assert (int(((idx >= 4) & (idx < 6)).sum()) > few) == over
        got = moe.moe_apply_sorted(x, idx, gates, *(m[4:6] for m in w),
                                   held=(4, 16))
        np.testing.assert_allclose(np.asarray(got),
                                   _by_loop(x, idx, gates, w, 4, 2),
                                   rtol=1e-4, atol=1e-5)


def _dense_unsort(x, idx, gates, w, first, n):
    """The un-sort as it stood before PR 48: every sorted pair through its
    expert, then ONE float32 [T, T x K] x [T x K, D] product at
    ``Precision.HIGHEST`` whose left operand is the gate where the pair
    is the token's and its expert is held."""
    t, k = idx.shape
    local = jnp.where((idx >= first) & (idx < first + n), idx - first, n)
    order = jnp.argsort(local.reshape(t * k), stable=True)
    sizes = moe.moe_load(local, n)
    live = jnp.arange(t * k) < jnp.sum(sizes)
    xs = x[order // k]
    gate_h = jax.lax.ragged_dot(xs, w[0][first:first + n], sizes)
    up_h = jax.lax.ragged_dot(xs, w[1][first:first + n], sizes)
    ys = jax.lax.ragged_dot((gate_h * jax.nn.sigmoid(gate_h)) * up_h,
                            w[2][first:first + n], sizes)
    ys = jnp.where(live[:, None], ys, 0.0)
    g = jnp.where(live, gates.reshape(t * k)[order], 0.0)
    to_token = jnp.where((order // k)[None] == jnp.arange(t)[:, None],
                         g[None], 0.0)
    return jnp.dot(to_token, ys, precision=jax.lax.Precision.HIGHEST)


def _held_pairs_case(t, first, n, held, fine=False, seed=5):
    """A layer case whose router sends exactly ``held`` pairs to experts
    ``first .. first + n - 1`` of 16 (None: as the router fell).
    ``fine``: gates and expert outputs of the form 1 + j x 2^-20, which
    no two bfloat16 parts hold."""
    x, idx, gates, w = _layer_case(t=t, seed=seed)
    if held is not None:
        mine = (idx >= first) & (idx < first + n)
        away = jnp.where(mine, (idx - first + n) % (16 - n) + first + n, idx)
        away = jnp.where(away >= 16, away - 16, away)
        assert not bool(((away >= first) & (away < first + n)).any())
        flat = away.reshape(-1)
        # a token's k picks stay distinct: one pair a token goes to expert
        # ``first``, a second round to ``first + 1``, and so on
        at = np.arange(held)
        flat = flat.at[(at % t) * idx.shape[1] + at // t].set(
            first + at // t)
        idx = flat.reshape(idx.shape)
        assert int(((idx >= first) & (idx < first + n)).sum()) == held
    if fine:
        j = np.arange(gates.size, dtype=np.float32).reshape(gates.shape)
        gates = jnp.asarray(1.0 + (j % 61 + 1) * 2.0 ** -20)
        w = [w[0], w[1], w[2] * (1.0 + 2.0 ** -20)]
    return x, idx, gates, w


UNSORT_CASES = {
    # name: tokens, block, first held, experts held, pairs held, fine
    "rows_under_one_block": (24, 512, 4, 4, None, False),
    "rows_of_one_block_exactly": (32, 24, 4, 4, None, False),
    "held_on_a_blocks_edge": (96, 32, 4, 2, 64, False),
    "held_one_past_the_edge": (96, 32, 4, 2, 65, False),
    "held_in_the_drawn_back_last_block": (96, 40, 4, 2, 140, False),
    "nothing_held": (96, 32, 4, 2, 0, False),
    "more_held_than_the_leading_rows": (96, 32, 4, 2, 180, False),
    "every_pair_held_is_the_whole_length": (32, 40, 0, 16, None, False),
    "all_three_bfloat16_parts_small": (24, 512, 4, 4, None, True),
    "all_three_bfloat16_parts_looped": (96, 32, 4, 2, 100, True),
}


@pytest.mark.parametrize("case", sorted(UNSORT_CASES))
def test_the_unsort_is_the_float32_sum_whatever_the_blocks(case,
                                                           monkeypatch):
    """The blocked three-pass un-sort against the loop over the held
    experts at the file's tolerances, and against the dense ``HIGHEST``
    product it replaced at float32 rounding."""
    t, block, first, n, held, fine = UNSORT_CASES[case]
    monkeypatch.setattr(moe, "UNSORT_BLOCK", block)
    x, idx, gates, w = _held_pairs_case(t, first, n, held, fine)
    got = np.asarray(moe.moe_apply_sorted(
        x, idx, gates, *(m[first:first + n] for m in w), held=(first, 16)))
    np.testing.assert_allclose(got, _by_loop(x, idx, gates, w, first, n),
                               rtol=1e-4, atol=1e-5)
    want = np.asarray(_dense_unsort(x, idx, gates, w, first, n))
    np.testing.assert_allclose(got, want, rtol=1e-6,
                               atol=1e-6 * np.abs(want).max())
    assert got.any() == (held != 0)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def _primitives(jaxpr):
    return [eqn.primitive.name for eqn in _equations(jaxpr)]


@pytest.mark.parametrize("held", [0, 1, 64, 65, 140, 180])
def test_the_unsorts_loop_takes_as_many_trips_as_the_pairs_held_fill(
        held, monkeypatch):
    """A probe build counts the trips of the un-sort's loop as they run:
    ``ceil(n_held / block)``, in the short form and the whole-length one
    (144 leading rows of 288 here)."""
    block = 32
    monkeypatch.setattr(moe, "UNSORT_BLOCK", block)
    x, idx, gates, w = _held_pairs_case(96, 4, 2, held)
    trips = []
    loop = jax.lax.fori_loop

    def probe(lower, upper, body, init):
        def counted(i, carry):
            jax.debug.callback(lambda: trips.append(1))
            return body(i, carry)
        return loop(lower, upper, counted, init)

    monkeypatch.setattr(jax.lax, "fori_loop", probe)
    got = jax.jit(lambda idx: moe.moe_apply_sorted(
        x, idx, gates, *(m[4:6] for m in w), held=(4, 16)))(idx)
    jax.block_until_ready(got)
    jax.effects_barrier()
    assert len(trips) == -(-held // block)
    np.testing.assert_allclose(np.asarray(got),
                               _by_loop(x, idx, gates, w, 4, 2),
                               rtol=1e-4, atol=1e-5)


def test_rows_of_one_block_or_less_hold_no_loop(monkeypatch):
    """The traced text: 96 tokens' 144 leading rows of 288 over a block of
    32 hold a ``while`` in each form of the ``cond``; rows of one block or
    less (every decode step) hold the product alone. Neither holds a constant
    of an operand's size, and each un-sort is three bfloat16 parts in
    ONE product with float32 out."""
    def text(t, block):
        monkeypatch.setattr(moe, "UNSORT_BLOCK", block)
        x, idx, gates, w = _layer_case(t=t)
        return jax.make_jaxpr(lambda idx, gates: moe.moe_apply_sorted(
            x, idx, gates, *(m[4:6] for m in w), held=(4, 16)))(idx, gates)

    looped = _primitives(text(96, 32).jaxpr)
    assert looped.count("cond") == 1 and looped.count("while") == 2
    # the short form one block exactly: the whole-length one alone loops
    assert _primitives(text(96, 144).jaxpr).count("while") == 1
    for t, block in ((24, 512), (96, 288), (24, 72)):
        single = text(t, block)
        names = _primitives(single.jaxpr)
        assert "while" not in names and "scan" not in names
        # two forms under the cond, the gated rows split twice in each
        assert names.count("reduce_precision") == 4
        assert all(np.size(c) <= 16 * 32 * 16 for c in single.consts)
    # 24 tokens x 3 picks: 40 leading rows or all 72, a product each
    dots = [e for e in _equations(text(24, 512).jaxpr)
            if e.primitive.name == "dot_general"]
    assert sorted([(str(v.aval.dtype),) + v.aval.shape for v in e.invars]
                  for e in dots) == [
        [("bfloat16", 24, 3 * rows), ("bfloat16", 3 * rows, 32)]
        for rows in (40, 72)]
    for e in dots:
        assert e.outvars[0].aval.dtype == jnp.float32
        assert e.params["precision"] is None


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips, four experts each: what the shares' routed parts give,
    with the attention, the residual and the shared expert counted once,
    is the whole layer of the uncut reference; and every share picks the
    same experts for every token."""
    layer = CFG.n_dense_layers                       # the first routed one
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 9, CFG.dim))
    pos = jnp.arange(9, dtype=jnp.int32)[None]
    whole_cfg = replace(CFG, n_experts=16, experts_first=0)
    w_all = make_weights(whole_cfg, 7)
    ref_all = ref.from_stacked(w_all, CFG.n_dense_layers)
    uncut = dict(MODEL, experts_held=dict(first=0, count=16, of=16))
    want, _, _, own = ref.layer(ref_all, layer, x[0], uncut)
    none = dict(MODEL, experts_held=dict(first=0, count=0, of=16))
    common, _, _, _ = ref.layer(ref_all, layer, x[0], none)

    def attend(p):
        def fn(q, entries):     # causal, over this window alone
            run = T._PagedRunner(p, None, None, None, n_heads=CFG.n_heads,
                                 n_kv=CFG.n_heads, base=0, eps=CFG.norm_eps,
                                 page_size=PS, kinds=kinds)
            return run._latent_expanded(p, q, lambda i: entries[0], 1, 9,
                                        pos)
        return fn

    total, picks = jnp.zeros_like(common), []
    for share in range(4):
        cfg = replace(CFG, experts_first=4 * share)
        kinds = T._block_runner(op_inputs(CFG, W), cfg.block_attrs(PS)).kinds
        assert kinds.experts_first == 4 * share
        p = {}
        for slot, (suffix, _, _) in cfg.layer_params(ROUTED, True).items():
            v = w_all[f"blocks.{suffix}"][0]
            p[slot] = v[4 * share:4 * share + 4] \
                if slot in T._EXPERT_SLOTS else v
        y, (load, idx) = T.block_forward(kinds, p, x, pos, attend(p))
        total = total + (y[0] - common)
        picks.append(np.asarray(idx))
        assert int(load.sum()) == int(
            ((idx >= 4 * share) & (idx < 4 * share + 4)).sum())
    np.testing.assert_allclose(np.asarray(total + common),
                               np.asarray(want), rtol=1e-4, atol=1e-5)
    for other in picks[1:]:
        assert np.array_equal(picks[0], other)
    assert np.array_equal(np.sort(picks[0][0], -1),
                          np.sort(np.asarray(own), -1))
    # every pair was somebody's: the shares' loads are all the pairs
    assert sum(int(((picks[0] >= 4 * s) & (picks[0] < 4 * s + 4)).sum())
               for s in range(4)) == 9 * CFG.moe_top_k


# -- the paged programs -------------------------------------------------------

def test_prefill_then_decode_through_pages_matches_the_reference():
    pre = run_op(T._block_paged_prefill, Tokens=PROMPTS, Lens=LENS,
                 Table=TABLE, Pools=empty_pool())
    active = [0, 1, 3]
    for r in active:
        want, _ = reference_logits(PROMPTS[r, :LENS[r]], [LENS[r] - 1])
        assert rel_l2(pre["Logits"][r], want[0]) < REL_L2_F32
    first = np.array(pre["NextTok"])
    first[2] = 0
    pos = LENS.copy()
    pos[2] = 1
    dec = run_op(T._block_paged_decode, steps=4, Tokens=first,
                 Positions=pos, Table=TABLE, Pools=pre["PoolsOut"])
    toks = np.asarray(dec["OutTokens"])
    for r in active:
        seq = np.concatenate([PROMPTS[r, :LENS[r]], first[r:r + 1],
                              toks[r, :3]])
        want, _ = reference_logits(seq, LENS[r] + np.arange(4))
        assert rel_l2(dec["Logits"][r], want).max() < REL_L2_F32
        assert np.array_equal(toks[r], np.argmax(want, -1))
    # the picks run over the router's whole width
    assert dec["Picks"].shape == (4, 4, ROUTED, CFG.moe_top_k)
    assert int(np.asarray(dec["Picks"]).max()) >= CFG.n_experts
    # Stats: every pair of the active rows' real tokens over the router's
    # width; those on experts 4-7 among them; the held experts alone in
    # the decode counters
    stats = dict(zip(T.PAGED_STATS, np.asarray(pre["Stats"])))
    assert stats["moe_assignments_total"] == 15 * CFG.moe_top_k * ROUTED
    assert 0 < stats["moe_held_assignments_total"] \
        < stats["moe_assignments_total"]
    assert stats["moe_decode_expert_calls_total"] == 0
    stats = dict(zip(T.PAGED_STATS, np.asarray(dec["Stats"])))
    assert stats["moe_assignments_total"] == 3 * 4 * CFG.moe_top_k * ROUTED
    picks = np.asarray(dec["Picks"])[active]
    held = (picks >= CFG.experts_first) \
        & (picks < CFG.experts_first + CFG.n_experts)
    assert stats["moe_held_assignments_total"] == int(held.sum())
    assert stats["moe_decode_expert_calls_total"] \
        == 4 * ROUTED * CFG.n_experts
    touched = sum(len(set(picks[:, s, layer][held[:, s, layer]].tolist()))
                  for s in range(4) for layer in range(ROUTED))
    assert stats["moe_decode_experts_touched_total"] == touched
    assert stats["moe_max_load_total"] <= stats["moe_held_assignments_total"]
    assert stats["latent_tokens_read_total"] \
        == sum(int(LENS[r]) + s + 1 for r in active for s in range(4))


def test_the_key_block_shrinks_where_heads_times_window_is_large(
        monkeypatch):
    """The same window with the cache read two pages at a time because a
    score pass may hold no more."""
    run = T._block_runner(op_inputs(), CFG.block_attrs(PS))
    pool = jax.random.normal(jax.random.PRNGKey(4),
                             (CFG.n_layers, 12, PS, CFG.entry_dim)) * 0.5
    table = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 0]], jnp.int32)
    h = run.embed(jnp.asarray(RNG.randint(0, CFG.vocab_size, (1, 5))))
    assert h.shape == (1, 5, CFG.dim)              # one stream
    pos0 = jnp.asarray([20], jnp.int32)
    one = run.forward(h, pool, table, pos0, 5)[0]
    monkeypatch.setattr(T, "_SCORE_BYTES", 4 * CFG.n_heads * 5 * 2 * PS)
    many = run.forward(h, pool, table, pos0, 5)[0]
    np.testing.assert_allclose(np.asarray(one), np.asarray(many),
                               rtol=2e-5, atol=2e-5)


# -- the engine ---------------------------------------------------------------

def make_engine():
    scope = fluid.Scope()
    for name, value in W.items():
        scope.set(name, value)
    return DecodeEngine(CFG, scope=scope, config=DecodeConfig(
        max_batch=3, prompt_buckets=(8, 32), max_new_tokens=8,
        page_size=PS, decode_block=2, prefill_batch=1))


@pytest.fixture(scope="module")
def engine():
    eng = make_engine()
    eng.warmup()
    yield eng
    eng.close()


@pytest.mark.parametrize("form", ["whole", "chunked"])
def test_the_padded_entry_changes_no_bit_of_logits_picks_or_cache(form):
    """Under the plain residual path (test_latent_moe.py: under
    hyper-connections)."""
    assert (CFG.entry_dim, CFG.stored_dim) == (24, 128)
    stored_width.check_padding_changes_no_bit(run_op, CFG, form, PS, MP)


def test_an_engine_stores_padded_what_it_would_store_unpadded(monkeypatch):
    """Whole-prompt programs alone (this engine has no chunk program),
    under the plain residual path."""
    stored_width.check_engines_agree(make_engine, monkeypatch, CFG, PS)


def test_engine_tokens_are_the_references_alone_and_co_scheduled(engine):
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, CFG.vocab_size, n) for n in (5, 21, 13, 8, 30)]
    alone = [engine.generate(p, max_new=6) for p in prompts]
    for p, out in zip(prompts, alone):
        want, _ = reference_logits(np.concatenate([p, out]))
        assert np.array_equal(out, np.argmax(want, -1)[p.size - 1:-1])
    before = engine.stats()
    together = [h.result(120)
                for h in [engine.submit(p, max_new=6) for p in prompts]]
    for a, b in zip(alone, together):
        assert np.array_equal(a, b)
    engine.assert_no_recompiles()
    after = engine.stats()
    moved = {k: after[k] - before[k] for k in T.PAGED_STATS}
    tokens = sum(p.size for p in prompts) + 5 * 5     # prompt + 5 steps
    assert moved["moe_assignments_total"] >= tokens * CFG.moe_top_k * ROUTED
    assert 0 < moved["moe_held_assignments_total"] \
        < moved["moe_assignments_total"]


def test_the_engines_logits_are_the_references_and_a_fault_is_not(engine):
    """The engine's own logits after a whole-prompt prefill and 8 steps, as
    the chip comparison takes them, against the reference routed by
    itself; and three faults the comparison must catch."""
    from benchmark.builders import serve_blocks
    prompt = np.random.RandomState(9).randint(0, CFG.vocab_size, 21)
    engine.close()
    got, picks, decoded = serve_blocks.engine_logits(engine, prompt, 8)
    seq, positions = np.concatenate([prompt, decoded[:-1]]), 20 + np.arange(9)
    want, _ = reference_logits(seq, positions)
    assert rel_l2(got, want).max() < REL_L2_F32 / 10
    assert picks.shape == (9, ROUTED, CFG.moe_top_k)
    faults = {
        "another share": dict(MODEL, experts_held=dict(first=8, count=4,
                                                       of=16)),
        "no groups": dict(MODEL, n_group=1, topk_group=1),
        "no shared expert": dict(MODEL, _use_shared=False)}
    for name, model in faults.items():
        other, _ = reference_logits(seq, positions, model)
        assert rel_l2(got, other).max() > REL_L2_F32, name
    fp8 = ref.from_stacked(W, CFG.n_dense_layers, jnp.float8_e4m3fn)
    other, _ = reference_logits(seq, positions, weights=fp8)
    assert rel_l2(got, other).max() > REL_L2_F32


# -- the decode program in place (decode_forms.py; PERF.md section 6, PR 45) --

def test_a_decode_dispatch_in_both_forms(monkeypatch):
    """``block_paged_decode`` over the pool as the engine stores it (the
    entry at a whole lane tile), dense and then in place."""
    import decode_forms
    decode_forms.check_a_dispatch_in_both_forms(
        run_op, CFG, PROMPTS, LENS, TABLE,
        [jnp.zeros((CFG.n_layers, 40, PS, CFG.stored_dim), jnp.float32)],
        monkeypatch, REL_L2_F32)


def test_the_in_place_decode_program_holds_no_view(monkeypatch):
    import decode_forms
    decode_forms.check_the_program_holds_no_view(
        CFG, dict(max_batch=3, page_size=PS, n_pages=40, pages_per_seq=MP,
                  prompt_buckets=(8,), decode_block=2), monkeypatch)


@pytest.mark.parametrize("hook", [False, True], ids=["off", "on"])
def test_an_engine_decodes_in_place_where_the_kernel_runs(hook,
                                                          monkeypatch):
    import decode_forms
    decode_forms.check_an_engines_tokens_and_its_counter(
        make_engine, CFG, reference_logits, monkeypatch, hook, PS)


# -- a share's decode step through the few-rows kernel (PR 61) ---------------

# the share's mechanisms at a model width and a hidden width of whole lane
# tiles: where ``few_rows_usable`` admits the kernel
WIDE = replace(CFG, name="latent-share-wide-experts", dim=128,
               expert_hidden=128)


def _decoded_by_a_wide_share(monkeypatch, hook, sorted_experts=False):
    """Four requests through an engine of ``WIDE`` (one alone, three
    co-scheduled), built with the Pallas interpreter behind the calls or
    not (``hook``) and the experts' gate as it is or refusing
    (``sorted_experts``): (their tokens, the pool, the counters, what the
    decode bundle says of its experts)."""
    import decode_forms
    scope = fluid.Scope()
    for name, value in make_weights(WIDE, 1).items():
        scope.set(name, value)
    with monkeypatch.context() as m:
        if hook:
            decode_forms.kernel_on(m, PS)
        if sorted_experts:
            m.setattr(moe, "few_rows_usable", lambda *a, **k: False)
        engine = DecodeEngine(WIDE, scope=scope, config=DecodeConfig(
            max_batch=3, prompt_buckets=(8, 32), max_new_tokens=8,
            page_size=PS, decode_block=2, prefill_batch=1))
        try:
            said = engine.programs.decode["experts_in_kernel"]
            engine.warmup()
            rng = np.random.RandomState(23)
            prompts = [rng.randint(0, WIDE.vocab_size, n)
                       for n in (5, 21, 13, 8)]
            tokens = [engine.generate(prompts[0], max_new=6)] + [
                h.result(120) for h in [engine.submit(p, max_new=6)
                                        for p in prompts[1:]]]
            engine.assert_no_recompiles()
            stats = engine.stats()
        finally:
            engine.close()
        pool, = engine._pools
        return tokens, np.asarray(pool), stats, said


@pytest.mark.parametrize("hook", [False, True], ids=["off", "on"])
def test_a_shares_engine_decodes_through_the_few_rows_kernel(hook,
                                                             monkeypatch):
    """Where the kernel runs, every decode dispatch of a share's engine
    puts its routed layers through ``moe_few_rows``
    (``decode_experts_in_kernel_total == decode_batches_total``) and
    decodes the tokens and leaves the pool that the sorted form does
    (float32: the same sums in another order); on a CPU without the hook
    the counter stays 0."""
    tokens, pool, stats, said = _decoded_by_a_wide_share(monkeypatch, hook)
    assert said is hook
    assert stats["decode_batches_total"] > 0
    assert stats["decode_experts_in_kernel_total"] == (
        stats["decode_batches_total"] if hook else 0)
    assert stats["pools_lost_total"] == 0
    assert 0 < stats["moe_held_assignments_total"] \
        < stats["moe_assignments_total"]
    if not hook:
        return
    want, want_pool, sorted_stats, sorted_said = _decoded_by_a_wide_share(
        monkeypatch, True, sorted_experts=True)
    assert not sorted_said
    assert sorted_stats["decode_experts_in_kernel_total"] == 0
    assert sorted_stats["decode_batches_total"] \
        == stats["decode_batches_total"]
    for a, b in zip(tokens, want):
        assert np.array_equal(a, b)
    np.testing.assert_allclose(pool[:, 1:], want_pool[:, 1:],
                               rtol=REL_L2_F32, atol=REL_L2_F32)
    assert np.abs(want_pool[:, 1:]).max() > 0
