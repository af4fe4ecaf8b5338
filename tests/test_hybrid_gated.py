"""A model whose full and window attention layers have THEIR OWN numbers of
query heads and rotations (YaRN in the full layers), a sigmoid gate on
every head's result, and a softmax router over experts ALL held beside a
shared one (Laguna-XS.2's mechanisms, models/hybrid_moe.py
HYBRID_GATED_TINY): ``block_forward``, the paged programs and DecodeEngine
against the plain reference (benchmark/reference/hybrid_moe_gated.py) at a
small size in float32. LOGITS are compared, never tokens.

REL_L2_F32: the programs and the reference compute the same float32
mathematics in another order (blocks of keys under a running softmax,
sorted pairs through a grouped product), which reads 1e-6 to 1e-5 here; a
term left out reads 1e-2 and more (every ``teeth`` test asks for 50 times
the tolerance), and bfloat16 anywhere would read 1e-3 to 1e-2.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.models.hybrid_moe import (HYBRID_GATED_TINY as CFG,
                                          HYBRID_MOE_TINY)
from paddle_tpu.ops import pallas_attention as PA
from paddle_tpu.ops import transformer_ops as T
from paddle_tpu.serving.decode_engine import DecodeConfig, DecodeEngine

from benchmark.builders.serve_hybrid import engine_logits
from benchmark.builders.serve_hybrid_gated import model_config
from benchmark.reference import hybrid_moe_gated as ref

# the ops' inputs, pools and tables of any HybridMoEConfig
from test_hybrid_moe import (PS, empty_pools, op_inputs, rel_l2, run_op,
                             tables)

REL_L2_F32 = 1e-4
KINDS = {0: ref.FULL, 1: ref.WINDOW}


def model_of(cfg):
    """The published config.json keys the reference reads, from ``cfg``."""
    y = cfg.yarn_full
    return dict(
        name=cfg.name, num_hidden_layers=cfg.n_layers,
        hidden_size=cfg.dim, vocab_size=cfg.vocab_size,
        intermediate_size=cfg.ffn_hidden,
        num_attention_heads=cfg.n_heads, head_dim=cfg.head_dim,
        num_key_value_heads=cfg.n_kv_full,
        num_attention_heads_per_layer=[cfg.heads(k)
                                       for k in cfg.layer_pattern],
        layer_types=[KINDS[k] for k in cfg.layer_pattern],
        mlp_layer_types=["dense"] * cfg.n_dense_layers + ["sparse"] * (
            cfg.n_layers - cfg.n_dense_layers),
        rope_parameters={
            ref.FULL: dict(
                rope_type="yarn", rope_theta=cfg.rope_base_full,
                factor=y["factor"],
                original_max_position_embeddings=y["original_max"],
                beta_fast=y["beta_fast"], beta_slow=y["beta_slow"],
                attention_factor=y["attention_factor"],
                partial_rotary_factor=cfg.rotary_dim / cfg.head_dim),
            ref.WINDOW: dict(
                rope_type="default", rope_theta=cfg.rope_base_window,
                partial_rotary_factor=cfg.rotary_dim_window
                / cfg.head_dim)},
        sliding_window=cfg.window, rms_norm_eps=cfg.norm_eps,
        num_experts=cfg.n_experts, num_experts_per_tok=cfg.moe_top_k,
        moe_intermediate_size=cfg.expert_hidden,
        shared_expert_intermediate_size=cfg.shared_hidden,
        moe_routed_scaling_factor=cfg.route_scale, gating=True,
        attention_bias=False, tie_word_embeddings=False,
        moe_apply_router_weight_on_input=False, torch_dtype=cfg.dtype)


def make_weights(cfg, seed=0):
    """Seeded float32 weights, every term alive: norms off 1."""
    out = {}
    shapes = cfg.param_shapes()
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    for k, (name, (shape, dt)) in zip(keys, sorted(shapes.items())):
        x = jax.random.normal(k, shape)
        out[name] = (1.0 + 0.1 * x if name.endswith("norm")
                     else 0.2 * x).astype(dt)
    return out


MODEL = model_of(CFG)
W = make_weights(CFG)


def reference_logits(seq, positions=None, model=MODEL, w=W, picks=None):
    forced = None
    if picks is not None:
        at = np.zeros((len(seq),), bool)
        at[positions] = True
        forced = {}
        for layer in range(picks.shape[1]):
            full = np.zeros((len(seq), picks.shape[2]), np.int32)
            full[positions] = picks[:, layer]
            forced[layer] = (at, full)
    logits, margins, gaps = ref.forward(ref.from_stacked(w, model),
                                        np.asarray(seq), model, positions,
                                        forced)
    return np.asarray(logits), np.asarray(margins), np.asarray(gaps)


def through_the_ops(cfg, w, prompt, chunk, steps=4, neighbour=None):
    """A prompt through the chunk program ``chunk`` tokens at a time (or,
    ``chunk`` None, the whole-prompt program), then ``steps`` decode
    steps, row 0 of two; row 1 inactive, or with ``neighbour`` another
    prompt's row beside it: (the logits at the prompt's last position and
    at the decoded ones, the picks there, the sequence, the decode op's
    outputs)."""
    table, ring = tables(cfg)
    rows = [prompt, neighbour if neighbour is not None else prompt[:1]]
    if neighbour is None:
        table[1], ring[1] = 0, 0
    pools = empty_pools(cfg)
    lens = np.asarray([len(r) for r in rows], np.int32)
    longest = max(lens)
    if chunk is None:
        toks = np.zeros((2, -(-longest // 8) * 8), np.int64)
        for i, r in enumerate(rows):
            toks[i, :len(r)] = r
        out = run_op(T._block_paged_prefill, cfg, w, Tokens=toks,
                     Lens=lens, Table=table, RingTable=ring, Pools=pools)
        pools = out["PoolsOut"]
        nxt = np.asarray(out["NextTok"]).copy()
        first_logits = np.asarray(out["Logits"])[:1]
        first_picks = np.asarray(out["Picks"])[:1]
    else:
        nxt = np.zeros((2,), np.int64)
        for off in range(0, longest, chunk):
            toks = np.zeros((2, chunk), np.int64)
            n = np.clip(lens - off, 0, chunk).astype(np.int32)
            for i, r in enumerate(rows):
                toks[i, :n[i]] = r[off:off + chunk]
            # a row whose prompt is over sits the chunk out on the null page
            live = n > 0
            out = run_op(
                T._block_paged_prefill_chunk, cfg, w, Tokens=toks,
                Lens=np.maximum(n, 1),
                Offsets=np.where(live, off, 0).astype(np.int32),
                Table=np.where(live[:, None], table, 0),
                RingTable=np.where(live[:, None], ring, 0), Pools=pools)
            pools = out["PoolsOut"]
            last = live & (lens <= off + chunk)
            nxt = np.where(last, np.asarray(out["NextTok"]), nxt)
            if last[0]:
                first_logits = np.asarray(out["Logits"])[:1]
                first_picks = np.asarray(out["Picks"])[:1]
    if neighbour is None:
        nxt[1] = 0
    dec = run_op(T._block_paged_decode, cfg, w, steps=steps, Tokens=nxt,
                 Positions=lens, Table=table, RingTable=ring, Pools=pools)
    toks = np.asarray(dec["OutTokens"])[0]
    logits = np.concatenate([first_logits,
                             np.asarray(dec["Logits"])[0]])[:1 + steps]
    picks = np.concatenate([first_picks,
                            np.asarray(dec["Picks"])[0]])[:1 + steps]
    seq = np.concatenate([prompt, nxt[:1], toks[:steps - 1]])
    return logits, picks, seq, dec


RNG = np.random.RandomState(0)
SHORT = RNG.randint(0, CFG.vocab_size, 11)     # a whole-prompt program
LONG = RNG.randint(0, CFG.vocab_size, 39)      # three chunks of 16
OTHER = RNG.randint(0, CFG.vocab_size, 27)


def compare(cfg, w, model, prompt, chunk):
    """(largest rel_l2 of the ops' logits against the reference of
    ``model`` computed with the ops' picks, largest gap of those picks)."""
    logits, picks, seq, _ = through_the_ops(cfg, w, prompt, chunk)
    positions = len(prompt) - 1 + np.arange(len(logits))
    want, _, gaps = reference_logits(seq, positions, model, w, picks)
    return rel_l2(logits, want).max(), gaps.max()


# -- one layer of each kind -------------------------------------------------

@pytest.mark.parametrize("layer, stack, kind", [(4, "full", 0),
                                                (1, "window", 1)])
def test_block_forward_is_the_references_layer_at_each_kinds_own_heads(
        layer, stack, kind):
    """A sparse layer of each kind, 6 | 8 query heads over 2 key/value
    heads, over one window with no cache."""
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 9, CFG.dim))
    pos = jnp.arange(9, dtype=jnp.int32)[None]
    kinds = T._block_runner(op_inputs(CFG, W),
                            CFG.block_attrs(PS)).kinds.of(kind)
    assert (kinds.n_heads, kinds.rotary_dim, kinds.name) == (
        CFG.heads(kind), CFG.rotary(kind), stack)
    assert (kinds.rotary_inv_freq is None) == (kind == 1)
    p = {slot: W[f"{stack}.{suffix}"][0] for slot, (suffix, _, _) in
         CFG.layer_params(1, kind, True).items()}

    def attend(q, kv):
        assert q.shape[2] == CFG.heads(kind)
        return T.masked_attention(q, *kv, pos, window=kinds.window)

    y, (load, idx) = T.block_forward(kinds, p, x, pos, attend)
    want, _, _, own = ref.layer(ref.from_stacked(W, MODEL), layer, x[0],
                                MODEL)
    assert rel_l2(y[0], want).max() < REL_L2_F32
    assert np.array_equal(np.sort(np.asarray(idx)[0], -1),
                          np.sort(np.asarray(own), -1))
    assert int(load.sum()) == 9 * CFG.moe_top_k


# -- the programs against the reference -------------------------------------

@pytest.mark.parametrize("prompt, chunk", [(SHORT, None), (LONG, 16),
                                           (SHORT, 3)])
def test_prefill_then_decode_through_pages_and_rings_matches_the_reference(
        prompt, chunk):
    """Whole-prompt, and in chunks each four windows long (the last a
    short one) or shorter than the ring; then decode steps that turn the
    ring further."""
    err, gap = compare(CFG, W, MODEL, prompt, chunk)
    assert err < REL_L2_F32 and gap < 1e-4


TEETH = {
    "no_gate": dict(_use_gate=False),
    "no_routed_scale": dict(_routed_scale=1.0),
    "no_shared_expert": dict(_use_shared=False),
    "no_yarn": dict(_use_yarn=False),
    "full_heads_grouped_as_the_window_layers": dict(
        _full_heads=CFG.n_heads_window),
    "window_5": dict(_window=5),
}


@pytest.mark.parametrize("name", sorted(TEETH))
def test_each_term_matters_to_the_comparison(name):
    """The reference with one term off lies far beyond the tolerance from
    the programs' logits, which the whole reference meets."""
    prompt = LONG[:21]                  # a chunk of 16 and one of 5
    positions = len(prompt) - 1 + np.arange(3)
    logits, picks, seq, _ = through_the_ops(CFG, W, prompt, 16, steps=2)
    whole, _, gaps = reference_logits(seq, positions, MODEL, W, picks)
    assert rel_l2(logits, whole).max() < REL_L2_F32 and gaps.max() < 1e-4
    without, _, _ = reference_logits(seq, positions,
                                     dict(MODEL, **TEETH[name]), W, picks)
    assert rel_l2(logits, without).min() > 50 * REL_L2_F32


def test_a_row_alone_is_the_row_among_neighbours_bit_for_bit():
    """Prefill in chunks and decode steps of row 0 beside an empty row and
    beside a neighbour of another length: the same bits."""
    alone, _, _, _ = through_the_ops(CFG, W, LONG, 16)
    beside, _, _, _ = through_the_ops(CFG, W, LONG, 16, neighbour=OTHER)
    assert np.array_equal(alone, beside)
    alone, _, _, _ = through_the_ops(CFG, W, SHORT, None)
    beside, _, _, _ = through_the_ops(CFG, W, SHORT, None,
                                      neighbour=OTHER[:9])
    assert np.array_equal(alone, beside)


def test_the_six_counters_tick_for_a_model_that_holds_its_experts_whole():
    steps = 4
    _, _, _, dec = through_the_ops(CFG, W, LONG, 16, steps=steps)
    stats = dict(zip(T.HYBRID_STATS, np.asarray(dec["Stats"])))
    n, routed = len(LONG), CFG.n_layers - CFG.n_dense_layers
    assert stats["moe_assignments_total"] == steps * CFG.moe_top_k * routed
    assert stats["moe_held_assignments_total"] \
        == stats["moe_assignments_total"]           # every expert is held
    assert stats["moe_decode_expert_calls_total"] \
        == steps * routed * CFG.n_experts
    # one row: a layer call touches exactly the K experts it picked
    assert stats["moe_decode_experts_touched_total"] \
        == steps * routed * CFG.moe_top_k
    assert stats["moe_max_load_total"] == steps * routed
    assert stats["attn_full_positions_total"] == 2 * sum(
        n + 1 + s for s in range(steps))
    assert stats["attn_window_positions_total"] == 3 * steps * CFG.window


# -- the decode kernel at 3 heads a group (the chip's form, interpreted) ----

def test_paged_flat_decode_pads_a_groups_heads_to_whole_sublane_tiles(
        monkeypatch):
    """6 query heads over 2 key/value heads of 128 | 128, flat in their
    pages: through the kernel (interpreted) the rows' results are the
    reference's, and a row's are the same bits whatever rows stand beside
    it."""
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(3, 6, 128), jnp.float32)
    k_pool = jnp.asarray(rng.randn(2, 9, 8, 256), jnp.float32)
    v_pool = jnp.asarray(rng.randn(2, 9, 8, 256), jnp.float32)
    table = jnp.asarray([[1, 2, 3], [4, 5, 0], [6, 7, 8]], jnp.int32)
    lens = jnp.asarray([20, 9, 24], jnp.int32)
    want = PA._ref_paged_attention(q, k_pool, v_pool, 1, table, lens, 2,
                                   128 ** -0.5)
    monkeypatch.setattr(PA, "_FORCE_INTERPRET", True)
    # two pages a block (the rows fold 2, 1 and 2): the chip's 512
    # positions would be 64 copies a block for the interpreter to unroll
    monkeypatch.setattr(PA, "PAGED_FLAT_BLOCK_KEYS", 16)
    got = jax.jit(lambda *a: PA.paged_flat_decode(*a))(
        q, k_pool, v_pool, 1, table, lens)
    assert got.shape == (3, 6, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    alone = jax.jit(lambda *a: PA.paged_flat_decode(*a))(
        q[:1], k_pool, v_pool, 1, table[:1], lens[:1])
    assert np.array_equal(np.asarray(alone[0]), np.asarray(got[0]))


# -- MiMo's configuration is what it was --------------------------------------

def test_mimos_tiny_configuration_keeps_its_parameters_and_attributes():
    """The fingerprints were taken on PR 54's tree (the parent of the PR
    that gave the class its new fields)."""
    shapes = HYBRID_MOE_TINY.param_shapes()

    def digest(mapping):
        return hashlib.sha256(
            repr(sorted(mapping.items())).encode()).hexdigest()[:16]

    assert (len(shapes), digest(shapes)) == (35, "206b4d6a38115fca")
    assert digest(HYBRID_MOE_TINY.block_attrs(2)) == "44f8e87f7d15a758"
    assert not [n for n in shapes if n.endswith((".wg", ".sh_w_gate"))]
    assert shapes["window.moe_bias"] == ([3, 16], "float32")
    assert shapes["full.wq"] == ([1, 32, 48], "float32")
    assert shapes["window.sink"] == ([3, 4], "float32")
    attrs = HYBRID_MOE_TINY.block_attrs(PS)
    assert attrs["scoring"] == "sigmoid"
    assert [sorted(k) for k in attrs["attn_kinds"]] == [
        ["base", "n_kv", "name", "pools", "sink", "stack", "window"]] * 2
    # and this model's names: the gate and the shared expert a stack
    mine = CFG.param_shapes()
    assert mine["window.wq"] == ([3, 32, 64], "float32")
    assert mine["full.wq"] == mine["lead.wq"] == ([1, 32, 48], "float32")
    assert mine["window.wg"] == ([3, 32, 8], "float32")
    assert mine["full.sh_w_down"] == ([1, 16, 32], "float32")
    assert "full.moe_bias" not in mine and "window.sink" not in mine


def test_the_builders_configuration_of_the_published_keys_is_this_one():
    cfg = model_config(MODEL)
    assert cfg == CFG


# -- through the engine -------------------------------------------------------

ENGINE = dict(max_batch=3, prompt_buckets=(8, 16, 48), max_new_tokens=8,
              page_size=PS, decode_block=2, prefill_batch=1, chunk_size=16,
              default_timeout_s=120.0)


@pytest.fixture(scope="module")
def scope():
    scope = fluid.Scope()
    for name, value in W.items():
        scope.set(name, value)
    return scope


def test_engine_logits_are_the_references_whole_chunked_and_decoded(scope):
    """The engine's own programs, as the benchmark's builder drives them:
    a prompt through the whole-prompt program and one through three
    chunks, each four windows long, and 8 decoded positions after each."""
    eng = DecodeEngine(CFG, scope=scope, config=DecodeConfig(**ENGINE),
                       auto_start=False)
    for prompt in (SHORT, LONG):
        got, picks, decoded = engine_logits(eng, prompt, 8)
        seq = np.concatenate([prompt, decoded[:-1]])
        positions = len(prompt) - 1 + np.arange(9)
        want, _, gaps = reference_logits(seq, positions, picks=picks)
        assert rel_l2(got, want).max() < REL_L2_F32 and gaps.max() < 1e-4
        fault, _, _ = reference_logits(
            seq, positions, dict(MODEL, _use_gate=False), picks=picks)
        assert rel_l2(got, fault).min() > 50 * REL_L2_F32


def test_requests_sharing_the_engine_are_bit_identical_and_count(scope):
    eng = DecodeEngine(CFG, scope=scope, config=DecodeConfig(**ENGINE))
    try:
        eng.warmup()
        prompts = [LONG, SHORT, OTHER, LONG[:20]]
        alone = [eng.generate(p, max_new=8) for p in prompts]
        before = eng.stats()
        reqs = [eng.submit(p, max_new=8) for p in prompts]
        for r, want in zip(reqs, alone):
            assert np.array_equal(r.result(60), want)
        after = eng.stats()
        for name in ("moe_decode_expert_calls_total",
                     "moe_decode_experts_touched_total",
                     "moe_assignments_total", "moe_max_load_total",
                     "attn_full_positions_total",
                     "attn_window_positions_total"):
            assert after[name] > before[name], name
        assert after["moe_held_assignments_total"] \
            == after["moe_assignments_total"]
        a = eng.allocator
        assert (a.in_use, a.in_use_of("window")) == (0, 0)
        eng.assert_no_recompiles()
    finally:
        eng.close()


# -- a decode step's experts through the few-rows kernel (interpreted) -------

def _few_rows_case(t, layer):
    from paddle_tpu.ops import moe
    rng = np.random.RandomState(7)
    e, d, f, k = 8, 128, 128, 3
    x = jnp.asarray(rng.randn(t, d), jnp.float32)
    shape = (3,) if layer is not None else ()
    w = [jnp.asarray(0.1 * rng.randn(*shape, e, *dims), jnp.float32)
         for dims in ((d, f), (d, f), (f, d))]
    # expert 5 is reached by nobody
    idx = jnp.asarray(rng.choice([0, 1, 2, 3, 4, 6, 7], (t, k)), jnp.int32)
    gates = jnp.asarray(rng.rand(t, k), jnp.float32)
    return moe, x, idx, gates, w


@pytest.mark.parametrize("t, layer", [(5, None), (16, 2), (40, 1)])
def test_the_few_rows_kernel_is_the_sorted_form(t, layer, monkeypatch):
    """The kernel a decode step's experts go through on the chip, against
    the sorted pairs' grouped product it stands in for: the same sums in
    float32, in another order (1e-6)."""
    moe, x, idx, gates, w = _few_rows_case(t, layer)
    assert not moe.few_rows_usable(t, w[0], w[2])       # a CPU: no kernel
    want = moe.moe_apply_sorted(x, idx, gates, *w, layer=layer)
    monkeypatch.setattr(PA, "_FORCE_INTERPRET", True)
    assert moe.few_rows_usable(t, w[0], w[2])
    assert moe.few_rows_usable(t, w[0], w[2], held=(0, 8))
    assert not moe.few_rows_usable(moe.FEW_ROWS + 1, w[0], w[2])
    got = jax.jit(lambda *a: moe.moe_apply_sorted(*a, layer=layer))(
        x, idx, gates, *w)
    assert got.shape == want.shape
    assert rel_l2(got, want).max() < 1e-5
    # a row alone is the row among the others, bit for bit
    alone = jax.jit(lambda *a: moe.moe_apply_sorted(*a, layer=layer))(
        x[:1], idx[:1], gates[:1], *w)
    assert np.array_equal(np.asarray(alone[0]), np.asarray(got[0]))


# -- the same kernel for one chip's SHARE of a layer, and for an expert
# -- taken a run of its hidden width at a time (PR 61) -----------------------

# 8 experts held of a router 32 wide: (the first held, the rows, the
# stack's layer, what the picks are drawn from: None is the whole width)
SHARE_CASES = {
    "first_0": dict(first=0, t=24),
    "first_not_0": dict(first=16, t=24),
    "the_last_run_of_the_router": dict(first=24, t=13),
    "a_row_with_no_held_pick": dict(first=8, t=9, absent_rows=(0, 4),
                                    picks=[8, 9, 11, 14]),
    "an_expert_nobody_reached": dict(
        first=8, t=40, picks=[3, 8, 9, 10, 12, 13, 14, 15, 20, 31]),
    "no_held_expert_reached": dict(first=8, t=6, picks=[0, 1, 2, 16, 30]),
    "stacked_with_a_traced_layer": dict(first=16, t=24, layer=2),
}


def _share_case(first, t, layer=None, picks=None, absent_rows=(), f=128):
    from paddle_tpu.ops import moe
    rng = np.random.RandomState(11)
    e, width, d, k = 8, 32, 128, 3
    x = jnp.asarray(rng.randn(t, d), jnp.float32)
    shape = (3,) if layer is not None else ()
    w = [jnp.asarray(0.1 * rng.randn(*shape, e, *dims), jnp.float32)
         for dims in ((d, f), (d, f), (f, d))]
    idx = np.stack([rng.choice(np.arange(width) if picks is None else picks,
                               k, replace=False) for _ in range(t)])
    for r in absent_rows:          # every pick of the row is another chip's
        idx[r] = [(first + e + j) % width for j in range(k)]
    gates = jnp.asarray(rng.rand(t, k), jnp.float32)
    return moe, x, jnp.asarray(idx, jnp.int32), gates, w, (first, width)


def _rows_agree(got, want, idx, held, e):
    """The rows with a held pick agree to the order of the float32 sums,
    the others are zeros in both; which rows have one."""
    first = 0 if held is None else held[0]
    live = np.asarray(((idx >= first) & (idx < first + e)).any(axis=1))
    got, want = np.asarray(got), np.asarray(want)
    if live.any():
        assert rel_l2(got[live], want[live]).max() < 1e-5
    assert not got[~live].any() and not want[~live].any()
    return live


@pytest.mark.parametrize("case", sorted(SHARE_CASES))
def test_the_few_rows_kernel_for_a_share_is_the_sorted_form(case,
                                                            monkeypatch):
    """``held=(first, width)``: the columns of the rows' weights are the
    experts held, a pick outside them is no column, and what the absent
    experts would add is left out as the sorted form leaves it out."""
    spec = dict(SHARE_CASES[case])
    layer = spec.get("layer")
    moe, x, idx, gates, w, held = _share_case(**spec)
    e = w[0].shape[-3]
    want = moe.moe_apply_sorted(x, idx, gates, *w, layer=layer, held=held)
    load = np.asarray(moe.moe_load(idx, e, first=held[0]))
    monkeypatch.setattr(PA, "_FORCE_INTERPRET", True)
    assert moe.few_rows_usable(x.shape[0], w[0], w[2], held)

    def through(*a):
        return moe.moe_apply_sorted(*a[:-1], layer=a[-1], held=held)

    args = (x, idx, gates, *w, None if layer is None else jnp.int32(layer))
    jaxpr = str(jax.make_jaxpr(through)(*args))
    # the one sort left is over the E experts held: which were reached
    assert "moe_few_rows" in jaxpr and "ragged_dot" not in jaxpr
    assert f"[{idx.size}]" not in jaxpr
    got = jax.jit(through)(*args)
    assert got.shape == want.shape and got.dtype == want.dtype
    live = _rows_agree(got, want, idx, held, e)
    if case == "a_row_with_no_held_pick":
        assert not live[0] and not live[4] and live.sum() == len(live) - 2
    if case == "an_expert_nobody_reached":
        assert (load == 0).sum() == 1 and load.sum() > 0
    if case == "no_held_expert_reached":
        assert load.sum() == 0 and not live.any()


@pytest.mark.parametrize("t", [5, 21])
@pytest.mark.parametrize("tiles", [2, 4])
@pytest.mark.parametrize("share", [False, True], ids=["whole", "share"])
def test_the_tiled_kernel_is_the_uncut_one(share, tiles, t, monkeypatch):
    """An expert too wide for VMEM twice over goes a run of its hidden
    width a grid step (``_hidden_tile``, read off the shapes against the
    two budgets, which are shrunk here to cut a 512-wide expert in 2 and
    in 4): a run's ``h`` needs no other run, so the result is the uncut
    kernel's to the order of the float32 sums; rows not a multiple of 16,
    a stacked layer, an expert nobody reached."""
    f, d = 512, 128
    moe, x, idx, gates, w, held = _share_case(
        8, t, layer=1, f=f, picks=None if share else [8, 9, 10, 12, 13, 15])
    if not share:
        idx, held = idx - 8, None
    monkeypatch.setattr(PA, "_FORCE_INTERPRET", True)

    def through():
        return jax.jit(lambda *a: moe.moe_apply_sorted(
            *a, layer=1, held=held))(x, idx, gates, *w)

    assert moe._hidden_tile(d, f, 4) == f
    want = through()
    monkeypatch.setattr(PA, "_VMEM_DEFAULT", 2 ** 20)
    monkeypatch.setattr(moe, "GROUPED_VMEM", 6 * d * (f // tiles) * 4)
    assert moe._hidden_tile(d, f, 4) == f // tiles
    assert moe.few_rows_usable(t, w[0], w[2], held)
    got = through()
    assert _rows_agree(got, want, idx, held, 8).any()
    monkeypatch.setattr(PA, "_FORCE_INTERPRET", False)
    _rows_agree(got, moe.moe_apply_sorted(x, idx, gates, *w, layer=1,
                                          held=held), idx, held, 8)


def _abstract(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _experts(e, d, f, dtype=jnp.bfloat16, down=None):
    return (_abstract((e, d, f), dtype),
            _abstract((e, f, d), dtype if down is None else down))


# what the gate says: (rows, (w_gate, w_down), held, the hook, the run of
# the hidden width a grid step or None where it refuses)
GATE_CASES = {
    "lagunas_experts_uncut": (64, _experts(256, 2048, 512), None, True,
                              512),
    "a_share_of_lagunas": (64, _experts(16, 2048, 512), (32, 256), True,
                           512),
    "deepseeks_share_512_a_step": (64, _experts(16, 7168, 2048), (0, 256),
                                   True, 512),
    "mimos_share_1024_a_step": (24, _experts(16, 4096, 2048), (16, 256),
                                True, 1024),
    "xing4s_experts_whole_under_a_raised_limit": (
        16, _experts(64, 3584, 1024), None, True, 1024),
    "exactly_128_rows_over_wide_experts": (
        128, _experts(64, 2048, 1536), None, True, 1536),
    "float32_experts": (8, _experts(8, 128, 128, jnp.float32), (0, 32),
                        True, 128),
    "one_row_more_than_a_tile": (129, _experts(256, 2048, 512), None, True,
                                 None),
    "a_cpu_without_the_hook": (64, _experts(256, 2048, 512), None, False,
                               None),
    "a_model_width_off_the_lane_tile": (64, _experts(8, 2000, 512), None,
                                        True, None),
    "a_hidden_width_off_the_lane_tile": (64, _experts(8, 2048, 500),
                                         (0, 32), True, None),
    "two_types": (64, _experts(8, 2048, 512, down=jnp.float32), None, True,
                  None),
    # 6 x 32,896 x 128 x 2 B: one lane tile of the hidden width is over
    "no_tile_within_the_budget": (64, _experts(8, 32896, 512), None, True,
                                  None),
}


@pytest.mark.parametrize("case", sorted(GATE_CASES))
def test_the_few_rows_gate(case, monkeypatch):
    """``few_rows_usable``: the backend, the rows, whole lane tiles, one
    type, and a tile of an expert within the budget; ``held`` refuses
    nothing."""
    from paddle_tpu.ops import moe
    assert moe.FEW_ROWS == 128
    t, (w_gate, w_down), held, hook, tile = GATE_CASES[case]
    monkeypatch.setattr(PA, "_FORCE_INTERPRET", hook)
    assert moe.few_rows_usable(t, w_gate, w_down, held) is (tile is not None)
    if tile is not None:
        d, f = w_gate.shape[-2:]
        assert moe._hidden_tile(d, f, w_gate.dtype.itemsize) == tile
        assert not moe.grouped_rows_usable(t, w_gate, w_down, held)
