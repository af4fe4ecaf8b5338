"""A model of gated short-convolution layers beside a few attention layers
with a norm a head, routed experts in every layer behind a leading dense
one (models/hybrid_conv_moe.py), through DecodeEngine at a tiny size on
the CPU: the engine's own logits against the plain reference (benchmark/
reference/hybrid_conv_moe.py) along every path a request takes, each fault
the configuration's ``departures`` name shown to fail, and the pieces
ISSUE 58 added to the program each against what it replaces: the ``conv``
mixer's step against its window, the state kind with ONE pool, the norm a
head, the paged kernel for value heads of half a lane tile, the router's
own divisor, a decode step's experts through the grouped kernel."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.models.hybrid_conv_moe import (CONV, FULL, HYBRID_CONV_TINY,
                                               HybridConvMoEConfig)
from paddle_tpu.ops import moe
from paddle_tpu.ops import pallas_attention as pa
from paddle_tpu.ops import short_conv
from paddle_tpu.ops.transformer_ops import (CONV_STATS, BlockKinds,
                                            _gqa_attention, _PagedRunner,
                                            decode_in_place,
                                            state_step_in_kernel)
from paddle_tpu.serving.decode_engine import DecodeConfig, DecodeEngine

from benchmark.builders import serve_hybrid_conv as builder
from benchmark.builders.serve_blocks import make_weights
from benchmark.reference import hybrid_conv_moe as ref

CFG = HYBRID_CONV_TINY
# heads of 64 over 2 key/value heads (keys and values 128 wide: one lane
# tile, two value heads in it) and experts of whole tiles: where the
# interpreter hook admits the packed paged kernel and the grouped kernel
WIDE = dataclasses.replace(CFG, name="hybrid-conv-wide", dim=128,
                           head_dim=64, ffn_hidden=128, expert_hidden=128)


def model_of(cfg):
    return dict(
        name="tiny-conv", model_type="lfm2_moe", vocab_size=cfg.vocab_size,
        hidden_size=cfg.dim, num_hidden_layers=cfg.n_layers,
        layer_types=[ref.CONV if k == CONV else ref.FULL
                     for k in cfg.layer_pattern],
        num_dense_layers=cfg.n_dense_layers,
        num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.n_kv,
        head_dim=cfg.head_dim, intermediate_size=cfg.ffn_hidden,
        moe_intermediate_size=cfg.expert_hidden,
        num_experts=cfg.n_experts, num_experts_per_tok=cfg.moe_top_k,
        norm_eps=cfg.norm_eps, norm_topk_prob=True, use_expert_bias=True,
        routed_scaling_factor=cfg.route_scale, conv_L_cache=cfg.d_conv,
        conv_bias=False, tie_word_embeddings=True,
        rope_parameters={"rope_theta": cfg.rope_base,
                         "rope_type": "default"},
        torch_dtype="float32")


MODEL = model_of(CFG)
ENGINE = dict(max_batch=3, prompt_buckets=(8, 16, 48), max_new_tokens=8,
              page_size=4, decode_block=2, chunk_size=16, prefill_batch=1,
              default_timeout_s=120.0)
STEPS = 6


def weights(seed=3, cfg=CFG):
    """The builder's weights, every matrix ten times as large (so that a
    layer moves the residual stream and a fault in one shows), and its
    stand-ins."""
    w = make_weights(cfg, seed)
    w = {k: v if k.endswith("norm") else v * 10 for k, v in w.items()}
    w.update(builder.stand_ins(cfg, w))
    return w


def scope_of(w):
    scope = fluid.Scope()
    for name, value in w.items():
        scope.set(name, value)
    return scope


@pytest.fixture(scope="module")
def served():
    w = weights()
    return w, scope_of(w)


def engine_of(scope, cfg=CFG, auto_start=False, **over):
    return DecodeEngine(cfg, scope=scope,
                        config=DecodeConfig(**dict(ENGINE, **over)),
                        auto_start=auto_start)


@pytest.fixture(scope="module")
def engine(served):
    eng = engine_of(served[1])
    eng.warmup()
    return eng


class _System:
    def __init__(self, w, model=MODEL):
        self.weights, self.config = w, model


def reference_at(w, prompt, decoded, picks=None, model=MODEL, **kw):
    sequence = np.concatenate([prompt, decoded[:-1]])
    positions = prompt.size - 1 + np.arange(decoded.size)
    return builder.reference_logits(_System(w, model), sequence, positions,
                                    picks, **kw)


def prompt_of(n, seed=0):
    return np.random.RandomState(seed).randint(
        0, CFG.vocab_size, n).astype(np.int64)


def rel_l2(got, want):
    return np.linalg.norm(got - want, axis=-1) \
        / np.linalg.norm(want, axis=-1)


# -- the model's programs -------------------------------------------------

def test_tiny_has_both_kinds_a_leading_dense_layer_and_a_period():
    assert CFG.layer_kinds == (CONV, FULL, CONV, CONV, FULL, CONV)
    assert (CFG.layers_of(FULL), CFG.layers_of(CONV)) == (2, 4)
    assert (CFG.layers_of(CONV, routed=False),
            CFG.layers_of(CONV, routed=True)) == (1, 3)
    assert [s[:2] + s[3:] for s in CFG.stacks()] == [
        ("Lead", "lead", 1, False), ("Full", "full", 2, True),
        ("Conv", "conv", 3, True)]
    shapes = CFG.param_shapes()
    assert shapes["full.q_norm"] == ([2, 8], "float32")     # ONE head wide
    assert shapes["conv.w_in"] == ([3, 32, 96], "float32")
    assert shapes["lead.conv_w"] == ([1, 3, 32], "float32")
    assert "lead.moe_router" not in shapes and "conv.w_gate" not in shapes
    with pytest.raises(ValueError):     # leading layers of two kinds
        HybridConvMoEConfig(layer_pattern=(1, 0, 1), n_dense_layers=2)
    with pytest.raises(ValueError):     # no attention layer at all
        HybridConvMoEConfig(layer_pattern=(1, 1, 1), n_dense_layers=1)


def test_the_builders_configuration_of_the_published_keys_is_this_one():
    assert builder.model_config(MODEL) == dataclasses.replace(
        CFG, name="tiny-conv")
    for wrong in (dict(conv_bias=True), dict(use_expert_bias=False),
                  dict(tie_word_embeddings=False),
                  dict(model_type="lfm2")):
        with pytest.raises(ValueError):
            builder.model_config(dict(MODEL, **wrong))


def test_programs_carry_a_state_kind_of_one_pool(engine):
    p = engine.programs
    assert p.stats == CONV_STATS
    assert CONV_STATS[-2:] == ("conv_state_updates_total",
                               "conv_prefill_positions_total")
    assert p.kinds == {"state": {"pages_per_seq": 1, "n_pages": 4,
                                 "pools": (2,), "unit": "entries",
                                 "table": ("StateTable", "state_table")}}
    n_pages = engine.allocator.n_pages
    # keys and values flat in their page; ONE pool of the state kind, the
    # tail flat: two inputs of the model's width, no recurrent state
    assert p.pool_specs == [
        ([2, n_pages, 4, 16], "float32"), ([2, n_pages, 4, 16], "float32"),
        ([4, 4, 2 * 32], "float32")]
    assert not p.decode["in_place"] and not p.decode["state_in_kernel"]
    assert not state_step_in_kernel(CFG.block_attrs(4)["attn_kinds"],
                                    p.pool_specs)
    for b in (p.decode, p.chunk, p.prefill[8]):
        assert b["feeds"][-4].endswith("state_table")
    assert engine.allocator.kinds == ("sequence", "state")
    assert engine.allocator.usable_of("state") == ENGINE["max_batch"]
    assert engine._pool_kind == ["sequence", "sequence", "state"]


def test_a_bf16_model_keeps_its_tail_in_bf16():
    specs = dataclasses.replace(CFG, dtype="bfloat16").state_spec()
    assert specs == [((2 * 32,), "bfloat16")]


# -- engine logits = reference along every path ---------------------------

@pytest.mark.parametrize("n", [4, 5, 8, 12, 16, 17, 37, 48])
def test_engine_logits_are_the_references(served, engine, n):
    """Whole-prompt programs at several ``lens`` of a bucket (4, 5 and 8
    of 8; 12 and 16 of 16: the padding does not enter the tail), a prompt
    through two chunks the second of ONE token (17: both of its older
    taps reach into the chunk before) and through three (37 = 16 + 16 +
    5; 48: three full ones), then decode steps through the cache with the
    other rows not live."""
    prompt = prompt_of(n, seed=n)
    builder.spoil_entry(engine)
    got, picks, decoded = builder.engine_logits(engine, prompt, STEPS)
    want, _, gaps = reference_at(served[0], prompt, decoded, picks)
    assert rel_l2(got, want).max() < 2e-5
    assert (np.argmax(got, -1) == np.argmax(want, -1)).all()
    assert gaps.max() < 1e-5      # the engine's picks are the reference's


def test_the_picks_before_the_compared_positions_are_the_engines(served):
    """Where no attention layer follows the first routed one, routing at a
    position reaches the next ``(d_conv - 1) x conv layers behind`` alone
    (``picks_reach``), and the probe hands back the engine's picks at
    those too (the path run on the prompt cut short there): in float32
    they are the reference's own, and the runs cut short leave the last
    one's logits what they were."""
    short = dataclasses.replace(CFG, name="conv-short",
                                layer_pattern=(1, 0, 1, 1))
    assert builder.picks_reach(short) == 4
    assert builder.picks_reach(CFG) is None         # attention behind
    assert builder.picks_reach(HybridConvMoEConfig(
        layer_pattern=(1, 0, 1, 1, 1), n_dense_layers=1)) == 6
    w = weights(cfg=short)
    eng = engine_of(scope_of(w), cfg=short)
    model = model_of(short)
    for n in (3, 12, 21):       # 3: fewer positions before than the reach
        prompt = prompt_of(n, seed=n)
        got, picks, decoded = builder.engine_logits(eng, prompt, STEPS,
                                                    back=4)
        plain, last, _ = builder.engine_logits(eng, prompt, STEPS)
        assert picks.shape[0] == min(4, n - (17 if n > 16 else 1)) \
            + 1 + STEPS
        assert (got == plain).all() and (picks[-last.shape[0]:] == last).all()
        want, margins, gaps = reference_at(w, prompt, decoded, picks,
                                           model=model)
        assert want.shape[0] == 1 + STEPS
        assert margins.shape == gaps.shape == (3, picks.shape[0])
        assert rel_l2(got, want).max() < 2e-5 and gaps.max() < 1e-5


@pytest.mark.parametrize("name, control, least", [
    ("the B gate", dict(model=dict(MODEL, _use_in_gate=False)), 0.3),
    ("the C gate", dict(model=dict(MODEL, _use_out_gate=False)), 0.3),
    ("the older taps", dict(model=dict(MODEL, _older_taps=False)), 0.3),
    ("the bias in the selection",
     dict(model=dict(MODEL, _use_bias=False)), None),
    ("the norm a head", dict(model=dict(MODEL, _head_norm="whole")), 0.03),
    ("bf16 weights", dict(through=jnp.float8_e4m3fn), 0.05)])
def test_each_term_matters_to_the_comparison(served, name, control, least):
    """The reference with one term of ``departures`` off reads far from
    the reference: the comparison has teeth for each. Without the bias
    the reference PICKS other experts (where nothing is forced), and
    where the engine's picks are forced on it they lie far under its
    own (the margin's side of the comparison)."""
    prompt, decoded = prompt_of(12), prompt_of(STEPS + 1, seed=9)
    want, _, _ = reference_at(served[0], prompt, decoded)
    off, _, _ = reference_at(served[0], prompt, decoded, **control)
    if least is not None:
        assert rel_l2(off, want).min() > least, name
        return
    assert rel_l2(off, want).max() > 0.05
    sequence = np.concatenate([prompt, decoded[:-1]])
    w = ref.from_stacked(served[0], MODEL)
    x = ref.f32(w["tok_emb"][jnp.asarray(sequence)])
    x, *_ = ref.layer(w, 0, x, MODEL)
    own = ref.layer(w, 1, x, MODEL)[3]
    forced = (np.ones((sequence.size,), bool), np.asarray(own))
    gap = ref.layer(w, 1, x, control["model"], forced)[2]
    assert float(jnp.max(gap)) > 0.02


# -- each fault, planted in the ENGINE, fails the builder's comparison -----

def _tail_not_carried(mp, w):
    """A chunk that continues a row starts from zeros all the same."""
    prefill = _PagedRunner._state_prefill

    def forgetful(self, p, z, mine, lyr, pos0, spec):
        return prefill(self, p, z, mine, lyr, jnp.zeros_like(pos0), spec)
    mp.setattr(_PagedRunner, "_state_prefill", forgetful)


def _never_from_zeros(mp, w):
    """The whole-prompt programs read the entry where they should start
    from zeros: a STALE tail, what the slot's last request left."""
    prefill = _PagedRunner._state_prefill

    def stale(self, p, z, mine, lyr, pos0, spec):
        if self.fresh:
            self.fresh, pos0 = False, jnp.maximum(pos0, 1)
        return prefill(self, p, z, mine, lyr, pos0, spec)
    mp.setattr(_PagedRunner, "_state_prefill", stale)


def _step_keeps_no_tail(mp, w):
    """A decode step that hands back the tail it was given."""
    step = short_conv.step

    def forgetful(p, z, s_pool, layer, held, tail0, eps):
        c, _, _ = step(p, z, s_pool, layer, held, tail0, eps)
        return c, None, tail0
    mp.setattr(short_conv, "step", forgetful)


def _norm_over_the_projection(mp, w):
    """The engine served a norm weight as wide as the projection, the
    head's repeated: ``_gqa_attention`` then norms the whole of it."""
    out = dict(w)
    for name, n in (("full.q_norm", CFG.n_heads), ("full.k_norm", CFG.n_kv)):
        out[name] = jnp.tile(w[name], (1, n))
    mp.setattr(HybridConvMoEConfig, "layer_params", _wide_norms(
        HybridConvMoEConfig.layer_params))
    return out


def _wide_norms(layer_params):
    def wide(self, n_layers, kind, routed):
        out = layer_params(self, n_layers, kind, routed)
        if kind == FULL:
            hd = self.head_dim
            out["QNorm"] = ("q_norm", [n_layers, self.n_heads * hd],
                            self.dtype)
            out["KNorm"] = ("k_norm", [n_layers, self.n_kv * hd],
                            self.dtype)
        return out
    return wide


def _no_selection_bias(mp, w):
    return {name: jnp.zeros_like(v) if name.endswith("moe_bias") else v
            for name, v in w.items()}


def _float8_weights(mp, w):
    return {name: v if name.endswith(("norm", "moe_bias", "moe_router"))
            else v.astype(jnp.float8_e4m3fn).astype(v.dtype)
            for name, v in w.items()}


def _gate_dropped(which):
    """The engine's mixer without one of its two gates: of the three
    parts ``W_in``'s result is split into, the gate's reads 1. ``T.jnp``
    is jax.numpy itself, which the reference splits with too: only a call
    from the engine's module loses the gate, whichever of the two is
    traced first in this process."""
    def plant(mp, w):
        import sys
        from paddle_tpu.ops import transformer_ops as T
        split = jnp.split

        def gates_of(x, n, axis=-1):
            parts = split(x, n, axis=axis)
            if n == 3 and axis == -1 \
                    and sys._getframe(1).f_globals is vars(T):
                parts[which] = jnp.ones_like(parts[which])
            return parts
        mp.setattr(T.jnp, "split", gates_of)
    return plant


@pytest.mark.parametrize("fault, plant, seen_by, clean", [
    ("none", None, (), ()),
    ("a chunk's tail not carried", _tail_not_carried,
     ("probe 17:", "probe 36:"), ("probe 4:", "probe 6:")),
    ("whole-prompt programs not reset", _never_from_zeros,
     ("probe 4:", "probe 6:"), ("probe 17:", "probe 36:")),
    ("a step keeps no tail", _step_keeps_no_tail, ("position",), ()),
    ("the B gate left out", _gate_dropped(0), ("position",), ()),
    ("the C gate left out", _gate_dropped(1), ("position",), ()),
    ("the norm over the whole projection", _norm_over_the_projection,
     ("position",), ()),
    ("no bias in the selection", _no_selection_bias, ("the engine's picks",),
     ()),
    ("float8 weights", _float8_weights, ("position",), ())])
def test_a_fault_in_the_engine_fails_the_builders_comparison(
        served, monkeypatch, fault, plant, seen_by, clean):
    """``serve_hybrid_conv.compare_with_reference``, the function that
    decides the cell's ``correct``, on an engine built WITH the fault
    against the clean reference: it returns findings, by the limit that
    is there to see the fault (float32 here, so the limits are float32's:
    the chip's are set between bf16's readings, PERF.md section 4). The
    entry held a request before the probes, as after a window, and is
    spoiled before each: a path that reads it where it must start from
    zeros is seen at the probes of that path (the whole-prompt programs':
    4 and 6 tokens; NaN ever after) and a tail that a chunk does not
    carry at the probes of several chunks (17 = a chunk of 16 and ONE
    token; 36: three quarters of a bucket that only rounds the page
    budget here, three chunks)."""
    w, scope = served
    monkeypatch.setattr(builder, "REL_L2", 2e-4)
    monkeypatch.setattr(builder, "MARGIN", 1e-4)
    served_w = plant(monkeypatch, w) if plant else None
    if served_w is not None:
        scope = scope_of(served_w)
    system = _System(w)
    system.cfg, system.engine = CFG, engine_of(scope, prompt_buckets=(8, 48))
    builder.engine_logits(system.engine, prompt_of(29, seed=1), 2)
    found = builder.compare_with_reference(system, seed=7)
    assert bool(found) == bool(seen_by), found
    for what in seen_by:
        assert any(what in f for f in found), (what, found)
    assert not [f for f in found if f.startswith(clean)] or not clean, found


# -- a slot's history is not observable ------------------------------------

def test_a_sequence_that_takes_a_slot_is_the_sequence_alone(served):
    """Requests one after the other through ONE slot and ONE state entry
    (``max_batch`` 1): each is bit for bit what it is alone on a fresh
    engine, whatever the entry's last holder left in its tails; and the
    counters count what ran."""
    prompts = [prompt_of(n, seed=n) for n in (7, 13, 29, 5)]
    eng = engine_of(served[1], auto_start=True, max_batch=1)
    shared = [np.asarray(eng.generate(p, max_new=6)) for p in prompts]
    s = eng.stats()
    eng.close()
    assert s["state_resets_total"] == s["prefill_total"] == len(prompts)
    assert s["pools_lost_total"] == 0 and s["page_stall_total"] == 0
    assert s["conv_prefill_positions_total"] == 4 * sum(
        p.size for p in prompts)
    assert s["conv_state_updates_total"] > 0
    assert s["moe_held_assignments_total"] == s["moe_assignments_total"] > 0
    for p, got in zip(prompts, shared):
        alone = engine_of(served[1], auto_start=True, max_batch=1)
        want = np.asarray(alone.generate(p, max_new=6))
        alone.close()
        assert (got == want).all()


def test_requests_sharing_the_engine_are_bit_identical(served):
    prompts = [prompt_of(n, seed=50 + n) for n in (6, 11, 21, 40, 9)]
    eng = engine_of(served[1], auto_start=True)
    futures = [eng.submit(p, max_new=5) for p in prompts]
    together = [np.asarray(f.result(timeout=120)) for f in futures]
    alone = [np.asarray(eng.generate(p, max_new=5)) for p in prompts]
    eng.close()
    for a, b in zip(together, alone):
        assert (a == b).all()


# -- the conv mixer: a step is a window of one position --------------------

def test_conv_step_is_the_window_position_by_position():
    """``short_conv.step`` against ``short_conv.window`` one position at a
    time, the tail carried flat from step to step, and against the
    convolution by its definition; a window that continues (``tail0`` the
    last two inputs) is the second half of the whole one, and padding
    past ``lens`` does not enter the tail."""
    rng = np.random.RandomState(0)
    b, t, c, k = 2, 9, 8, 3
    g = jnp.asarray(rng.randn(b, t, c), jnp.float32)
    p = {"ConvW": jnp.asarray(rng.randn(k, c), jnp.float32)}
    lens = jnp.asarray([t, t], jnp.int32)
    whole, none, tail = short_conv.window(
        p, g, None, jnp.zeros((b, k - 1, c)), lens, 1e-5)
    assert none is None
    padded = np.concatenate([np.zeros((b, k - 1, c)), np.asarray(g)], 1)
    by_definition = sum(np.asarray(p["ConvW"])[j] * padded[:, j:j + t]
                        for j in range(k))
    assert np.allclose(whole, by_definition, atol=1e-6)
    assert np.allclose(tail, g[:, -2:])
    flat = jnp.zeros((b, (k - 1) * c))
    for i in range(t):
        y, none, flat = short_conv.step(p, g[:, i], None, 0,
                                        jnp.ones((b,), bool), flat, 1e-5)
        assert none is None
        assert np.allclose(y, whole[:, i], atol=1e-6), i
    assert np.allclose(flat.reshape(b, k - 1, c), tail)
    first, _, mid = short_conv.window(
        p, g[:, :5], None, jnp.zeros((b, k - 1, c)),
        jnp.asarray([5, 4], jnp.int32), 1e-5)
    assert np.allclose(mid[0], g[0, 3:5]) and np.allclose(mid[1], g[1, 2:4])
    second, _, _ = short_conv.window(p, g[:, 5:], None, mid[:1].repeat(2, 0),
                                     jnp.asarray([4, 4], jnp.int32), 1e-5)
    assert np.allclose(second[0], whole[0, 5:], atol=1e-6)
    assert not short_conv.step_in_kernel((4, 4, 64), "bfloat16")


# -- the norm a head --------------------------------------------------------

def test_the_norm_a_head_is_not_the_norm_over_the_projection():
    """``_gqa_attention`` reads the form off the weight's width: [hd] norms
    every head by itself (the reference's), [heads * hd] the whole
    projection (OLMo's), and the two differ."""
    rng = np.random.RandomState(1)
    d, h, g, hd = 16, 4, 2, 8
    kinds = BlockKinds(n_heads=h, n_kv=g, base=1e4, eps=1e-5, key_dim=hd,
                       v_dim=hd)
    p = {s: jnp.asarray(rng.randn(d, n * hd), jnp.float32)
         for s, n in (("Wq", h), ("Wk", g), ("Wv", g))}
    p["Wo"] = jnp.eye(h * hd, dtype=jnp.float32)
    qn = jnp.asarray(1 + rng.rand(hd), jnp.float32)
    u = jnp.asarray(rng.randn(1, 3, d), jnp.float32)
    seen = {}

    def attend(q, entries):
        seen["q"], seen["k"] = q, entries[0]
        return jnp.zeros(q.shape[:2] + (h * hd,), q.dtype)

    pos = jnp.arange(3)[None]
    _gqa_attention(kinds, dict(p, QNorm=qn, KNorm=qn), u, pos * 0, attend)
    a_head = dict(seen)
    _gqa_attention(kinds, dict(p, QNorm=jnp.tile(qn, h),
                               KNorm=jnp.tile(qn, g)), u, pos * 0, attend)
    q = (u @ p["Wq"]).reshape(1, 3, h, hd)      # position 0: no rotation
    want = q * jax.lax.rsqrt(jnp.mean(q * q, -1, keepdims=True) + 1e-5) * qn
    assert np.allclose(a_head["q"], want, atol=1e-5)
    assert np.abs(np.asarray(a_head["q"] - seen["q"])).max() > 0.05
    assert np.abs(np.asarray(a_head["k"] - seen["k"])).max() > 0.05


# -- the paged kernel for value heads of half a lane tile -------------------

def _pools(rng, layers, pages, ps, g, dk, dv, dtype=jnp.float32):
    return (jnp.asarray(rng.randn(layers, pages, ps, g * dk), dtype),
            jnp.asarray(rng.randn(layers, pages, ps, g * dv), dtype))


@pytest.mark.parametrize("g, rep, dv", [(8, 4, 64), (2, 3, 64), (4, 1, 32)])
def test_the_packed_paged_kernel_is_the_reference(monkeypatch, g, rep, dv):
    """``paged_flat_decode`` through the Pallas interpreter at value heads
    that are a PART of a lane tile (LFM2's 8 heads of 64 under 32 query
    heads; 3 query heads a group, which go in padded; four 32-wide heads a
    tile) against ``_ref_paged_attention``: rows of unequal length, a row
    of ONE position, a row that ends on a page's edge and one that fills
    its table, each its own pages in any order."""
    rng = np.random.RandomState(2)
    ps, pps, dk = 8, 5, 64
    k_pool, v_pool = _pools(rng, 2, 1 + 6 * pps, ps, g, dk, dv)
    lengths = jnp.asarray([13, 1, 16, 40, 7, 24], jnp.int32)
    table = jnp.asarray(1 + rng.permutation(6 * pps).reshape(6, pps),
                        jnp.int32)
    q = jnp.asarray(rng.randn(6, g * rep, dk), jnp.float32)
    assert not pa.paged_packed_usable(k_pool.shape, v_pool.shape, g)
    want = pa._ref_paged_attention(q, k_pool, v_pool, 1, table, lengths, g,
                                   dk ** -0.5)
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    # two pages a block: the row of 40 positions folds three, where the
    # chip's 512 would be 64 copies a block for the interpreter to unroll
    monkeypatch.setattr(pa, "PAGED_FLAT_BLOCK_KEYS", 2 * ps)
    assert pa.paged_packed_usable(k_pool.shape, v_pool.shape, g)
    assert not pa.paged_flat_usable(k_pool.shape, v_pool.shape, g)
    got = jax.jit(lambda *a: pa.paged_flat_decode(*a))(
        q, k_pool, v_pool, 1, table, lengths)
    assert got.shape == (6, g * rep, dv)
    assert np.allclose(got, want, atol=2e-5, rtol=2e-5)
    # a row's result depends on its own pages and length alone
    alone = jax.jit(lambda *a: pa.paged_flat_decode(*a))(
        q[3:4], k_pool, v_pool, 1, table[3:4], lengths[3:4])
    assert (np.asarray(alone[0]) == np.asarray(got[3])).all()


def test_the_packed_gate_asks_for_whole_tiles_of_part_tile_heads(
        monkeypatch):
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    k = (1, 9, 8, 512)
    assert pa.paged_packed_usable(k, k, 8)              # 8 heads of 64
    assert not pa.paged_packed_usable(k, k, 4)          # 128: the flat form
    assert pa.paged_flat_usable(k, k, 4)
    assert not pa.paged_packed_usable(k, (1, 9, 8, 192), 4)   # 48 a head
    assert not pa.paged_packed_usable(k, (1, 9, 8, 64), 1)    # half a tile
    assert not pa.paged_packed_usable((1, 9, 8, 96), (1, 9, 8, 128), 2)
    kinds = HybridConvMoEConfig().block_attrs(64)["attn_kinds"]
    shapes = [[1, 99, 64, 512], [1, 99, 64, 512], [4, 257, 4096]]
    assert decode_in_place("gqa", kinds, shapes)
    assert decode_in_place("gqa", kinds, shapes, 0)
    assert not decode_in_place("gqa", kinds, shapes, 1)


# -- the router's own divisor ------------------------------------------------

def test_moe_route_with_the_models_divisor_is_the_references_rule():
    """``moe_route(scoring="sigmoid", bias, eps=1e-6)`` picks and weighs as
    the reference's router does; the default divisor (DeepSeek-V3's 1e-20)
    stays what it was and differs where the scores are tiny."""
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(11, 16), jnp.float32)
    wr = jnp.asarray(rng.randn(16, 8), jnp.float32)
    bias = jnp.asarray(0.1 * rng.randn(8), jnp.float32)
    idx, gates = moe.moe_route(x, wr, 3, "sigmoid", bias, 1.5, eps=1e-6)
    picked, want, _, _ = ref._route(
        x, wr, bias, jnp.zeros((11,), bool), jnp.zeros((11, 3), jnp.int32),
        K=3, scale=1.5, use_bias=True)
    assert (np.asarray(idx) == np.asarray(picked)).all()
    assert np.allclose(gates, want, rtol=1e-6)
    s = jax.nn.sigmoid(x @ wr)
    own = jnp.take_along_axis(s, idx, -1)
    assert np.allclose(gates, 1.5 * own / (own.sum(-1, keepdims=True)
                                           + 1e-6), rtol=1e-6)
    tiny = -jnp.ones((4, 16), jnp.float32)      # logits of -16: 1e-7
    wpos = 1.0 + 0.01 * jnp.abs(wr)
    a = moe.moe_route(tiny, wpos, 3, "sigmoid", None)[1]
    b = moe.moe_route(tiny, wpos, 3, "sigmoid", None, eps=1e-6)[1]
    assert np.allclose(a.sum(-1), 1.0) and float(b.sum(-1).max()) < 0.5


# -- a decode step's experts through the grouped kernel ----------------------

@pytest.mark.parametrize("layer", [None, 1])
def test_a_wide_decode_steps_experts_go_through_the_grouped_kernel(
        monkeypatch, layer):
    """More than ``FEW_ROWS`` rows over experts all held: the call puts
    the sorted pairs through ``moe_grouped_rows``, here in the interpreter,
    and gives what the three ``ragged_dot`` give."""
    rng = np.random.RandomState(4)
    t, k, e, d, f = moe.FEW_ROWS + 64, 4, 8, 128, 128
    x = jnp.asarray(rng.randn(t, d), jnp.float32)
    idx = jnp.asarray(np.stack([rng.permutation(e)[:k] for _ in range(t)]),
                      jnp.int32)
    gates = jnp.asarray(rng.rand(t, k), jnp.float32)
    lead = () if layer is None else (2,)
    wg, wu = (jnp.asarray(0.1 * rng.randn(*lead, e, d, f), jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(0.1 * rng.randn(*lead, e, f, d), jnp.float32)
    apply = lambda: jax.jit(lambda *a: moe.moe_apply_sorted(
        *a, layer=layer))(x, idx, gates, wg, wu, wd)
    assert not moe.grouped_rows_usable(t, wg, wd)
    want = apply()
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    assert moe.grouped_rows_usable(t, wg, wd)
    assert not moe.grouped_rows_usable(moe.FEW_ROWS, wg, wd)
    # LFM2's expert: two of them are 37.7 MB, under the grouped kernel's 48
    # and, since PR 61, the few-rows kernel's at a step of 128 rows or
    # fewer; the cell's 256 rows are the grouped kernel's
    big = jax.ShapeDtypeStruct((4, 64, 2048, 1536), jnp.bfloat16)
    down = jax.ShapeDtypeStruct((4, 64, 1536, 2048), jnp.bfloat16)
    assert moe.few_rows_usable(64, big, down)
    assert not moe.few_rows_usable(256, big, down)
    assert moe.grouped_rows_usable(256, big, down)
    got = jax.jit(lambda *a: moe.moe_apply_sorted(*a, layer=layer))(
        x, idx, gates, wg, wu, wd)
    assert np.allclose(got, want, atol=1e-4, rtol=1e-4)


# -- the widened model through both kernels ---------------------------------

def test_the_wide_model_decodes_through_both_kernels(monkeypatch):
    """WIDE through an engine built with the interpreter hook on: its
    decode program attends through the packed kernel (``in_place``) and,
    at ``max_batch`` over FEW_ROWS, its routed layers go through the
    grouped kernel; logits are the reference's."""
    w = weights(cfg=WIDE)
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    # two of the engine's pages a block: the chip's 512 positions would be
    # 128 copies a block for the interpreter to unroll
    monkeypatch.setattr(pa, "PAGED_FLAT_BLOCK_KEYS", 2 * ENGINE["page_size"])
    monkeypatch.setattr(moe, "FEW_ROWS", 2)
    eng = engine_of(scope_of(w), cfg=WIDE, prompt_buckets=(8, 16))
    assert eng.programs.decode["in_place"]
    assert eng.programs.prefill[8]["experts_in_kernel"]
    prompt = prompt_of(11, seed=5)
    got, picks, decoded = builder.engine_logits(eng, prompt, 4)
    want, _, gaps = reference_at(w, prompt, decoded, picks,
                                 model=model_of(WIDE))
    assert rel_l2(got, want).max() < 5e-5
    assert gaps.max() < 1e-5
