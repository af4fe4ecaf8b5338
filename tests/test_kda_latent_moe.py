"""A model of Kimi-delta-attention layers (the delta rule with a decay a
CHANNEL) beside a latent-attention layer in ONE stack, a share of each
layer's routed experts held behind a leading dense layer
(models/kda_latent_moe.py), through DecodeEngine at a tiny size on the CPU:
the engine's own logits against the plain reference
(benchmark/reference/kda_latent_moe_share.py) along every path a request
takes, the faults the configuration's ``departures`` name shown to fail,
and the pieces ISSUE 62 added to the program each against what it stands
on: the rule's per-channel chunk form against the recurrence with every
gate at its floor, the latent kind among ``attn_kinds``, the four shares
against the uncut layer; and Olmo-Hybrid's tiny model, whose mixer is the
same module at its defaults, giving the programs and the tokens it gave."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.models.hybrid_delta import HYBRID_DELTA_TINY
from paddle_tpu.models.kda_latent_moe import (KDA, KDA_LATENT_TINY, LATENT,
                                              KdaLatentMoEConfig)
from paddle_tpu.ops import delta_rule as dr
from paddle_tpu.ops import pallas_attention as pa
from paddle_tpu.ops import transformer_ops as T
from paddle_tpu.serving.decode_engine import DecodeConfig, DecodeEngine

from benchmark.builders import serve_delta
from benchmark.builders import serve_kda_latent as builder
from benchmark.builders.serve_blocks import make_weights
from benchmark.reference import kda_latent_moe_share as ref

import program_text
from decode_forms import kernel_on

CFG = KDA_LATENT_TINY
FLOOR = CFG.gate_floor


def model_of(cfg):
    return dict(
        name="tiny-kda", vocab_size=cfg.vocab_size, hidden_size=cfg.dim,
        num_hidden_layers=cfg.n_layers,
        layer_types=[ref.MLA if k == LATENT else ref.KDA
                     for k in cfg.layer_pattern],
        first_k_dense_replace=cfg.n_dense_layers,
        num_attention_heads=cfg.n_heads, head_dim=cfg.kda_key_dim,
        _kda_value_dim=cfg.kda_value_dim, q_lora_rank=None,
        kv_lora_rank=cfg.kv_rank, qk_nope_head_dim=cfg.nope_dim,
        qk_rope_head_dim=cfg.rope_dim, v_head_dim=cfg.v_dim,
        rope_theta=cfg.rope_base, rms_norm_eps=cfg.norm_eps,
        short_conv_kernel_size=cfg.d_conv, kda_lower_bound=cfg.gate_floor,
        intermediate_size=cfg.ffn_hidden,
        moe_intermediate_size=cfg.expert_hidden,
        num_experts=cfg.n_experts, num_experts_per_tok=cfg.moe_top_k,
        n_group=cfg.n_group, topk_group=cfg.topk_group,
        routed_scaling_factor=cfg.route_scale, score_function="sigmoid",
        norm_topk_prob=True, moe_router_enable_expert_bias=True,
        experts_held={"first": cfg.experts_first, "count": cfg.n_experts,
                      "of": cfg.router_width}, torch_dtype="float32")


MODEL = model_of(CFG)
ENGINE = dict(max_batch=3, prompt_buckets=(8, 16, 48), max_new_tokens=8,
              page_size=4, decode_block=2, chunk_size=16, prefill_batch=1,
              default_timeout_s=120.0)
STEPS = 6
REL_L2_F32 = 2e-4       # float32 engine against float32 reference


def weights(seed=3, cfg=CFG):
    """The builder's weights, every matrix ten times as large (so that a
    layer moves the residual stream and a fault in one shows), and its
    stand-ins."""
    w = make_weights(cfg, seed)
    w = {k: v if k.endswith("norm") or k == "tok_emb" else v * 10
         for k, v in w.items()}
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
    def __init__(self, w, model=MODEL, cfg=CFG):
        self.weights, self.config, self.cfg = w, model, cfg


def prompt_of(n, seed=0):
    return np.random.RandomState(seed + n).randint(
        0, CFG.vocab_size, n).astype(np.int64)


def probe(engine, w, n, model=MODEL, with_logits=False, **kw):
    """(rel_l2 of the engine's logits [1 + STEPS] against the reference's
    at a prompt of ``n`` tokens and STEPS decoded, the first kda layer's
    state's, the largest forced-pick gap); ``with_logits``: and the
    engine's logits and tokens themselves."""
    prompt = prompt_of(n)
    got, picks, decoded = builder.engine_logits(engine, prompt, STEPS, 0)
    state = builder.first_state(engine)
    sequence = np.concatenate([prompt, decoded[:-1]])
    want, _, gaps, want_state = builder.reference_logits(
        _System(w, model), sequence, n - 1 + np.arange(1 + STEPS), picks,
        **kw)
    errs = (builder.rel_l2(got, want),
            float(np.linalg.norm(state - want_state)
                  / np.linalg.norm(want_state)), float(gaps.max()))
    return errs + (got, decoded) if with_logits else errs


# -- the model's programs -------------------------------------------------

def test_tiny_has_both_kinds_a_leading_dense_layer_and_a_share():
    assert CFG.layer_kinds == (KDA, KDA, KDA, LATENT, KDA, KDA)
    assert (CFG.layers_of(LATENT), CFG.layers_of(KDA)) == (1, 5)
    assert [s[:2] + s[3:] for s in CFG.stacks()] == [
        ("Lead", "lead", 1, False), ("Latent", "latent", 1, True),
        ("Kda", "kda", 4, True)]
    shapes = CFG.param_shapes()
    assert shapes["kda.wa"] == ([4, 32, 8], "float32")      # a CHANNEL
    assert shapes["kda.dt_bias"] == ([4, 8], "float32")
    assert shapes["kda.a_log"] == ([4, 2], "float32")       # a head
    assert shapes["latent.wq"] == ([1, 32, 32], "float32")  # no low rank
    assert shapes["latent.wg"] == ([1, 32, 2], "float32")   # a gate a head
    assert shapes["kda.moe_router"] == ([4, 32, 16], "float32")
    assert shapes["kda.moe_w_gate"] == ([4, 4, 32, 16], "float32")
    assert "latent.wqa" not in shapes and "lead.moe_router" not in shapes


def test_a_bf16_model_keeps_its_state_in_float32():
    specs = dataclasses.replace(CFG, dtype="bfloat16").state_spec()
    assert [dt for _, dt in specs] == ["float32", "bfloat16"]


@pytest.mark.parametrize("kw", [
    dict(layer_pattern=(1, 1, 1)), dict(experts_first=13),
    dict(n_group=3), dict(gate_floor=0.0), dict(n_dense_layers=6),
    dict(layer_pattern=(1, 0, 1, 1), n_dense_layers=2)])
def test_a_configuration_that_is_not_this_model_is_refused(kw):
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, **kw)


def test_programs_carry_two_cache_kinds_and_three_pools(engine):
    p = engine.programs
    assert p.stats == T.KDA_LATENT_STATS == T.HYBRID_STATS + (
        "kda_state_updates_total", "kda_prefill_positions_total",
        "attn_latent_positions_total")
    assert p.kinds == {"state": {"pages_per_seq": 1, "n_pages": 4,
                                 "pools": (1, 2), "unit": "entries",
                                 "table": ("StateTable", "state_table")}}
    n_pages = engine.allocator.n_pages
    # ONE latent pool at whole lane tiles; a matrix a head, float32; the
    # tail of the convolved channels flat
    assert p.pool_specs == [
        ([1, n_pages, 4, 128], "float32"), ([5, 4, 2, 4, 6], "float32"),
        ([5, 4, 3 * 28], "float32")]
    assert engine.allocator.kinds == ("sequence", "state")
    # the gates' answers: a CPU, and a state of 4 x 6 a head is no tile
    assert not p.decode["in_place"] and not p.decode["state_in_kernel"]
    assert not dr.step_in_kernel(*p.pool_specs[1])
    attrs = CFG.block_attrs(4)
    assert [k.get("mixer") for k in attrs["attn_kinds"]] == ["latent",
                                                            "kda"]
    assert attrs["attn_kinds"][1]["rule"] == {
        "scope": "kda", "beta_max": 1.0, "floor": FLOOR}


def test_the_gates_answer_for_a_latent_kind(monkeypatch):
    """``decode_in_place`` / ``prefill_in_kernel`` asked kind by kind: the
    latent kind as a latent model is, the state kind never."""
    attrs = KdaLatentMoEConfig(
        n_experts=128, router_width=512).block_attrs(64)
    kinds = attrs["attn_kinds"]
    shapes = [(1, 64, 64, 640), (5, 9, 32, 128, 128), (5, 9, 36864)]
    widths = (128, 128)
    assert not T.decode_in_place("latent", kinds, shapes)
    monkeypatch.setattr(pa, "_use_pallas", lambda: True)
    assert T.decode_in_place("latent", kinds, shapes)
    assert T.decode_in_place("latent", kinds, shapes, 0)
    assert not T.decode_in_place("latent", kinds, shapes, 1)
    assert T.prefill_in_kernel("latent", kinds, widths, shapes, 2048, 177,
                               2048)
    assert not T.prefill_in_kernel("latent", kinds, widths, shapes, 2048,
                                   177, 2048, 1)
    # entries that are no whole lane tiles: the reference, as a latent
    # model's
    assert not T.decode_in_place("latent", kinds, [(1, 64, 64, 576)]
                                 + shapes[1:])
    # the state kind's step asks its mixer's own gate (``delta_rule.
    # step_in_kernel``): the cell's pool passes where the kernels run, the
    # tiny model's [5, 4, 2, 4, 6] and a bfloat16 pool nowhere
    specs = [(s, "float32") for s in shapes]
    assert T.state_step_in_kernel(kinds, specs)
    assert T.state_step_in_kernel(
        kinds, [specs[0], ((5, 257, 32, 128, 128), "float32"), specs[2]])
    assert not T.state_step_in_kernel(
        kinds, [specs[0], ((5, 4, 2, 4, 6), "float32"), specs[2]])
    assert not T.state_step_in_kernel(
        kinds, [specs[0], (shapes[1], "bfloat16"), specs[2]])
    monkeypatch.setattr(pa, "_use_pallas", lambda: False)
    assert not T.state_step_in_kernel(kinds, specs)


@pytest.mark.parametrize("label, scopes", [
    ("decode", ("kda/conv", "kda/step", "cache/kda", "attn/latent",
                "cache/latent", "mla/absorb", "moe/route", "moe/shared")),
    ("chunk", ("kda/conv", "kda/chunk", "cache/kda", "attn/latent",
               "cache/latent", "mla/expand", "moe/experts")),
    ("prefill_8", ("kda/conv", "kda/chunk", "attn/latent", "cache/latent",
                   "attn/latent/gate"))])
def test_the_scopes_a_trace_names_are_in_the_programs(engine, label, scopes):
    bundle = program_text.bundles_of(engine.programs)[label]
    text = program_text.lower_bundle(bundle, 3).as_text(debug_info=True)
    for scope in scopes:
        assert scope in text, scope
    assert "kda/step" not in text or label == "decode"
    assert "delta/" not in text


# -- engine logits = reference along every path ---------------------------

@pytest.mark.parametrize("n", [3, 8, 12, 16, 17, 37])
def test_engine_logits_and_state_are_the_references(served, engine, n):
    """Prefill (whole: 3-16; two chunks: 17; three: 37) then decode
    through both caches against the full forward: logits, the first kda
    layer's state, and the engine's picks the reference's own."""
    err, s_err, gap = probe(engine, served[0], n)
    assert err.max() < REL_L2_F32, err
    assert s_err < REL_L2_F32
    assert gap < 1e-4


def test_a_prompt_through_two_chunks_is_the_prompt_whole(served, engine):
    """The same 14 tokens through the whole-prompt program and through the
    chunk program twice (8 + 6, the state and the tail carried, the latent
    pages filled chunk by chunk): the same logits, state and latent
    entries."""
    prompt = prompt_of(14)
    table = np.zeros((1, engine.pages_per_seq), np.int32)
    table[0, :5] = 1 + np.arange(5)
    state = engine._kind_tables([{engine.STATE: [1]}])
    tokens = np.zeros((1, 16), np.int64)
    tokens[0, :14] = prompt
    engine._run_prefill_program(16, tokens, np.asarray([14], np.int32),
                                table, *state)
    whole = np.asarray(engine.kept["prefill_16"]["logits"])[0]
    whole_pools = [np.asarray(p) for p in engine._pools]
    for off, n in ((0, 8), (8, 6)):
        tokens = np.zeros((1, 16), np.int64)
        tokens[0, :n] = prompt[off:off + n]
        engine._run_chunk_program(
            tokens, np.asarray([n], np.int32), np.asarray([off], np.int32),
            table, *state)
    chunked = np.asarray(engine.kept["chunk"]["logits"])[0]
    assert builder.rel_l2(chunked, whole) < 1e-5
    pools = [np.asarray(p) for p in engine._pools]
    np.testing.assert_allclose(pools[1][:, 1], whole_pools[1][:, 1],
                               rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(pools[2][:, 1], whole_pools[2][:, 1],
                               rtol=1e-4, atol=2e-5)
    at = np.arange(14)
    np.testing.assert_allclose(
        pools[0][0, 1 + at // 4, at % 4], whole_pools[0][0, 1 + at // 4,
                                                        at % 4],
        rtol=1e-4, atol=2e-5)


def test_the_new_counters_tick_in_the_engines_totals(served):
    eng = engine_of(served[1], prompt_buckets=(8,), chunk_size=None)
    builder.engine_logits(eng, prompt_of(5), 2, 0)
    s = eng.stats()
    # a prompt of 5 through 5 kda layers; two steps of one live row; the
    # latent layer attended 6 then 7 positions
    assert s["kda_prefill_positions_total"] == 5 * 5
    assert s["kda_state_updates_total"] == 5 * 2
    assert s["attn_latent_positions_total"] == 6 + 7
    assert s["attn_full_positions_total"] == 0
    assert s["latent_tokens_read_total"] == 0
    assert s["moe_held_assignments_total"] > 0


FAULTS = {
    "a decay a head (the channels' mean)": dict(_decay="head"),
    "no decay": dict(_decay="none"),
    "the write strength doubled": dict(_beta_max=2.0),
    "a SiLU output gate": dict(_out_gate="silu"),
    "the latent layer's gate a head left out": dict(_use_head_gate=False),
    "the older taps dropped": dict(_older_taps=False),
    "the shared expert left out": dict(_use_shared=False),
    "a state rounded to bf16 a position": dict(_state_dtype="bfloat16"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_comparison_sees_a_term_switched_off(served, engine, fault):
    """The reference with one term off or over is NOT what the engine
    computes: the limits that pass the engine fail it."""
    err, s_err, _ = probe(engine, served[0], 37, dict(MODEL, **FAULTS[fault]))
    assert max(err.max(), s_err) > 10 * REL_L2_F32, (err, s_err)


def test_the_comparison_sees_float8_weights(served, engine):
    err, _, _ = probe(engine, served[0], 12, through=jnp.float8_e4m3fn)
    assert err.min() > 100 * REL_L2_F32


def test_a_bf16_state_pool_fails_the_builders_comparison(served,
                                                         monkeypatch):
    """``serve_kda_latent.compare_with_reference``, the function that
    decides the cell's ``correct``, on an engine whose STATE POOL is bf16
    (its programs computing in float32) against the clean reference, at
    float32's limits: findings, and the state's among them; the engine as
    it is has none."""
    w, scope = served
    monkeypatch.setattr(builder, "REL_L2", REL_L2_F32)
    monkeypatch.setattr(builder, "STATE_REL_L2", REL_L2_F32)
    monkeypatch.setattr(builder, "PROBE_STEPS", 2)
    system = _System(w)
    system.engine = engine_of(scope, prompt_buckets=(8, 48))
    assert builder.compare_with_reference(system, seed=7) == []
    spec = KdaLatentMoEConfig.state_spec
    monkeypatch.setattr(KdaLatentMoEConfig, "state_spec", lambda self: [
        (spec(self)[0][0], "bfloat16"), spec(self)[1]])
    system.engine = engine_of(scope, prompt_buckets=(8, 48))
    found = builder.compare_with_reference(system, seed=7)
    assert any("state rel_l2" in f for f in found), found
    assert any("position" in f for f in found), found


# -- the rule with a decay a channel ---------------------------------------

H, DK, DV = 3, 8, 10


def rule_inputs(b, t, g, seed=0):
    rng = np.random.RandomState(seed)
    q = dr._l2(jnp.asarray(rng.randn(b, t, H, DK), jnp.float32)) * DK ** -0.5
    k = dr._l2(jnp.asarray(rng.randn(b, t, H, DK), jnp.float32))
    v = jnp.asarray(rng.randn(b, t, H, DV), jnp.float32)
    beta = jnp.asarray(rng.rand(b, t, H), jnp.float32)
    s0 = jnp.asarray(rng.randn(b, H, DK, DV), jnp.float32)
    g = jnp.asarray(g(rng, (b, t, H, DK)), jnp.float32)
    return q, k, v, g, beta, s0


def by_steps(q, k, v, g, beta, state):
    outs = []
    for t in range(q.shape[1]):
        o, state = dr.rule_step(q[:, t], k[:, t], v[:, t], g[:, t],
                                beta[:, t], state)
        outs.append(o)
    return jnp.stack(outs, 1), state


GATES = {
    "every gate at the floor": lambda rng, shape: np.full(shape, FLOOR),
    "gates near 0": lambda rng, shape: -1e-3 * rng.rand(*shape),
    "gates spread over (floor, 0)":
        lambda rng, shape: FLOOR * rng.rand(*shape) ** 3,
    "one channel at the floor beside one that keeps":
        lambda rng, shape: np.where(np.arange(shape[-1]) % 2, FLOOR, -1e-6)
        * np.ones(shape),
}


class _ExpSpy:
    """jax.numpy with the largest argument ``exp`` was given recorded."""

    def __init__(self):
        self.top = -np.inf

    def __getattr__(self, name):
        return getattr(jnp, name)

    def exp(self, x):
        self.top = max(self.top, float(jnp.max(x)))
        return jnp.exp(x)


@pytest.mark.parametrize("t", [64, 150])
@pytest.mark.parametrize("gate", sorted(GATES))
def test_the_channel_chunk_form_is_the_recurrence(monkeypatch, gate, t):
    """``chunk_rule`` with a decay a channel against ``rule_step`` position
    by position, a state carried in: the same outputs and state, finite,
    and NO ``exp`` OF A POSITIVE NUMBER anywhere (every gate at -5 for a
    whole chunk would ask the factored form for ``exp(320)``)."""
    args = rule_inputs(2, t, GATES[gate])
    spy = _ExpSpy()
    monkeypatch.setattr(dr, "jnp", spy)
    with jax.default_matmul_precision("highest"):
        o, s = dr.chunk_rule(*args)
    monkeypatch.undo()
    assert spy.top <= 0.0, spy.top
    want_o, want_s = by_steps(*args)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o, want_o, rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(s, want_s, rtol=1e-4, atol=2e-5)


def test_a_channels_decay_scales_the_states_rows():
    q, k, v, g, beta, s0 = (np.asarray(x) for x in rule_inputs(
        1, 1, GATES["gates spread over (floor, 0)"]))
    o, s = dr.rule_step(*(jnp.asarray(x[:, 0]) for x in (q, k, v, g, beta)),
                        jnp.asarray(s0))
    for h in range(H):
        kk, qq, vv = k[0, 0, h], q[0, 0, h], v[0, 0, h]
        want = (np.eye(DK) - beta[0, 0, h] * np.outer(kk, kk)) \
            @ (np.exp(g[0, 0, h])[:, None] * s0[0, h]) \
            + beta[0, 0, h] * np.outer(kk, vv)
        np.testing.assert_allclose(s[0, h], want, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(o[0, h], want.T @ qq, rtol=1e-5,
                                   atol=1e-6)


def test_the_gates_forms_are_the_kinds_data():
    rng = np.random.RandomState(1)
    p = {"ALog": jnp.log(jnp.linspace(1.0, 4.0, H)),
         "DtBias": jnp.asarray(rng.randn(H * DK), jnp.float32)}
    ab = jnp.asarray(rng.randn(5, H * DK + H) * 3, jnp.float32)
    g, beta = dr.gates(p, ab, beta_max=1.0, floor=FLOOR)
    assert g.shape == (5, H, DK) and beta.shape == (5, H)
    assert float(g.min()) >= FLOOR and float(g.max()) < 0
    assert 0 < float(beta.min()) and float(beta.max()) < 1
    want = FLOOR / (1 + np.exp(-np.exp(np.asarray(p["ALog"]))[:, None] * (
        np.asarray(ab[:, :-H]) + np.asarray(p["DtBias"])).reshape(5, H, DK)))
    np.testing.assert_allclose(g, want, rtol=1e-5)
    # a decay a head, no floor, the module's ceiling: Gated Delta Networks'
    p_head = dict(p, DtBias=p["DtBias"][:H])
    g, beta = dr.gates(p_head, ab[:, :2 * H])
    assert g.shape == (5, H) and float(beta.max()) > 1
    np.testing.assert_allclose(
        g, -np.exp(np.asarray(p["ALog"])) * np.logaddexp(
            0, np.asarray(ab[:, :H]) + np.asarray(p_head["DtBias"])),
        rtol=1e-5)


# -- the share -------------------------------------------------------------

# the tiny model at widths that are whole lane tiles: where the gates of
# ops/moe.py admit the kernels under the interpreter hook
WIDE = dataclasses.replace(CFG, name="kda-latent-wide", dim=128,
                           expert_hidden=128)


@pytest.mark.parametrize("through, rows", [
    ("the_reference", 9), ("the_few_rows_kernel", 9),
    ("the_grouped_rows_kernel", 150)])
def test_the_four_shares_add_up_to_the_uncut_layer(through, rows,
                                                   monkeypatch):
    """Four chips, four experts each: what the shares' routed parts give,
    with the shared expert counted once, is the feed-forward of the uncut
    reference; and every share picks the same experts for every token.
    Through the kernels too (the interpreter hook at widths the gates
    admit): a share of a quarter at 9 rows, and at 150, more than the
    few-rows kernel takes, whose sorted rows go through
    ``moe_grouped_rows`` since PR 63."""
    from paddle_tpu.ops import moe
    cfg, model = (CFG, MODEL) if through == "the_reference" else (
        WIDE, model_of(WIDE))
    if through != "the_reference":
        monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    shape = jax.ShapeDtypeStruct((4, cfg.dim, cfg.expert_hidden),
                                 jnp.float32)
    assert moe.few_rows_usable(rows, shape, shape, (0, 16)) is (
        through == "the_few_rows_kernel")
    assert moe.grouped_rows_usable(rows, shape, shape, (0, 16)) is (
        through == "the_grouped_rows_kernel")
    whole = dataclasses.replace(cfg, n_experts=16, experts_first=0)
    w_all = weights(7, whole)
    layer = cfg.n_dense_layers          # the first routed one: kda.* 0
    u = jax.random.normal(jax.random.PRNGKey(4), (1, rows, cfg.dim))
    uncut = dict(model, experts_held=dict(first=0, count=16, of=16))
    ref_all = ref.from_stacked(w_all, uncut)
    with jax.default_matmul_precision("highest"):
        want, _, _, own = ref.experts(ref_all, layer, u[0], uncut)
        shared, _, _, _ = ref.experts(ref_all, layer, u[0], dict(
            model, experts_held=dict(first=0, count=0, of=16)))
    total, picks = jnp.zeros_like(shared), []
    for share in range(4):
        held = dataclasses.replace(cfg, experts_first=4 * share)
        attrs = {k: v for k, v in held.block_attrs(4).items()
                 if k != "page_size"}
        kinds = T.BlockKinds(
            eps=attrs.pop("epsilon"), **{k: attrs[k] for k in (
                "n_heads", "attention", "ffn", "residual", "moe_top_k",
                "scoring", "route_scale", "route_eps", "n_group",
                "topk_group", "experts_first")})
        p = {}
        for slot, (suffix, _, _) in held.layer_params(4, KDA, True).items():
            v = w_all[f"kda.{suffix}"][0]
            p[slot] = v[4 * share:4 * share + 4] \
                if slot in T._EXPERT_SLOTS else v
        with jax.default_matmul_precision("highest"):
            y, (load, idx) = T._routed_ffn(kinds, p, u, None)
        total = total + (y[0] - shared)
        picks.append(np.asarray(idx))
        assert int(load.sum()) == int(
            ((idx >= 4 * share) & (idx < 4 * share + 4)).sum())
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    for other in picks[1:]:
        assert np.array_equal(picks[0], other)
    assert np.array_equal(np.sort(picks[0][0], -1),
                          np.sort(np.asarray(own), -1))


# -- Olmo-Hybrid's tiny model: the same module at its defaults --------------

OLMO_GEOMETRY = dict(max_batch=3, page_size=4, n_pages=40, pages_per_seq=8,
                     prompt_buckets=(8, 16), decode_block=2, chunk_size=8)
# tests/program_text.py ``fingerprint`` on the tree before ISSUE 62
# (463859c): the rule's per-head form is the program it was
OLMO_PINNED = {"prefill_8": ("e5df9355ca101af4", 3224),
               "decode": ("a01a7aeb459388ee", 2235),
               "chunk": ("5c7ebe5a3dabd962", 3511)}
# serve_delta.engine_logits on the same tree: prompts of 5 and 37 tokens
# (RandomState(n)), the weights of ``weights(3)`` with Olmo's stand-ins
OLMO_TOKENS = {5: [4, 45, 90, 51, 87, 51, 92],
               37: [47, 63, 39, 29, 51, 92, 38]}


@pytest.mark.parametrize("label", sorted(OLMO_PINNED))
def test_olmos_programs_are_what_they_were(label):
    progs = HYBRID_DELTA_TINY.build_paged_programs(**OLMO_GEOMETRY)
    got = program_text.fingerprint(program_text.lower_bundle(
        program_text.bundles_of(progs)[label], len(progs.pool_specs)))
    assert got == OLMO_PINNED[label], (label, got)


def test_olmos_tiny_model_gives_the_tokens_it_gave():
    cfg = HYBRID_DELTA_TINY
    w = make_weights(cfg, 3)
    w = {k: v if k.endswith("norm") else v * 10 for k, v in w.items()}
    w.update(serve_delta.stand_ins(cfg))
    eng = engine_of(scope_of(w), cfg)
    for n, want in OLMO_TOKENS.items():
        prompt = np.random.RandomState(n).randint(
            0, cfg.vocab_size, n).astype(np.int64)
        _, decoded, _ = serve_delta.engine_logits(eng, prompt, STEPS)
        assert decoded.tolist() == want, (n, decoded.tolist())


# -- a request's tokens do not depend on its company -------------------------

def test_requests_in_a_mix_are_the_requests_alone(served):
    """Five requests over three slots (whole prompts and chunked ones,
    entries and pages reused): each one's tokens are what it gives alone
    on a fresh engine."""
    prompts = [prompt_of(n, seed=s) for s, n in enumerate((5, 12, 17, 30,
                                                           9))]
    alone = []
    for p in prompts:
        eng = engine_of(served[1], auto_start=True)
        alone.append(np.asarray(eng.generate(p, max_new=6)))
        eng.close()
    eng = engine_of(served[1], auto_start=True)
    handles = [eng.submit(p, max_new=6) for p in prompts]
    mixed = [np.asarray(h.result(120)) for h in handles]
    stats = eng.stats()
    eng.close()
    for a, m in zip(alone, mixed):
        assert np.array_equal(a, m), (a, m)
    assert stats["page_stall_total"] == 0 and stats["pools_lost_total"] == 0
    assert stats["state_resets_total"] == len(prompts)


def test_a_large_shares_sum_is_taken_in_each_tokens_own_order():
    """A share of a quarter of the router: a token's result does not move
    with the OTHER rows of the call (the blocks' walk summed a token's
    pairs in the order they fell among the others')."""
    from paddle_tpu.ops import moe
    rng = np.random.RandomState(0)
    t, k, e, width, d, f = 300, 8, 4, 16, 16, 8
    x = jnp.asarray(rng.randn(t, d), jnp.float32)
    w = [jnp.asarray(rng.randn(e, *s) * 0.3, jnp.float32)
         for s in ((d, f), (d, f), (f, d))]
    idx = jnp.asarray(np.stack([rng.permutation(width)[:k]
                                for _ in range(t)]), jnp.int32)
    gates = jnp.asarray(rng.rand(t, k), jnp.float32)
    out = moe.moe_apply_sorted(x, idx, gates, *w, held=(4, width))
    # the same 7 rows among other company
    other = np.asarray(idx).copy()
    other[7:] = np.stack([rng.permutation(width)[:k]
                          for _ in range(t - 7)])
    out2 = moe.moe_apply_sorted(x, jnp.asarray(other), gates, *w,
                                held=(4, width))
    assert np.array_equal(np.asarray(out[:7]), np.asarray(out2[:7]))
    # and it is the held experts' part of the sum
    want = np.zeros((t, d), np.float32)
    xn, (wg, wu, wd) = np.asarray(x), (np.asarray(a) for a in w)
    for i in range(t):
        for j in range(k):
            ex = int(idx[i, j]) - 4
            if 0 <= ex < e:
                g = xn[i] @ wg[ex]
                want[i] += float(gates[i, j]) * (
                    (g / (1 + np.exp(-g)) * (xn[i] @ wu[ex])) @ wd[ex])
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)


# -- more than 128 rows of a share through the grouped kernel (PR 63) --------

# 130 slots: a decode step's 130 rows x 3 picks and a window's 144 x 3 are
# more rows than the few-rows kernel takes, over a quarter of the router
WIDE_ENGINE = dict(ENGINE, max_batch=130, prompt_buckets=(144, 288),
                   chunk_size=144)


@pytest.fixture(scope="module")
def wide_served():
    w = weights(cfg=WIDE)
    return w, scope_of(w)


def _wide_run(scope, hook):
    """Three requests over the 130-slot engine, a whole prompt, one through
    two chunks and a short one: (the engine's own logits and tokens of each
    ALONE through the path a request of its length takes, the tokens each
    gives alone on a live engine, those it gives among the other two, that
    engine's totals)."""
    prompts = [np.random.RandomState(n).randint(
        0, WIDE.vocab_size, n).astype(np.int64) for n in (140, 200, 9)]
    with pytest.MonkeyPatch.context() as m:
        if hook:
            kernel_on(m, ENGINE["page_size"])
        eng = engine_of(scope, cfg=WIDE, **WIDE_ENGINE)
        bundles = [eng.programs.decode, eng.programs.chunk,
                   *eng.programs.prefill.values()]
        assert [b["experts_in_kernel"] for b in bundles] \
            == [hook] * len(bundles)
        probes = [builder.engine_logits(eng, p, 3, 0) for p in prompts]
        eng.close()
        eng = engine_of(scope, cfg=WIDE, auto_start=True, **WIDE_ENGINE)
        try:
            alone = [np.asarray(eng.generate(p, max_new=4)) for p in prompts]
            together = [np.asarray(h.result(300)) for h in [
                eng.submit(p, max_new=4) for p in prompts]]
            stats = eng.stats()
        finally:
            eng.close()
    return probes, alone, together, stats


def test_the_wide_engine_is_the_same_through_the_grouped_kernel(
        wide_served):
    """The tiny model at whole lane tiles with 130 slots, the hook off and
    on: the same tokens, logits within the file's limit, a request in the
    mix the request alone on BOTH sides (a row's result in the kernel
    depends on no other row), and the two counters tick with the hook (the
    decode step's for a step of more than 128 rows, the window's for a
    share) and not without."""
    off, on = (_wide_run(wide_served[1], hook) for hook in (False, True))
    for (probes, alone, together, stats), hook in ((off, False), (on, True)):
        for (_, _, decoded), one, mixed in zip(probes, alone, together):
            assert np.array_equal(one, mixed)
            assert np.array_equal(np.asarray(decoded)[:4], one)
        windows = stats["prefill_dispatch_total"] \
            + stats["chunk_prefill_total"]
        assert windows == 8 and stats["decode_batches_total"] > 0
        assert stats["prefill_experts_in_kernel_total"] == (
            windows if hook else 0)
        assert stats["decode_experts_in_kernel_total"] == (
            stats["decode_batches_total"] if hook else 0)
        assert stats["pools_lost_total"] == 0
    for (want, _, tokens), (got, _, again) in zip(off[0], on[0]):
        assert np.array_equal(np.asarray(tokens), np.asarray(again))
        assert builder.rel_l2(got, want).max() < REL_L2_F32


# -- the decode steps' states through the kernel (PR 64) ---------------------

# the tiny model with a state of whole tiles a head (8 x 128): where
# ``delta_rule.step_in_kernel`` admits ``delta_state_step`` under the hook
TILES = dataclasses.replace(CFG, name="kda-latent-tiles", kda_key_dim=8,
                            kda_value_dim=128)
TILES_PROMPTS = ((5, 0), (17, 1), (30, 2), (9, 3))   # (tokens, seed)


@pytest.fixture(scope="module")
def tiles_runs():
    """The engine at TILES with the hook off and on: {hook: (the probes of a
    whole prompt and of one through two chunks against the reference, the
    tokens four requests give alone, those they give together over three
    slots, that engine's totals)}."""
    w = weights(cfg=TILES)
    scope, model = scope_of(w), model_of(TILES)
    prompts = [prompt_of(n, seed=s) for n, s in TILES_PROMPTS]
    runs = {}
    for hook in (False, True):
        with pytest.MonkeyPatch.context() as m:
            if hook:
                kernel_on(m, ENGINE["page_size"])
            eng = engine_of(scope, cfg=TILES)
            assert eng.programs.pool_specs[1] == ([5, 4, 2, 8, 128],
                                                  "float32")
            assert eng.programs.decode["state_in_kernel"] is hook
            probes = [probe(eng, w, n, model=model, with_logits=True)
                      for n in (5, 17)]
            eng.close()
            alone = []
            for p in prompts:
                eng = engine_of(scope, cfg=TILES, auto_start=True)
                alone.append(np.asarray(eng.generate(p, max_new=6)))
                eng.close()
            eng = engine_of(scope, cfg=TILES, auto_start=True)
            try:
                together = [np.asarray(h.result(300)) for h in [
                    eng.submit(p, max_new=6) for p in prompts]]
                stats = eng.stats()
            finally:
                eng.close()
        runs[hook] = probes, alone, together, stats
    return runs


@pytest.mark.parametrize("hook", [False, True], ids=["off", "on"])
def test_the_tiles_engine_is_the_reference_and_counts_its_state_steps(
        tiles_runs, hook):
    """Logits, the first kda layer's state and the picks against the
    reference within the file's limits, the steps' states through
    ``delta_state_step`` or through jax.numpy; ``state_step_in_kernel_total``
    ticks with ``decode_batches_total`` under the hook and stays 0 without."""
    probes, _, _, stats = tiles_runs[hook]
    for err, s_err, gap, _, _ in probes:
        assert err.max() < REL_L2_F32, err
        assert s_err < REL_L2_F32
        assert gap < 1e-4
    assert stats["decode_batches_total"] > 0
    assert stats["state_step_in_kernel_total"] == (
        stats["decode_batches_total"] if hook else 0)
    assert stats["kda_state_updates_total"] > 0
    assert stats["pools_lost_total"] == 0 and stats["page_stall_total"] == 0


@pytest.mark.parametrize("hook", [False, True], ids=["off", "on"])
def test_a_request_in_the_tiles_mix_is_the_request_alone(tiles_runs, hook):
    """Four requests over three slots (an entry reused, a free one beside
    the held): an entry's step depends on no other entry, in the kernel as
    in jax.numpy."""
    _, alone, together, _ = tiles_runs[hook]
    for one, mixed in zip(alone, together):
        assert np.array_equal(one, mixed), (one, mixed)


def test_the_tiles_engine_gives_the_same_tokens_hook_off_and_on(tiles_runs):
    off, on = tiles_runs[False], tiles_runs[True]
    for (*_, want, tokens), (*_, got, again) in zip(off[0], on[0]):
        assert np.array_equal(np.asarray(tokens), np.asarray(again))
        assert builder.rel_l2(got, want).max() < REL_L2_F32
    for one, other in zip(off[1] + off[2], on[1] + on[2]):
        assert np.array_equal(one, other)
