"""A model of gated delta-rule layers beside a few full-attention layers
(models/hybrid_delta.py) through DecodeEngine at a tiny size on the CPU:
the engine's own logits and first-layer state against the plain reference
(benchmark/reference/hybrid_delta.py: the rule as the RECURRENCE, where
the engine's programs compute it in chunks) along every path a request
takes, each planted fault (tests/hybrid_delta_faults.py) seen by the
builder's comparison at the probes of its own path, and the ways a cache
entry that NO POSITION INDEXES could make a result depend on a slot's
history: a reused entry, rows that are not live, a chunk job that waits for
pages."""
import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.models.hybrid_delta import (DELTA, FULL, HYBRID_DELTA_TINY,
                                            HybridDeltaConfig)
from paddle_tpu.ops.transformer_ops import (DELTA_STATS, HYBRID_STATS,
                                            SSM_STATS, BlockKinds,
                                            decode_in_place, stats_names)
from paddle_tpu.serving.decode_engine import DecodeConfig, DecodeEngine

from benchmark.builders import serve_delta
from benchmark.builders.serve_blocks import make_weights

import program_text
from hybrid_delta_faults import FAULTS

CFG = HYBRID_DELTA_TINY
MODEL = dict(
    name="tiny-delta", model_type="olmo_hybrid", vocab_size=CFG.vocab_size,
    hidden_size=CFG.dim, num_hidden_layers=CFG.n_layers,
    layer_types=["linear_attention", "linear_attention",
                 "full_attention"] * 2,
    num_attention_heads=CFG.n_heads, num_key_value_heads=CFG.n_kv,
    intermediate_size=CFG.ffn_hidden, hidden_act="silu",
    attention_bias=False, tie_word_embeddings=False,
    rope_parameters={"rope_theta": None},
    linear_num_key_heads=CFG.delta_heads,
    linear_num_value_heads=CFG.delta_heads,
    linear_key_head_dim=CFG.delta_key_dim,
    linear_value_head_dim=CFG.delta_value_dim,
    linear_conv_kernel_dim=CFG.d_conv, linear_allow_neg_eigval=True,
    rms_norm_eps=CFG.norm_eps, torch_dtype="float32")
ENGINE = dict(max_batch=3, prompt_buckets=(8, 16, 48), max_new_tokens=8,
              page_size=4, decode_block=2, chunk_size=16, prefill_batch=1,
              default_timeout_s=120.0)
STEPS = 6


def weights(cfg=CFG, seed=3):
    """The builder's weights, every matrix ten times as large (so that a
    layer moves the residual stream and a fault in one shows)."""
    w = make_weights(cfg, seed)
    w = {k: v if k.endswith("norm") else v * 10 for k, v in w.items()}
    w.update(serve_delta.stand_ins(cfg))
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


def engine_of(scope, cfg=CFG, **over):
    return DecodeEngine(cfg, scope=scope,
                        config=DecodeConfig(**dict(ENGINE, **over)),
                        auto_start=False)


@pytest.fixture(scope="module")
def engine(served):
    eng = engine_of(served[1])
    eng.warmup()
    return eng


class _System:
    def __init__(self, w, model=MODEL):
        self.weights, self.config = w, model


def reference_at(w, prompt, decoded, model=MODEL, **kw):
    sequence = np.concatenate([prompt, decoded[:-1]])
    positions = prompt.size - 1 + np.arange(decoded.size)
    return serve_delta.reference_logits(_System(w, model), sequence,
                                        positions, **kw)


def prompt_of(n, seed=0):
    return np.random.RandomState(seed).randint(
        0, CFG.vocab_size, n).astype(np.int64)


# -- the model's programs -------------------------------------------------

def test_tiny_has_both_kinds_over_two_periods_and_no_lane_tile_widths():
    assert CFG.layer_kinds == (DELTA, DELTA, FULL, DELTA, DELTA, FULL)
    assert CFG.conv_channels == 54 and CFG.delta_value_dim % 128
    assert (CFG.layers_of(FULL), CFG.layers_of(DELTA)) == (2, 4)
    with pytest.raises(ValueError):
        HybridDeltaConfig(n_layers=3, attn_period=4)


def test_the_builders_configuration_is_the_tiny_one_and_refuses_others():
    assert serve_delta.model_config(MODEL) == dataclasses.replace(
        CFG, name="tiny-delta")
    for bad in (dict(linear_allow_neg_eigval=False),
                dict(rope_parameters={"rope_theta": 10000.0}),
                dict(tie_word_embeddings=True),
                dict(linear_num_key_heads=1),
                dict(layer_types=MODEL["layer_types"][::-1])):
        with pytest.raises(ValueError):
            serve_delta.model_config(dict(MODEL, **bad))


def test_programs_carry_a_state_kind_of_one_entry_a_request(engine):
    p = engine.programs
    assert p.stats == DELTA_STATS == HYBRID_STATS + (
        "delta_state_updates_total", "delta_prefill_positions_total")
    assert p.kinds == {"state": {"pages_per_seq": 1, "n_pages": 4,
                                 "pools": (2, 3), "unit": "entries",
                                 "table": ("StateTable", "state_table")}}
    n_pages = engine.allocator.n_pages
    # keys and values flat in their page; a matrix a head, float32; the
    # tail of the convolved channels flat
    assert p.pool_specs == [
        ([2, n_pages, 4, 24], "float32"), ([2, n_pages, 4, 24], "float32"),
        ([4, 4, 3, 4, 10], "float32"), ([4, 4, 3 * 54], "float32")]
    assert not p.decode["in_place"]
    assert not decode_in_place("gqa", CFG.block_attrs(4)["attn_kinds"],
                               [s for s, _ in p.pool_specs])
    for b in (p.decode, p.chunk, p.prefill[8]):
        assert b["feeds"][-5].endswith("state_table")
    assert engine.allocator.kinds == ("sequence", "state")
    assert engine.allocator.usable_of("state") == ENGINE["max_batch"]


def test_a_bf16_model_keeps_its_state_in_float32():
    specs = dataclasses.replace(CFG, dtype="bfloat16").state_spec()
    assert [dt for _, dt in specs] == ["float32", "bfloat16"]


@pytest.mark.parametrize("mixers, names", [
    ((), HYBRID_STATS), (("ssm",), SSM_STATS), (("delta",), DELTA_STATS)])
def test_the_counters_follow_the_mixers_a_model_holds(mixers, names):
    kinds = BlockKinds(n_heads=4, layer_kinds=[0], attn_kinds=[
        {"name": "full"}] + [{"name": m, "mixer": m} for m in mixers])
    assert stats_names(kinds) == names


def test_the_mixers_residual_form_and_norms_are_read_off_the_parameters():
    """A layer without ``AttnNorm`` reads the stream as it is and norms
    its sublayer's output; the attention layers hold the query/key
    norm."""
    full, delta = (CFG.layer_params(1, k) for k in (FULL, DELTA))
    for table in (full, delta):
        assert "AttnNorm" not in table and "MlpNorm" not in table
        assert {"AttnPostNorm", "MlpPostNorm"} <= set(table)
    assert {"QNorm", "KNorm"} <= set(full) and "QNorm" not in delta
    kinds = CFG.block_attrs(4)["attn_kinds"]
    assert [k.get("mixer") for k in kinds] == [None, "delta"]


@pytest.mark.parametrize("label, scopes", [
    ("decode", ("delta/conv", "delta/step", "cache/state", "attn/full")),
    ("chunk", ("delta/conv", "delta/chunk", "cache/state", "attn/full")),
    ("prefill_8", ("delta/conv", "delta/chunk", "cache/state"))])
def test_the_scopes_a_trace_names_are_in_the_programs(engine, label, scopes):
    bundle = program_text.bundles_of(engine.programs)[label]
    text = program_text.lower_bundle(bundle, 4).as_text(debug_info=True)
    for scope in scopes:
        assert scope in text, scope
    assert "delta/step" not in text or label == "decode"


# -- engine logits = reference along every path ---------------------------

@pytest.mark.parametrize("n", [3, 5, 8, 12, 16, 17, 37, 48])
def test_engine_logits_are_the_references(served, engine, n):
    """Whole-prompt programs at several ``lens`` of a bucket (3, 5 and 8
    of 8; 12 and 16 of 16: the padding neither moves the state nor enters
    the tail), a prompt just over a chunk (17) and through three chunks
    (37 = 16 + 16 + 5; 48: three full ones), then decode steps with the
    other rows not live; the entry full of NaN before each."""
    prompt = prompt_of(n, seed=n)
    serve_delta.spoil_entry(engine)
    got, decoded, state = serve_delta.engine_logits(engine, prompt, STEPS)
    want, want_state = reference_at(served[0], prompt, decoded)
    assert serve_delta.rel_l2(got, want).max() < 1e-4
    assert (np.argmax(got, -1) == np.argmax(want, -1)).all()
    assert float(serve_delta.rel_l2(state.reshape(-1),
                                    want_state.reshape(-1))) < 1e-4


def test_the_cut_is_the_first_layers_of_the_whole_model(served):
    """One period of the tiny model's two, served from the first layers'
    weights: the reference of the WHOLE model stopped after them."""
    cut = dataclasses.replace(CFG, n_layers=3)
    w = {k: v[:cut.layers_of(DELTA)] if k.startswith("delta.")
         else v[:cut.layers_of(FULL)] if k.startswith("full.") else v
         for k, v in served[0].items()}
    eng = engine_of(scope_of(w), cut)
    prompt = prompt_of(21, seed=6)
    got, decoded, state = serve_delta.engine_logits(eng, prompt, STEPS)
    model = dict(MODEL, num_hidden_layers=3,
                 layer_types=MODEL["layer_types"][:3])
    want, want_state = reference_at(served[0], prompt, decoded, model)
    assert serve_delta.rel_l2(got, want).max() < 1e-4
    whole, _ = reference_at(served[0], prompt, decoded)
    assert serve_delta.rel_l2(whole, want).min() > 1e-2


def test_the_comparison_sees_float8_weights(served):
    prompt, decoded = prompt_of(12), prompt_of(STEPS + 1, seed=9)
    want, want_state = reference_at(served[0], prompt, decoded)
    off, off_state = reference_at(served[0], prompt, decoded,
                                  through=jnp.float8_e4m3fn)
    assert serve_delta.rel_l2(off, want).min() > 0.02
    assert serve_delta.rel_l2(off_state.reshape(-1),
                              want_state.reshape(-1)) > 0.01


# -- each fault, planted in the ENGINE, fails the builder's comparison -----

EVERY = ("probe 6:", "probe 36:", "probe 17:", "probe 40:", "probe 48:")
CHUNKED = EVERY[1:]


@pytest.mark.parametrize("fault, seen_by, clean", [
    ("none", (), EVERY),
    ("beta without its factor 2", EVERY, ()),
    ("the decay left out", EVERY, ()),
    ("queries and keys not L2-normed", EVERY, ()),
    ("the tail not carried across a chunk", CHUNKED, EVERY[:1]),
    ("whole-prompt programs read their entry", EVERY[:1], CHUNKED),
    ("the first chunk reads its entry", CHUNKED, EVERY[:1]),
    ("the state pool in bf16", ("the first delta layer's state",), ()),
    ("alpha missing from the correction", EVERY, ())])
def test_a_fault_in_the_engine_fails_the_builders_comparison(
        served, monkeypatch, fault, seen_by, clean):
    """``serve_delta.compare_with_reference``, the function that decides
    the cell's ``correct``, on an engine built WITH the fault against the
    clean reference: it returns findings (float32 here, so the limits are
    float32's: the chip's are set between bf16's readings, PERF.md section
    4). The entries held a request before the probes, as after a window.
    A fault of one prefill path is seen at the probes of that path and at
    no other."""
    w, scope = served
    monkeypatch.setattr(serve_delta, "REL_L2", 2e-4)
    monkeypatch.setattr(serve_delta, "STATE_REL_L2", 2e-4)
    cfg = FAULTS[fault](monkeypatch, CFG) if fault != "none" else CFG
    system = _System(w)
    system.cfg = cfg
    system.engine = engine_of(scope, cfg, prompt_buckets=(8, 48))
    serve_delta.engine_logits(system.engine, prompt_of(29, seed=1), 2)
    found = serve_delta.compare_with_reference(system, seed=7)
    assert bool(found) == bool(seen_by), found
    for what in seen_by:
        assert any(what in f for f in found), (what, found)
    if seen_by:
        assert not [f for f in found if f.startswith(clean)], found


# -- an entry's history is not observable ---------------------------------

def poison(engine, keep_pages, keep_entries):
    """Every state entry but those named filled with NaN, every page but
    those named with 1e3 (a page's mask is a softmax weight of exactly 0,
    which hides any finite value; an entry has no mask at all)."""
    pools = []
    for kind, pool in zip(engine._pool_kind, engine._pools):
        keep = keep_entries if kind == "state" else keep_pages
        mask = np.ones((pool.shape[1],), bool)
        mask[list(keep)] = False
        shape = (1, -1) + (1,) * (pool.ndim - 2)
        pools.append(jnp.where(mask.reshape(shape),
                               jnp.nan if kind == "state" else 1e3, pool))
    engine._pools = pools


def test_rows_that_are_not_live_and_entries_not_held_touch_nothing(
        served, engine):
    """A live row between two that are not, its entry the last, every
    other entry NaN and every other page garbage: its logits are the
    reference's, and the entries it does not hold come back as they
    were."""
    prompt = prompt_of(7, seed=21)
    c = engine.config
    need = engine.allocator.pages_for(prompt.size + STEPS + c.decode_block)
    pages = 5 + np.arange(need)
    table = np.zeros((1, engine.pages_per_seq), np.int32)
    table[0, :need] = pages
    held = {"state": [3]}
    poison(engine, pages, [3])
    tokens = np.zeros((1, 8), np.int64)
    tokens[0, :7] = prompt
    nxt = engine._run_prefill_program(
        8, tokens, np.asarray([7], np.int32), table,
        *engine._kind_tables([held]))
    logits = [np.asarray(engine.kept["prefill_8"]["logits"])[:1]]
    decoded = [int(nxt[0])]
    toks, pos = np.zeros((3,), np.int64), np.ones((3,), np.int32)
    tables = np.zeros((3, engine.pages_per_seq), np.int32)
    tables[1] = table[0]
    states = engine._kind_tables([None, held, None])
    while len(decoded) <= STEPS:
        toks[1], pos[1] = decoded[-1], prompt.size + len(decoded) - 1
        out = engine._run_decode_program(toks, pos, tables, *states)
        logits.append(np.asarray(engine.kept["decode"]["logits"])[1])
        decoded.extend(int(t) for t in out[1])
    got = np.concatenate(logits)[:1 + STEPS]
    decoded = np.asarray(decoded[:1 + STEPS], np.int64)
    assert np.isfinite(got).all()
    want, _ = reference_at(served[0], prompt, decoded)
    assert serve_delta.rel_l2(got, want).max() < 1e-4
    state = np.asarray(engine._pools[2])
    assert np.isnan(state[:, [0, 1, 2]]).all() \
        and np.isfinite(state[:, 3]).all()
    engine._pools, _ = engine._zeroed_pools()


def test_a_request_on_a_reused_slot_is_the_request_on_a_fresh_engine(
        served):
    """One slot, so the second and third requests take the entry and the
    pages the first left full: bit for bit the tokens and the logits of
    the same requests on an engine nothing has used; whole-prompt and
    chunked. The loop's own dispatches fetch no logits: here they are
    made in the probe form, as a caller outside the loop makes them."""
    first, short, long_ = (prompt_of(n, seed=s)
                           for n, s in ((14, 1), (6, 2), (29, 3)))

    def serve(prompts):
        out = []
        with engine_of(served[1], max_batch=1) as eng:
            probe = eng._run_decode_program
            eng._run_decode_program = lambda *a, loop: probe(*a)
            eng.start()
            for p in prompts:
                toks = eng.generate(p, max_new=5)
                out.append((np.asarray(toks), np.asarray(
                    eng.kept["decode"]["logits"])))
            s = eng.stats()
        return out, s

    used, s = serve([first, short, long_])
    assert s["state_resets_total"] == s["prefill_total"] == 3
    assert s["state_entries_in_use"] == 0 and s["pools_lost_total"] == 0
    for got, p in zip(used[1:], (short, long_)):
        (want,), _ = serve([p])
        assert np.array_equal(got[0], want[0])
        assert np.array_equal(got[1], want[1])


def test_requests_in_a_mix_are_the_requests_alone(served):
    """Five requests over three slots, two of them through chunks: each
    gets the tokens it gets alone, every request was reset once, and the
    books of the state kind and of the rule's counters balance."""
    prompts = [prompt_of(n, seed=40 + n) for n in (5, 33, 9, 16, 21)]
    with engine_of(served[1]) as eng:
        eng.start()
        alone = [np.asarray(eng.generate(p, max_new=6)) for p in prompts]
        reqs = [eng.submit(p, max_new=6) for p in prompts]
        mixed = [np.asarray(r.result(120)) for r in reqs]
        s = eng.stats()
        system = _System(served[0])
        system.cfg, system.engine = CFG, eng
        assert serve_delta.state_findings(system, 0) == []
    for a, m in zip(alone, mixed):
        assert np.array_equal(a, m)
    assert s["state_resets_total"] == s["prefill_total"] == 10
    assert s["state_entries_in_use"] == 0
    assert s["pages_in_use"] == 0 and s["pools_lost_total"] == 0
    assert s["delta_prefill_positions_total"] == 4 * 2 * sum(
        p.size for p in prompts) == 4 * s["prefill_tokens_total"]
    assert s["delta_state_updates_total"] % 4 == 0
    assert s["ssm_state_updates_total"] == 0
    assert 0 < s["state_bytes_held_total"] < s["cache_bytes_held_total"]
    # an entry: 4 layers x (3 x 4 x 10 float32 + 3 x 54 float32)
    assert s["state_bytes_held_total"] % (4 * (120 + 162) * 4) == 0


def test_pages_bound_admission_while_a_chunk_job_holds_its_entry(served):
    """A pool that holds one long request and little more: the second
    waits for PAGES with slots free, while the first, a chunk job, carries
    its state entry through its chunks; every request gets the tokens it
    gets alone."""
    prompts = [prompt_of(n, seed=60 + n) for n in (45, 40, 7)]
    with engine_of(served[1]) as eng:
        eng.start()
        alone = [np.asarray(eng.generate(p, max_new=4)) for p in prompts]
    # 48 + 8 + 2 positions a request at most: 15 pages; 19 hold one long
    # request and the short one, never both long ones
    with engine_of(served[1], n_pages=20) as eng:
        eng.start()
        reqs = [eng.submit(p, max_new=4) for p in prompts]
        mixed = [np.asarray(r.result(120)) for r in reqs]
        s = eng.stats()
    for a, m in zip(alone, mixed):
        assert np.array_equal(a, m)
    assert s["page_wait_total"] > 0 and s["chunk_prefill_total"] >= 6
    assert s["state_resets_total"] == s["prefill_total"] == 3
    assert s["pools_lost_total"] == 0 and s["state_entries_in_use"] == 0


def test_a_handoff_after_prefill_decodes_to_the_same_tokens(served):
    prompt = prompt_of(27, seed=8)          # two chunks: 16 + 11
    with engine_of(served[1]) as a, engine_of(served[1]) as b:
        a.start(), b.start()
        want = np.asarray(a.generate(prompt, max_new=7))
        b.generate(prompt_of(10, seed=5), max_new=4)    # b's entries used
        blob = a.submit(prompt, max_new=7, prefill_only=True).result(120)
        assert blob["kinds"] == {"state": [1]} and len(blob["cache"]) == 4
        assert blob["cache"][2].shape == (4, 1, 3, 4, 10)
        assert a.stats()["state_entries_in_use"] == 0
        got = np.asarray(b.import_handoff(blob).result(120))
        assert b.stats()["state_entries_in_use"] == 0
    assert np.array_equal(got, want)
