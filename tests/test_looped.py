"""A model whose ONE stack of layers is run several times a token, every
pass with keys and values of its own (models/looped.py), through
DecodeEngine at a tiny size on the CPU: the engine's own logits against
the plain reference (benchmark/reference/looped.py) in float32 and bf16,
behind the paged attention call's reference and behind its kernel (the
interpreter hook), the
depth and the order of its cache, each way the loop could be wrong and
still look right (looped_faults.py) failing the builder's comparison, and
an engine whose POOL, not its slots, bounds the batch."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.models.llama import LlamaConfig
from paddle_tpu.models.looped import LOOPED_TINY
from paddle_tpu.ops import pallas_attention as pa
from paddle_tpu.ops.transformer_ops import (LOOP_STATS, PAGED_STATS,
                                            BlockKinds, stats_names)
from paddle_tpu.serving.decode_engine import DecodeConfig, DecodeEngine

from benchmark.builders import serve_loop
from benchmark.builders.serve_blocks import engine_logits, make_weights
from benchmark.reference import looped as ref

from looped_faults import FAULTS

CFG = LOOPED_TINY
# the same loop at a head a lane tile wide, which the paged kernel takes
WIDE = dataclasses.replace(CFG, name="looped-wide", n_heads=2, n_kv=2,
                           head_dim=128)
ENGINE = dict(max_batch=3, prompt_buckets=(8, 16), max_new_tokens=8,
              page_size=4, decode_block=2, prefill_batch=1,
              default_timeout_s=120.0)
STEPS = 6


def model_of(cfg):
    """The published keys the reference reads, at ``cfg``'s sizes."""
    return dict(
        name=cfg.name, model_type="ouro", vocab_size=cfg.vocab_size,
        hidden_size=cfg.dim, num_hidden_layers=cfg.n_layers,
        total_ut_steps=cfg.passes, num_attention_heads=cfg.n_heads,
        num_key_value_heads=cfg.n_kv, head_dim=cfg.head_dim,
        intermediate_size=cfg.ffn_hidden, rope_theta=cfg.rope_base,
        rms_norm_eps=cfg.norm_eps, torch_dtype=cfg.dtype)


def weights_of(cfg, seed=3):
    """The builder's weights, every matrix ten times as large (so that a
    layer moves the residual stream and a fault in one shows) and every
    norm drawn about 1 (so that a norm left out, or one taken for
    another, shows)."""
    w = make_weights(cfg, seed)
    rng = np.random.RandomState(seed)
    return {k: (v * (1 + 0.3 * rng.standard_normal(v.shape))).astype(v.dtype)
            if k.endswith("norm") else v * 10 for k, v in w.items()}


def scope_of(w):
    scope = fluid.Scope()
    for name, value in w.items():
        scope.set(name, value)
    return scope


def engine_of(cfg, scope, **over):
    return DecodeEngine(cfg, scope=scope, place=fluid.CPUPlace(),
                        config=DecodeConfig(**dict(ENGINE, **over)),
                        auto_start=False)


class _System:
    def __init__(self, cfg, w):
        self.cfg, self.weights, self.config = cfg, w, model_of(cfg)


def reference_at(system, prompt, decoded, **kw):
    sequence = np.concatenate([prompt, decoded[:-1]])
    positions = prompt.size - 1 + np.arange(decoded.size)
    return serve_loop.reference_logits(system, sequence, positions, **kw)


def prompt_of(n, seed=0, cfg=CFG):
    return np.random.RandomState(seed).randint(
        0, cfg.vocab_size, n).astype(np.int64)


@pytest.fixture(scope="module")
def served():
    w = weights_of(CFG)
    return _System(CFG, w), scope_of(w)


@pytest.fixture(scope="module")
def engine(served):
    eng = engine_of(CFG, served[1])
    eng.warmup()
    return eng


# -- the model's programs -------------------------------------------------

def test_no_count_of_the_tiny_model_equals_another():
    counts = (CFG.n_layers, CFG.passes, CFG.n_heads, CFG.cache_layers,
              len(CFG.cache_spec()) + 3)
    assert len(set(counts)) == len(counts) and CFG.n_kv == CFG.n_heads
    assert CFG.cache_layers == CFG.passes * CFG.n_layers == 6


def test_the_pools_are_passes_times_as_deep_as_the_weights(engine):
    programs = engine.programs
    assert [shape for shape, _ in programs.pool_specs] == [
        [6, engine.allocator.usable_pages + 1, 4, 4, 8]] * 2
    assert programs.stats == LOOP_STATS == stats_names(BlockKinds(
        n_heads=4, passes=3))
    assert LOOP_STATS[:len(PAGED_STATS)] == PAGED_STATS
    assert not programs.decode["in_place"]         # the CPU: no kernel
    gb = programs.decode["program"].global_block()
    assert tuple(gb.vars["blocks.wq"].shape) == (2, 32, 32)


@pytest.mark.parametrize("what, kw", [
    ("routed", dict(ffn="routed")), ("mhc", dict(residual="mhc")),
    ("several kinds", dict(layer_kinds=[0, 0], attn_kinds=[
        {"name": "full", "n_kv": 4, "base": 1e4, "window": None,
         "sink": False, "stack": "Full", "pools": [0, 1]}])),
    ("no pass", dict(passes=0))])
def test_a_stack_run_several_times_is_one_kind_of_plain_dense_layer(
        what, kw):
    with pytest.raises(ValueError, match="passes"):
        BlockKinds(**dict(dict(n_heads=4, passes=2), **kw))


# -- engine logits = reference --------------------------------------------

FORMS = {
    # form -> (configuration, the hook, dtype, limit on REL_L2)
    "dense-float32": (CFG, False, "float32", 2e-5),
    "dense-bfloat16": (CFG, False, "bfloat16", 0.15),
    "in_place-float32": (WIDE, True, "float32", 2e-5),
    "in_place-bfloat16": (WIDE, True, "bfloat16", 0.15),
}


@pytest.mark.parametrize("form", sorted(FORMS))
def test_engine_logits_are_the_references(form, monkeypatch):
    """Prefill through a whole-prompt program (3 of 8, all 8, 12 of 16),
    then decode steps through the cache with the other rows not live: the
    logits of the reference's full forward over the same tokens; the exit
    gate moves none of them; and the counters say every pass ran."""
    cfg, hook, dtype, limit = FORMS[form]
    cfg = dataclasses.replace(cfg, dtype=dtype)
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", hook)
    monkeypatch.setattr(pa, "PAGED_BLOCK_KEYS", 2 * ENGINE["page_size"])
    w = weights_of(cfg)
    system, eng = _System(cfg, w), engine_of(cfg, scope_of(w))
    assert eng.programs.decode["in_place"] is hook
    try:
        for n in (3, 8, 12):
            prompt = prompt_of(n, seed=n, cfg=cfg)
            got, _, decoded = engine_logits(eng, prompt, STEPS)
            want = reference_at(system, prompt, decoded)
            assert serve_loop.rel_l2(got, want).max() < limit, (form, n)
            if dtype == "float32":
                assert (np.argmax(got, -1) == np.argmax(want, -1)).all()
        s = eng.stats()
    finally:
        eng.close()
    # three probes of 6 decoded positions, one live row: 18 row-steps of
    # passes x layers layer passes each
    steps = 3 * STEPS
    assert s["loop_layer_passes_total"] == cfg.cache_layers * steps
    assert s["loop_positions_attended_total"] == cfg.cache_layers * sum(
        n + 1 + i for n in (3, 8, 12) for i in range(STEPS))


def test_no_logit_depends_on_the_exit_gate(served, engine):
    system, _ = served
    prompt = prompt_of(7, seed=2)
    got, _, decoded = engine_logits(engine, prompt, STEPS)
    loud = dict(system.weights)
    loud["exit_gate.w"] = loud["exit_gate.w"] * 50 + 3
    eng = engine_of(CFG, scope_of(loud))
    try:
        again, _, _ = engine_logits(eng, prompt, STEPS)
    finally:
        eng.close()
    assert np.array_equal(got, again)
    sequence = np.concatenate([prompt, decoded[:-1]])
    _, gates = ref.forward(ref.from_stacked(system.weights), sequence,
                           system.config, return_gates=True)
    assert gates.shape == (CFG.passes, sequence.size)
    assert np.abs(np.asarray(gates)).min() > 0


def test_entry_of_pass_s_layer_j_lies_at_s_times_layers_plus_j(served):
    """After a prefill the pools hold, at cache layer ``s * L + j`` of the
    request's pages, the keys and values the reference's layer ``j``
    attended in pass ``s``: every one of the 6, none twice."""
    system, scope = served
    prompt = prompt_of(11, seed=5)
    eng = engine_of(CFG, scope)
    try:
        engine_logits(eng, prompt, 0)
        pools = [np.asarray(p) for p in eng._pools]
    finally:
        eng.close()
    _, entries = ref.forward(ref.from_stacked(system.weights), prompt,
                             system.config, return_entries=True)
    assert len(entries) == CFG.cache_layers
    pages = 1 + np.arange(3)        # the probe's: pages 1..3 hold 11 of 12
    for at, (k, v) in enumerate(entries):
        for pool, want in zip(pools, (k, v)):
            held = pool[at, pages].reshape((12,) + pool.shape[3:])[:11]
            assert np.allclose(held, want, rtol=1e-4, atol=1e-5), at
    keys = [np.asarray(k) for k, _ in entries]
    for a in range(len(keys)):
        for b in range(a):
            assert not np.allclose(keys[a], keys[b], atol=1e-3), (a, b)


def test_one_pass_without_post_norms_is_the_llama_block_bit_for_bit():
    """``passes`` 1 and no post-norm: the traversal and the block of every
    other dense model, by the logits of the Llama programs over the same
    weights."""
    plain = dataclasses.replace(CFG, name="looped-once", passes=1,
                                post_norm=False)
    llama = LlamaConfig(vocab_size=CFG.vocab_size, dim=CFG.dim,
                        n_layers=CFG.n_layers, n_heads=CFG.n_heads,
                        n_kv_heads=CFG.n_kv, ffn_hidden=CFG.ffn_hidden,
                        rope_base=CFG.rope_base, norm_eps=CFG.norm_eps,
                        dtype="float32")
    w = weights_of(CFG)
    prompt = prompt_of(7, seed=1)
    tokens = []
    for cfg in (plain, llama):
        eng = engine_of(cfg, scope_of(w))
        try:
            eng.start()
            tokens.append(np.asarray(eng.generate(prompt, max_new=8)))
            if cfg is plain:
                assert eng.programs.stats == PAGED_STATS
                eng.close()     # the probe takes the pool's first pages
                logits = engine_logits(eng, prompt, 0)[0]
        finally:
            eng.close()
    assert np.array_equal(*tokens)
    want = ref.forward(ref.from_stacked(w), prompt, dict(
        model_of(plain), _post_norms=False), [prompt.size - 1])
    assert serve_loop.rel_l2(logits, np.asarray(want)).max() < 2e-5


# -- the comparison has teeth ---------------------------------------------

@pytest.mark.parametrize("control, least", [
    (dict(model=dict(model_of(CFG), _post_norms=False)), 0.1),
    (dict(model=dict(model_of(CFG), _norm_every_pass=False)), 0.1),
    (dict(model=dict(model_of(CFG), total_ut_steps=2)), 0.1),
    (dict(through=jnp.float8_e4m3fn), 0.03)])
def test_the_comparison_sees_a_term_left_out_of_the_reference(
        served, control, least):
    prompt, decoded = prompt_of(12), prompt_of(STEPS + 1, seed=9)
    want = reference_at(served[0], prompt, decoded)
    off = reference_at(served[0], prompt, decoded, **control)
    assert serve_loop.rel_l2(off, want).min() > least


def test_the_reference_at_the_engines_precision_reads_as_the_engine_does():
    """What a bf16 engine differs from the float32 reference by is its
    precision: the reference ROUNDED to bf16 at every place a bf16 program
    rounds (``_round_dtype``) stands as far from the float32 reference as
    the engine does, within a factor of two; through float32 it is the
    reference itself, bit for bit."""
    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    w = weights_of(cfg)
    system, eng = _System(cfg, w), engine_of(cfg, scope_of(w))
    try:
        prompt = prompt_of(12, seed=12, cfg=cfg)
        got, _, decoded = engine_logits(eng, prompt, STEPS)
    finally:
        eng.close()
    want = reference_at(system, prompt, decoded)
    at = {t: reference_at(system, prompt, decoded, model=dict(
        system.config, _round_dtype=t)) for t in (jnp.float32, jnp.bfloat16)}
    assert np.array_equal(at[jnp.float32], want)
    engine_err = serve_loop.rel_l2(got, want).mean()
    rounded_err = serve_loop.rel_l2(at[jnp.bfloat16], want).mean()
    assert 0.5 < rounded_err / engine_err < 2, (rounded_err, engine_err)


@pytest.mark.parametrize("fault", ["none"] + sorted(FAULTS))
def test_a_fault_in_the_engine_fails_the_builders_comparison(
        fault, monkeypatch):
    """``serve_loop.compare_with_reference``, the function that decides
    the cell's ``correct``, on an engine built WITH the fault against the
    clean reference: it returns findings (float32 here, so the limit is
    float32's; the chip's is set under the same plants' readings at the
    published sizes, PERF.md section 4). Behind the kernel, as on the
    chip. The pages held a request before the probes, as after a
    window."""
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(pa, "PAGED_BLOCK_KEYS", 2 * ENGINE["page_size"])
    monkeypatch.setattr(serve_loop, "REL_L2", 2e-4)
    w = weights_of(WIDE)
    cfg = FAULTS[fault](monkeypatch, WIDE) if fault != "none" else WIDE
    system = _System(WIDE, w)
    system.engine = engine_of(cfg, scope_of(w))
    assert system.engine.programs.decode["in_place"]
    try:
        engine_logits(system.engine, prompt_of(9, seed=1, cfg=WIDE), 2)
        found = serve_loop.compare_with_reference(system, seed=7)
    finally:
        system.engine.close()
    assert bool(found) == (fault != "none"), found
    if fault == "a pass reads the pass before's cache":
        # a decode step's fault: the prompts' last positions are clean
        assert not [f for f in found if f.startswith((
            "probe 6: position 5 ", "probe 12: position 11 "))], found


# -- a request alone is the request among peers ---------------------------

def test_requests_in_a_mix_are_the_requests_alone(served):
    prompts = [prompt_of(n, seed=40 + n) for n in (5, 13, 9, 16, 3)]
    eng = engine_of(CFG, served[1])
    try:
        eng.start()
        alone = [np.asarray(eng.generate(p, max_new=6)) for p in prompts]
        reqs = [eng.submit(p, max_new=6) for p in prompts]
        mixed = [np.asarray(r.result(120)) for r in reqs]
        s = eng.stats()
    finally:
        eng.close()
    for a, m in zip(alone, mixed):
        assert np.array_equal(a, m)
    assert s["pages_in_use"] == 0 and s["pools_lost_total"] == 0
    assert s["loop_layer_passes_total"] % CFG.cache_layers == 0
    # a position's entry: 6 cache layers x (K and V of 4 heads x 8 float32)
    assert s["cache_bytes_held_total"] % (6 * 2 * 4 * 8 * 4 * 4) == 0


# -- the pool, not the slots, bounds the batch ----------------------------

@pytest.mark.serving
def test_a_pool_of_three_requests_serves_eight_slots_requests(served):
    """Eight slots over a pool that holds three requests: every request
    completes with the tokens it gives alone, admission waited for PAGES
    with slots free (page_wait_total), decode dispatches ran meanwhile
    (decode_page_bound_total), nothing was shed and nothing preempted."""
    prompts = [prompt_of(5 + n % 4, seed=60 + n) for n in range(8)]
    roomy = engine_of(CFG, served[1], max_batch=8)
    try:
        roomy.start()
        alone = [np.asarray(roomy.generate(p, max_new=8)) for p in prompts]
        free = roomy.stats()
    finally:
        roomy.close()
    assert free["page_wait_total"] == free["decode_page_bound_total"] == 0
    # a request reserves its bucket (8) and its 8 new tokens and the last
    # dispatch's overshoot: 5 pages of 4; three of them and the null page
    eng = engine_of(CFG, served[1], max_batch=8, n_pages=3 * 5 + 1,
                    max_queue=16)
    try:
        eng.start()
        reqs = [eng.submit(p, max_new=8) for p in prompts]
        tight = [np.asarray(r.result(120)) for r in reqs]
        s = eng.stats()
    finally:
        eng.close()
    for a, t in zip(alone, tight):
        assert np.array_equal(a, t)
    assert s["page_wait_total"] > 0 and s["decode_page_bound_total"] > 0
    assert s["decode_page_bound_total"] <= s["decode_batches_total"]
    assert s["retired_total"] == 8 and s["pages_in_use"] == 0
    assert s["shed_total"] == 0 and s.get("preempted_total", 0) == 0
    assert s["pools_lost_total"] == 0


@pytest.mark.serving
def test_that_pool_holds_more_rows_than_three_whole_reservations(served):
    """The same pool under requests of unequal answers: a row takes its
    pages as it writes them through all 6 cache layers' pools, so more
    than three rows are live in a decode dispatch at times; no growth
    ever finds the pool empty, every request returns its tokens alone
    and every page comes back."""
    prompts = [prompt_of(5 + n % 4, seed=80 + n) for n in range(10)]
    news = [8, 3, 6, 2, 8, 4, 7, 3, 5, 8]
    roomy = engine_of(CFG, served[1], max_batch=8)
    try:
        roomy.start()
        alone = [np.asarray(roomy.generate(p, max_new=n))
                 for p, n in zip(prompts, news)]
    finally:
        roomy.close()
    eng = engine_of(CFG, served[1], max_batch=8, n_pages=3 * 5 + 1,
                    max_queue=16)
    rows, run = [], eng._run_decode_program

    def watched(*args, **kw):
        live = [s for s in eng.slots if s is not None]
        rows.append(len(live))
        assert sum(len(s.held["sequence"]) for s in live) <= 15
        assert all(s.grows_to == eng._pages_needed(s.req.prompt.size,
                                                   s.req.max_new)
                   for s in live)
        return run(*args, **kw)

    eng._run_decode_program = watched
    try:
        reqs = [eng.submit(p, max_new=n) for p, n in zip(prompts, news)]
        eng.start()
        tight = [np.asarray(r.result(120)) for r in reqs]
        s = eng.stats()
    finally:
        eng.close()
    for a, t in zip(alone, tight):
        assert np.array_equal(a, t)
    assert max(rows) > 3
    assert s["page_stall_total"] == 0 and s["pages_grown_total"] > 0
    assert s["page_wait_total"] > 0
    assert s["retired_total"] == 10 and s["pages_in_use"] == 0
    assert s["shed_total"] == 0 and s["pools_lost_total"] == 0
