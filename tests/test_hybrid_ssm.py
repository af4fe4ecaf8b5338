"""A model of selective state-space layers beside a few attention layers
(models/hybrid_ssm.py) through DecodeEngine at a tiny size on the CPU:
the engine's own logits against the plain reference (benchmark/reference/
hybrid_ssm.py) along every path a request takes, and each way a cache
entry that NO POSITION INDEXES could make a result depend on a slot's
history (ISSUE 39's hazards): a reused entry, a bucket's padding, rows
that are not live, a chunk boundary, a handoff."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.models.hybrid_ssm import (FULL, HYBRID_SSM_TINY, SSM,
                                          HybridSSMConfig)
from paddle_tpu.ops import pallas_attention as pa
from paddle_tpu.ops import ssm
from paddle_tpu.ops.transformer_ops import (SSM_STATS, _PagedRunner,
                                            decode_in_place,
                                            state_step_in_kernel)
from paddle_tpu.serving.decode_engine import DecodeConfig, DecodeEngine
from paddle_tpu.serving.kv_pages import PageAllocator, PagesExhaustedError

from benchmark.builders import serve_ssm
from benchmark.builders.serve_blocks import make_weights
from benchmark.reference import hybrid_ssm as ref

CFG = HYBRID_SSM_TINY
# the state widened to whole tiles, 8 states a channel over 128 channels:
# where the interpreter hook admits the decode step's kernel
# (ops/ssm.py step_in_kernel; CFG's 4 x 48 keeps the jax.numpy step)
WIDE = dataclasses.replace(CFG, name="hybrid-ssm-wide-state", dim=64,
                           head_dim=16, d_state=8)


def model_of(cfg):
    return dict(
        name="tiny-ssm", model_type="jamba", vocab_size=cfg.vocab_size,
        hidden_size=cfg.dim, num_hidden_layers=cfg.n_layers,
        attn_layer_period=cfg.attn_period,
        attn_layer_offset=cfg.attn_offset,
        num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.n_kv,
        intermediate_size=cfg.ffn_hidden, mamba_d_state=cfg.d_state,
        mamba_d_conv=cfg.d_conv, mamba_dt_rank=cfg.dt_rank,
        mamba_expand=cfg.expand, rms_norm_eps=cfg.norm_eps,
        torch_dtype="float32")


MODEL = model_of(CFG)
ENGINE = dict(max_batch=3, prompt_buckets=(8, 16, 48), max_new_tokens=8,
              page_size=4, decode_block=2, chunk_size=16, prefill_batch=1,
              default_timeout_s=120.0)
STEPS = 6


def weights(seed=3, cfg=CFG):
    """The builder's weights, every matrix ten times as large (so that a
    layer moves the residual stream and a fault in one shows)."""
    w = make_weights(cfg, seed)
    w = {k: v if k.endswith("norm") else v * 10 for k, v in w.items()}
    w.update(serve_ssm.stand_ins(cfg, w))
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


def reference_at(w, prompt, decoded, model=MODEL, **kw):
    sequence = np.concatenate([prompt, decoded[:-1]])
    positions = prompt.size - 1 + np.arange(decoded.size)
    return serve_ssm.reference_logits(_System(w, model), sequence, positions,
                                      **kw)


def prompt_of(n, seed=0):
    return np.random.RandomState(seed).randint(
        0, CFG.vocab_size, n).astype(np.int64)


# -- the model's programs -------------------------------------------------

def test_tiny_has_both_kinds_over_two_periods_and_no_lane_tile_widths():
    assert CFG.layer_kinds == (SSM, FULL, SSM, SSM, FULL, SSM)
    assert CFG.d_inner == 48 and CFG.d_inner % 128
    assert (CFG.layers_of(FULL), CFG.layers_of(SSM)) == (2, 4)
    with pytest.raises(ValueError):
        HybridSSMConfig(n_layers=4, attn_period=8, attn_offset=7)


def test_programs_carry_a_state_kind_of_one_entry_a_request(engine):
    p = engine.programs
    assert p.stats == SSM_STATS
    assert p.kinds == {"state": {"pages_per_seq": 1, "n_pages": 4,
                                 "pools": (2, 3), "unit": "entries",
                                 "table": ("StateTable", "state_table")}}
    n_pages = engine.allocator.n_pages
    # keys and values flat in their page; the state with the channels on
    # its minor axis, float32 whatever the model's type; the tail flat
    assert p.pool_specs == [
        ([2, n_pages, 4, 6], "float32"), ([2, n_pages, 4, 6], "float32"),
        ([4, 4, 4, 48], "float32"), ([4, 4, 3 * 48], "float32")]
    assert not p.decode["in_place"]
    assert not decode_in_place("gqa", CFG.block_attrs(4)["attn_kinds"],
                               [s for s, _ in p.pool_specs])
    for b in (p.decode, p.chunk, p.prefill[8]):
        assert b["feeds"][-5].endswith("state_table")
    assert engine.allocator.kinds == ("sequence", "state")
    assert engine.allocator.usable_of("state") == ENGINE["max_batch"]


def test_a_bf16_model_keeps_its_state_in_float32():
    import dataclasses
    specs = dataclasses.replace(CFG, dtype="bfloat16").state_spec()
    assert [dt for _, dt in specs] == ["float32", "bfloat16"]


# -- engine logits = reference along every path ---------------------------

@pytest.mark.parametrize("n", [3, 5, 8, 12, 16, 37, 48])
def test_engine_logits_are_the_references(served, engine, n):
    """Whole-prompt programs at several ``lens`` of a bucket (3, 5 and 8
    of 8; 12 and 16 of 16: the padding neither moves the state nor enters
    the tail), and a prompt through three chunks (37 = 16 + 16 + 5; 48:
    three full ones), then decode steps with the other rows not live."""
    prompt = prompt_of(n, seed=n)
    got, decoded, state = serve_ssm.engine_logits(engine, prompt, STEPS)
    want, want_state = reference_at(served[0], prompt, decoded,
                                    with_state=True)
    assert serve_ssm.rel_l2(got, want).max() < 2e-5
    assert (np.argmax(got, -1) == np.argmax(want, -1)).all()
    assert np.allclose(state, want_state, rtol=1e-4, atol=1e-7)


@pytest.mark.parametrize("control, least", [
    (dict(model=dict(MODEL, _inner_norms=False)), 0.1),
    (dict(model=dict(MODEL, _state_dtype="bfloat16")), 1e-4),
    (dict(through=jnp.float8_e4m3fn), 0.05)])
def test_the_comparison_sees_a_term_left_out(served, control, least):
    prompt, decoded = prompt_of(12), prompt_of(STEPS + 1, seed=9)
    want = reference_at(served[0], prompt, decoded)
    off = reference_at(served[0], prompt, decoded, **control)
    assert serve_ssm.rel_l2(off, want).min() > least


def test_the_comparison_sees_a_state_that_was_not_reset(served):
    w = ref.from_stacked(served[0], MODEL)
    _, left = ref.forward(w, prompt_of(9, seed=4), MODEL, None, None, True)
    assert sorted(left) == [i for i, k in enumerate(CFG.layer_kinds)
                            if k == SSM]
    prompt, decoded = prompt_of(12), prompt_of(STEPS + 1, seed=9)
    want = reference_at(served[0], prompt, decoded)
    stale = reference_at(served[0], prompt, decoded, carried=left)
    assert serve_ssm.rel_l2(stale, want).min() > 0.05


# -- each fault, planted in the ENGINE, fails the builder's comparison -----

def _state_in_bf16(mp, w):
    spec = HybridSSMConfig.state_spec
    mp.setattr(HybridSSMConfig, "state_spec", lambda self: [
        (spec(self)[0][0], "bfloat16"), spec(self)[1]])


def _no_inner_norms(mp, w):
    mp.setattr(ssm, "_rms", lambda x, scale, eps: x)


def _never_from_zeros(which):
    """The programs of one prefill path (``whole``: the whole-prompt
    programs; ``chunk``: a prompt's first chunk) read the entry where
    they should start from zeros."""
    def plant(mp, w):
        prefill = _PagedRunner._state_prefill

        def stale(self, p, z, mine, lyr, pos0, spec):
            if self.fresh == (which == "whole"):
                self.fresh, pos0 = False, jnp.maximum(pos0, 1)
            return prefill(self, p, z, mine, lyr, pos0, spec)
        mp.setattr(_PagedRunner, "_state_prefill", stale)
    return plant


def _float8_weights(mp, w):
    """The weights the engine serves, rounded through float8 (the
    reference keeps the originals)."""
    return {name: v if name.endswith("norm")
            else v.astype(jnp.float8_e4m3fn).astype(v.dtype)
            for name, v in w.items()}


@pytest.mark.parametrize("fault, plant, seen_by, clean", [
    ("none", None, (), ()),
    ("state kept in bf16", _state_in_bf16, ("state",), ()),
    ("inner norms left out", _no_inner_norms, ("state", "position"), ()),
    ("whole-prompt programs not reset", _never_from_zeros("whole"),
     ("probe 2: the first state", "probe 6: the first state"),
     ("probe 17", "probe 36", "probe 40")),
    ("first chunk not reset", _never_from_zeros("chunk"),
     ("probe 17: the first state", "probe 17: position"),
     ("probe 2:", "probe 6:")),
    ("float8 weights", _float8_weights, ("position",), ())])
def test_a_fault_in_the_engine_fails_the_builders_comparison(
        served, monkeypatch, fault, plant, seen_by, clean):
    """``serve_ssm.compare_with_reference``, the function that decides the
    cell's ``correct``, on an engine built WITH the fault against the
    clean reference: it returns findings, by the limit that is there to
    see the fault (float32 here, so the limits are float32's: the chip's
    are set between bf16's readings, PERF.md section 4). The entries held
    a request before the probes, as after a window. A path that does not
    reset is seen at the probes of that path and at no other: the
    whole-prompt programs' at the short probes, the chunk program's at
    the probe just over a chunk (and the longer ones)."""
    w, scope = served
    monkeypatch.setattr(serve_ssm, "REL_L2", 2e-4)
    monkeypatch.setattr(serve_ssm, "STATE_REL_L2", 2e-4)
    served_w = plant(monkeypatch, w) if plant else None
    if served_w is not None:
        scope = fluid.Scope()
        for name, value in served_w.items():
            scope.set(name, value)
    system = _System(w)
    system.cfg, system.engine = CFG, engine_of(scope, prompt_buckets=(8, 48))
    serve_ssm.engine_logits(system.engine, prompt_of(29, seed=1), 2)
    found = serve_ssm.compare_with_reference(system, seed=7)
    assert bool(found) == bool(seen_by), found
    for what in seen_by:
        assert any(what in f for f in found), (what, found)
    assert not [f for f in found if f.startswith(clean)] or not clean, found


# -- an entry's history is not observable ---------------------------------

def poison(engine, keep_pages, keep_entries):
    """Every state entry but those named filled with NaN, every page but
    those named with 1e3 (a page's mask is a softmax weight of exactly 0,
    which hides any finite value; an entry has no mask at all): what other
    requests, free entries and the null entry hold must not reach a live
    row."""
    pools = []
    for kind, pool in zip(engine._pool_kind, engine._pools):
        keep = keep_entries if kind == "state" else keep_pages
        mask = np.ones((pool.shape[1],), bool)
        mask[list(keep)] = False
        shape = (1, -1) + (1,) * (pool.ndim - 2)
        pools.append(jnp.where(mask.reshape(shape),
                               jnp.nan if kind == "state" else 1e3, pool))
    engine._pools = pools


@pytest.fixture(scope="module")
def wide():
    """(WIDE's weights, its engine built and warmed with the interpreter
    hook on): its decode program steps its state layers' entries through
    the kernel, and stays that program with the hook off again (jit's
    cache goes by the function)."""
    w = weights(cfg=WIDE)
    with pytest.MonkeyPatch.context() as m:
        m.setattr(pa, "_FORCE_INTERPRET", True)
        eng = engine_of(scope_of(w), cfg=WIDE)
        assert eng.programs.decode["state_in_kernel"]
        eng.warmup()
    return w, eng


@pytest.fixture(params=["jnp", "kernel"])
def either(request):
    """(weights, engine, the reference's model) with the decode step's
    states in jax.numpy (CFG) and through the kernel (WIDE)."""
    if request.param == "jnp":
        w, _ = request.getfixturevalue("served")
        return w, request.getfixturevalue("engine"), MODEL
    return request.getfixturevalue("wide") + (model_of(WIDE),)


def test_rows_that_are_not_live_and_entries_not_held_touch_nothing(either):
    """A live row between two that are not, its entry the last, every
    other entry NaN and every other page garbage: its logits are the
    reference's, and the entries it does not hold come back as they
    were."""
    w, engine, model = either
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
    want = reference_at(w, prompt, decoded, model=model)
    assert serve_ssm.rel_l2(got, want).max() < 2e-5
    state = np.asarray(engine._pools[2])
    assert np.isnan(state[:, [0, 1, 2]]).all() \
        and np.isfinite(state[:, 3]).all()
    engine._pools, _ = engine._zeroed_pools()


@pytest.mark.parametrize("n", [5, 16, 37])
def test_engine_logits_through_the_kernel_are_the_references(wide, n):
    """``test_engine_logits_are_the_references``' probes (a bucket with
    padding, a full one, three chunks) at WIDE, the decode steps' states
    through the interpreted kernel: the same tolerances."""
    w, engine = wide
    prompt = prompt_of(n, seed=n)
    got, decoded, state = serve_ssm.engine_logits(engine, prompt, STEPS)
    want, want_state = reference_at(w, prompt, decoded, model=model_of(WIDE),
                                    with_state=True)
    assert serve_ssm.rel_l2(got, want).max() < 2e-5
    assert (np.argmax(got, -1) == np.argmax(want, -1)).all()
    assert np.allclose(state, want_state, rtol=1e-4, atol=1e-7)


@pytest.mark.serving
@pytest.mark.parametrize("hook", [False, True], ids=["off", "on"])
def test_the_engine_counts_its_state_steps_through_the_kernel(
        hook, monkeypatch):
    """``state_step_in_kernel_total`` ticks with ``decode_batches_total``
    for an engine built where the kernel runs and stays 0 where it does
    not (the bundle's ``state_in_kernel``: a report, which chooses
    nothing); CFG's narrow state is never admitted, hook or not. The
    tokens are the reference's both ways."""
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", hook)
    attrs = CFG.block_attrs(4)["attn_kinds"]
    assert not state_step_in_kernel(
        attrs, [([2, 40, 4, 6], "float32")] * 2
        + [([4, 4, 4, 48], "float32"), ([4, 4, 144], "float32")])
    assert not state_step_in_kernel(None, [])
    w = weights(cfg=WIDE)
    engine = engine_of(scope_of(w), cfg=WIDE, auto_start=True)
    try:
        assert engine.programs.decode["state_in_kernel"] is hook
        assert not engine.programs.decode["in_place"]   # heads of 16
        engine.warmup()
        requests = [engine.submit(prompt_of(n, seed=n), max_new=6,
                                  timeout=120) for n in (3, 7, 12, 5)]
        tokens = [r.result(120) for r in requests]
        stats = engine.stats()
    finally:
        engine.close()
    assert stats["decode_batches_total"] > 0
    assert stats["state_step_in_kernel_total"] == (
        stats["decode_batches_total"] if hook else 0)
    assert stats["pools_lost_total"] == 0
    for n, t in zip((3, 7, 12, 5), tokens):
        want = reference_at(w, prompt_of(n, seed=n), np.asarray(t, np.int64),
                            model=model_of(WIDE))
        assert np.array_equal(np.argmax(want, -1), t)


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
    """Five requests over three slots, one of them through chunks: each
    gets the tokens it gets alone, every request was reset once, and the
    state kind's books balance."""
    prompts = [prompt_of(n, seed=40 + n) for n in (5, 33, 9, 16, 21)]
    with engine_of(served[1]) as eng:
        eng.start()
        alone = [np.asarray(eng.generate(p, max_new=6)) for p in prompts]
        reqs = [eng.submit(p, max_new=6) for p in prompts]
        mixed = [np.asarray(r.result(120)) for r in reqs]
        s = eng.stats()
    for a, m in zip(alone, mixed):
        assert np.array_equal(a, m)
    assert s["state_resets_total"] == s["prefill_total"] == 10
    assert s["state_entries_in_use"] == 0
    assert s["pages_in_use"] == 0 and s["pools_lost_total"] == 0
    assert s["ssm_prefill_positions_total"] == 4 * 2 * sum(
        p.size for p in prompts)
    assert 0 < s["state_bytes_held_total"] < s["cache_bytes_held_total"]
    # an entry: 4 layers x (4 x 48 float32 + 3 x 48 float32)
    assert s["state_bytes_held_total"] % (4 * (4 * 48 + 3 * 48) * 4) == 0


def test_a_handoff_after_prefill_decodes_to_the_same_tokens(served):
    prompt = prompt_of(27, seed=8)          # two chunks: 16 + 11
    with engine_of(served[1]) as a, engine_of(served[1]) as b:
        a.start(), b.start()
        want = np.asarray(a.generate(prompt, max_new=7))
        b.generate(prompt_of(10, seed=5), max_new=4)    # b's entries used
        blob = a.submit(prompt, max_new=7, prefill_only=True).result(120)
        assert blob["kinds"] == {"state": [1]} and len(blob["cache"]) == 4
        assert blob["cache"][2].shape == (4, 1, 4, 48)
        assert a.stats()["state_entries_in_use"] == 0
        got = np.asarray(b.import_handoff(blob).result(120))
        assert b.stats()["state_entries_in_use"] == 0
    assert np.array_equal(got, want)


def test_a_blob_of_another_models_cache_kinds_is_refused(served):
    from paddle_tpu.serving.batching import ServingError
    prompt = prompt_of(6)
    with engine_of(served[1]) as a:
        a.start()
        blob = a.submit(prompt, max_new=4, prefill_only=True).result(120)
        with pytest.raises(ServingError):
            a.import_handoff(dict(blob, kinds={"window": [1]}))
        assert a.worker_alive()


# -- the allocator's books for the state kind ------------------------------

def allocator():
    a = PageAllocator(9, 4)
    a.add_kind("state", 4)
    return a


def test_state_kind_has_its_own_ids_and_its_null_entry():
    a = allocator()
    assert a.kinds == ("sequence", "state")
    assert (a.usable_of("state"), a.available_of("state")) == (3, 3)
    assert a.alloc(1, "state") == [1] and a.alloc(2, "state") == [2, 3]
    assert a.in_use_of("state") == 3 and a.in_use == 0


@pytest.mark.parametrize("bad", [[0], [4], [2, 2]])
def test_state_kind_refuses_the_null_entry_a_stranger_and_a_double(bad):
    a = allocator()
    a.alloc(3, "state")
    a.free([2], "state")
    with pytest.raises(ValueError):
        a.free(bad, "state")


def test_state_kind_exhaustion_is_a_typed_shed_and_grants_nothing():
    a = allocator()
    a.alloc(3, "state")
    with pytest.raises(PagesExhaustedError):
        a.alloc(1, "state")
    assert a.available_of("state") == 0 and a.available == 8


def test_a_request_is_granted_all_its_kinds_or_none(engine):
    a = engine.allocator
    taken = a.alloc(3, "state")
    with pytest.raises(PagesExhaustedError):
        engine._alloc(2)
    assert a.in_use == 0                 # the pages went back
    a.free(taken, "state")
    held = engine._alloc(2)
    assert sorted(held) == ["sequence", "state"]
    assert (len(held["sequence"]), held["state"]) == (2, [1])
    engine._free(held)
    assert a.in_use == 0 and a.in_use_of("state") == 0
    with pytest.raises(ValueError):
        engine._free(held)               # a double free is refused


# -- the recurrence, apart from the engine --------------------------------

def _scan_inputs(t, b=2, c=5, n=3, seed=0):
    rng = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)
    dt = jax.nn.softplus(f(b, t, c) - 2.0)
    return (dt, f(b, t, c), f(b, t, n), f(b, t, n),
            -jnp.exp(f(n, c) * 0.5), f(c), f(b, n, c))


def _scan_by_hand(dt, c, bm, cm, a, d, state):
    dt, c, bm, cm, a, d, state = (np.asarray(x, np.float64) for x in (
        dt, c, bm, cm, a, d, state))
    ys = np.zeros(c.shape)
    for t in range(c.shape[1]):
        state = np.exp(dt[:, t, None, :] * a) * state \
            + (dt[:, t] * c[:, t])[:, None, :] * bm[:, t, :, None]
        ys[:, t] = (state * cm[:, t, :, None]).sum(1) + d * c[:, t]
    return ys, state


@pytest.mark.parametrize("t, block", [
    (12, 1), (12, 2), (12, 3), (12, 4), (12, 5), (12, 12), (12, 16),
    (7, 2), (7, 3), (7, 8), (1, 1), (1, 4), (40, 8)])
def test_the_chunked_scan_is_the_sequential_one(t, block, monkeypatch):
    """The carried ``lax.scan`` (what runs wherever the kernel's gate
    fails: here, a CPU at 5 channels) at ``block`` positions a loop
    iteration: blocks that do and do not divide the length, a block
    longer than the window, a block of one. The kernel's own cases are
    tests/test_ssm_prefill_scan.py's."""
    monkeypatch.setattr(ssm, "SCAN_BLOCK", block)
    args = _scan_inputs(t)
    y, state = ssm.scan_window(*args)
    want_y, want_state = _scan_by_hand(*args)
    assert np.allclose(y, want_y, atol=2e-5)
    assert np.allclose(state, want_state, atol=2e-5)


def _layer_params(seed=5):
    w = weights(seed)
    return {slot: w["ssm." + suffix][1] for slot, (suffix, _, _)
            in CFG.layer_params(1, SSM).items() if "ssm." + suffix in w}


@pytest.mark.parametrize("lens", [1, 2, 3, 7, 10])
def test_padding_neither_moves_the_state_nor_enters_the_tail(lens):
    """A window of 10 of which ``lens`` positions are real (lens under
    the tail's 3 too) leaves what the real positions alone leave,
    whatever the padding holds."""
    p = _layer_params()
    rng = np.random.RandomState(lens)
    z = jnp.asarray(rng.randn(1, 10, 48), jnp.float32)
    state0 = jnp.asarray(rng.randn(1, 4, 48), jnp.float32)
    tail0 = jnp.asarray(rng.randn(1, 3, 48), jnp.float32)
    y, state, tail = ssm.window(p, z, state0, tail0,
                                jnp.asarray([lens], jnp.int32), 1e-6)
    wy, wstate, wtail = ssm.window(p, z[:, :lens], state0, tail0,
                                   jnp.asarray([lens], jnp.int32), 1e-6)
    assert np.allclose(y[:, :lens], wy, atol=1e-6)
    assert np.allclose(state, wstate, atol=1e-6)
    assert np.array_equal(tail, wtail)
    full = np.concatenate([tail0, z[:, :lens]], axis=1)
    assert np.array_equal(tail, full[:, -3:])


@pytest.mark.parametrize("cut", [1, 2, 5, 9])
def test_a_window_in_two_calls_is_the_window_in_one(cut):
    """What crosses a chunk boundary (the state and the tail) is all the
    second chunk needs; the decode step is the window of one position."""
    p = _layer_params()
    rng = np.random.RandomState(cut)
    z = jnp.asarray(rng.randn(2, 10, 48), jnp.float32)
    zeros = (jnp.zeros((2, 4, 48)), jnp.zeros((2, 3, 48)))
    n = lambda k: jnp.full((2,), k, jnp.int32)
    y, state, tail = ssm.window(p, z, *zeros, n(10), 1e-6)
    y1, s1, t1 = ssm.window(p, z[:, :cut], *zeros, n(cut), 1e-6)
    y2, s2, t2 = ssm.window(p, z[:, cut:], s1, t1, n(10 - cut), 1e-6)
    assert np.allclose(jnp.concatenate([y1, y2], 1), y, atol=1e-6)
    assert np.allclose(s2, state, atol=1e-6) and np.array_equal(t2, tail)
    y9, s9, t9 = ssm.window(p, z[:, :9], *zeros, n(9), 1e-6)
    # a step: the rows' states as the entries of a one-layer pool, all held
    # and its tail flat, as the tail pool stores an entry
    ys, ss, ts = ssm.step(p, z[:, 9], s9[None], 0, jnp.ones((2,), bool),
                          t9.reshape(2, -1), 1e-6)
    assert np.allclose(ys, y[:, 9], atol=1e-6)
    assert np.allclose(ss[0], state, atol=1e-6)
    assert np.array_equal(ts.reshape(tail.shape), tail)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_step_over_the_flat_tail_is_the_step_over_its_rows(dtype):
    """``conv_step_flat`` on an entry as the tail pool stores it, against
    ``conv_step`` on the [k - 1, C] view of it: the same tail, and the same
    output to the order of a sum over 4 taps."""
    r = np.random.RandomState(2)
    b, k, c = 5, 4, 256
    z = jnp.asarray(r.randn(b, c), dtype)
    tail0 = jnp.asarray(r.randn(b, k - 1, c), dtype)
    w = jnp.asarray(r.randn(k, c), dtype)
    bias = jnp.asarray(r.randn(c), dtype)
    want, want_tail = ssm.conv_step(z, tail0, w, bias)
    got, tail = ssm.conv_step_flat(z, tail0.reshape(b, -1), w, bias)
    assert got.dtype == want.dtype and tail.dtype == tail0.dtype
    assert np.array_equal(tail.reshape(want_tail.shape), want_tail)
    tol = 1e-6 if dtype == "float32" else 2e-2
    assert np.allclose(np.asarray(got, np.float32),
                       np.asarray(want, np.float32), rtol=tol, atol=tol)
