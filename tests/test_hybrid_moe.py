"""A model that mixes full and sliding-window attention layers (unequal
key/value head counts, keys wider than values, a part of each head rotated,
scaled values, sinks in the window layers) over TWO KINDS OF CACHE in one
allocator, as one chip's share of an expert-parallel layer: through the
paged programs and DecodeEngine, against the plain reference of the same
share (benchmark/reference/hybrid_moe_share.py) at a small size in float32.
"""
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.models.hybrid_moe import HYBRID_MOE_TINY as CFG
from paddle_tpu.models.latent_moe import LATENT_MOE_TINY, LATENT_SHARE_TINY
from paddle_tpu.models.llama import LLAMA_TINY
from paddle_tpu.ops import transformer_ops as T
from paddle_tpu.serving.decode_engine import DecodeConfig, DecodeEngine
from paddle_tpu.serving.kv_pages import PageAllocator, PagesExhaustedError

from benchmark.builders.serve_hybrid import engine_logits
from benchmark.reference import hybrid_moe_share as ref

REL_L2_F32 = 1e-4
PS = 2                              # page size: a ring of 2 pages
MP = 24                             # pages a row


def model_of(cfg):
    """The published config.json keys the reference reads, from ``cfg``."""
    n_routed = cfg.n_layers - cfg.n_dense_layers
    return dict(
        num_hidden_layers=cfg.n_layers, hidden_size=cfg.dim,
        num_attention_heads=cfg.n_heads, head_dim=cfg.head_dim,
        v_head_dim=cfg.v_head_dim, num_key_value_heads=cfg.n_kv_full,
        swa_num_key_value_heads=cfg.n_kv_window,
        rope_theta=cfg.rope_base_full, swa_rope_theta=cfg.rope_base_window,
        partial_rotary_factor=(cfg.rotary_dim + 0.5) / cfg.head_dim,
        attention_value_scale=cfg.value_scale, sliding_window=cfg.window,
        hybrid_layer_pattern=list(cfg.layer_pattern),
        moe_layer_freq=[0] * cfg.n_dense_layers + [1] * n_routed,
        add_swa_attention_sink_bias=cfg.sink_window,
        add_full_attention_sink_bias=cfg.sink_full,
        layernorm_epsilon=cfg.norm_eps,
        num_experts_per_tok=cfg.moe_top_k, routed_scaling_factor=None,
        n_group=1, topk_group=1, scoring_func="sigmoid",
        experts_held=dict(first=cfg.experts_first, count=cfg.n_experts,
                          of=cfg.router_width))


def make_weights(cfg, seed=0):
    """Seeded float32 weights, every term alive: norms off 1, a selection
    bias of the size of the score gaps, sinks of the size of the scores."""
    out = {}
    shapes = cfg.param_shapes()
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    for k, (name, (shape, dt)) in zip(keys, sorted(shapes.items())):
        x = jax.random.normal(k, shape)
        if name.endswith("norm"):
            x = 1.0 + 0.1 * x
        elif name.endswith("moe_bias"):
            x = 0.1 * x
        elif not name.endswith("sink"):
            x = 0.2 * x
        out[name] = x.astype(dt)
    return out


MODEL = model_of(CFG)
W = make_weights(CFG)


def rel_l2(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return np.linalg.norm(got - want, axis=-1) \
        / np.linalg.norm(want, axis=-1)


def op_inputs(cfg, w, **feeds):
    ins = {"Emb": [w["tok_emb"]], "FinalNorm": [w["final_norm"]],
           "LmHead": [w["lm_head"]]}
    for prefix, scope, kind, n, routed in cfg.stacks():
        for slot, (suffix, _, _) in cfg.layer_params(n, kind,
                                                     routed).items():
            ins[prefix + slot] = [w[f"{scope}.{suffix}"]]
    ins.update({k: [jnp.asarray(v)] for k, v in feeds.items()})
    return ins


def run_op(op, cfg=CFG, w=W, steps=1, **feeds):
    pools = feeds.pop("Pools")
    ins = op_inputs(cfg, w, **feeds)
    ins["Pools"] = list(pools)
    out = op(None, ins, dict(cfg.block_attrs(PS), steps=steps))
    return {k: v if k == "PoolsOut" else v[0] for k, v in out.items()}


def empty_pools(cfg=CFG, n_pages=2 * MP + 1, rows=2):
    ring = rows * cfg.ring_pages(PS) + 1
    return [jnp.zeros((cfg.layers_of(kind), pages, PS,
                       cfg.n_kv(kind) * width), jnp.float32)
            for kind, pages in ((0, n_pages), (1, ring))
            for width in (cfg.head_dim, cfg.v_head_dim)]


def tables(cfg=CFG, rows=2):
    """Row r owns pages r*MP+1.. of the sequence kind and ring r."""
    n = cfg.ring_pages(PS)
    table = 1 + np.arange(rows * MP, dtype=np.int32).reshape(rows, MP)
    ring = 1 + np.arange(rows * n, dtype=np.int32).reshape(rows, n)
    return table, ring


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


def through_the_ops(cfg, w, prompt, chunk, steps=4):
    """A prompt through the chunk program ``chunk`` tokens at a time (or,
    ``chunk`` None, the whole-prompt program), then ``steps`` decode
    steps, row 0 of two, row 1 inactive: (the logits at the prompt's last
    position and at the decoded ones, the picks there, the sequence)."""
    table, ring = tables(cfg)
    table[1], ring[1] = 0, 0
    pools = empty_pools(cfg)
    n = len(prompt)
    if chunk is None:
        width = -(-n // 8) * 8
        toks = np.zeros((2, width), np.int64)
        toks[0, :n] = prompt
        out = run_op(T._block_paged_prefill, cfg, w, Tokens=toks,
                     Lens=np.asarray([n, 1], np.int32), Table=table,
                     RingTable=ring, Pools=pools)
        pools = out["PoolsOut"]
    for off in range(0, n if chunk else 0, chunk or 1):
        sl = prompt[off:off + chunk]
        toks = np.zeros((2, chunk), np.int64)
        toks[0, :len(sl)] = sl
        out = run_op(T._block_paged_prefill_chunk, cfg, w, Tokens=toks,
                     Lens=np.asarray([len(sl), 1], np.int32),
                     Offsets=np.asarray([off, 0], np.int32), Table=table,
                     RingTable=ring, Pools=pools)
        pools = out["PoolsOut"]
    first = np.asarray(out["NextTok"]).copy()
    first[1] = 0
    dec = run_op(T._block_paged_decode, cfg, w, steps=steps, Tokens=first,
                 Positions=np.asarray([n, 1], np.int32), Table=table,
                 RingTable=ring, Pools=pools)
    toks = np.asarray(dec["OutTokens"])[0]
    logits = np.concatenate([np.asarray(out["Logits"])[:1],
                             np.asarray(dec["Logits"])[0]])[:1 + steps]
    picks = np.concatenate([np.asarray(out["Picks"])[:1],
                            np.asarray(dec["Picks"])[0]])[:1 + steps]
    seq = np.concatenate([prompt, first[:1], toks[:steps - 1]])
    return logits, picks, seq, dec


RNG = np.random.RandomState(0)
SHORT = RNG.randint(0, CFG.vocab_size, 11)     # a whole-prompt program
LONG = RNG.randint(0, CFG.vocab_size, 39)      # three chunks of 16


def compare(cfg, w, model, prompt, chunk):
    """(largest rel_l2 of the ops' logits against the reference of
    ``model`` computed with the ops' picks, largest gap of those picks)."""
    logits, picks, seq, _ = through_the_ops(cfg, w, prompt, chunk)
    positions = len(prompt) - 1 + np.arange(len(logits))
    want, _, gaps = reference_logits(seq, positions, model, w, picks)
    return rel_l2(logits, want).max(), gaps.max()


# -- the programs against the reference -------------------------------------

@pytest.mark.parametrize("prompt, chunk", [(SHORT, None), (LONG, 16),
                                           (SHORT, 3)])
def test_prefill_then_decode_through_both_cache_kinds_matches_the_reference(
        prompt, chunk):
    """Whole-prompt, and in chunks each four windows long (the last a
    short one) or shorter than the ring; then decode steps that turn the
    ring further."""
    err, gap = compare(CFG, W, MODEL, prompt, chunk)
    assert err < REL_L2_F32 and gap < 1e-4


def test_the_full_layers_fold_their_pages_a_block_of_keys_at_a_time(
        monkeypatch):
    """With room for 2 pages of keys a pass, a full layer folds its
    24-page row in 12 blocks under the running softmax; the logits do not
    move."""
    whole, _, _, _ = through_the_ops(CFG, W, LONG, 16, steps=2)
    monkeypatch.setattr(T, "_KEY_BLOCK", 2 * PS)
    seen, fold = [], T._PagedRunner._gqa_blocked
    monkeypatch.setattr(
        T._PagedRunner, "_gqa_blocked",
        lambda self, q, read, n_blocks, kb, *a: (
            seen.append((n_blocks, kb)),
            fold(self, q, read, n_blocks, kb, *a))[1])
    blocked, _, _, _ = through_the_ops(CFG, W, LONG, 16, steps=2)
    assert rel_l2(blocked, whole).max() < 1e-5
    assert set(seen) == {(MP // 2, 2 * PS)}


def test_stats_count_the_positions_each_kind_of_layer_attended():
    _, _, _, dec = through_the_ops(CFG, W, LONG, 16, steps=4)
    stats = dict(zip(T.HYBRID_STATS, np.asarray(dec["Stats"])))
    n = len(LONG)
    # 2 full layers over the row's length, 3 window layers over 4
    assert stats["attn_full_positions_total"] == 2 * sum(
        n + 1 + s for s in range(4))
    assert stats["attn_window_positions_total"] == 3 * 4 * CFG.window
    assert stats["latent_tokens_read_total"] == 0
    assert stats["moe_assignments_total"] == 4 * CFG.moe_top_k * 4
    assert dec["Picks"].shape == (2, 4, 4, CFG.moe_top_k)


# -- every new term moves the reference, and the programs follow -----------

VARIANTS = {
    "window_3": (dict(window=3), dict(_window=3)),
    "window_5": (dict(window=5), dict(_window=5)),
    "no_sink": (dict(sink_window=False), dict(_use_sink=False)),
    "no_value_scale": (dict(value_scale=1.0), dict(_value_scale=1.0)),
    "bases_swapped": (dict(rope_base_full=CFG.rope_base_window,
                           rope_base_window=CFG.rope_base_full),
                      dict(_swap_bases=True)),
    "whole_rotation": (dict(rotary_dim=CFG.head_dim),
                       dict(_rotary_dim=CFG.head_dim)),
}


@pytest.mark.parametrize("name", sorted(VARIANTS))
def test_each_term_changes_the_reference_and_the_programs_follow(name):
    change, switch = VARIANTS[name]
    cfg = replace(CFG, **change)
    model = dict(MODEL, **switch)
    prompt = LONG[:21]                  # a chunk of 16 and one of 5
    positions = len(prompt) - 1 + np.arange(3)
    logits, picks, seq, _ = through_the_ops(cfg, W, prompt, 16, steps=2)
    changed, _, gaps = reference_logits(seq, positions, model, W, picks)
    assert rel_l2(logits, changed).max() < REL_L2_F32 and gaps.max() < 1e-4
    unchanged, _, _ = reference_logits(seq, positions, MODEL, W, picks)
    assert rel_l2(changed, unchanged).min() > 50 * REL_L2_F32


# -- today's models are untouched -------------------------------------------

def _todays_gqa_attention(kinds, p, u, pos, attend_fn):
    """ops/transformer_ops.py _gqa_attention as PR 32 left it."""
    b, t, _ = u.shape
    hd = p["Wq"].shape[-1] // kinds.n_heads
    q = T.apply_rope_at(T.qmat(u, p, "Wq").reshape(b, t, kinds.n_heads, hd),
                        pos, kinds.base)
    k = T.apply_rope_at(T.qmat(u, p, "Wk").reshape(b, t, kinds.n_kv, hd),
                        pos, kinds.base)
    v = T.qmat(u, p, "Wv").reshape(b, t, kinds.n_kv, hd)
    return T.qmat(attend_fn(q, (k, v)), p, "Wo")


def test_with_no_new_term_gqa_attention_is_todays_bit_for_bit():
    kinds = T.BlockKinds(n_heads=4, n_kv=2, base=1e4)
    assert (kinds.key_dim, kinds.rotary_dim, kinds.value_scale,
            kinds.window, kinds.sink, kinds.attn_kinds,
            kinds.layer_kinds) == (None, None, 1.0, None, False, None, None)
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    p = {"Wq": jax.random.normal(keys[0], (32, 32)),
         "Wk": jax.random.normal(keys[1], (32, 16)),
         "Wv": jax.random.normal(keys[2], (32, 16)),
         "Wo": jax.random.normal(keys[3], (32, 32))}
    u = jax.random.normal(keys[4], (2, 5, 32))
    pos = jnp.asarray([[0, 1, 2, 3, 4], [7, 8, 9, 10, 11]], jnp.int32)

    def attend(q, kv):          # any function of all three
        k, v = kv
        return (q.reshape(2, 5, 2, 2, 8) * k[:, :, :, None]
                + v[:, :, :, None]).reshape(2, 5, 32)

    assert np.array_equal(
        np.asarray(T._gqa_attention(kinds, p, u, pos, attend)),
        np.asarray(_todays_gqa_attention(kinds, p, u, pos, attend)))


@pytest.mark.parametrize("cfg, n_pools", [
    (LLAMA_TINY, 2), (LATENT_MOE_TINY, 1), (LATENT_SHARE_TINY, 1)])
def test_a_model_with_one_cache_kind_keeps_its_pools_and_its_one_table(
        cfg, n_pools):
    programs = cfg.build_paged_programs(
        max_batch=3, page_size=4, n_pages=31, pages_per_seq=10,
        prompt_buckets=(8, 16), decode_block=2, chunk_size=8)
    assert programs.kinds == {} and len(programs.pool_specs) == n_pools
    assert all(shape[:3] == [cfg.n_layers, 31, 4]
               for shape, _ in programs.pool_specs)
    for bundle, data in ((programs.decode, 3), (programs.chunk, 4),
                         (programs.prefill[8], 3)):
        feeds = bundle["feeds"]
        assert len(feeds) == data + n_pools
        assert [f for f in feeds if "table" in f] == [feeds[data - 1]]
    if cfg is not LLAMA_TINY:
        assert programs.stats == T.PAGED_STATS
        assert programs.decode["feeds"][-1] == "dc_pool"


# -- the shares add up ------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips, four experts each, a window layer with unequal head
    counts and widths: what the shares' routed parts give, with the
    attention and the residual counted once, is the whole layer of the
    uncut reference; and every share picks the same experts."""
    layer = 1                                   # window.*[0]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 9, CFG.dim))
    pos = jnp.arange(9, dtype=jnp.int32)[None]
    whole_cfg = replace(CFG, n_experts=16, experts_first=0)
    w_all = make_weights(whole_cfg, 7)
    uncut = dict(MODEL, experts_held=dict(first=0, count=16, of=16))
    want, _, _, own = ref.layer(ref.from_stacked(w_all, uncut), layer,
                                x[0], uncut)
    none = dict(MODEL, experts_held=dict(first=0, count=0, of=16))
    common, _, _, _ = ref.layer(ref.from_stacked(w_all, none), layer,
                                x[0], none)
    total, picks = jnp.zeros_like(common), []
    for share in range(4):
        cfg = replace(CFG, experts_first=4 * share)
        kinds = T._block_runner(op_inputs(cfg, W),
                                cfg.block_attrs(PS)).kinds.of(1)
        assert (kinds.experts_first, kinds.n_kv, kinds.window) \
            == (4 * share, 4, CFG.window)
        p = {}
        for slot, (suffix, _, _) in cfg.layer_params(3, 1, True).items():
            v = w_all[f"window.{suffix}"][0]
            p[slot] = v[4 * share:4 * share + 4] \
                if slot in T._EXPERT_SLOTS else v

        def attend(q, kv):       # over this window alone, no cache
            return T.masked_attention(q, *kv, pos, window=CFG.window,
                                      sink=p["Sink"])

        y, (load, idx) = T.block_forward(kinds, p, x, pos, attend)
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
    assert sum(int(((picks[0] >= 4 * s) & (picks[0] < 4 * s + 4)).sum())
               for s in range(4)) == 9 * CFG.moe_top_k


# -- the allocator ------------------------------------------------------------

def test_one_allocator_keeps_its_invariants_for_each_kind():
    a = PageAllocator(9, 4)
    assert a.kinds == ("sequence",)
    a.add_kind("window", 5)
    assert a.kinds == ("sequence", "window")
    with pytest.raises(ValueError):
        a.add_kind("window", 5)
    seq, ring = a.alloc(3), a.alloc(2, "window")
    # each kind has its own page ids, from 1, and its own counts
    assert seq == [1, 2, 3] and ring == [1, 2]
    assert (a.in_use, a.available, a.usable_pages) == (3, 5, 8)
    assert (a.in_use_of("window"), a.available_of("window"),
            a.usable_of("window")) == (2, 2, 4)
    with pytest.raises(PagesExhaustedError):
        a.alloc(3, "window")
    assert a.available_of("window") == 2          # no partial grant
    with pytest.raises(ValueError, match="double free"):
        a.free([3], "window")                     # a sequence page's id
    a.free([3])
    with pytest.raises(ValueError, match="double free"):
        a.free([3])
    with pytest.raises(ValueError, match="outside"):
        a.free([5], "window")
    a.free(ring, "window")
    assert a.alloc(4, "window") == [1, 2, 3, 4]
    assert a.export_state([1], "window") == {"pages": [1], "page_size": 4}
    with pytest.raises(ValueError, match="not a live"):
        a.export_state([3])


ENGINE = dict(max_batch=3, prompt_buckets=(8, 16, 48), max_new_tokens=8,
              page_size=PS, decode_block=2, prefill_batch=1, chunk_size=16,
              default_timeout_s=120.0)


@pytest.fixture(scope="module")
def scope():
    scope = fluid.Scope()
    for name, value in W.items():
        scope.set(name, value)
    return scope


@pytest.fixture(scope="module")
def engine(scope):
    eng = DecodeEngine(CFG, scope=scope, config=DecodeConfig(**ENGINE))
    eng.warmup()
    yield eng
    eng.close()


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
            seq, positions, dict(MODEL, _use_sink=False), picks=picks)
        assert rel_l2(got, fault).min() > 50 * REL_L2_F32


def test_a_window_layers_pages_are_a_ring_whatever_the_length(engine):
    a = engine.allocator
    assert engine.ring == {"window": 4, "pages_per_seq": 2,
                           "n_pages": 3 * 2 + 1, "pools": (2, 3),
                           "table": ("RingTable", "ring_table")}
    assert a.kinds == ("sequence", "window") and a.usable_of("window") == 6
    before = engine.stats()
    out = engine.generate(LONG, max_new=8)
    after = engine.stats()
    want, _, _ = reference_logits(np.concatenate([LONG, out])[:-1])
    assert np.array_equal(out, np.argmax(want, -1)[len(LONG) - 1:])
    # 47 positions went through a ring of 2 pages of 2: pages 2..23 of
    # the sequence took the place of an earlier one, the decode
    # dispatches' overshoot included
    turns = after["window_pages_recycled_total"] \
        - before["window_pages_recycled_total"]
    assert 21 <= turns <= 23
    assert (a.in_use, a.in_use_of("window")) == (0, 0)
    # what the slot held at each decode dispatch: its sequence pages and
    # its ring, whole; against the positions resident
    held = after["cache_bytes_held_total"] - before["cache_bytes_held_total"]
    resident = after["cache_positions_resident_total"] \
        - before["cache_positions_resident_total"]
    page = {k: engine._page_bytes[k] for k in a.kinds}
    assert page == {"sequence": 2 * PS * 2 * 20 * 4,
                    "window": 3 * PS * 4 * 20 * 4}
    dispatches = after["decode_batches_total"] - before["decode_batches_total"]
    n_pages = a.pages_for(max(48, len(LONG) + 8 + 2))
    assert held == dispatches * (n_pages * page["sequence"]
                                 + 2 * page["window"])
    assert resident == sum(len(LONG) + 2 * i for i in range(dispatches))
    full = after["attn_full_positions_total"] \
        - before["attn_full_positions_total"]
    window = after["attn_window_positions_total"] \
        - before["attn_window_positions_total"]
    assert window == 3 * 4 * 2 * dispatches and full > 10 * window / 3


def test_requests_sharing_the_engine_are_bit_identical_to_running_alone(
        engine):
    """Co-scheduled requests, and then requests that reuse the pages of
    both kinds that retired ones freed (stale rings, stale pages)."""
    prompts = [LONG, SHORT, RNG.randint(0, CFG.vocab_size, 29),
               RNG.randint(0, CFG.vocab_size, 5), LONG[:20]]
    alone = [engine.generate(p, max_new=8) for p in prompts]
    reqs = [engine.submit(p, max_new=8) for p in prompts]
    for r, want in zip(reqs, alone):
        assert np.array_equal(r.result(60), want)
    a = engine.allocator
    assert (a.in_use, a.in_use_of("window")) == (0, 0)
    engine.assert_no_recompiles()


def test_a_shed_or_failed_request_frees_both_kinds(scope):
    """A ring for one of three slots: a grant is both kinds or neither,
    the second request waits for the first one's ring and then runs."""
    eng = DecodeEngine(CFG, scope=scope, config=DecodeConfig(**ENGINE))
    try:
        a = eng.allocator
        taken = a.alloc(6, "window")            # no ring to be had
        with pytest.raises(PagesExhaustedError):
            eng._alloc(5)
        assert a.in_use == 0                    # the 5 went back
        a.free(taken[:2], "window")             # one ring
        alone = eng.generate(SHORT, max_new=8)
        reqs = [eng.submit(p, max_new=8) for p in (LONG, SHORT)]
        assert np.array_equal(reqs[1].result(60), alone)
        assert len(reqs[0].result(60)) == 8
        assert eng.stats()["page_wait_total"] > 0
        assert (a.in_use, a.in_use_of("window")) == (0, 4)
        # a request whose dispatch fails gives both kinds back
        eng._run_prefill_program = None
        with pytest.raises(TypeError):
            eng.submit(SHORT, max_new=8).result(60)
        assert (a.in_use, a.in_use_of("window")) == (0, 4)
    finally:
        eng.close()
    # close() with requests in flight frees both kinds too
    eng = DecodeEngine(CFG, scope=scope, config=DecodeConfig(**ENGINE))
    eng.submit(LONG, max_new=8)
    eng.close()
    assert (eng.allocator.in_use, eng.allocator.in_use_of("window")) == (0, 0)


def test_the_handoff_blob_round_trips_both_kinds(engine, scope):
    want = engine.generate(LONG, max_new=8)
    blob = engine.submit(LONG, max_new=8, prefill_only=True).result(60)
    assert len(blob["kinds"]["window"]) == 2 and len(blob["cache"]) == 4
    assert [x.shape[1] for x in blob["cache"]] == [
        len(blob["pages"])] * 2 + [2, 2]
    a = engine.allocator
    assert (a.in_use, a.in_use_of("window")) == (0, 0)
    other = DecodeEngine(CFG, scope=scope, config=DecodeConfig(**ENGINE))
    try:
        # pages of both kinds that are not the exporter's
        other.allocator.alloc(3)
        other.allocator.alloc(1, "window")
        got = other.import_handoff(blob).result(60)
        assert np.array_equal(got, want)
        assert (other.allocator.in_use,
                other.allocator.in_use_of("window")) == (3, 1)
    finally:
        other.close()
