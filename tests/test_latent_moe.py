"""Latent attention, drop-free routed experts with a shared one and the
hyper-connection residual path, served through the paged programs and
DecodeEngine, against the plain reference
(benchmark/reference/latent_moe_mhc.py) at a small size in float32.

The comparison's limit here is REL_L2_F32: the programs and the reference
both compute in float32, so they agree to rounding (1e-6), and every term
the chip comparison is meant to catch (benchmark/builders/serve_blocks.py)
moves the logits by far more than that.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.models.latent_moe import LATENT_MOE_TINY as CFG
from paddle_tpu.models.llama import LLAMA_TINY
from paddle_tpu.ops import moe
from paddle_tpu.ops import transformer_ops as T
from paddle_tpu.serving.batching import ServingError
from paddle_tpu.serving.decode_engine import (DecodeConfig, DecodeEngine,
                                              PoolsLostError)

from benchmark.reference import latent_moe_mhc as ref
from pool_donation import aliased_bytes, check_dispatch_donates
import stored_width

REL_L2_F32 = 1e-4
PS, MP = 4, 8                      # page size, pages a row
MODEL = dict(
    hc_mult=CFG.n_streams, num_hidden_layers=CFG.n_layers,
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
    num_experts_per_tok=CFG.moe_top_k,
    routed_scaling_factor=CFG.route_scale, n_shared_experts=CFG.n_shared,
    mhc_h_res_clamp_min=CFG.hc_clamp[0],
    mhc_h_res_clamp_max=CFG.hc_clamp[1],
    hc_sinkhorn_iters=CFG.sinkhorn_iters, hc_eps=CFG.hc_eps)


def make_weights(seed=0):
    """Seeded float32 weights, every term alive: norms off 1, selection
    bias of the size of the score gaps, mixing gates and biases wide
    enough that the 20th Sinkhorn round still moves the result."""
    out = {}
    shapes = CFG.param_shapes()
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    for k, (name, (shape, dt)) in zip(keys, sorted(shapes.items())):
        x = jax.random.normal(k, shape)
        if name.endswith("norm"):
            x = 1.0 + 0.1 * x
        elif name.endswith("alpha"):
            x = jnp.broadcast_to(jnp.asarray([0.5, 0.5, 2.0]), shape)
        elif name.endswith("moe_bias"):
            x = 0.1 * x
        elif name.endswith("_bias"):
            x = 0.5 * x
        else:
            x = 0.2 * x
        out[name] = x.astype(dt)
    return out


W = make_weights()
REF_W = ref.from_stacked(W, CFG.n_dense_layers)


def rel_l2(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return np.linalg.norm(got - want, axis=-1) \
        / np.linalg.norm(want, axis=-1)


def op_inputs(**feeds):
    ins = {"Emb": [W["tok_emb"]], "FinalNorm": [W["final_norm"]],
           "LmHead": [W["lm_head"]]}
    for prefix, scope, n, routed in (
            ("Lead", "lead", CFG.n_dense_layers, False),
            ("", "blocks", CFG.n_layers - CFG.n_dense_layers, True)):
        for slot, (suffix, _, _) in CFG.layer_params(n, routed).items():
            ins[prefix + slot] = [W[f"{scope}.{suffix}"]]
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


def reference_logits(seq, positions=None, **switches):
    logits, margins, _ = ref.forward(
        REF_W if "_weights" not in switches else switches.pop("_weights"),
        np.asarray(seq), dict(MODEL, **switches), positions)
    return np.asarray(logits), np.asarray(margins)


# rows of unequal length; row 0 (7 tokens) crosses from its 2nd to its
# 3rd page inside a 4-step dispatch; row 2 is an inactive slot
LENS = np.array([7, 3, 1, 5], np.int32)
TABLE = np.zeros((4, MP), np.int32)
TABLE[0, :4] = [1, 2, 3, 4]
TABLE[1, :3] = [5, 6, 7]
TABLE[3, :4] = [8, 9, 10, 11]
RNG = np.random.RandomState(0)
PROMPTS = RNG.randint(0, CFG.vocab_size, (4, 8))


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
    # the inactive slot wrote nothing but the null page
    before, after = pre["PoolsOut"][0], dec["PoolsOut"][0]
    unowned = [p for p in range(1, 40) if p not in TABLE]
    assert np.array_equal(np.asarray(before)[:, unowned],
                          np.asarray(after)[:, unowned])
    # what Stats counts: the three active rows' real tokens
    routed = CFG.n_layers - CFG.n_dense_layers
    stats = dict(zip(T.PAGED_STATS, np.asarray(pre["Stats"])))
    assert stats["moe_assignments_total"] == 15 * CFG.moe_top_k * routed
    assert stats["moe_decode_expert_calls_total"] == 0
    stats = dict(zip(T.PAGED_STATS, np.asarray(dec["Stats"])))
    assert stats["moe_assignments_total"] == 3 * 4 * CFG.moe_top_k * routed
    assert stats["moe_decode_expert_calls_total"] \
        == 4 * routed * CFG.n_experts
    assert 0 < stats["moe_decode_experts_touched_total"] \
        <= 4 * routed * 3 * CFG.moe_top_k
    assert stats["latent_tokens_read_total"] \
        == sum(int(LENS[r]) + s + 1 for r in active for s in range(4))


def test_prefill_in_chunks_writes_what_the_whole_prompt_does():
    prompt = RNG.randint(0, CFG.vocab_size, 19)
    table = np.zeros((1, MP), np.int32)
    table[0, :6] = 1 + np.arange(6)
    pools = empty_pool()
    for off in range(0, 19, 8):
        sl = prompt[off:off + 8]
        tokens = np.zeros((1, 8), np.int64)
        tokens[0, :sl.size] = sl
        out = run_op(T._block_paged_prefill_chunk, Tokens=tokens,
                     Lens=np.asarray([sl.size], np.int32),
                     Offsets=np.asarray([off], np.int32), Table=table,
                     Pools=pools)
        pools = out["PoolsOut"]
    want, _ = reference_logits(prompt, [18])
    assert rel_l2(out["Logits"][0], want[0]) < REL_L2_F32
    tokens = np.zeros((1, 24), np.int64)
    tokens[0, :19] = prompt
    whole = run_op(T._block_paged_prefill, Tokens=tokens,
                   Lens=np.asarray([19], np.int32), Table=table,
                   Pools=empty_pool())
    np.testing.assert_allclose(
        np.asarray(whole["PoolsOut"][0])[:, 1:5],
        np.asarray(pools[0])[:, 1:5], rtol=0, atol=1e-5)
    assert int(whole["NextTok"][0]) == int(out["NextTok"][0])
    dec = run_op(T._block_paged_decode, steps=3,
                 Tokens=np.asarray(out["NextTok"]),
                 Positions=np.asarray([19], np.int32), Table=table,
                 Pools=pools)
    seq = np.concatenate([prompt, np.asarray(out["NextTok"]),
                          np.asarray(dec["OutTokens"])[0, :2]])
    want, _ = reference_logits(seq, 19 + np.arange(3))
    assert rel_l2(dec["Logits"][0], want).max() < REL_L2_F32


def test_absorbed_attention_is_expanded_attention():
    """One window of three tokens through ``forward``, expanded, and the
    same tokens through three decode steps, absorbed, over the same
    cache."""
    run = T._block_runner(op_inputs(), CFG.block_attrs(PS))
    pool = jax.random.normal(jax.random.PRNGKey(3),
                             (CFG.n_layers, 12, PS, CFG.entry_dim)) * 0.5
    table = jnp.asarray([[1, 2, 3, 0, 0, 0, 0, 0], [4, 5, 6, 7, 0, 0, 0, 0]],
                        jnp.int32)
    pos0 = jnp.asarray([6, 11], jnp.int32)
    h = run.embed(jnp.asarray(RNG.randint(0, CFG.vocab_size, (2, 3))))
    paged, pool2 = run.forward(h, pool, table, pos0, 3)
    stepped, pool3 = [], pool
    for i in range(3):
        out, pool3 = run.decode_step(h[:, i:i + 1], pool3, table, pos0 + i)
        stepped.append(out)
    np.testing.assert_allclose(np.asarray(paged),
                               np.asarray(jnp.concatenate(stepped, axis=1)),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(pool2)[:, 1:],
                               np.asarray(pool3)[:, 1:], rtol=0, atol=1e-5)
    assert not np.array_equal(np.asarray(pool3)[:, 1:],
                              np.asarray(pool)[:, 1:])


def test_expanded_attention_walks_its_key_blocks(monkeypatch):
    """The same window with the cache read two pages at a time."""
    run = T._block_runner(op_inputs(), CFG.block_attrs(PS))
    pool = jax.random.normal(jax.random.PRNGKey(4),
                             (CFG.n_layers, 12, PS, CFG.entry_dim)) * 0.5
    table = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 0]], jnp.int32)
    h = run.embed(jnp.asarray(RNG.randint(0, CFG.vocab_size, (1, 5))))
    pos0 = jnp.asarray([20], jnp.int32)
    one = run.forward(h, pool, table, pos0, 5)[0]
    monkeypatch.setattr(T, "_KEY_BLOCK", 2 * PS)
    many = run.forward(h, pool, table, pos0, 5)[0]
    np.testing.assert_allclose(np.asarray(one), np.asarray(many),
                               rtol=2e-5, atol=2e-5)


def test_expert_layer_drops_no_token_under_a_skewed_router():
    """One expert takes most tokens and several take none; every
    token-expert pair is still computed: the sorted form equals the loop
    over experts, token for token."""
    t, d, e, f, k = 96, 32, 8, 16, 2
    keys = jax.random.split(jax.random.PRNGKey(5), 6)
    x = jax.random.normal(keys[0], (t, d))
    router = 0.2 * jax.random.normal(keys[1], (d, e))
    bias = jnp.asarray([4.0, 0.0, 0.0, 0.0, 0.0, -4.0, -4.0, -4.0])
    w_gate = 0.2 * jax.random.normal(keys[2], (e, d, f))
    w_up = 0.2 * jax.random.normal(keys[3], (e, d, f))
    w_down = 0.2 * jax.random.normal(keys[4], (e, f, d))
    idx, gates = moe.moe_route(x, router, k, "sigmoid", bias, 2.0)
    load = np.asarray(moe.moe_load(idx, e))
    assert load.sum() == t * k and load[0] == t and (load[5:] == 0).all()
    got = moe.moe_apply_sorted(x, idx, gates, w_gate, w_up, w_down)
    want = np.zeros((t, d), np.float32)
    for j in range(e):
        weight = np.asarray(jnp.sum(jnp.where(idx == j, gates, 0.0), -1))
        want += weight[:, None] * np.asarray(
            ref.swiglu(x, w_gate[j], w_up[j], w_down[j]))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)
    assert np.abs(want).sum(-1).min() > 0          # no token left out
    # the reference's own routing agrees with the program's
    rw = {"l0.moe_router": router, "l0.moe_bias": bias}
    rm = dict(num_experts_per_tok=k, routed_scaling_factor=2.0)
    picked, g, margin, gap = ref.route(rw, 0, x, rm)
    assert np.array_equal(np.sort(picked, -1), np.sort(idx, -1))
    assert float(jnp.max(gap)) == 0.0
    # forced to the runner-up in place of its last pick at token 3, the
    # reference reports a gap of exactly that token's margin
    order = np.argsort(-np.asarray(jax.nn.sigmoid(x @ router) + bias), -1)
    forced = np.array(picked)
    forced[3] = [order[3, 0], order[3, 2]]
    at = np.arange(t) == 3
    picked2, _, _, gap2 = ref.route(rw, 0, x, rm, (at, forced))
    assert np.array_equal(picked2[3], forced[3])
    assert np.array_equal(np.asarray(picked2)[~at], np.asarray(picked)[~at])
    assert float(gap2[3]) == pytest.approx(float(margin[3]), rel=1e-5)
    assert float(jnp.max(jnp.where(at, 0.0, gap2))) == 0.0
    # half the load on padding: the valid mask leaves it out
    valid = jnp.arange(t) < t // 2
    assert int(moe.moe_load(idx, e, valid).sum()) == t // 2 * k


def test_sinkhorn_output_is_doubly_stochastic():
    m = jnp.exp(0.5 * jax.random.normal(jax.random.PRNGKey(6), (64, 4, 4)))
    out = np.asarray(T.sinkhorn_knopp(m, 20, 1e-6))
    assert np.abs(out.sum(-1) - 1).max() < 1e-5
    assert np.abs(out.sum(-2) - 1).max() < 1e-5
    assert (out > 0).all()


def test_yarn_frequencies_are_the_references():
    got = T.yarn_inv_freq(64, 10000.0, 64.0, 4096, 32.0, 1.0)
    want = ref.yarn_inv_freq(64, 10000.0, 64.0, 4096, 32.0, 1.0)
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-6)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    assert got[0] == pytest.approx(plain[0])         # fast pairs kept
    assert got[-1] == pytest.approx(plain[-1] / 64)  # slow ones divided
    assert T.yarn_mscale(64.0) == pytest.approx(1.4159, abs=1e-4)


# -- the engine ----------------------------------------------------------

def make_engine(**kw):
    scope = fluid.Scope()
    for name, value in W.items():
        scope.set(name, value)
    cfg = dict(max_batch=3, prompt_buckets=(8, 32), max_new_tokens=8,
               page_size=PS, decode_block=2, prefill_batch=2, chunk_size=8)
    cfg.update(kw)
    return DecodeEngine(CFG, scope=scope, config=DecodeConfig(**cfg))


@pytest.fixture(scope="module")
def engine():
    eng = make_engine()
    eng.warmup()
    yield eng
    eng.close()


def test_engine_keeps_whole_prompt_programs_only_where_reachable(engine):
    # bucket 32 is beyond chunk_size 8: prompts that long go in slices
    assert sorted(engine.programs.prefill) == [8]
    assert engine.programs.chunk_size == 8
    assert [tuple(p.shape) for p in engine._pools] == [
        (CFG.n_layers, engine.allocator.n_pages, PS, CFG.stored_dim)]


def test_engine_tokens_are_the_references_alone_and_co_scheduled(engine):
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, CFG.vocab_size, n) for n in (5, 21, 13, 8, 30)]
    alone = [engine.generate(p, max_new=6) for p in prompts]
    for p, out in zip(prompts, alone):
        seq = np.concatenate([p, out])
        want, margins = reference_logits(seq)
        assert np.array_equal(out, np.argmax(want, -1)[p.size - 1:-1])
    before = engine.stats()
    handles = [engine.submit(p, max_new=6) for p in prompts]
    together = [h.result(120) for h in handles]
    for a, b in zip(alone, together):
        assert np.array_equal(a, b)
    engine.assert_no_recompiles()                  # churn compiled nothing
    after = engine.stats()
    # a chunk dispatch ticks its real and its padded tokens
    chunked = [p.size for p in prompts if p.size > 8]
    whole = [p.size for p in prompts if p.size <= 8]
    assert after["prefill_tokens_total"] - before["prefill_tokens_total"] \
        == sum(chunked) + sum(whole)
    slices = sum(-(-n // 8) for n in chunked)
    assert after["chunk_prefill_total"] - before["chunk_prefill_total"] \
        == slices
    padded = after["prefill_padded_tokens_total"] \
        - before["prefill_padded_tokens_total"]
    # chunk_size a slice, the bucket a whole-prompt request's dispatch
    assert padded == 8 * slices + 8 * len(whole)
    assert after["moe_assignments_total"] > before["moe_assignments_total"]
    assert after["latent_tokens_read_total"] \
        > before["latent_tokens_read_total"]


def test_handoff_carries_the_one_pool_cache(engine):
    prompt = np.random.RandomState(8).randint(0, CFG.vocab_size, 6)
    want = engine.generate(prompt, max_new=5)
    blob = engine.submit(prompt, max_new=5, prefill_only=True).result(60)
    assert blob["kind"] == "kv_handoff" and len(blob["cache"]) == 1
    assert blob["cache"][0].shape[2:] == (PS, CFG.stored_dim)
    other = make_engine()
    try:
        got = other.import_handoff(blob).result(60)
        assert np.array_equal(got, want)
        bad = dict(blob, cache=[blob["cache"][0][..., :8]])
        with pytest.raises(ServingError, match="cache entries"):
            other.import_handoff(bad)
    finally:
        other.close()


# -- an entry is stored at whole lane tiles -------------------------------

@pytest.mark.parametrize("kv_rank,rope_dim,stored", [
    (16, 8, 128),           # this file's model: 24 wide, one tile
    (512, 64, 640),         # the published widths: 4.5 tiles -> 5
    (120, 8, 128),          # a whole tile already: left alone
    (448, 64, 512),
    (128, 1, 256)])
def test_an_entry_is_stored_at_the_next_whole_lane_tile(kv_rank, rope_dim,
                                                        stored):
    from dataclasses import replace
    cfg = replace(CFG, kv_rank=kv_rank, rope_dim=rope_dim)
    assert cfg.entry_dim == kv_rank + rope_dim
    assert cfg.stored_dim == stored
    assert cfg.cache_spec() == [((stored,), cfg.dtype)]
    # the weights keep their published shapes: the pad is the cache's
    shape, _ = cfg.param_shapes()["blocks.wkva"]
    assert shape[-1] == cfg.entry_dim


@pytest.mark.parametrize("form", ["whole", "chunked"])
def test_the_padded_entry_changes_no_bit_of_logits_picks_or_cache(form):
    stored_width.check_padding_changes_no_bit(run_op, CFG, form, PS, MP)


def test_an_engine_stores_padded_what_it_would_store_unpadded(monkeypatch):
    """Whole-prompt and chunk programs, under hyper-connections."""
    stored_width.check_engines_agree(make_engine, monkeypatch, CFG, PS)


@pytest.mark.parametrize("label", ["prefill_8", "chunk", "decode"])
def test_every_program_consumes_the_pool_it_is_fed(label):
    """The one latent pool is donated to each program the engine has:
    the array fed is deleted, tokens and pool bytes are the undonated
    program's, and XLA aliases the whole pool."""
    eng = make_engine()
    eng.close()
    assert sorted(eng._bundles()) == ["chunk", "decode", "prefill_8"]
    b, arrays, fed = check_dispatch_donates(eng, label, CFG.vocab_size)
    assert len(fed) == 1
    assert aliased_bytes(eng, b, arrays, eng._pools) >= eng._pools[0].nbytes
    # what the program returns beside tokens and pool stays on the device:
    # nothing, in the form of the decode program that the loop dispatches
    assert set(eng.kept.get(label, ())) == (
        set() if label == "decode" else {"logits", "picks"})


def test_a_chunk_that_loses_the_pool_fails_the_job_and_the_live_slot(
        monkeypatch):
    """The second slice of a chunked prefill raises once the pool is
    consumed: the chunk job and the slot decoding beside it both fail
    with PoolsLostError, their pages come back, and the engine serves
    the same prompts again with a fresh engine's tokens."""
    eng = make_engine()
    try:
        eng.warmup()
        rng = np.random.RandomState(9)
        short, long_ = (rng.randint(0, CFG.vocab_size, n) for n in (6, 21))
        want = [eng.generate(p, max_new=8) for p in (short, long_)]
        eng._stop.set()
        eng._worker.join(10.0)
        run, chunk, calls = eng.exe.run, eng.programs.chunk["program"], []

        def failing(prog, *args, **kw):
            outs = run(prog, *args, **kw)
            calls.append(prog)
            if prog is chunk and calls.count(chunk) == 2:
                raise RuntimeError("INTERNAL: the program failed")
            return outs

        monkeypatch.setattr(eng.exe, "run", failing)
        before = eng.stats()
        reqs = [eng.submit(p, max_new=8) for p in (short, long_)]
        eng.start()
        for r in reqs:
            with pytest.raises(PoolsLostError):
                r.result(120)
        after = eng.stats()
        assert after["pools_lost_total"] - before["pools_lost_total"] == 1
        assert after["errors_total"] - before["errors_total"] == 2
        assert eng.allocator.in_use == 0 and not eng._chunk_jobs
        assert not np.asarray(eng._pools[0]).any()
        for p, w in zip((short, long_), want):
            assert np.array_equal(eng.generate(p, max_new=8), w)
        eng.assert_no_recompiles()
        # the counter ticks as a dispatch RETURNS: the failed one, which
        # consumed its pool, is in neither count
        assert after["pools_consumed_total"] == (
            after["decode_batches_total"] + after["prefill_dispatch_total"]
            + after["chunk_prefill_total"] + 3)         # the warm-up's
    finally:
        eng.close()


@pytest.mark.parametrize("kw,match", [
    (dict(draft_cfg=LLAMA_TINY), "speculative"),
    (dict(quantize=True), "int8")])
def test_engine_refuses_what_the_model_has_no_form_of(kw, match):
    draft = kw.pop("draft_cfg", None)
    scope = fluid.Scope()
    with pytest.raises(NotImplementedError, match=match) as e:
        DecodeEngine(CFG, scope=scope, draft_cfg=draft, auto_start=False,
                     config=DecodeConfig(prompt_buckets=(8,), chunk_size=None,
                                         **kw))
    assert CFG.name in str(e.value)


def test_llama_engine_is_unchanged_by_the_cache_specification():
    from paddle_tpu.models.llama import build_llama_paged_programs
    progs = LLAMA_TINY.build_paged_programs(
        max_batch=2, page_size=4, n_pages=9, pages_per_seq=4,
        prompt_buckets=(8,), decode_block=2)
    hd = LLAMA_TINY.dim // LLAMA_TINY.n_heads
    assert progs.pool_specs == [
        ([LLAMA_TINY.n_layers, 9, 4, LLAMA_TINY.n_kv_heads, hd], "float32")
    ] * 2
    assert progs.stats == () and "extras" not in progs.decode
    assert build_llama_paged_programs.__doc__
    with pytest.raises(NotImplementedError, match="latent_moe"):
        from dataclasses import replace
        replace(LLAMA_TINY, moe_experts=4).build_paged_programs(
            max_batch=2, page_size=4, n_pages=9, pages_per_seq=4,
            prompt_buckets=(8,))


# -- the comparison has teeth --------------------------------------------

def _quantized(weights, kind):
    """Every matrix of the model through int8 (per-column scale) or
    float8 e4m3 and back."""
    out = {}
    for name, w in weights.items():
        small = name.endswith(("norm", "alpha", "_bias")) \
            or "hc_" in name or "router" in name
        if small:
            out[name] = w
        elif kind == "int8":
            s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
            out[name] = jnp.round(w / s) * s
        else:
            out[name] = w.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return out


@pytest.fixture(scope="module")
def probe(engine):
    """The engine's own logits after a chunked prompt and 8 steps, as the
    chip comparison takes them."""
    from benchmark.builders import serve_blocks
    prompt = np.random.RandomState(9).randint(0, CFG.vocab_size, 21)
    engine.close()
    got, picks, decoded = serve_blocks.engine_logits(engine, prompt, 8)
    seq, positions = np.concatenate([prompt, decoded[:-1]]), 20 + np.arange(9)
    # the programs' own picks are the reference's, position for position
    want = [np.asarray(ref.route(REF_W, i, u, MODEL)[0])[positions]
            for i, u in _routed_inputs(seq)]
    assert picks.shape == (9, CFG.n_layers - CFG.n_dense_layers,
                           CFG.moe_top_k)
    for layer, own in enumerate(want):
        assert np.array_equal(np.sort(picks[:, layer], -1),
                              np.sort(own, -1))
    return got, seq, positions


def _routed_inputs(seq):
    """(layer, the router's input) of each routed layer in the reference's
    forward pass over ``seq``."""
    seen = []
    keep = ref.experts

    def spy(w, i, u, m, forced=None):
        seen.append((i, u))
        return keep(w, i, u, m, forced)

    ref.experts = spy
    try:
        ref.forward(REF_W, np.asarray(seq), MODEL)
    finally:
        ref.experts = keep
    return seen


TEETH = {
    "bf16 router": dict(_router_dtype=jnp.bfloat16),
    "no shared expert": dict(_use_shared=False),
    "no selection bias": dict(_use_bias=False),
    "19 sinkhorn rounds": dict(_sinkhorn_iters=19),
    "no mscale^2": dict(_mscale_power=0),
    "int8 weights": dict(_weights="int8"),
    "fp8 weights": dict(_weights="fp8"),
}


def test_the_engines_logits_are_the_references(probe):
    got, seq, positions = probe
    want, _ = reference_logits(seq, positions)
    assert rel_l2(got, want).max() < REL_L2_F32 / 10


@pytest.mark.parametrize("case", sorted(TEETH))
def test_a_fault_the_comparison_must_catch_fails_it(probe, case):
    got, seq, positions = probe
    switches = dict(TEETH[case])
    if "_weights" in switches:
        switches["_weights"] = ref.from_stacked(
            _quantized(W, switches["_weights"]), CFG.n_dense_layers)
    want, _ = reference_logits(seq, positions, **switches)
    assert rel_l2(got, want).max() > REL_L2_F32, case


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
