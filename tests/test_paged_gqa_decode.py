"""A GQA decode step attends its pages where they lie.

On a backend that runs the Pallas kernels (the chip; here the hook
``pallas_attention._FORCE_INTERPRET``) a decode op over plain GQA pools
takes the in-place form (PERF.md section 6, PR 37): the steps carry the
pools themselves, each layer writes its entry into its page and
``paged_gqa_decode`` folds the row's pages, to the row's own length,
under a running softmax. Held here, on test_paged_cache_inplace.py's
rows (unequal lengths, a row that crosses a page inside a 4-step
dispatch, the inactive slot, a row that runs past ``kmax``, a row that
runs onto a null table entry) at a head 128 wide:

- the kernel against ``_attend_math`` over the gathered view, a case a
  row;
- a row alone against the same row among peers, bit for bit;
- a whole ``llama_paged_decode`` dispatch in both forms: the same tokens
  and the same pools on every page but the null one;
- structure: no array of the dense view's shape, no pool as a scan's
  ``xs`` / ``ys``;
- the gate: narrow-head models build the dense form, and the engine's
  ``decode_in_place_total`` says which it dispatched.

A LATENT model's one pool (PERF.md section 6, PR 45) is attended in place
by ``paged_latent_decode``, a third fold under the same schedule: a block
of pages copied once, keys whole and values at its leading lane tiles.
Held here on the same cases against the absorbed form's own two products
over the gathered view, at an entry padded past its latent and rotated
columns; the decode form itself in tests/test_latent_moe.py and
tests/test_latent_share.py.

A model that MIXES KINDS OF LAYER is asked kind by kind (PERF.md section
6, PR 42): its sequence kind, whose entries lie FLAT in their pages (keys
``g * dk`` wide beside values ``g * dv``), is attended in place by
``paged_flat_decode`` while its window rings and its states keep their
form. Held at MiMo's head shape (4 key/value heads, keys 192 beside values
128) and Jamba's (one head of 128): the kernel against ``_attend_masked``
over the gathered view, the same cases; a dispatch of the engine of
HYBRID_MOE_TINY and of HYBRID_SSM_TINY, widened to whole lane tiles, in
both forms; the gate, one reason to refuse at a time.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from benchmark.builders.serve import make_generator_weights
from benchmark.builders import serve_hybrid, serve_ssm
from benchmark.builders.serve_blocks import make_weights
from paddle_tpu.models.hybrid_moe import HYBRID_MOE_TINY
from paddle_tpu.models.hybrid_ssm import HYBRID_SSM_TINY
from paddle_tpu.models.latent_moe import LATENT_MOE_TINY
from paddle_tpu.models.llama import LLAMA_TINY, LlamaConfig
from paddle_tpu.ops import pallas_attention as pa
from paddle_tpu.ops import transformer_ops as T
from paddle_tpu.serving import DecodeConfig, DecodeEngine

import program_text
import test_paged_cache_inplace as rows
from test_paged_cache_inplace import (B, KMAX, MP, NP, POS, POS_END, PS,
                                      TABLE, TOK)

L, D, NH, NKV, HD, F, V = 3, 64, 4, 2, 128, 64, 50
ATTRS = dict(n_heads=NH, n_kv_heads=NKV, rope_base=10000.0, epsilon=1e-5,
             page_size=PS)


@pytest.fixture
def kernel_on(monkeypatch):
    """The Pallas kernels through the interpreter, a block two pages long
    so that rows of these lengths fold several blocks."""
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(pa, "PAGED_BLOCK_KEYS", 2 * PS)
    monkeypatch.setattr(pa, "PAGED_LATENT_BLOCK_KEYS", 2 * PS)


# a latent entry as stored: 100 latent columns and 40 rotated ones, padded
# to two lane tiles; the weights attend its first whole tile, which holds
# the latent (the caller cuts it to 100)
KV_RANK, ROPE, ENTRY, LATENT_SCALE = 100, 40, 256, 0.11

# how a page holds its entries -> (kernel, kv heads, key width, value
# width, query heads): heads inside positions, [PS, g, hd]; the entry
# flat, [PS, g * dk] beside [PS, g * dv]; or ONE pool of latent entries,
# [PS, entry], keys and values both
LAYOUTS = {
    "latent": (functools.partial(pa.paged_latent_decode, scale=LATENT_SCALE,
                                 width=T.whole_tiles(KV_RANK)),
               1, ENTRY, 128, 6),
    "heads": (pa.paged_gqa_decode, NKV, HD, HD, NH),
    # a key/value head a query head (a looped model's 16 of 16: PR 43):
    # the same product under a mask that keeps one head in g, not rep in g
    "heads_one_each": (pa.paged_gqa_decode, NH, HD, HD, NH),
    "flat_4x192_128": (pa.paged_flat_decode, 4, 192, 128, 8),
    "flat_1x128": (pa.paged_flat_decode, 1, 128, 128, 5),
}


def _pools(dtype, seed=0, layout="heads"):
    _, g, dk, dv, _ = LAYOUTS[layout]
    kk, kv = jax.random.split(jax.random.PRNGKey(seed))
    if layout == "latent":      # numbers in the padding too: the query's
        # zeros meet them
        return (jax.random.normal(kk, (L, NP, PS, dk)).astype(dtype),)
    if layout.startswith("heads"):
        return tuple(jax.random.normal(k, (L, NP, PS, g, dk)).astype(dtype)
                     for k in (kk, kv))
    return (jax.random.normal(kk, (L, NP, PS, g * dk)).astype(dtype),
            jax.random.normal(kv, (L, NP, PS, g * dv)).astype(dtype))


def _query(dtype, seed, layout="heads"):
    _, _, dk, _, heads = LAYOUTS[layout]
    q = jax.random.normal(jax.random.PRNGKey(seed),
                          (B, heads, dk)).astype(dtype)
    if layout == "latent":      # ``q_abs | q_pe`` and zeros behind them
        q = jnp.where(jnp.arange(dk) < KV_RANK + ROPE, q, 0)
    return q


def _view_attention(q, *pools_layer_table_lengths):
    """The dense form's own attention of one query a row at position
    ``length - 1`` over the gathered view: ``_attend_math`` for pools with
    heads inside positions, ``_attend_masked`` for flat entries, and for
    ONE pool of latent entries what ``_latent_absorbed`` does between its
    two halves of the expansion."""
    *pools, layer, table, lengths = pools_layer_table_lengths
    heads, dk = q.shape[1:]
    if len(pools) == 1:
        view = T._PagedRunner({"Wq": jnp.zeros((L, D, heads * dk))}, None,
                              None, None, n_heads=heads, n_kv=1, base=1e4,
                              eps=1e-5, page_size=PS).gather(
            pools[0], table)[layer]                     # [B, kmax, entry]
        s = jnp.einsum("bhc,bkc->bhk", q, view,
                       preferred_element_type=jnp.float32) * LATENT_SCALE
        seen = jnp.arange(view.shape[1])[None] < lengths[:, None]
        w = jax.nn.softmax(jnp.where(seen[:, None], s, -1e30), axis=-1)
        return jnp.einsum("bhk,bkc->bhc", w.astype(view.dtype),
                          view[..., :128],
                          preferred_element_type=jnp.float32)
    k_pool, v_pool = pools
    flat = k_pool.ndim == 4
    g = k_pool.shape[3] // dk if flat else k_pool.shape[3]
    run = T._PagedRunner({"Wq": jnp.zeros((L, D, heads * dk))}, None, None,
                         None, n_heads=heads, n_kv=g, base=1e4, eps=1e-5,
                         page_size=PS)
    views = [run.gather(pool, table)[layer] for pool in (k_pool, v_pool)]
    at = lengths[:, None] - 1
    if not flat:
        return run._attend_math(q[:, None], *views, at, 1)[:, 0].reshape(
            q.shape)
    return run._attend_masked(
        q[:, None], *(v.reshape(v.shape[:2] + (g, -1)) for v in views),
        at)[:, 0].reshape(q.shape[:2] + (-1,))


# (table row, length attended): the rows of TABLE at the lengths a 4-step
# dispatch from POS / POS_END gives them, as ``forward_in_place`` hands
# them over (position + 1, ``kmax`` at most)
CASES = {
    "before_its_page_boundary": (0, 8),      # ends exactly on a page
    "crossed_into_its_third_page": (0, 9),
    "two_blocks_and_a_tail": (1, 14),
    "onto_a_null_table_entry": (1, 17),      # position 16: page 0
    "inactive_slot": (2, 2),                 # position 1, null table
    "one_position": (3, 1),
    "short_row": (3, 3),
    "all_of_its_table": (3, KMAX),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_kernel_against_the_gathered_view(layout, case, dtype, kernel_on):
    """Every row of a batch at once, this case's row among them (peers of
    other lengths before and behind it), against the dense form's
    attention. float32 pools: the same math in another order; bf16: the
    weights are rounded to the cache's type before they meet the
    values."""
    row, length = CASES[case]
    lengths = np.array([5, 12, 2, 7], np.int32)
    lengths[row] = length
    args = (_query(dtype, 5, layout), *_pools(dtype, layout=layout),
            jnp.int32(1), jnp.asarray(TABLE), jnp.asarray(lengths))
    got = np.asarray(jax.jit(LAYOUTS[layout][0])(*args), np.float32)
    want = np.asarray(_view_attention(*args), np.float32)
    tol = 2e-2 if dtype == "bfloat16" else 2e-6
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_a_row_alone_is_the_row_among_peers(layout, kernel_on):
    """Bit for bit: a row's blocks and the order its softmax is folded in
    are fixed by the program, so neither the batch's longest row nor what
    another row left in the buffers reaches its result."""
    kernel = jax.jit(LAYOUTS[layout][0])
    pools = _pools("bfloat16", layout=layout)
    q = _query(jnp.bfloat16, 6, layout)
    lengths = np.array([9, 17, 2, KMAX], np.int32)
    among = np.asarray(kernel(q, *pools, jnp.int32(2),
                              jnp.asarray(TABLE), jnp.asarray(lengths)))
    for row in (0, 1, 3):
        alone = np.asarray(kernel(
            q[row:row + 1], *pools, jnp.int32(2),
            jnp.asarray(TABLE[row:row + 1]),
            jnp.asarray(lengths[row:row + 1])))
        assert np.array_equal(alone[0].view(np.uint16),
                              among[row].view(np.uint16)), row


def _decode_case(dtype, pos, steps=4):
    """(op, inputs, attrs) of ``llama_paged_decode`` at the toy shapes,
    a head 128 wide."""
    shapes = {"Wq": (D, NH * HD), "Wk": (D, NKV * HD), "Wv": (D, NKV * HD),
              "Wo": (NH * HD, D), "WGate": (D, F), "WUp": (D, F),
              "WDown": (F, D)}
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 16))
    ins = {slot: (jax.random.normal(next(keys), (L, m, n)) * 0.2
                  ).astype(dtype) for slot, (m, n) in shapes.items()}
    ins["AttnNorm"] = ins["MlpNorm"] = jnp.ones((L, D), dtype)
    ins["Emb"] = jax.random.normal(next(keys), (V, D)).astype(dtype)
    ins["FinalNorm"] = jnp.ones((D,), dtype)
    ins["LmHead"] = (jax.random.normal(next(keys), (D, V)) * 0.2
                     ).astype(dtype)
    ins["KPages"], ins["VPages"] = _pools(dtype, seed=7)
    ins.update(Table=jnp.asarray(TABLE), Tokens=jnp.asarray(TOK),
               Positions=jnp.asarray(pos))
    return T._llama_paged_decode, ins, dict(ATTRS, steps=steps)


@pytest.mark.parametrize("pos", [POS, POS_END], ids=["pos", "pos_end"])
def test_a_dispatch_in_both_forms(pos, monkeypatch):
    """Four steps of every row, dense form then in-place form, float32
    (the two forms are then the same sums in another order; at bf16 the
    kernel rounds its weights to the cache's type where ``_attend_math``
    does not, and this toy's argmax does not survive that). The same
    tokens, and the same pools on pages 1 and up. Outside the comparison,
    as in the dense form "in no defined order": the null page, and the
    last token of the two rows whose fourth position is on it (row 1 at
    16, the inactive row 2 at 4: in place they meet on one offset of one
    page, in a view each had a copy)."""
    op, ins, attrs = _decode_case("float32", pos)
    assert not T.decode_in_place("gqa", None, [ins["KPages"].shape] * 2)
    dense = rows._jit(op, attrs)(ins)
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(pa, "PAGED_BLOCK_KEYS", 2 * PS)
    assert T.decode_in_place("gqa", None, [ins["KPages"].shape] * 2)
    in_place = rows._jit(op, attrs)(ins)
    got, want = (np.asarray(x["OutTokens"]) for x in (in_place, dense))
    assert got.shape == (B, 4)
    assert np.array_equal(got[:, :3], want[:, :3])
    assert np.array_equal(got[[0, 3], 3], want[[0, 3], 3])
    for name in ("KPagesOut", "VPagesOut"):
        a, b = (np.asarray(x[name])[:, 1:] for x in (in_place, dense))
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
        # and the dispatch wrote: positions inside kmax of the live rows,
        # nothing else (row 3 past kmax: dropped, its last page's head as
        # it was)
        was = np.asarray(ins[name[:-3]])[:, 1:]
        changed = {int(p) + 1 for p in np.nonzero(
            (a != was).any(axis=(0, 2, 3, 4)))[0]}
        touched = {int(TABLE[r, p // PS]) for r in (0, 1, 3)
                   for p in range(pos[r], min(pos[r] + 4, KMAX))} - {0}
        assert changed == touched, (name, changed, touched)
        if pos[3] + 4 > KMAX:
            head = pos[3] % PS
            assert np.array_equal(a[:, TABLE[3, -1] - 1, :head],
                                  was[:, TABLE[3, -1] - 1, :head])


def _all_shapes(jaxpr, found=None):
    """The shape of every value in ``jaxpr`` and the jaxprs inside it."""
    found = set() if found is None else found
    for v in jaxpr.invars + jaxpr.constvars:
        found.add(tuple(getattr(v.aval, "shape", ())))
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            found.add(tuple(getattr(v.aval, "shape", ())))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _all_shapes(sub, found)
    return found


# the dense view of a [L, NP, PS, NKV, HD] pool: stacked, a layer's, as
# gathered, and heads before positions
VIEW_SHAPES = {(L, B, KMAX, NKV, HD), (B, KMAX, NKV, HD),
               (1, B, KMAX, NKV, HD), (L, B, MP, PS, NKV, HD),
               (B, NKV, KMAX, HD)}


def test_the_in_place_op_holds_no_view(monkeypatch):
    """No array of the dense view's shape anywhere in the op, the pools
    carried by its scans and never their ``xs`` or ``ys``; and the
    detector sees the dense form, which has both a view and a carry."""
    op, ins, attrs = _decode_case("bfloat16", POS)
    pool = {tuple(ins["KPages"].shape)}
    dense = jax.make_jaxpr(rows._jit(op, attrs))(ins)
    assert _all_shapes(dense.jaxpr) & VIEW_SHAPES
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    in_place = jax.make_jaxpr(rows._jit(op, attrs))(ins)
    assert not _all_shapes(in_place.jaxpr) & VIEW_SHAPES
    streamed, carried = rows._scan_cache_use(in_place, pool)
    assert not streamed, streamed
    assert carried == 2         # the step scan and the layer scan
    # one kernel instance a program: inside the layer scan
    text = str(in_place)
    assert text.count("name=paged_gqa_decode") == 1, text.count(
        "paged_gqa_decode")


WIDE = LlamaConfig(vocab_size=64, dim=256, n_layers=2, n_heads=2,
                   n_kv_heads=1, ffn_hidden=64, dtype="float32")
assert WIDE.dim // WIDE.n_heads == 128
GEOMETRY = dict(max_batch=3, page_size=4, n_pages=40, pages_per_seq=8,
                prompt_buckets=(8, 16), decode_block=2, chunk_size=8)


# HYBRID_MOE_TINY and HYBRID_SSM_TINY with their sequence kind's entries at
# whole lane tiles: MiMo's head (keys 192 beside values 128, 64 of them
# rotated; two key/value heads here) and Jamba's (one head of 128)
MOE_WIDE = dataclasses.replace(HYBRID_MOE_TINY, name="hybrid-moe-wide",
                               head_dim=192, v_head_dim=128, rotary_dim=64)
SSM_WIDE = dataclasses.replace(HYBRID_SSM_TINY, name="hybrid-ssm-wide",
                               head_dim=128)


@pytest.mark.parametrize("hook", [False, True], ids=["off", "on"])
def test_the_gate_reads_the_model_and_the_backend(hook, monkeypatch):
    """Plain GQA pools with whole-tile heads, a mixed model's sequence
    kind with flat whole-tile entries, or a latent model's ONE pool of
    whole-tile entries (PERF.md section 6, PR 45), on a backend that runs
    the kernel: everything else builds the dense form."""
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", hook)
    built = {name: cfg.build_paged_programs(**GEOMETRY).decode["in_place"]
             for name, cfg in (("wide", WIDE), ("narrow", LLAMA_TINY),
                               ("latent", LATENT_MOE_TINY),
                               ("hybrid", HYBRID_MOE_TINY),
                               ("hybrid_ssm", HYBRID_SSM_TINY),
                               ("hybrid_wide", MOE_WIDE),
                               ("hybrid_ssm_wide", SSM_WIDE))}
    assert built == {"wide": hook, "narrow": False, "latent": hook,
                     "hybrid": False, "hybrid_ssm": False,
                     "hybrid_wide": hook, "hybrid_ssm_wide": hook}
    pool = (2, 40, 4, 1, 128)
    assert T.decode_in_place("gqa", None, [pool, pool]) is hook
    assert T.decode_in_place("latent", None, [(2, 40, 4, 640)]) is hook
    for attention, kinds, pools in (
            ("latent", None, [(2, 40, 4, 576)]),            # 4.5 tiles
            ("latent", None, [(2, 40, 4, 640)] * 2),        # two pools
            ("latent", None, [(2, 40, 4, 5, 128)]),         # no flat entry
            ("gqa", None, [pool, (2, 40, 4, 1, 256)]),      # key != value
            ("gqa", None, [(2, 40, 4, 2, 64)] * 2),         # half a tile
            ("gqa", None, [(2, 40, 4, 128)] * 2)):          # flat entries
        assert not T.decode_in_place(attention, kinds, pools)


FULL_KIND = {"name": "full", "n_kv": 2, "base": 1e4, "window": None,
             "sink": False, "stack": "Full", "pools": [0, 1]}
FLAT_POOLS = [(2, 40, 4, 384), (2, 40, 4, 256)]
REFUSED = {
    "a_sink": (dict(FULL_KIND, sink=True), FLAT_POOLS),
    "a_window": (dict(FULL_KIND, window=4), FLAT_POOLS),
    "a_state_space_mixer": (dict(FULL_KIND, mixer="ssm"), FLAT_POOLS),
    "keys_of_no_whole_tiles": (FULL_KIND, [(2, 40, 4, 24), (2, 40, 4, 256)]),
    "value_heads_of_half_a_tile": (FULL_KIND,
                                   [(2, 40, 4, 384), (2, 40, 4, 128)]),
    "heads_inside_positions": (FULL_KIND, [(2, 40, 4, 2, 128)] * 2),
    "pools_of_other_pages": (FULL_KIND, [(2, 40, 4, 384), (2, 48, 4, 256)]),
}


@pytest.mark.parametrize("why", sorted(REFUSED))
def test_the_gate_answers_a_mixed_model_kind_by_kind(why, monkeypatch):
    """In place: the kind that keeps the whole sequence, attends, has no
    sink and stores flat whole-tile entries, where the backend runs the
    kernel; each other kind of the same model, and the same kind for any
    one reason, keeps the dense form."""
    window = dict(FULL_KIND, name="window", window=4, sink=True, n_kv=4,
                  stack="Window", pools=[2, 3])
    rings = [(3, 7, 4, 768), (3, 7, 4, 512)]
    kinds, pools = (FULL_KIND, window), FLAT_POOLS + rings
    assert not T.decode_in_place("gqa", kinds, pools)           # no Pallas
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    assert T.decode_in_place("gqa", kinds, pools)
    assert [T.decode_in_place("gqa", kinds, pools, k)
            for k in (0, 1)] == [True, False]
    assert not T.decode_in_place("latent", kinds, pools)
    spec, mine = REFUSED[why]
    assert not T.decode_in_place("gqa", (spec, window), mine + rings)
    assert not T.decode_in_place("gqa", (spec, window), mine + rings, 0)


def _scope_of(weights):
    scope = fluid.Scope()
    for name, value in weights.items():
        scope.set(name, value)
    return scope


def _wide_scope():
    return _scope_of(make_generator_weights(WIDE, 11, False))


def _mixed_scope(cfg):
    """The builders' weights, every matrix ten times as large so that a
    layer moves the residual stream, and the stand-ins of what a draw of
    normal(0, 0.02) would make invisible."""
    w = {k: v if k.endswith("norm") else v * 10
         for k, v in make_weights(cfg, 3).items()}
    w.update(serve_ssm.stand_ins(cfg, w) if cfg is SSM_WIDE
             else serve_hybrid.stand_ins(cfg, cfg.param_shapes()))
    return _scope_of(w)


MIXED_ENGINE = dict(max_batch=3, prompt_buckets=(8, 16, 48),
                    max_new_tokens=8, page_size=4, decode_block=2,
                    chunk_size=16, prefill_batch=1, default_timeout_s=120.0)


def _mixed_dispatches(cfg, scope):
    """A prompt through the whole-prompt program and one through three
    chunks, then 6 decoded positions each, as the benchmark's builders
    drive the engine's own programs: (logits, tokens, pools)."""
    probe = serve_ssm.engine_logits if cfg is SSM_WIDE \
        else serve_hybrid.engine_logits
    engine = DecodeEngine(cfg, scope=scope, place=fluid.CPUPlace(),
                          config=DecodeConfig(**MIXED_ENGINE),
                          auto_start=False)
    rng = np.random.RandomState(1)
    logits, tokens = [], []
    for n in (11, 39):
        out = probe(engine, rng.randint(0, cfg.vocab_size, n), 6)
        logits.append(out[0])
        tokens.append(out[1] if cfg is SSM_WIDE else out[2])
    return (np.concatenate(logits), np.concatenate(tokens),
            [np.asarray(p) for p in engine._pools],
            engine.programs.decode["in_place"])


@pytest.mark.parametrize("cfg", [MOE_WIDE, SSM_WIDE],
                         ids=["full_and_window", "full_and_state"])
def test_a_mixed_models_dispatches_in_both_forms(cfg, monkeypatch):
    """The engine's decode program with its sequence kind on the dense
    view, then in place (float32: the same sums in another order): the
    same tokens, the same logits within the forms' rounding, and the same
    pools of every kind on every page and entry but the null ones."""
    scope = _mixed_scope(cfg)
    want, want_tokens, want_pools, dense = _mixed_dispatches(cfg, scope)
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    monkeypatch.setattr(pa, "PAGED_FLAT_BLOCK_KEYS", 8)  # two pages
    got, got_tokens, got_pools, in_place = _mixed_dispatches(cfg, scope)
    assert (dense, in_place) == (False, True)
    assert np.array_equal(got_tokens, want_tokens)
    err = np.linalg.norm(got - want, axis=-1) \
        / np.linalg.norm(want, axis=-1)
    assert err.max() < 1e-4, err.max()
    for a, b in zip(got_pools, want_pools):
        np.testing.assert_allclose(a[:, 1:], b[:, 1:], rtol=1e-4, atol=1e-4)
        assert np.abs(b[:, 1:]).max() > 0


def test_the_mixed_program_holds_no_view_of_its_sequence_kind(monkeypatch):
    """The decode program's text in both forms: in place, no array of the
    sequence kind's views ([rows, kv heads, kmax, width], a layer each)
    and the kernel under the kind's scope; the window kind keeps the view
    of its rings in both."""
    b, kmax = GEOMETRY["max_batch"], 8 * GEOMETRY["page_size"]
    views = [f"tensor<{b}x2x{kmax}x{w}xf32>" for w in (192, 128)]
    rings = f"tensor<3x{b}x4x{4 * 192}xf32>"
    for hook in (False, True):
        monkeypatch.setattr(pa, "_FORCE_INTERPRET", hook)
        progs = MOE_WIDE.build_paged_programs(**GEOMETRY)
        lowered = program_text.lower_bundle(progs.decode,
                                            len(progs.pool_specs))
        text = lowered.as_text()
        assert [v in text for v in views] == [not hook] * 2
        assert rings in text
        # the interpreter leaves the kernel's name in its scopes alone
        assert ("attn/full/paged_flat_decode"
                in lowered.as_text(debug_info=True)) is hook


@pytest.mark.serving
@pytest.mark.parametrize("model", ["wide", "mixed"])
@pytest.mark.parametrize("hook", [False, True], ids=["off", "on"])
def test_the_engine_counts_its_in_place_dispatches(hook, model, monkeypatch):
    """``decode_in_place_total`` equals ``decode_batches_total`` for an
    engine built where the kernel runs and stays 0 where it does not: a
    model of plain GQA layers, and one that mixes kinds of layer whose
    sequence kind is attended in place."""
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", hook)
    cfg, scope = (WIDE, _wide_scope()) if model == "wide" \
        else (SSM_WIDE, _mixed_scope(SSM_WIDE))
    engine = DecodeEngine(
        cfg, scope=scope, place=fluid.CPUPlace(),
        config=DecodeConfig(max_batch=3, prompt_buckets=(8,),
                            max_new_tokens=6, page_size=4, decode_block=2,
                            default_timeout_s=120.0))
    try:
        assert engine.programs.decode["in_place"] is hook
        engine.warmup()
        rng = np.random.RandomState(3)
        prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int64)
                   for n in (3, 7, 5, 2)]
        requests = [engine.submit(p, max_new=6, timeout=120)
                    for p in prompts]
        tokens = [r.result(120) for r in requests]
        stats = engine.stats()
    finally:
        engine.close()
    assert stats["decode_batches_total"] > 0
    assert stats["decode_in_place_total"] == (
        stats["decode_batches_total"] if hook else 0)
    assert stats["pools_lost_total"] == 0
    assert all(len(t) == 6 for t in tokens)
