"""A decode step attends its pages where they lie.

A decode op runs its steps against the pools themselves: each layer of a
sequence kind writes its entry into its page and calls its paged
attention function (ops/pallas_attention.py ``paged_gqa_decode``,
``paged_flat_decode``, ``paged_latent_decode``), which asks its own gate:
the Pallas kernel on a backend that runs it (the chip; here the hook
``pallas_attention._FORCE_INTERPRET``) over pools it takes, the plain
jax.numpy reference of the same signature and the same promise everywhere
else (PERF.md section 6, PR 37, 42, 45 and 46). Held here, on
test_paged_cache_inplace.py's rows (unequal lengths, a row that crosses a
page inside a 4-step dispatch, the inactive slot, a row that runs past
``kmax``, a row that runs onto a null table entry):

- the kernel AND the reference against a float64 numpy gather-and-softmax
  of each row's pages, a case a row, in every layout: Mistral's heads
  inside positions at a head 128 wide, a key/value head a query head, the
  flat entries of a mixed model's sequence kind at MiMo's head shape (4
  key/value heads, keys 192 beside values 128) and Jamba's (one head of
  128), a latent model's one pool (an entry padded past its latent and
  rotated columns); the reference alone where no kernel goes: a sink, a
  narrow entry (576 wide, four and a half lane tiles), heads 8 wide;
- a row alone against the same row among peers, bit for bit;
- a whole ``llama_paged_decode`` dispatch behind the reference and behind
  the kernel: the same tokens and the same pools on every page but the
  null one; a dispatch of the engine of HYBRID_MOE_TINY and of
  HYBRID_SSM_TINY, widened to whole lane tiles, the same way;
- structure: no program holds a view of a sequence kind's layers
  ([layers, rows, kmax, ...]), kernel or not; behind the kernel no array
  with the rows' ``kmax`` positions at all, and no pool as a scan's ``xs``
  / ``ys``;
- the report: ``decode_in_place`` (a program's ``in_place``, the engine's
  ``decode_in_place_total``) says whether a decode program attends through
  a kernel, one reason to refuse at a time, and chooses nothing.
"""
import dataclasses
import functools
import re

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
    monkeypatch.setattr(pa, "PAGED_FLAT_BLOCK_KEYS", 2 * PS)


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
    if layout.startswith("latent"):     # numbers in the padding too: the
        # query's zeros meet them
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


# where no kernel goes, whatever the backend: layout -> (call, kv heads,
# key width, value width, query heads). A sink (one more column of each
# head's denominator); an entry that is not whole lane tiles (latent
# attention's 512 + 64 as published, 576 wide: the weights attend its
# first 512); heads narrower than a lane tile
SINK = np.linspace(-1.0, 2.0, 8).astype(np.float32)
NARROW = {
    "flat_4x192_128_sink": (functools.partial(pa.paged_flat_decode,
                                              sink=jnp.asarray(SINK)),
                            4, 192, 128, 8),
    "latent_576": (functools.partial(pa.paged_latent_decode,
                                     scale=LATENT_SCALE, width=512),
                   1, 576, 512, 6),
    "heads_8": (pa.paged_gqa_decode, NKV, 8, 8, NH),
}
LAYOUTS.update(NARROW)


def _gathered_attention(layout, q, *pools_layer_table_lengths):
    """One query a row over the first ``lengths[row]`` positions of its
    pages, gathered in its table's order: float64 numpy, a row and a head
    at a time, nothing of the code under test."""
    *pools, layer, table, lengths = (
        np.asarray(x, np.float64 if np.ndim(x) > 2 else None)
        for x in pools_layer_table_lengths)
    _, g, dk, dv, heads = LAYOUTS[layout]
    q = np.asarray(q, np.float64)
    latent = len(pools) == 1
    scale = LATENT_SCALE if latent else dk ** -0.5
    sink = SINK if layout.endswith("sink") else None
    out = np.zeros((q.shape[0], heads, dv))
    for row in range(q.shape[0]):
        n = int(np.clip(lengths[row], 1, KMAX))
        keys = pools[0][layer][table[row]].reshape(KMAX, g, dk)[:n]
        values = keys[..., :dv] if latent \
            else pools[1][layer][table[row]].reshape(KMAX, g, dv)[:n]
        for h in range(heads):
            kv = h // (heads // g)
            scores = keys[:, kv] @ q[row, h] * scale
            m = scores.max() if sink is None else max(scores.max(), sink[h])
            e = np.exp(scores - m)
            total = e.sum() + (0 if sink is None else np.exp(sink[h] - m))
            out[row, h] = (e / total) @ values[:, kv]
    return out


# (table row, length attended): the rows of TABLE at the lengths a 4-step
# dispatch from POS / POS_END gives them, as ``decode_step`` hands
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


@functools.lru_cache(maxsize=None)
def _jitted(layout, kernel):
    """The layout's call jitted, one function for the kernel and one for
    the reference: jit's cache goes by the function and knows nothing of
    the hook a trace was made under."""
    call = LAYOUTS[layout][0]
    return jax.jit(lambda *args: call(*args))


def _against_the_gathered_view(layout, case, dtype, kernel):
    """Every row of a batch at once, this case's row among them (peers of
    other lengths before and behind it). float32 pools: the same math in
    another order; bf16: the weights are rounded to the cache's type
    before they meet the values."""
    row, length = CASES[case]
    lengths = np.array([5, 12, 2, 7], np.int32)
    lengths[row] = length
    args = (_query(dtype, 5, layout), *_pools(dtype, layout=layout),
            jnp.int32(1), jnp.asarray(TABLE), jnp.asarray(lengths))
    call = _jitted(layout, kernel)
    assert ("pallas_call" in str(jax.make_jaxpr(call)(*args))) is kernel
    got = np.asarray(call(*args), np.float64)
    want = _gathered_attention(layout, *args)
    tol = 2e-2 if dtype == "bfloat16" else 4e-6
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("layout", sorted(set(LAYOUTS) - set(NARROW)))
@pytest.mark.parametrize("impl", ["kernel", "reference"])
def test_kernel_against_the_gathered_view(impl, layout, case, dtype,
                                          request):
    """The Pallas kernel through the interpreter, and the jax.numpy
    reference the same call is where its gate does not pass (here: the
    CPU), each against the float64 gather-and-softmax."""
    if impl == "kernel":
        request.getfixturevalue("kernel_on")
    _against_the_gathered_view(layout, case, dtype, impl == "kernel")


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("layout", sorted(NARROW))
def test_reference_where_no_kernel_goes(layout, case, dtype, kernel_on):
    """A sink, an entry of no whole lane tiles, a head narrower than one:
    the gate refuses them on a backend that runs the kernels too, and the
    call is the reference."""
    _against_the_gathered_view(layout, case, dtype, False)


@pytest.mark.parametrize("layout", sorted(set(LAYOUTS) - set(NARROW)))
def test_a_row_alone_is_the_row_among_peers(layout, kernel_on):
    """Bit for bit: a row's blocks and the order its softmax is folded in
    are fixed by the program, so neither the batch's longest row nor what
    another row left in the buffers reaches its result."""
    kernel = _jitted(layout, True)
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
    """Four steps of every row, the reference behind the call then the
    kernel, float32 (the same sums in another order). The same tokens, and
    the same pools on pages 1 and up. Outside the comparison, "in no
    defined order": the null page, and the last token of the two rows
    whose fourth position is on it (row 1 at 16, the inactive row 2 at 4:
    they meet on one offset of one page, and the kernel reads the page
    after every row of the step has written it)."""
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


def _views(pool, layers=None):
    """(the shapes of a view of the layers of ``pool`` [L, NP, PS, ...],
    as gathered and as reshaped; the shapes of ONE layer's rows)."""
    entry = tuple(pool.shape[3:])
    a_layers = {(B, KMAX) + entry, (B, MP, PS) + entry,
                (B,) + entry[:-1] + (KMAX,) + entry[-1:]}
    return ({(n,) + shape for shape in a_layers
             for n in (1, pool.shape[0])}, a_layers)


@pytest.mark.parametrize("hook", [False, True], ids=["off", "on"])
def test_the_decode_op_holds_no_view(hook, monkeypatch):
    """No array [layers, rows, kmax, ...] anywhere in the op, whatever is
    behind the call. Behind the reference a layer's own rows, gathered
    where they are attended; behind the kernel not those either, the
    pools carried by its scans and never their ``xs`` or ``ys``, and one
    kernel instance a program: inside the layer scan."""
    op, ins, attrs = _decode_case("bfloat16", POS)
    stacked, a_layers = _views(ins["KPages"])
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", hook)
    jaxpr = jax.make_jaxpr(rows._jit(op, attrs))(ins)
    shapes = _all_shapes(jaxpr.jaxpr)
    assert not shapes & stacked, shapes & stacked
    assert bool(shapes & a_layers) is not hook
    streamed, carried = rows._scan_cache_use(
        jaxpr, {tuple(ins["KPages"].shape)})
    assert not streamed, streamed
    assert carried == 2         # the step scan and the layer scan
    assert str(jaxpr).count("name=paged_gqa_decode") == hook


def test_the_speculative_op_holds_no_view():
    """Nor does a speculative round: its windows and the draft's steps
    run against the pools, a layer's rows gathered where it attends
    them, and every pool is carried by the layer scans."""
    op, ins, attrs = rows._case("llama_paged_spec_step")
    jaxpr = jax.make_jaxpr(rows._jit(op, attrs))(ins)
    shapes = _all_shapes(jaxpr.jaxpr)
    for name in ("KPages", "DraftKPages"):
        stacked, a_layers = _views(ins[name])
        assert not shapes & stacked, (name, shapes & stacked)
        assert shapes & a_layers
    streamed, carried = rows._scan_cache_use(
        jaxpr, {tuple(ins[n].shape) for n in ("KPages", "DraftKPages")})
    assert not streamed, streamed
    # the draft's window and its two steps, the target's window
    assert carried == 2 + (rows.ATTRS["gamma"] - 1)


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
    the kernel: everywhere else the same step calls the reference, and
    the program says so."""
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", hook)
    for block in ("PAGED_BLOCK_KEYS", "PAGED_FLAT_BLOCK_KEYS",
                  "PAGED_LATENT_BLOCK_KEYS"):
        monkeypatch.setattr(pa, block, 8)           # two pages
    programs = {name: cfg.build_paged_programs(**GEOMETRY)
                for name, cfg in (("wide", WIDE), ("narrow", LLAMA_TINY),
                                  ("latent", LATENT_MOE_TINY),
                                  ("hybrid", HYBRID_MOE_TINY),
                                  ("hybrid_ssm", HYBRID_SSM_TINY),
                                  ("hybrid_wide", MOE_WIDE),
                                  ("hybrid_ssm_wide", SSM_WIDE))}
    built = {name: p.decode["in_place"] for name, p in programs.items()}
    assert built == {"wide": hook, "narrow": False, "latent": hook,
                     "hybrid": False, "hybrid_ssm": False,
                     "hybrid_wide": hook, "hybrid_ssm_wide": hook}
    # the report is the calls' own answer: a kernel's scope is in the
    # program's text where it says so and nowhere else
    for name, p in programs.items():
        text = program_text.lower_bundle(
            p.decode, len(p.pool_specs)).as_text(debug_info=True)
        assert bool(re.search(r"/paged_(gqa|flat|latent)_decode", text)) \
            is built[name], name
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
    "value_heads_that_divide_no_tile": (FULL_KIND,
                                        [(2, 40, 4, 384), (2, 40, 4, 96)]),
    "heads_inside_positions": (FULL_KIND, [(2, 40, 4, 2, 128)] * 2),
    "pools_of_other_pages": (FULL_KIND, [(2, 40, 4, 384), (2, 48, 4, 256)]),
}


@pytest.mark.parametrize("why", sorted(REFUSED))
def test_the_gate_answers_a_mixed_model_kind_by_kind(why, monkeypatch):
    """In place: the kind that keeps the whole sequence, attends, has no
    sink and stores flat whole-tile entries, where the backend runs the
    kernel; each other kind of the same model, and the same kind for any
    one reason, has no kernel (the reference, the view of its rings, its
    state entries)."""
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
    # value heads of HALF a tile have the packed form of the kernel (PR 58)
    assert T.decode_in_place("gqa", (FULL_KIND, window), [
        (2, 40, 4, 384), (2, 40, 4, 128)] + rings, 0)
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
    """The decode program's text, reference or kernel behind the call: no
    view of the sequence kind's layers, stacked ([layers, rows, kmax,
    width]) or a layer each with heads before positions ([rows, kv heads,
    kmax, width]: the form PR 46 took away); behind the reference a
    layer's rows as they are gathered, behind the kernel nothing with the
    rows' ``kmax`` positions and the kernel under the kind's scope; the
    window kind keeps the view of its rings in both."""
    b, kmax = GEOMETRY["max_batch"], 8 * GEOMETRY["page_size"]
    gone = [f"tensor<{b}x2x{kmax}x{w}xf32>" for w in (192, 128)] \
        + [f"tensor<{n}x{b}x{kmax}x{2 * w}xf32>" for w in (192, 128)
           for n in (1, 2)]
    gathered = [f"tensor<{b}x{kmax}x2x{w}xf32>" for w in (192, 128)]
    rings = f"tensor<3x{b}x4x{4 * 192}xf32>"
    monkeypatch.setattr(pa, "PAGED_FLAT_BLOCK_KEYS", 8)  # two pages
    for hook in (False, True):
        monkeypatch.setattr(pa, "_FORCE_INTERPRET", hook)
        progs = MOE_WIDE.build_paged_programs(**GEOMETRY)
        lowered = program_text.lower_bundle(progs.decode,
                                            len(progs.pool_specs))
        text = lowered.as_text()
        assert not [v for v in gone if v in text]
        assert [v in text for v in gathered] == [not hook] * 2
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
    monkeypatch.setattr(pa, "PAGED_FLAT_BLOCK_KEYS", 8)  # two pages
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
