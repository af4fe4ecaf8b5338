"""Prefill attention keeps its scores on the chip.

On a backend that runs the Pallas kernels (the chip; here the hook
``pallas_attention._FORCE_INTERPRET``) the two folds of a block-kind
model's prefill programs, ``_PagedRunner._latent_expanded`` and
``_gqa_blocked``, hand each block of a row's keys to the kernel
``prefill_fold``, which keeps the [queries, keys] scores, their exponents
and the weights in VMEM (PERF.md section 6, PR 44). Everywhere else the
folds are plain jax.numpy, and that form is the kernel's reference. Held
here:

- the kernel against the fold it replaces, a case a shape the callers
  have: a window that starts at ``pos0 > 0`` with more keys than queries,
  fewer blocks seen than given, rows of unequal length, keys 192 beside
  values 128, groups of 1, 16 and 20 over one head, a block that one row
  sees nothing of, a sink, latent attention's shared rotated part, bf16;
- the gate, one reason to refuse at a time, and what a program's bundle
  says of itself;
- the engine's ``prefill_attn_in_kernel_total``.

The engines of the four models that reach the folds run their builders'
probes in both forms in test_prefill_forms.py (``prefill_forms.py``).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.models.hybrid_moe import HYBRID_MOE_TINY
from paddle_tpu.models.hybrid_ssm import HYBRID_SSM_TINY
from paddle_tpu.models.latent_moe import LATENT_MOE_TINY
from paddle_tpu.models.llama import LLAMA_TINY
from paddle_tpu.ops import pallas_attention as pa
from paddle_tpu.ops import transformer_ops as T
from paddle_tpu.serving import DecodeConfig, DecodeEngine

import prefill_forms
from prefill_forms import LATENT_WIDE, MOE_WIDE, SSM_WIDE

KB = 16                 # keys a visit: two tiles of 8


@pytest.fixture
def kernel_on(monkeypatch):
    """The kernel through the interpreter, tiles of 8 queries by 8 keys."""
    prefill_forms.kernel_on(monkeypatch)


def _runner(n_heads, n_kv, **kinds):
    return T._PagedRunner(
        {}, None, None, None, n_heads=n_heads, n_kv=n_kv, base=1e4,
        eps=1e-6, page_size=4,
        kinds=T.BlockKinds(n_heads=n_heads, n_kv=n_kv, **kinds))


def _blocks_of(*arrays):
    """``read_block`` over dense [B, K, ...] arrays, KB positions a block."""
    def read_block(i):
        out = tuple(jax.lax.dynamic_slice_in_dim(a, i * KB, KB, axis=1)
                    for a in arrays)
        return out if len(out) > 1 else out[0]
    return read_block


# case -> (heads, kv heads, key width, value width, the rows' pos0, window,
# blocks given, dtype, sink)
GQA = {
    "a_window_behind_its_keys": (4, 4, 128, 128, [37], 16, 4, "f32", False),
    "fewer_blocks_seen_than_given": (4, 4, 128, 128, [3], 16, 4, "f32",
                                     False),
    "rows_of_unequal_length": (4, 2, 128, 128, [37, 0, 18], 16, 4, "f32",
                               False),
    "keys_192_values_128": (4, 2, 192, 128, [20], 16, 3, "f32", False),
    "groups_of_16": (32, 2, 192, 128, [9], 8, 2, "f32", False),
    "20_heads_over_one": (20, 1, 128, 128, [21], 16, 3, "f32", False),
    "a_block_one_row_sees_nothing_of": (4, 4, 128, 128, [40, 2], 8, 3,
                                        "f32", False),
    "a_sink": (4, 2, 192, 128, [20, 5], 16, 3, "f32", True),
    "bf16": (4, 2, 192, 128, [20, 5], 16, 3, "bf16", False),
}


@pytest.mark.parametrize("case", sorted(GQA))
def test_the_kernel_is_the_gqa_fold(case, kernel_on):
    """``_gqa_blocked`` with a visit in the kernel against itself in
    jax.numpy: the same attention, to the order of the sums."""
    n_heads, n_kv, dk, dv, pos0, t, n_blocks, dt, sink = GQA[case]
    dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16}[dt]
    b = len(pos0)
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 4)
    q = jax.random.normal(keys[0], (b, t, n_heads, dk)).astype(dtype)
    k = jax.random.normal(keys[1], (b, n_blocks * KB, n_kv, dk)) \
        .astype(dtype)
    v = jax.random.normal(keys[2], (b, n_blocks * KB, n_kv, dv)) \
        .astype(dtype)
    sk = jax.random.normal(keys[3], (n_heads,)) if sink else None
    q_pos = jnp.asarray(pos0, jnp.int32)[:, None] \
        + jnp.arange(t, dtype=jnp.int32)[None]
    run = _runner(n_heads, n_kv, key_dim=dk)
    want, got = (np.asarray(jax.jit(
        lambda q, k, v, form=form: run._gqa_blocked(
            q, _blocks_of(k, v), n_blocks, KB, q_pos, sk, form))(q, k, v),
        np.float32) for form in (False, True))
    assert np.abs(want).max() > 0.1
    tol = 2e-5 if dt == "f32" else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


LATENT = {
    "heads_of_128_64_128": (4, 128, 64, 128, [37, 2], 16, 4, "f32"),
    "a_rotated_part_of_whole_tiles": (2, 128, 128, 256, [11], 8, 2, "f32"),
    "bf16_heads": (4, 128, 64, 128, [20], 16, 3, "bf16"),
}


@pytest.mark.parametrize("case", sorted(LATENT))
def test_the_kernel_is_the_expanded_latent_fold(case, kernel_on):
    """``_latent_expanded`` likewise: a head's own key part in one
    product, the rotated part all heads share in a second."""
    n_heads, nope, rope, vd, pos0, t, n_blocks, dt = LATENT[case]
    dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16}[dt]
    b, rank = len(pos0), 16
    keys = jax.random.split(jax.random.PRNGKey(len(case)), 4)
    q = (jax.random.normal(keys[0], (b, t, n_heads, nope)).astype(dtype),
         jax.random.normal(keys[1], (b, t, n_heads, rope)).astype(dtype))
    cache = jax.random.normal(      # an entry stored at whole lane tiles
        keys[2], (b, n_blocks * KB, T.whole_tiles(rank + rope))) \
        .astype(dtype)
    p = {"Wkvb": (0.3 * jax.random.normal(
        keys[3], (rank, n_heads * (nope + vd)))).astype(dtype)}
    q_pos = jnp.asarray(pos0, jnp.int32)[:, None] \
        + jnp.arange(t, dtype=jnp.int32)[None]
    run = _runner(n_heads, n_heads, attention="latent", kv_rank=rank,
                  rope_dim=rope, nope_dim=nope, v_dim=vd,
                  softmax_scale=(nope + rope) ** -0.5)
    want, got = (np.asarray(jax.jit(
        lambda q, cache, form=form: run._latent_expanded(
            p, q, _blocks_of(cache), n_blocks, KB, q_pos, form))(q, cache),
        np.float32) for form in (False, True))
    assert np.abs(want).max() > 0.1
    tol = 2e-5 if dt == "f32" else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


def test_a_tile_is_a_divisor_no_smaller_than_a_lane_tile():
    assert [pa._tile(n, 512) for n in (8, 256, 512, 2048, 768, 1152, 1000)] \
        == [8, 256, 512, 512, 256, 128, None]
    assert [pa._tile(n, 8) for n in (4, 16, 48, 12)] == [4, 8, 8, None]


FULL = {"name": "full", "n_kv": 2, "base": 1e4, "window": None,
        "sink": False, "stack": "Full", "pools": [0, 1]}
WINDOW = dict(FULL, name="window", window=4, sink=True, n_kv=4,
              stack="Window", pools=[2, 3])
FLAT = [(2, 40, 4, 384), (2, 40, 4, 256)]
RINGS = [(3, 7, 4, 768), (3, 7, 4, 512)]
REFUSED = {
    "a_window": (dict(FULL, window=4), FLAT),
    "a_state_space_mixer": (dict(FULL, mixer="ssm"), FLAT),
    "value_heads_of_half_a_tile": (FULL, [(2, 40, 4, 384), (2, 40, 4, 128)]),
    "heads_inside_positions": (FULL, [(2, 40, 4, 2, 128)] * 2),
}


@pytest.mark.parametrize("why", sorted(REFUSED))
def test_the_gate_answers_kind_by_kind(why, monkeypatch):
    """Through the kernel: latent attention with heads of whole lane
    tiles, and a mixed model's kind that keeps the whole sequence,
    attends and stores flat entries with whole-tile value heads (a sink
    is no reason to refuse: it is folded in after the blocks), where the
    backend runs the kernel and the window cuts into tiles; nothing
    else."""
    def asked(attention, kinds, pools, t=16, kind=None, widths=(128, 128)):
        return T.prefill_in_kernel(attention, kinds, widths, pools, t, 12,
                                   None, kind)

    kinds, pools = (FULL, WINDOW), FLAT + RINGS
    latent = [(3, 40, 4, 128)]
    assert not asked("gqa", kinds, pools)               # no Pallas
    assert not asked("latent", None, latent)
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", True)
    assert asked("latent", None, latent)
    assert not asked("latent", None, latent, widths=(8, 8))
    assert not asked("latent", None, latent, widths=(128, 192))
    assert not asked("gqa", None, [(2, 40, 4, 2, 128)] * 2)   # plain GQA
    assert asked("gqa", kinds, pools)
    assert [asked("gqa", kinds, pools, kind=i) for i in (0, 1)] \
        == [True, False]
    assert asked("gqa", (dict(FULL, sink=True), WINDOW), pools, kind=0)
    spec, mine = REFUSED[why]
    assert not asked("gqa", (spec, WINDOW), mine + RINGS)
    assert not asked("gqa", (spec, WINDOW), mine + RINGS, kind=0)
    # a window that does not cut into tiles
    monkeypatch.setattr(pa, "PREFILL_BLOCK_Q", 8)
    assert asked("gqa", kinds, pools, t=48)
    assert not asked("gqa", kinds, pools, t=12)


GEOMETRY = dict(max_batch=3, page_size=4, n_pages=40, pages_per_seq=12,
                prompt_buckets=(8, 16, 48), decode_block=2, chunk_size=16)


@pytest.mark.parametrize("hook", [False, True], ids=["off", "on"])
def test_a_bundle_says_how_its_program_attends(hook, monkeypatch):
    """``attn_in_kernel`` of every prefill and chunk bundle: what the op
    decides where it lowers, asked where the program is built."""
    monkeypatch.setattr(pa, "_FORCE_INTERPRET", hook)
    for cfg, want in ((LATENT_WIDE, hook), (MOE_WIDE, hook),
                      (SSM_WIDE, hook), (LATENT_MOE_TINY, False),
                      (HYBRID_MOE_TINY, False), (HYBRID_SSM_TINY, False)):
        progs = cfg.build_paged_programs(**GEOMETRY)
        said = [b["attn_in_kernel"] for b in progs.prefill.values()] \
            + [progs.chunk["attn_in_kernel"]]
        assert said == [want] * len(said), cfg.name
    llama = LLAMA_TINY.build_paged_programs(
        **dict(GEOMETRY, chunk_size=None))
    assert not any("attn_in_kernel" in b for b in llama.prefill.values())


@pytest.mark.serving
@pytest.mark.parametrize("hook", [False, True], ids=["off", "on"])
def test_the_engine_counts_its_prefills_through_the_kernel(hook,
                                                          monkeypatch):
    """``prefill_attn_in_kernel_total`` equals the whole-prompt plus the
    chunk dispatches of an engine built where the kernel runs, and stays 0
    where it does not (every CPU)."""
    if hook:
        prefill_forms.kernel_on(monkeypatch)
    engine = DecodeEngine(
        SSM_WIDE, scope=prefill_forms.scope_of(SSM_WIDE),
        place=fluid.CPUPlace(),
        config=DecodeConfig(max_batch=3, prompt_buckets=(8, 48),
                            max_new_tokens=4, page_size=4, decode_block=2,
                            chunk_size=16, prefill_batch=1,
                            default_timeout_s=120.0))
    try:
        engine.warmup()
        rng = np.random.RandomState(3)
        requests = [engine.submit(
            rng.randint(0, SSM_WIDE.vocab_size, (n,)).astype(np.int64),
            max_new=4, timeout=120) for n in (5, 39, 7)]
        assert all(len(r.result(120)) == 4 for r in requests)
        stats = engine.stats()
    finally:
        engine.close()
    dispatches = stats["prefill_dispatch_total"] \
        + stats["chunk_prefill_total"]
    assert stats["prefill_dispatch_total"] == 2
    assert stats["chunk_prefill_total"] == 3
    assert stats["prefill_attn_in_kernel_total"] \
        == (dispatches if hook else 0)
