"""Flash attention + ring attention numerics on the virtual CPU mesh."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas_attention import (flash_attention,
                                             _ref_attention_lse,
                                             attention_with_lse)
from paddle_tpu.parallel import make_mesh
from paddle_tpu.parallel.ring_attention import ring_attention_sharded


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.RandomState(0)
    shape = (2, 2, 64, 16)
    return tuple(jnp.asarray(rng.randn(*shape), jnp.float32)
                 for _ in range(3))


def test_flash_matches_reference(qkv):
    q, k, v = qkv
    for causal in (False, True):
        o = flash_attention(q, k, v, causal, None)
        ref, _ = _ref_attention_lse(q, k, v, 1.0 / 4.0, causal)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)


def test_flash_gradients(qkv):
    q, k, v = qkv

    def f(q, k, v):
        return flash_attention(q, k, v, True, None).sum()

    g1 = jax.grad(f)(q, k, v)

    def ref(q, k, v):
        return _ref_attention_lse(q, k, v, 1.0 / 4.0, True)[0].sum()

    g2 = jax.grad(ref)(q, k, v)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-4, atol=1e-5)


def test_lse_merge_consistency(qkv):
    """Splitting keys in two and lse-merging must equal full attention."""
    from paddle_tpu.parallel.ring_attention import _merge
    q, k, v = qkv
    full, _ = attention_with_lse(q, k, v, causal=False)
    o1, l1 = attention_with_lse(q, k[:, :, :32], v[:, :, :32], causal=False)
    o2, l2 = attention_with_lse(q, k[:, :, 32:], v[:, :, 32:], causal=False)
    merged, _ = _merge(o1, l1, o2, l2)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(full),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_8way(qkv, causal):
    q, k, v = qkv
    mesh = make_mesh({"sp": 8})
    out = ring_attention_sharded(q, k, v, mesh, axis="sp", causal=causal)
    ref, _ = _ref_attention_lse(q, k, v, 1.0 / 4.0, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_ring_attention_long_context_trains():
    """Long-context smoke at a realistic ratio: seq 2048 over sp=8
    (256 tokens/device), causal, THROUGH the flagship program — the
    mha op dispatches to ring attention and gradients flow (the
    long-context path trains, not just computes)."""
    import paddle_tpu as fluid
    from paddle_tpu.models.llama import LlamaConfig, build_llama

    cfg = LlamaConfig(vocab_size=128, dim=32, n_layers=1, n_heads=2,
                      n_kv_heads=2, ffn_hidden=64, dtype="float32")
    seq = 2048
    tokens = fluid.layers.data(name="tokens", shape=[-1, seq],
                               dtype="int64", append_batch_size=False)
    targets = fluid.layers.data(name="targets", shape=[-1, seq],
                                dtype="int64", append_batch_size=False)
    _, loss = build_llama(cfg, tokens, targets, shard_sp=True)
    fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    pe = fluid.ParallelExecutor(loss_name=loss.name,
                                mesh=make_mesh({"sp": 8}))
    rng = np.random.RandomState(0)
    toks = rng.randint(0, 128, (2, seq)).astype(np.int64)
    losses = []
    for _ in range(3):
        out = pe.run(feed={"tokens": toks,
                           "targets": np.roll(toks, -1, 1)},
                     fetch_list=[loss.name])
        losses.append(float(np.asarray(out[0]).reshape(())))
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses     # same batch → must drop


def test_ring_matches_flash_long_seq():
    """Numeric parity flash vs ring at seq 1024 (128 tokens/device)."""
    rng = np.random.RandomState(1)
    q, k, v = (jnp.asarray(rng.randn(1, 2, 1024, 16), jnp.float32) * 0.3
               for _ in range(3))
    mesh = make_mesh({"sp": 8})
    out = ring_attention_sharded(q, k, v, mesh, axis="sp", causal=True)
    ref = flash_attention(q, k, v, True, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_pallas_kernels_interpret_match_reference():
    """Exercise the REAL pallas forward+backward kernels through the
    interpreter on CPU (round 3: the backward kernel replaced the naive
    jax.vjp fallback that materialized [B,H,T,T] scores)."""
    import paddle_tpu.ops.pallas_attention as pa
    rng = np.random.RandomState(3)
    shape = (1, 2, 256, 128)            # t, d satisfy the kernel gates
    q, k, v = (jnp.asarray(rng.randn(*shape) * 0.5, jnp.float32)
               for _ in range(3))
    sc = 1.0 / np.sqrt(128)
    pa._FORCE_INTERPRET = True
    try:
        for causal in (False, True):
            o = pa.flash_attention(q, k, v, causal, None)
            ref, _ = pa._ref_attention_lse(q, k, v, sc, causal)
            np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                                       rtol=2e-4, atol=2e-5)

            def f(q, k, v, c=causal):
                return (pa.flash_attention(q, k, v, c, None)
                        * jnp.arange(128)).sum()

            def g(q, k, v, c=causal):
                return (pa._ref_attention_lse(q, k, v, sc, c)[0]
                        * jnp.arange(128)).sum()

            got = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
            want = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
            for a, b, name in zip(got, want, "qkv"):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4,
                    err_msg=f"d{name} causal={causal}")
    finally:
        pa._FORCE_INTERPRET = False


def test_kernels_under_a_mesh_are_mapped_by_hand():
    """A Mosaic call cannot be partitioned by GSPMD (JAX refuses to
    lower it), so under a mesh attention_core maps the flash kernels
    over dp/tp with shard_map. Through the interpreter, on a dp2 x tp2
    mesh with sharded inputs: same output and dq as with no mesh, and
    the kernel gate that a ragged last block used to slip past."""
    import paddle_tpu.ops.pallas_attention as pa
    from paddle_tpu.ops.transformer_ops import attention_core
    from paddle_tpu.parallel.mesh import mesh_scope
    rng = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rng.randn(2, 128, 2, 128) * 0.5, jnp.float32)
               for _ in range(3))                       # [B, T, H, D]
    assert pa._kernel_shapes_ok(jnp.zeros((1, 1, 256, 128)),
                                jnp.zeros((1, 1, 256, 128)))
    assert not pa._kernel_shapes_ok(jnp.zeros((1, 1, 192, 128)),
                                    jnp.zeros((1, 1, 192, 128)))

    def out_and_dq(q, k, v):
        return jax.value_and_grad(
            lambda q: attention_core(q, k, v).sum() * 0.01)(q)

    mesh = make_mesh({"dp": 2, "tp": 2})
    pa._FORCE_INTERPRET = True
    try:
        want = out_and_dq(q, k, v)
        with mesh_scope(mesh):
            sh = mesh.sharding("dp", None, "tp", None)
            got = jax.jit(out_and_dq)(*(jax.device_put(a, sh)
                                        for a in (q, k, v)))
    finally:
        pa._FORCE_INTERPRET = False
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
