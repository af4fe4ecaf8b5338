"""An engine's prefill programs in both forms of their attention fold.

The four models whose prefill folds a row's pages a block of keys at a
time (latent attention under hyper-connections and as a share, full and
window layers, attention beside state-space layers) at their tiny sizes
with the heads WIDENED TO WHOLE LANE TILES, which is where the kernel
``prefill_fold`` is admitted (the tiny configurations themselves are
narrower: there the gate keeps the jax.numpy fold, on the chip too).
test_prefill_forms.py runs each model's builder's probe (``engine_logits``:
a prompt through the whole-prompt program, one through three chunks, then
decoded positions, as the chip comparison drives the engine's own
programs) with the fold in jax.numpy and then through the kernel in the
Pallas interpreter: the same tokens and picks, the same logits and pools
to the order of the sums.
"""
import dataclasses

import numpy as np

import paddle_tpu as fluid
from benchmark.builders import serve_hybrid, serve_ssm
from benchmark.builders.serve_blocks import make_weights
from paddle_tpu.models.hybrid_moe import HYBRID_MOE_TINY
from paddle_tpu.models.hybrid_ssm import HYBRID_SSM_TINY
from paddle_tpu.models.latent_moe import (LATENT_MOE_TINY,
                                          LATENT_SHARE_TINY)
from paddle_tpu.ops import pallas_attention as pa
from paddle_tpu.serving import DecodeConfig, DecodeEngine

import decode_forms

# xing4's and DeepSeek-V3's head (128 | 64 rotated | 128), MiMo's (keys 192
# beside values 128, 64 of them rotated; two key/value heads here) and
# Jamba's (one head of 128)
LATENT_WIDE = dataclasses.replace(LATENT_MOE_TINY, name="latent-moe-wide",
                                  nope_dim=128, rope_dim=64, v_dim=128)
SHARE_WIDE = dataclasses.replace(LATENT_SHARE_TINY, name="latent-share-wide",
                                 nope_dim=128, rope_dim=64, v_dim=128)
MOE_WIDE = dataclasses.replace(HYBRID_MOE_TINY, name="hybrid-moe-wide",
                               head_dim=192, v_head_dim=128, rotary_dim=64)
SSM_WIDE = dataclasses.replace(HYBRID_SSM_TINY, name="hybrid-ssm-wide",
                               head_dim=128)

ENGINE = dict(max_batch=3, prompt_buckets=(8, 16, 48), max_new_tokens=8,
              page_size=4, decode_block=2, chunk_size=16, prefill_batch=1,
              default_timeout_s=120.0)


def kernel_on(monkeypatch):
    """The Pallas kernels through the interpreter, ``prefill_fold`` in
    tiles of 8 queries by 8 keys and 16 keys a visit: a window of 16 is two
    query tiles, a row of 48 positions three visits of two key tiles. The
    hook takes the decode steps through their paged kernels too: blocks of
    two of ``ENGINE``'s pages, as decode_forms.py has them."""
    decode_forms.kernel_on(monkeypatch, ENGINE["page_size"])
    monkeypatch.setattr(pa, "PREFILL_BLOCK_Q", 8)
    monkeypatch.setattr(pa, "PREFILL_BLOCK_KEYS", 8)
    monkeypatch.setattr(pa, "PREFILL_VISIT_KEYS", 16)


def scope_of(cfg):
    """The builders' weights, every matrix ten times as large so that a
    layer moves the residual stream, and the stand-ins of what a draw of
    normal(0, 0.02) would make invisible."""
    w = {k: v if k.endswith("norm") else v * 10
         for k, v in make_weights(cfg, 3).items()}
    if cfg is SSM_WIDE:
        w.update(serve_ssm.stand_ins(cfg, w))
    elif cfg is MOE_WIDE:
        w.update(serve_hybrid.stand_ins(cfg, cfg.param_shapes()))
    scope = fluid.Scope()
    for name, value in w.items():
        scope.set(name, value)
    return scope


def dispatches(cfg, scope, probe):
    """A prompt through the whole-prompt program and one through three
    chunks, then 6 decoded positions each: (every array the probe gives,
    the pools, whether each prefill program says it attends through the
    kernel)."""
    engine = DecodeEngine(cfg, scope=scope, place=fluid.CPUPlace(),
                          config=DecodeConfig(**ENGINE), auto_start=False)
    rng = np.random.RandomState(1)
    out = [np.asarray(x) for n in (11, 39)
           for x in probe(engine, rng.randint(0, cfg.vocab_size, n), 6)]
    bundles = list(engine.programs.prefill.values()) \
        + [engine.programs.chunk]
    return (out, [np.asarray(p) for p in engine._pools],
            {b["attn_in_kernel"] for b in bundles})


def check_both_forms(cfg, probe, monkeypatch, tol=1e-4):
    """The probe's dispatches with the fold in jax.numpy, then through the
    kernel (float32: the same sums in another order): the same whole
    numbers (tokens, picks), the same logits within the forms' rounding,
    and the same pools of every kind on every page and entry but the null
    ones."""
    scope = scope_of(cfg)
    want, want_pools, said = dispatches(cfg, scope, probe)
    assert said == {False}
    with monkeypatch.context() as m:
        kernel_on(m)
        got, got_pools, said = dispatches(cfg, scope, probe)
    assert said == {True}
    for a, b in zip(got, want):
        if np.issubdtype(b.dtype, np.integer):
            assert np.array_equal(a, b)
        else:
            err = np.linalg.norm(a - b, axis=-1) \
                / np.linalg.norm(b, axis=-1)
            assert err.max() < tol, err.max()
    for a, b in zip(got_pools, want_pools):
        np.testing.assert_allclose(a[:, 1:], b[:, 1:], rtol=tol, atol=tol)
        assert np.abs(b[:, 1:]).max() > 0
