"""The plain reference of the Llama-family block against the program, at a
tiny size on the CPU, on seeded random weights."""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.models.llama import LLAMA_TINY, build_llama

from benchmark.reference import llama_family as ref

MODEL = dict(n_layers=LLAMA_TINY.n_layers, n_heads=LLAMA_TINY.n_heads,
             n_kv_heads=LLAMA_TINY.n_kv_heads,
             rope_base=LLAMA_TINY.rope_base, norm_eps=LLAMA_TINY.norm_eps)


@pytest.fixture(scope="module")
def program_and_reference():
    main_p, startup_p = fluid.Program(), fluid.Program()
    main_p.random_seed = startup_p.random_seed = 11
    with fluid.program_guard(main_p, startup_p):
        tokens = fluid.layers.data(name="tokens", shape=[-1, 16],
                                   dtype="int64", append_batch_size=False)
        targets = fluid.layers.data(name="targets", shape=[-1, 16],
                                    dtype="int64", append_batch_size=False)
        logits, loss = build_llama(LLAMA_TINY, tokens, targets)
    toks = np.random.RandomState(0).randint(0, LLAMA_TINY.vocab_size,
                                            (2, 16))
    tgts = np.roll(toks, -1, 1)
    scope = fluid.Scope()
    exe = fluid.Executor()
    with fluid.scope_guard(scope):
        exe.run(startup_p)
        got = exe.run(main_p, feed={"tokens": toks, "targets": tgts},
                      fetch_list=[logits, loss])
    weights = {n: np.asarray(v) for n, v in scope.vars.items()
               if v is not None}
    return got, weights, toks, tgts


def test_logits_agree_with_the_plain_reference(program_and_reference):
    (logits, _), weights, toks, _ = program_and_reference
    want = np.asarray(ref.forward(weights, toks, **MODEL))
    # float32 on both sides, the same products in another order: a few
    # ulps of the largest logit (0.7). A wrong rotary pairing, mask or
    # head grouping moves logits by tenths.
    assert np.max(np.abs(want - logits)) < 5e-6


def test_loss_agrees_and_starts_at_ln_vocabulary(program_and_reference):
    (_, loss), weights, toks, tgts = program_and_reference
    want = float(ref.next_token_loss(weights, toks, tgts, **MODEL))
    assert float(np.asarray(loss).reshape(())) == pytest.approx(want,
                                                                abs=1e-5)
    # normal(0, 0.02) weights: near-uniform logits, as the training
    # cell's first-loss check assumes
    assert abs(want - np.log(LLAMA_TINY.vocab_size)) < 0.1


def test_reference_is_causal(program_and_reference):
    _, weights, toks, _ = program_and_reference
    a = np.asarray(ref.forward(weights, toks, **MODEL))
    changed = toks.copy()
    changed[:, -1] = (changed[:, -1] + 1) % LLAMA_TINY.vocab_size
    b = np.asarray(ref.forward(weights, changed, **MODEL))
    assert np.array_equal(a[:, :-1], b[:, :-1])
    assert not np.allclose(a[:, -1], b[:, -1])
