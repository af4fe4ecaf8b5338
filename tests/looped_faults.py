"""Faults planted in the ENGINE of a model whose stack is run several times
a token (models/looped.py), each one a way in which the loop could be wrong
and still give fluent logits: ISSUE 43's list. tests/test_looped.py shows
at a tiny size that the builder's comparison (benchmark/builders/
serve_loop.py ``compare_with_reference``) fails on every one of them; the
same plants, on the chip at the published sizes, gave the readings the
comparison's limit was set under (PERF.md section 4).

A plant is ``plant(mp, cfg) -> cfg'``: ``mp`` a ``pytest.MonkeyPatch``, the
configuration the engine is then built from returned (changed or not).
"""
import dataclasses

import jax.numpy as jnp

from paddle_tpu.ops import transformer_ops as T


def _cache_layer_is(of):
    """Every form's ``attend_write`` handed cache layer ``of(layer, n)``."""
    def plant(mp, cfg):
        inner, n = T._PagedRunner._stack_forward, cfg.n_layers

        def faulty(self, h, pools, q_pos, attend_write):
            def wrong(p, q, entries, pools, lyr, kind=None):
                return attend_write(p, q, entries, pools, of(lyr, n), kind)
            return inner(self, h, pools, q_pos, wrong)

        mp.setattr(T._PagedRunner, "_stack_forward", faulty)
        return cfg
    return plant


def _reads_the_pass_before(mp, cfg):
    """A decode step of pass ``s`` writes its own cache layers and ATTENDS
    pass ``s - 1``'s (the step's paged attention call is handed ``layer -
    layers``); prefill is left alone."""
    kernel, n = T.paged_gqa_decode, cfg.n_layers

    def wrong(q, k_pool, v_pool, lyr, table, lens):
        return kernel(q, k_pool, v_pool, jnp.where(lyr >= n, lyr - n, lyr),
                      table, lens)

    mp.setattr(T, "paged_gqa_decode", wrong)
    return cfg


def _norm_after_the_last_pass_alone(mp, cfg):
    """The final norm where every other model has it: once, before the
    head, and not between the passes."""
    def close(self, h):
        return self._exit_gate(h)

    def logits_of(self, hl):
        return (T.rms_normalize(hl, self.fnorm, self.eps)
                @ self.head).astype(jnp.float32)

    mp.setattr(T._PagedRunner, "_close_pass", close)
    mp.setattr(T._PagedRunner, "logits_of", logits_of)
    return cfg


FAULTS = {
    # in the decode step's paged attention call, kernel or reference
    "a pass reads the pass before's cache": _reads_the_pass_before,
    "one cache shared by all passes": _cache_layer_is(lambda lyr, n: lyr % n),
    "the final norm after the last pass alone":
        _norm_after_the_last_pass_alone,
    "the post-norms left out":
        lambda mp, cfg: dataclasses.replace(cfg, post_norm=False),
    "a pass fewer":
        lambda mp, cfg: dataclasses.replace(cfg, passes=cfg.passes - 1),
}
