"""Faults planted in the ENGINE of a model whose state layers are gated
delta-rule linear attention (models/hybrid_delta.py), each one a way in
which the rule or its cache entry could be wrong and still give fluent
logits: ISSUE 47's list. tests/test_hybrid_delta.py shows at a tiny size
that the builder's comparison (benchmark/builders/serve_delta.py
``compare_with_reference``) fails on every one of them at the probes of
its own path; the same plants, on the chip at the published sizes, gave
the readings the comparison's limits were set under (PERF.md section 4):

    chiprun --timeout 3000 -- python3 tests/hybrid_delta_faults.py [seed]

A plant is ``plant(mp, cfg) -> cfg'``: ``mp`` a ``pytest.MonkeyPatch``, the
configuration the engine is then built from returned (changed or not).
"""
import os
import sys

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":          # run as a script from a checkout
    sys.path.insert(0, ROOT)

from paddle_tpu.models.hybrid_delta import HybridDeltaConfig
from paddle_tpu.ops import delta_rule as dr
from paddle_tpu.ops import transformer_ops as T


def _beta_without_its_factor(mp, cfg):
    mp.setattr(dr, "BETA_MAX", 1.0)
    return cfg


def _no_decay(mp, cfg):
    gates = dr.gates

    def undecayed(p, ab):
        g, beta = gates(p, ab)
        return jnp.zeros_like(g), beta

    mp.setattr(dr, "gates", undecayed)
    return cfg


def _no_l2(mp, cfg):
    mp.setattr(dr, "_l2", lambda x: x)
    return cfg


def _tail_not_carried(mp, cfg):
    """A window that continues a request convolves as if it started one:
    zeros for the three inputs before it. The whole-prompt programs and
    the decode steps are left alone."""
    window = dr.window

    def forgetful(p, z, state0, tail0, lens, eps):
        return window(p, z, state0, jnp.zeros_like(tail0), lens, eps)

    mp.setattr(dr, "window", forgetful)
    return cfg


def _never_from_zeros(which):
    """The programs of one prefill path (``whole``: the whole-prompt
    programs; ``chunk``: a prompt's first chunk) read the entry where
    they should start from zeros."""
    def plant(mp, cfg):
        prefill = T._PagedRunner._state_prefill

        def stale(self, p, z, mine, lyr, pos0, spec):
            if self.fresh == (which == "whole"):
                self.fresh, pos0 = False, jnp.maximum(pos0, 1)
            return prefill(self, p, z, mine, lyr, pos0, spec)

        mp.setattr(T._PagedRunner, "_state_prefill", stale)
        return cfg
    return plant


def _state_in_bf16(mp, cfg):
    spec = HybridDeltaConfig.state_spec
    mp.setattr(HybridDeltaConfig, "state_spec", lambda self: [
        (spec(self)[0][0], "bfloat16"), spec(self)[1]])
    return cfg


def _undecayed_correction(mp, cfg):
    """``u_t = beta_t (v_t - S_{t-1}^T k_t)``: the correction reads the
    state as the last position left it, without this position's decay;
    the rest of the rule as it is. Position by position (the chunked form
    has no such term to leave out), in prefill and in decode."""
    def step(q, k, v, g, beta, state):
        u = beta[..., None] * (v - jnp.sum(state * k[..., None], axis=-2))
        state = jnp.exp(g)[..., None, None] * state \
            + k[..., None] * u[..., None, :]
        return jnp.sum(state * q[..., None], axis=-2), state

    def window(q, k, v, g, beta, state0, chunk=None):
        def body(state, xs):
            o, state = step(*xs, state)
            return state, o
        state, o = jax.lax.scan(
            body, state0, tuple(jnp.moveaxis(x, 1, 0)
                                for x in (q, k, v, g, beta)))
        return jnp.moveaxis(o, 0, 1), state

    mp.setattr(dr, "rule_step", step)
    mp.setattr(dr, "chunk_rule", window)
    return cfg


FAULTS = {
    "beta without its factor 2": _beta_without_its_factor,
    "the decay left out": _no_decay,
    "queries and keys not L2-normed": _no_l2,
    "the tail not carried across a chunk": _tail_not_carried,
    "whole-prompt programs read their entry": _never_from_zeros("whole"),
    "the first chunk reads its entry": _never_from_zeros("chunk"),
    "the state pool in bf16": _state_in_bf16,
    "alpha missing from the correction": _undecayed_correction,
}


def main(seed):
    """``controls`` with everything it prints written to chiprun_out/ too:
    the chip tool shows a call's last lines alone."""
    import contextlib
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    log = open(os.path.join(ROOT, "chiprun_out",
                            f"hybrid_delta_faults_{seed}.txt"), "w")

    class Both:
        def write(self, text):
            log.write(text)
            return sys.__stdout__.write(text)

        def flush(self):
            log.flush()
            sys.__stdout__.flush()

    with contextlib.redirect_stdout(Both()):
        controls(seed)


def controls(seed):
    """Every control at the published sizes, on the chip: the engine
    clean, the reference from float8 weights and with its stream in bf16,
    each fault planted. Prints ``serve_delta.compare_with_reference``'s
    own lines and how many findings it returned."""
    import json
    import numpy as np
    import pytest
    import paddle_tpu as fluid
    from benchmark.builders import serve_delta as sd

    print("compile cache", fluid.enable_compile_cache(), flush=True)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "olmo-hybrid-7b.json")) as f:
        config = json.load(f)
    config["builder"]["engine"]["n_pages"] = 513   # the probes' pages
    system = sd.ServeDeltaSystem(config, seed)
    system.engine.close()
    scope = system.scope

    def engine_of(cfg):
        return sd.HandleKeepingEngine(
            cfg, scope=scope, auto_start=False,
            config=sd.DecodeConfig(**config["builder"]["engine"]))

    print("== the engine as it is", flush=True)
    print(len(sd.compare_with_reference(system, seed)), "findings",
          flush=True)
    print("== the reference from float8 (e4m3) weights, and with its "
          "stream in bf16, against itself", flush=True)
    for prompt in sd.probe_prompts(system, seed):
        tail = np.random.RandomState(0).randint(
            0, system.cfg.vocab_size, sd.PROBE_STEPS)
        sequence = np.concatenate([prompt, tail])
        positions = prompt.size - 1 + np.arange(1 + sd.PROBE_STEPS)
        want, want_state = sd.reference_logits(system, sequence, positions)
        got, state = sd.reference_logits(system, sequence, positions,
                                         through=jnp.float8_e4m3fn)
        err = float(sd.rel_l2(state.reshape(-1), want_state.reshape(-1)))
        print(f"probe of {prompt.size}: rel_l2 "
              f"{np.round(sd.rel_l2(got, want), 4).tolist()} state "
              f"{err:.5f}", flush=True)
        # and from the published weights with nothing but its residual
        # stream rounded to bf16 behind every layer: what the model makes
        # of the engine's smallest rounding
        config["_stream_dtype"] = "bfloat16"
        got, _ = sd.reference_logits(system, sequence, positions)
        del config["_stream_dtype"]
        print(f"probe of {prompt.size}, the stream in bf16: rel_l2 "
              f"{np.round(sd.rel_l2(got, want), 4).tolist()}", flush=True)
    for name, plant in FAULTS.items():
        print("== " + name, flush=True)
        with pytest.MonkeyPatch.context() as mp:
            system.cfg = plant(mp, system.cfg)
            system.engine = engine_of(system.cfg)
            # the entries and pages hold a request, as after a window
            sd.engine_logits(system.engine, np.arange(3000) % 1000, 4)
            print(len(sd.compare_with_reference(system, seed)),
                  "findings", flush=True)


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 2147483777)
