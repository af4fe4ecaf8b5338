"""Speculative greedy decoding (llama_spec_generate): the output must
be EXACTLY the target-only greedy tokens — acceptance only changes how
many target forwards it takes, never what comes out. Verified with a
perfect draft (copied target weights, 100% acceptance), an unrelated
random draft (low acceptance), batch>1 (lockstep-min path), and the
gamma-overshoot / single-token edges.
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.models.llama import (LlamaConfig, build_llama_generator,
                                     build_llama_spec_generator)

TARGET = LlamaConfig(vocab_size=97, dim=32, n_layers=3, n_heads=4,
                     n_kv_heads=2, ffn_hidden=64, dtype="float32")
DRAFT = LlamaConfig(vocab_size=97, dim=16, n_layers=1, n_heads=2,
                    n_kv_heads=1, ffn_hidden=32, dtype="float32")
PROMPT = 7


def _programs(max_new, gamma, draft_cfg=DRAFT):
    spec_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(spec_p, startup):
        ptok = fluid.layers.data(name="ptok", shape=[-1, PROMPT],
                                 dtype="int64", append_batch_size=False)
        spec_out = build_llama_spec_generator(TARGET, draft_cfg, ptok,
                                              max_new_tokens=max_new,
                                              gamma=gamma)
    gen_p = fluid.Program()
    with fluid.program_guard(gen_p, fluid.Program()):
        gtok = fluid.layers.data(name="gtok", shape=[-1, PROMPT],
                                 dtype="int64", append_batch_size=False)
        gen_out = build_llama_generator(TARGET, gtok,
                                        max_new_tokens=max_new)
    return spec_p, startup, spec_out, gen_p, gen_out


def _copy_draft_weights(scope):
    """Copy the target's trained tensors under the draft.* names —
    the 'perfect draft' arrangement (the slot list lives in
    models/llama.py next to the generator that defines it)."""
    from paddle_tpu.models.llama import copy_weights_as_draft
    copy_weights_as_draft(scope)


def _run_both(max_new, gamma, batch=3, copy_draft=False,
              draft_cfg=DRAFT, seed=0):
    spec_p, startup, spec_out, gen_p, gen_out = _programs(
        max_new, gamma, draft_cfg)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(seed)
    prompt = rng.randint(0, TARGET.vocab_size,
                         (batch, PROMPT)).astype(np.int64)
    with fluid.scope_guard(scope):
        # spec startup initializes BOTH models; the target-only
        # program then runs against the same scope (same param names),
        # so both programs decode from identical target weights
        exe.run(startup)
        if copy_draft:
            _copy_draft_weights(scope)
        want = np.asarray(exe.run(gen_p, feed={"gtok": prompt},
                                  fetch_list=[gen_out],
                                  mode="test")[0])
        got = np.asarray(exe.run(spec_p, feed={"ptok": prompt},
                                 fetch_list=[spec_out],
                                 mode="test")[0])
    return prompt, want, got


def test_spec_decode_random_draft_exact():
    """An unrelated tiny draft (low acceptance) must still reproduce
    target greedy exactly — every emitted token is a target argmax."""
    prompt, want, got = _run_both(max_new=11, gamma=3)
    np.testing.assert_array_equal(got[:, :PROMPT], prompt)
    np.testing.assert_array_equal(got, want)


def test_spec_decode_perfect_draft_exact():
    """Draft == target (weights copied): 100% acceptance path."""
    _, want, got = _run_both(max_new=9, gamma=3, copy_draft=True,
                             draft_cfg=TARGET)
    np.testing.assert_array_equal(got, want)


@pytest.mark.slow      # ~18s: edge-gamma compiles; exactness pinned
def test_spec_decode_gamma_overshoot_and_single_token():   # by the fast tests too
    """gamma larger than max_new (the final round overshoots the
    budget) and the max_new=1 edge (prefill only, loop never runs)."""
    _, want, got = _run_both(max_new=3, gamma=6)
    np.testing.assert_array_equal(got, want)
    _, want1, got1 = _run_both(max_new=1, gamma=4)
    np.testing.assert_array_equal(got1, want1)


def test_spec_decode_batch_lockstep():
    """Rows with different acceptance lengths stay exact under the
    lockstep-min rule (larger batch, more rounds)."""
    _, want, got = _run_both(max_new=14, gamma=2, batch=5, seed=3)
    np.testing.assert_array_equal(got, want)


def test_spec_decode_guards():
    import pytest
    with pytest.raises(ValueError, match="share a vocab"):
        bad = LlamaConfig(vocab_size=64, dim=16, n_layers=1, n_heads=2,
                          n_kv_heads=1, ffn_hidden=32, dtype="float32")
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            ptok = fluid.layers.data(name="p", shape=[-1, 4],
                                     dtype="int64",
                                     append_batch_size=False)
            build_llama_spec_generator(TARGET, bad, ptok, 4)
    # sampling params validate EAGERLY at program build, not at first
    # trace (top_p=0 would otherwise silently disable nucleus
    # filtering via index wraparound — see warp_logits)
    from paddle_tpu.layers import transformer as tfl
    for bad_kw, msg in ((dict(temperature=-0.5), "temperature"),
                        (dict(temperature=0.8, top_p=0.0), "top_p"),
                        (dict(temperature=0.8, top_k=-2), "top_k")):
        with pytest.raises(ValueError, match=msg):
            main, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main, startup):
                ptok = fluid.layers.data(name="p", shape=[-1, 4],
                                         dtype="int64",
                                         append_batch_size=False)
                tfl.llama_spec_generate(
                    ptok, vocab_size=32, max_new_tokens=4, dim=16,
                    n_layers=1, n_heads=2, n_kv_heads=1, ffn_hidden=32,
                    draft_dim=16, draft_n_layers=1, draft_n_heads=2,
                    draft_n_kv_heads=1, draft_ffn_hidden=32,
                    **bad_kw)


def test_spec_decode_draft_keeps_own_rope_base():
    """A draft trained with a different rope_base must be served with
    ITS base (config-plumbing regression): still exact, and the op's
    attrs carry both bases."""
    import dataclasses
    draft = dataclasses.replace(DRAFT, rope_base=10000.0)
    assert draft.rope_base != TARGET.rope_base
    _, want, got = _run_both(max_new=8, gamma=2, draft_cfg=draft)
    np.testing.assert_array_equal(got, want)
    spec_p, _, _, _, _ = _programs(4, 2, draft)
    op = [o for o in spec_p.global_block().ops
          if o.type == "llama_spec_generate"][0]
    assert op.attr("draft_rope_base") == draft.rope_base
    assert op.attr("rope_base") == TARGET.rope_base


def test_spec_decode_rejects_int8_scope():
    """Running the spec program against a quantized scope must raise
    loudly instead of feeding int8 arrays into float matmuls."""
    import pytest
    from paddle_tpu.models.llama import quantize_generator_weights
    spec_p, startup, spec_out, _, _ = _programs(4, 2)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    prompt = np.zeros((1, PROMPT), np.int64)
    with fluid.scope_guard(scope):
        exe.run(startup)
        quantize_generator_weights(scope)   # rewrites blocks.* to int8
        with pytest.raises(NotImplementedError, match="float-only"):
            exe.run(spec_p, feed={"ptok": prompt},
                    fetch_list=[spec_out], mode="test")


def test_spec_decode_aot_exports(tmp_path):
    """The spec program (bounded while_loop, two KV caches) AOT-exports
    via save_inference_model with NO stochasticity warning (greedy-only
    by construction) and the framework-free predictor reproduces the
    executor's tokens exactly."""
    import warnings
    from paddle_tpu.io import load_compiled_predictor
    d = str(tmp_path / "spec_model")
    spec_p, startup, spec_out, _, _ = _programs(5, 2)
    scope = fluid.Scope()
    exe = fluid.Executor()
    prompt = (np.arange(2 * PROMPT).reshape(2, PROMPT)
              % (TARGET.vocab_size - 3)).astype(np.int64)
    with fluid.scope_guard(scope):
        exe.run(startup)
        want = np.asarray(exe.run(spec_p, feed={"ptok": prompt},
                                  fetch_list=[spec_out],
                                  mode="test")[0])
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            fluid.io.save_inference_model(d, ["ptok"], [spec_out], exe,
                                          main_program=spec_p)
    pred = load_compiled_predictor(d)
    got = np.asarray(pred.run({"ptok": prompt})[0])
    np.testing.assert_array_equal(got, want)


def test_spec_decode_eos_masking_matches_generator():
    """eos_id/pad_id: sequences that emit eos keep emitting pad, and
    the spec output still equals build_llama_generator(eos_id=...)'s
    token for token. The eos token is chosen FROM an unmasked greedy
    run so the stop actually triggers mid-generation."""
    max_new, gamma = 12, 3
    spec0_p, startup, spec0_out, gen0_p, gen0_out = _programs(
        max_new, gamma)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(1)
    prompt = rng.randint(0, TARGET.vocab_size,
                         (3, PROMPT)).astype(np.int64)
    with fluid.scope_guard(scope):
        exe.run(startup)
        free = np.asarray(exe.run(gen0_p, feed={"gtok": prompt},
                                  fetch_list=[gen0_out],
                                  mode="test")[0])
        # a token the greedy model emits mid-stream in some row
        gen_part = free[:, PROMPT:]
        eos = int(gen_part[0, max_new // 2])
        assert (gen_part == eos).any()

        gen_p = fluid.Program()
        with fluid.program_guard(gen_p, fluid.Program()):
            gtok = fluid.layers.data(name="gtok", shape=[-1, PROMPT],
                                     dtype="int64",
                                     append_batch_size=False)
            gen_out = build_llama_generator(TARGET, gtok,
                                            max_new_tokens=max_new,
                                            eos_id=eos, pad_id=0)
        spec_p = fluid.Program()
        with fluid.program_guard(spec_p, fluid.Program()):
            ptok = fluid.layers.data(name="ptok", shape=[-1, PROMPT],
                                     dtype="int64",
                                     append_batch_size=False)
            spec_out = build_llama_spec_generator(
                TARGET, DRAFT, ptok, max_new_tokens=max_new,
                gamma=gamma, eos_id=eos, pad_id=0)
        want = np.asarray(exe.run(gen_p, feed={"gtok": prompt},
                                  fetch_list=[gen_out],
                                  mode="test")[0])
        got = np.asarray(exe.run(spec_p, feed={"ptok": prompt},
                                 fetch_list=[spec_out],
                                 mode="test")[0])
    # the eos masking really fired: some row has trailing pads
    assert (want[:, PROMPT:] == 0).any()
    np.testing.assert_array_equal(got, want)


def test_spec_decode_rejects_moe_configs():
    import dataclasses
    import pytest
    moe = dataclasses.replace(TARGET, moe_experts=4)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ptok = fluid.layers.data(name="p", shape=[-1, 4], dtype="int64",
                                 append_batch_size=False)
        with pytest.raises(NotImplementedError, match="MoE"):
            build_llama_spec_generator(moe, DRAFT, ptok, 4)
        with pytest.raises(NotImplementedError, match="MoE"):
            build_llama_spec_generator(TARGET,
                                       dataclasses.replace(
                                           DRAFT, moe_experts=2),
                                       ptok, 4)


def test_spec_decode_round_stats():
    """return_stats exposes (tokens, rounds, emitted): a perfect draft
    takes far fewer verification rounds than a random one for the same
    (identical) output — the observable speculation efficiency."""
    def rounds_for(copy_draft, draft_cfg):
        spec_p, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(spec_p, startup):
            ptok = fluid.layers.data(name="ptok", shape=[-1, PROMPT],
                                     dtype="int64",
                                     append_batch_size=False)
            out, rounds, emitted = build_llama_spec_generator(
                TARGET, draft_cfg, ptok, max_new_tokens=12, gamma=3,
                return_stats=True)
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        prompt = (np.arange(2 * PROMPT).reshape(2, PROMPT)
                  % (TARGET.vocab_size - 3)).astype(np.int64)
        with fluid.scope_guard(scope):
            exe.run(startup)
            if copy_draft:
                _copy_draft_weights(scope)
            toks, r, e = exe.run(spec_p, feed={"ptok": prompt},
                                 fetch_list=[out, rounds, emitted],
                                 mode="test")
        return (np.asarray(toks), int(np.asarray(r).reshape(())),
                int(np.asarray(e).reshape(())))

    toks_p, r_perfect, e_p = rounds_for(True, TARGET)
    toks_r, r_random, e_r = rounds_for(False, DRAFT)
    assert e_p == e_r == 12
    # 11 loop-emitted tokens (+1 from prefill), gamma+1=4 per round max
    assert r_perfect <= 4, r_perfect
    assert r_random >= r_perfect, (r_random, r_perfect)
    # same trained target => same tokens regardless of draft quality
    np.testing.assert_array_equal(toks_p, toks_r)


# ---------------------------------------------------------------------------
# sampled speculative decoding (temperature > 0): rejection resampling
# must reproduce the plain sampler's distribution exactly. Pinned two
# ways: the top_k=1 degenerate case is bitwise-greedy (sharp), and the
# free-sampling case is distribution-equal (statistical, with a power
# check that the tolerance isn't vacuous).
# ---------------------------------------------------------------------------

TINY = LlamaConfig(vocab_size=24, dim=16, n_layers=1, n_heads=2,
                   n_kv_heads=1, ffn_hidden=32, dtype="float32")
TINY_DRAFT = LlamaConfig(vocab_size=24, dim=8, n_layers=1, n_heads=2,
                         n_kv_heads=1, ffn_hidden=16, dtype="float32")


def _sampling_programs(max_new, gamma, temperature, top_k=0, top_p=1.0,
                       draft_cfg=TINY_DRAFT, cfg=TINY,
                       return_stats=False):
    spec_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(spec_p, startup):
        ptok = fluid.layers.data(name="ptok", shape=[-1, PROMPT],
                                 dtype="int64", append_batch_size=False)
        spec_out = build_llama_spec_generator(
            cfg, draft_cfg, ptok, max_new_tokens=max_new, gamma=gamma,
            temperature=temperature, top_k=top_k, top_p=top_p,
            return_stats=return_stats)
    gen_p = fluid.Program()
    with fluid.program_guard(gen_p, fluid.Program()):
        gtok = fluid.layers.data(name="gtok", shape=[-1, PROMPT],
                                 dtype="int64", append_batch_size=False)
        gen_out = build_llama_generator(cfg, gtok,
                                        max_new_tokens=max_new,
                                        temperature=temperature,
                                        top_k=top_k, top_p=top_p)
    return spec_p, startup, spec_out, gen_p, gen_out


def _sharpen(scope, names=("lm_head", "draft.lm_head"), factor=50.0):
    """Random-init models emit near-uniform logits (every distribution
    trivially matches every other); boosting the heads makes the
    target and draft distributions sharp AND different, giving the
    statistical tests power."""
    for nm in names:
        v = scope.find_var(nm)
        if v is not None:
            scope.set(nm, np.asarray(v) * factor)


def test_spec_sampling_topk1_is_exactly_greedy():
    """temperature>0 + top_k=1 degenerates to greedy: the warped
    distributions are one-hot, so rejection resampling must emit
    exactly the plain generator's (greedy) tokens — a bitwise pin of
    the whole sampled branch's plumbing."""
    spec_p, startup, spec_out, gen_p, gen_out = _sampling_programs(
        max_new=11, gamma=3, temperature=0.9, top_k=1)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(11)
    prompt = rng.randint(0, TINY.vocab_size,
                         (3, PROMPT)).astype(np.int64)
    with fluid.scope_guard(scope):
        exe.run(startup)
        _sharpen(scope)
        want = np.asarray(exe.run(gen_p, feed={"gtok": prompt},
                                  fetch_list=[gen_out],
                                  mode="test")[0])
        got = np.asarray(exe.run(spec_p, feed={"ptok": prompt},
                                 fetch_list=[spec_out],
                                 mode="test")[0])
    np.testing.assert_array_equal(got, want)


def _empirical(exe, prog, out, feed_name, prompt, n_runs, max_new,
               vocab):
    """Empirical per-position marginals of the generated tokens over
    n_runs runs (each run folds a fresh step into the rng)."""
    counts = np.zeros((max_new, vocab))
    for _ in range(n_runs):
        toks = np.asarray(exe.run(prog, feed={feed_name: prompt},
                                  fetch_list=[out], mode="test")[0])
        for j in range(max_new):
            np.add.at(counts[j], toks[:, PROMPT + j], 1)
    return counts / counts.sum(axis=1, keepdims=True)


def _tvd(p, q):
    return 0.5 * np.abs(p - q).sum(axis=-1)


def test_spec_sampling_matches_target_distribution():
    """Free sampling at temperature 1: the spec sampler's per-position
    marginals must match the plain sampler's (TVD small), with a
    random draft whose own distribution is FAR from the target's (the
    power check) — i.e. rejection resampling corrects the draft."""
    max_new, gamma, batch, runs = 3, 2, 24, 14
    spec_p, startup, spec_out, gen_p, gen_out = _sampling_programs(
        max_new=max_new, gamma=gamma, temperature=1.0)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(5)
    prompt = np.tile(rng.randint(0, TINY.vocab_size,
                                 (1, PROMPT)).astype(np.int64),
                     (batch, 1))
    with fluid.scope_guard(scope):
        exe.run(startup)
        _sharpen(scope)
        p_gen = _empirical(exe, gen_p, gen_out, "gtok", prompt, runs,
                           max_new, TINY.vocab_size)
        p_spec = _empirical(exe, spec_p, spec_out, "ptok", prompt, runs,
                            max_new, TINY.vocab_size)
    # Calibration (measured at these sizes): TVD(spec, gen) lands at
    # 0.03-0.09 for a correct sampler; a broken one (uniform-flattened,
    # draft-distribution leak) sits at the distribution distance
    # >= 2*tol the power check pins below. tol = 0.2 is ~3-6x the
    # observed sampling noise yet well under the power floor.
    tol = 0.2
    # power: the target's sampled marginal must be far from uniform BY
    # MORE than the match tolerance — otherwise "everything matches
    # everything" and the test is void (observed: 0.54-0.83)
    uniform = np.full(TINY.vocab_size, 1.0 / TINY.vocab_size)
    for j in range(max_new):
        assert _tvd(p_gen[j], uniform) > 2 * tol, (
            "powerless test: sharpen() failed", j, _tvd(p_gen[j], uniform))
    # the claim: spec sampling ≡ target sampling, per position
    for j in range(max_new):
        assert _tvd(p_spec[j], p_gen[j]) < tol, (
            j, _tvd(p_spec[j], p_gen[j]), tol)


def test_spec_sampling_perfect_draft_distribution_and_stats():
    """Draft == target weights at temperature 1: p == q so every draft
    token is accepted — rounds hits the ceiling exactly — and the
    output distribution still matches the plain sampler's."""
    max_new, gamma, batch, runs = 3, 2, 24, 14
    spec_p, startup, spec_outs, gen_p, gen_out = _sampling_programs(
        max_new=max_new, gamma=gamma, temperature=1.0,
        draft_cfg=TINY, return_stats=True)
    spec_out, rounds_v, emitted_v = spec_outs
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(9)
    prompt = np.tile(rng.randint(0, TINY.vocab_size,
                                 (1, PROMPT)).astype(np.int64),
                     (batch, 1))
    with fluid.scope_guard(scope):
        exe.run(startup)
        _sharpen(scope)
        _copy_draft_weights(scope)
        out, rounds, emitted = exe.run(
            spec_p, feed={"ptok": prompt},
            fetch_list=[spec_out, rounds_v, emitted_v], mode="test")
        # full acceptance: ceil((max_new - 1) / (gamma + 1)) rounds
        # (tiny float noise between the two cache paths may cost a
        # round on rare token ties — allow exactly one extra)
        ideal = -(-(max_new - 1) // (gamma + 1))
        assert ideal <= int(rounds) <= ideal + 1, (int(rounds), ideal)
        assert int(emitted) == max_new, int(emitted)
        p_gen = _empirical(exe, gen_p, gen_out, "gtok", prompt, runs,
                           max_new, TINY.vocab_size)
        p_spec = _empirical(exe, spec_p, spec_out, "ptok", prompt, runs,
                            max_new, TINY.vocab_size)
    tol = 0.2              # calibrated in the matching test above
    uniform = np.full(TINY.vocab_size, 1.0 / TINY.vocab_size)
    for j in range(max_new):
        assert _tvd(p_gen[j], uniform) > 2 * tol, (
            "powerless test", j, _tvd(p_gen[j], uniform))
        assert _tvd(p_spec[j], p_gen[j]) < tol, (
            j, _tvd(p_spec[j], p_gen[j]), tol)


def test_spec_sampling_eos_masking():
    """Sampled mode honors the eos/pad sticky-done convention: with
    top_k=1 (deterministic) and eos_id set to a token the plain
    generator emits mid-sequence, both paths must produce identical
    pad-masked rows."""
    spec_p0, startup0, spec_out0, gen_p0, gen_out0 = _sampling_programs(
        max_new=10, gamma=3, temperature=0.7, top_k=1)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(21)
    prompt = rng.randint(0, TINY.vocab_size,
                         (4, PROMPT)).astype(np.int64)
    with fluid.scope_guard(scope):
        exe.run(startup0)
        _sharpen(scope)
        base = np.asarray(exe.run(gen_p0, feed={"gtok": prompt},
                                  fetch_list=[gen_out0],
                                  mode="test")[0])
        # pick an eos that appears in the middle of some row
        mid = base[:, PROMPT + 2:PROMPT + 8]
        eos = int(mid.flat[0])

        spec_p, startup, spec_out = None, None, None
        with fluid.unique_name.guard():
            spec_p, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(spec_p, startup):
                ptok = fluid.layers.data(name="ptok",
                                         shape=[-1, PROMPT],
                                         dtype="int64",
                                         append_batch_size=False)
                spec_out = build_llama_spec_generator(
                    TINY, TINY_DRAFT, ptok, max_new_tokens=10, gamma=3,
                    temperature=0.7, top_k=1, eos_id=eos, pad_id=0)
            gen_p = fluid.Program()
            with fluid.program_guard(gen_p, fluid.Program()):
                gtok = fluid.layers.data(name="gtok",
                                         shape=[-1, PROMPT],
                                         dtype="int64",
                                         append_batch_size=False)
                gen_out = build_llama_generator(
                    TINY, gtok, max_new_tokens=10, temperature=0.7,
                    top_k=1, eos_id=eos, pad_id=0)
        want = np.asarray(exe.run(gen_p, feed={"gtok": prompt},
                                  fetch_list=[gen_out],
                                  mode="test")[0])
        got = np.asarray(exe.run(spec_p, feed={"ptok": prompt},
                                 fetch_list=[spec_out],
                                 mode="test")[0])
    assert (want[:, PROMPT:] == 0).any(), "eos never triggered pad"
    np.testing.assert_array_equal(got, want)


def test_sampled_spec_aot_export_warns_fixed_key(tmp_path):
    """An AOT artifact bakes ONE fixed PRNG key, so exporting a
    SAMPLED spec program must warn loudly (llama_spec_generate was
    rng-free when it was registered; the stateful flag and the
    temperature gate must both track the sampling mode now). The
    greedy no-warn half of the gate is pinned by
    test_spec_decode_aot_exports above, which exports at temperature 0
    under ``warnings.simplefilter("error")``."""
    import warnings
    from paddle_tpu.io import save_inference_model

    spec_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(spec_p, startup):
        ptok = fluid.layers.data(name="ptok", shape=[-1, PROMPT],
                                 dtype="int64", append_batch_size=False)
        spec_out = build_llama_spec_generator(
            TINY, TINY_DRAFT, ptok, max_new_tokens=4, gamma=2,
            temperature=0.9)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            save_inference_model(str(tmp_path / "m"), ["ptok"],
                                 [spec_out], exe,
                                 main_program=spec_p)
    msgs = [str(x.message) for x in w]
    assert any("FIXED key" in m and "llama_spec_generate" in m
               for m in msgs), msgs


@pytest.mark.slow      # ~17s: trains a real draft
def test_trained_draft_achieves_real_acceptance():
    """The deployment story end-to-end: an INDEPENDENTLY trained small
    draft (dim 16, L1) speculating for a larger target (dim 48, L2) on
    a learnable language must clear the measured break-even acceptance
    (~1.4 tokens/round at gamma 4; builder, an earlier installation,
    not re-measured) by a wide margin — the random(~1.0) and
    copy(~ceiling) bounds bracket it; this pins that a REAL draft
    lands near the top. Output exactness is free (greedy mode)."""
    V, SEQ, PRM, NEW, GAMMA = 64, 24, 6, 16, 4
    tgt = LlamaConfig(vocab_size=V, dim=48, n_layers=2, n_heads=4,
                      n_kv_heads=2, ffn_hidden=96, dtype="float32")
    drf = LlamaConfig(vocab_size=V, dim=16, n_layers=1, n_heads=2,
                      n_kv_heads=1, ffn_hidden=32, dtype="float32")

    from paddle_tpu.models.llama import (build_llama,
                                         GENERATOR_STACK_SUFFIXES,
                                         GENERATOR_SINGLETON_NAMES)

    def train(cfg, seed, steps=180):
        with fluid.unique_name.guard():
            p, st = fluid.Program(), fluid.Program()
            p.random_seed = st.random_seed = seed
            with fluid.program_guard(p, st):
                toks = fluid.layers.data(name="toks", shape=[-1, SEQ],
                                         dtype="int64",
                                         append_batch_size=False)
                tgts = fluid.layers.data(name="tgts", shape=[-1, SEQ],
                                         dtype="int64",
                                         append_batch_size=False)
                _, loss = build_llama(cfg, toks, tgts, shard_pp=True)
                fluid.optimizer.Adam(learning_rate=4e-3).minimize(loss)
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        rng = np.random.RandomState(7)   # same data stream for both
        with fluid.scope_guard(scope):
            exe.run(st)
            for _ in range(steps):
                start = rng.randint(0, V, (16, 1))
                stride = rng.randint(1, 4, (16, 1))
                s = (start + stride * np.arange(SEQ + 1)) % V
                exe.run(p, feed={"toks": s[:, :-1], "tgts": s[:, 1:]},
                        fetch_list=[loss])
        return scope

    tscope = train(tgt, 11)
    dscope = train(drf, 13)

    spec_p, spec_st = fluid.Program(), fluid.Program()
    with fluid.program_guard(spec_p, spec_st):
        ptok = fluid.layers.data(name="ptok", shape=[-1, PRM],
                                 dtype="int64", append_batch_size=False)
        out_v, rounds_v, emitted_v = build_llama_spec_generator(
            tgt, drf, ptok, max_new_tokens=NEW, gamma=GAMMA,
            return_stats=True)
    serve = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(serve):
        exe.run(spec_st)
        for k in tscope.vars:
            if serve.find_var(k) is not None:
                serve.set(k, np.asarray(tscope.find_var(k)))
        for sfx in GENERATOR_STACK_SUFFIXES:
            serve.set(f"draft.{sfx}",
                      np.asarray(dscope.find_var(f"blocks.{sfx}")))
        for nm in GENERATOR_SINGLETON_NAMES:
            serve.set(f"draft.{nm}", np.asarray(dscope.find_var(nm)))
        rng = np.random.RandomState(3)
        start = rng.randint(0, V, (8, 1))
        stride = rng.randint(1, 4, (8, 1))
        prompts = ((start + stride * np.arange(PRM)) % V).astype(
            np.int64)
        _, rounds, emitted = exe.run(
            spec_p, feed={"ptok": prompts},
            fetch_list=[out_v, rounds_v, emitted_v], mode="test")
    r, e = int(np.asarray(rounds)), int(np.asarray(emitted))
    tokens_per_round = (e - 1) / max(r, 1)
    assert e == NEW, (r, e)
    # measured at 5.0 (the gamma+1 ceiling); 2.5 leaves margin for
    # training noise while staying far above the 1.4 break-even
    assert tokens_per_round >= 2.5, (r, e, tokens_per_round)
