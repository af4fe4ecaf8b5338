"""KV-cache generation for the Llama flagship: one fused XLA program
(prefill + decode scan) whose parameter names match the training-side
llama_decoder_stack — a trained scope generates directly.

Correctness pin: greedy generation with the KV cache must emit exactly
the tokens produced by naive full-recompute decoding (re-running the
training forward on the growing sequence and taking argmax of the last
position each step).
"""
import numpy as np

import paddle_tpu as fluid
from paddle_tpu.models.llama import (LlamaConfig, build_llama,
                                     build_llama_generator)

CFG = LlamaConfig(vocab_size=64, dim=32, n_layers=2, n_heads=4,
                  n_kv_heads=2, ffn_hidden=64, dtype="float32")
PROMPT, NEW = 6, 5


def _train_and_programs():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        tokens = fluid.layers.data(name="tokens", shape=[-1, 16],
                                   dtype="int64", append_batch_size=False)
        targets = fluid.layers.data(name="targets", shape=[-1, 16],
                                    dtype="int64",
                                    append_batch_size=False)
        _, loss = build_llama(CFG, tokens, targets, shard_pp=True)
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)

    fwd_p = fluid.Program()
    with fluid.program_guard(fwd_p, fluid.Program()):
        ftok = fluid.layers.data(name="ftok", shape=[-1, -1],
                                 dtype="int64", append_batch_size=False)
        logits, _ = build_llama(CFG, ftok, None, shard_pp=True)

    gen_p = fluid.Program()
    with fluid.program_guard(gen_p, fluid.Program()):
        ptok = fluid.layers.data(name="ptok", shape=[-1, PROMPT],
                                 dtype="int64", append_batch_size=False)
        gen_out = build_llama_generator(CFG, ptok, max_new_tokens=NEW)
    return main, startup, loss, fwd_p, logits, gen_p, gen_out


def test_generate_matches_full_recompute():
    main, startup, loss, fwd_p, logits, gen_p, gen_out = \
        _train_and_programs()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(0)
    with fluid.scope_guard(scope):
        exe.run(startup)
        # a few training steps so weights are non-trivial
        for step in range(5):
            toks = rng.randint(0, CFG.vocab_size, (4, 16)).astype(
                np.int64)
            exe.run(main, feed={"tokens": toks,
                                "targets": np.roll(toks, -1, 1)},
                    fetch_list=[loss])

        prompt = rng.randint(0, CFG.vocab_size, (3, PROMPT)).astype(
            np.int64)

        # naive greedy: re-run the full forward on the growing sequence
        seq = prompt.copy()
        for _ in range(NEW):
            lg = np.asarray(exe.run(fwd_p, feed={"ftok": seq},
                                    fetch_list=[logits],
                                    mode="test")[0])
            nxt = lg[:, -1, :].argmax(-1).astype(np.int64)
            seq = np.concatenate([seq, nxt[:, None]], axis=1)

        got = np.asarray(exe.run(gen_p, feed={"ptok": prompt},
                                 fetch_list=[gen_out], mode="test")[0])
    assert got.shape == (3, PROMPT + NEW)
    np.testing.assert_array_equal(got[:, :PROMPT], prompt)
    np.testing.assert_array_equal(got, seq)


def test_generator_standalone_runs():
    """The generator program also runs standalone (own startup) for
    users who load weights separately."""
    gen_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(gen_p, startup):
        ptok = fluid.layers.data(name="ptok", shape=[-1, PROMPT],
                                 dtype="int64", append_batch_size=False)
        out = build_llama_generator(CFG, ptok, max_new_tokens=NEW)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        prompt = np.zeros((2, PROMPT), np.int64)
        got = np.asarray(exe.run(gen_p, feed={"ptok": prompt},
                                 fetch_list=[out], mode="test")[0])
    assert got.shape == (2, PROMPT + NEW)
    assert ((got >= 0) & (got < CFG.vocab_size)).all()


def test_sampling_modes():
    """temperature>0 with top_k=1 must equal greedy; free sampling
    yields in-range tokens and is step-dependent (rng folds)."""
    gen_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(gen_p, startup):
        ptok = fluid.layers.data(name="ptok", shape=[-1, PROMPT],
                                 dtype="int64", append_batch_size=False)
        greedy = build_llama_generator(CFG, ptok, max_new_tokens=NEW)
    k1_p = fluid.Program()
    with fluid.program_guard(k1_p, fluid.Program()):
        ptok = fluid.layers.data(name="ptok", shape=[-1, PROMPT],
                                 dtype="int64", append_batch_size=False)
        topk1 = build_llama_generator(CFG, ptok, max_new_tokens=NEW,
                                      temperature=0.8, top_k=1)
    samp_p = fluid.Program()
    with fluid.program_guard(samp_p, fluid.Program()):
        ptok = fluid.layers.data(name="ptok", shape=[-1, PROMPT],
                                 dtype="int64", append_batch_size=False)
        samp = build_llama_generator(CFG, ptok, max_new_tokens=NEW,
                                     temperature=1.5, top_p=0.9)

    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(7)
    with fluid.scope_guard(scope):
        exe.run(startup)
        prompt = rng.randint(0, CFG.vocab_size, (2, PROMPT)).astype(
            np.int64)
        g = np.asarray(exe.run(gen_p, feed={"ptok": prompt},
                               fetch_list=[greedy], mode="test")[0])
        k1 = np.asarray(exe.run(k1_p, feed={"ptok": prompt},
                                fetch_list=[topk1], mode="test")[0])
        s1 = np.asarray(exe.run(samp_p, feed={"ptok": prompt},
                                fetch_list=[samp], mode="test")[0])
        s2 = np.asarray(exe.run(samp_p, feed={"ptok": prompt},
                                fetch_list=[samp], mode="test")[0])
    np.testing.assert_array_equal(g, k1)        # top_k=1 == greedy
    assert ((s1 >= 0) & (s1 < CFG.vocab_size)).all()
    # different executor steps fold different rng keys
    assert not np.array_equal(s1[:, PROMPT:], s2[:, PROMPT:])


def test_generator_save_load_inference_model(tmp_path):
    """The generator program (with its fused llama_generate op)
    round-trips through save/load_inference_model: a fresh scope loads
    the deployment artifact and emits the same tokens."""
    gen_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(gen_p, startup):
        ptok = fluid.layers.data(name="ptok", shape=[-1, PROMPT],
                                 dtype="int64", append_batch_size=False)
        out = build_llama_generator(CFG, ptok, max_new_tokens=NEW)

    rng = np.random.RandomState(9)
    prompt = rng.randint(0, CFG.vocab_size, (2, PROMPT)).astype(np.int64)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        want = np.asarray(exe.run(gen_p, feed={"ptok": prompt},
                                  fetch_list=[out], mode="test")[0])
        fluid.io.save_inference_model(str(tmp_path), ["ptok"], [out],
                                      exe, main_program=gen_p)

    scope2 = fluid.Scope()
    with fluid.scope_guard(scope2):
        prog2, feeds, fetches = fluid.io.load_inference_model(
            str(tmp_path), exe)
        got = np.asarray(exe.run(prog2, feed={feeds[0]: prompt},
                                 fetch_list=fetches, mode="test")[0])
    np.testing.assert_array_equal(got, want)


def test_quantized_generation_close_to_float():
    """Weight-only int8 serving path: quantize_generator_weights +
    build_llama_generator(quantize=True). Greedy tokens from the int8
    program must overwhelmingly agree with the float program on a
    briefly-trained model (int8 per-channel error is ~1e-2 relative,
    far under trained logit gaps)."""
    from paddle_tpu.models.llama import quantize_generator_weights
    main, startup, loss, _, _, gen_p, gen_out = _train_and_programs()

    qgen_p = fluid.Program()
    with fluid.program_guard(qgen_p, fluid.Program()):
        qtok = fluid.layers.data(name="qtok", shape=[-1, PROMPT],
                                 dtype="int64", append_batch_size=False)
        qgen_out = build_llama_generator(CFG, qtok, max_new_tokens=NEW,
                                         quantize=True)

    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(1)
    with fluid.scope_guard(scope):
        exe.run(startup)
        for step in range(30):
            toks = rng.randint(0, CFG.vocab_size, (4, 16)).astype(
                np.int64)
            exe.run(main, feed={"tokens": toks,
                                "targets": np.roll(toks, -1, 1)},
                    fetch_list=[loss])
        prompt = rng.randint(0, CFG.vocab_size, (8, PROMPT)).astype(
            np.int64)
        ref = np.asarray(exe.run(gen_p, feed={"ptok": prompt},
                                 fetch_list=[gen_out], mode="test")[0])

        quantize_generator_weights(scope)
        # scope now holds int8 weights + @scale companions
        assert np.asarray(scope.find_var("blocks.wq")).dtype == np.int8
        assert np.asarray(scope.find_var("lm_head")).dtype == np.int8
        assert scope.find_var("blocks.wq@scale") is not None
        got = np.asarray(exe.run(qgen_p, feed={"qtok": prompt},
                                 fetch_list=[qgen_out], mode="test")[0])

    np.testing.assert_array_equal(got[:, :PROMPT], prompt)
    agree = (got == ref).mean()
    assert agree >= 0.9, (agree, got, ref)


def test_eos_masks_remaining_tokens():
    """After a row emits eos_id, the static decode loop emits pad_id
    for that row (HF generate convention — no early exit under XLA)."""
    main, startup, loss, _, _, gen_p, gen_out = _train_and_programs()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(3)
    with fluid.scope_guard(scope):
        exe.run(startup)
        prompt = rng.randint(0, CFG.vocab_size, (2, PROMPT)).astype(
            np.int64)
        base = np.asarray(exe.run(gen_p, feed={"ptok": prompt},
                                  fetch_list=[gen_out],
                                  mode="test")[0])
        # choose row 0's FIRST generated token as the "eos" (a later
        # pick could repeat an earlier emission and fire early)
        eos = int(base[0, PROMPT])
        pad = CFG.vocab_size - 1
        egen_p = fluid.Program()
        with fluid.program_guard(egen_p, fluid.Program()):
            etok = fluid.layers.data(name="etok", shape=[-1, PROMPT],
                                     dtype="int64",
                                     append_batch_size=False)
            egen_out = build_llama_generator(
                CFG, etok, max_new_tokens=NEW, eos_id=eos, pad_id=pad)
        got = np.asarray(exe.run(egen_p, feed={"etok": prompt},
                                 fetch_list=[egen_out],
                                 mode="test")[0])
    for row in got:
        newp = row[PROMPT:]
        hits = np.where(newp == eos)[0]
        if hits.size:
            after = newp[hits[0] + 1:]
            assert (after == pad).all(), (row, eos, pad)
    # row 0 hit the eos at its first new token; the rest is pad
    assert got[0, PROMPT] == eos
    assert (got[0, PROMPT + 1:] == pad).all()
    assert (got[:, :PROMPT] == prompt).all()


def test_generation_tp_dp_sharded_matches_single_device():
    """Multi-chip serving: the fused generator runs under a dp x tp
    mesh (Megatron splits on the stacked weights) and must emit exactly
    the single-device tokens."""
    from paddle_tpu.parallel import make_mesh

    main, startup, loss, _, _, gen_p, gen_out = _train_and_programs()

    sgen_p = fluid.Program()
    with fluid.program_guard(sgen_p, fluid.Program()):
        stok = fluid.layers.data(name="stok", shape=[-1, PROMPT],
                                 dtype="int64", append_batch_size=False)
        sgen_out = build_llama_generator(CFG, stok, max_new_tokens=NEW,
                                         shard_tp=True, shard_dp=True)

    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(5)
    with fluid.scope_guard(scope):
        exe.run(startup)
        for step in range(3):
            toks = rng.randint(0, CFG.vocab_size, (4, 16)).astype(
                np.int64)
            exe.run(main, feed={"tokens": toks,
                                "targets": np.roll(toks, -1, 1)},
                    fetch_list=[loss])
        prompt = rng.randint(0, CFG.vocab_size, (4, PROMPT)).astype(
            np.int64)
        ref = np.asarray(exe.run(gen_p, feed={"ptok": prompt},
                                 fetch_list=[gen_out], mode="test")[0])
        pe = fluid.ParallelExecutor(
            main_program=sgen_p, scope=scope,
            mesh=make_mesh({"dp": 2, "tp": 4}))
        got = np.asarray(pe.run(feed={"stok": prompt},
                                fetch_list=[sgen_out.name])[0])
    np.testing.assert_array_equal(got, ref)


def test_moe_generation_matches_eval_forward():
    """MoE flagship generation: per-layer trained weights are stacked
    via stack_generator_weights, and KV-cache decode must emit exactly
    the tokens of naive full-recompute greedy decoding through the
    training program in test mode (both use drop-free top-k routing —
    training-style capacity competition would make cached decode
    batch-dependent)."""
    from paddle_tpu.models.llama import stack_generator_weights

    mcfg = LlamaConfig(vocab_size=64, dim=32, n_layers=2, n_heads=4,
                       n_kv_heads=2, ffn_hidden=48, dtype="float32",
                       moe_experts=4, moe_top_k=2)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        tokens = fluid.layers.data(name="tokens", shape=[-1, 16],
                                   dtype="int64", append_batch_size=False)
        targets = fluid.layers.data(name="targets", shape=[-1, 16],
                                    dtype="int64",
                                    append_batch_size=False)
        _, loss = build_llama(mcfg, tokens, targets)
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    fwd_p = fluid.Program()
    with fluid.program_guard(fwd_p, fluid.Program()):
        ftok = fluid.layers.data(name="ftok", shape=[-1, -1],
                                 dtype="int64", append_batch_size=False)
        logits, _ = build_llama(mcfg, ftok, None)
    gen_p = fluid.Program()
    with fluid.program_guard(gen_p, fluid.Program()):
        ptok = fluid.layers.data(name="ptok", shape=[-1, PROMPT],
                                 dtype="int64", append_batch_size=False)
        gen_out = build_llama_generator(mcfg, ptok, max_new_tokens=NEW)

    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(7)
    with fluid.scope_guard(scope):
        exe.run(startup)
        for step in range(4):
            toks = rng.randint(0, mcfg.vocab_size, (4, 16)).astype(
                np.int64)
            exe.run(main, feed={"tokens": toks,
                                "targets": np.roll(toks, -1, 1)},
                    fetch_list=[loss])
        prompt = rng.randint(0, mcfg.vocab_size, (3, PROMPT)).astype(
            np.int64)
        seq = prompt.copy()
        for _ in range(NEW):
            lg = np.asarray(exe.run(fwd_p, feed={"ftok": seq},
                                    fetch_list=[logits],
                                    mode="test")[0])
            nxt = lg[:, -1, :].argmax(-1).astype(np.int64)
            seq = np.concatenate([seq, nxt[:, None]], axis=1)

        stack_generator_weights(mcfg, scope)
        got = np.asarray(exe.run(gen_p, feed={"ptok": prompt},
                                 fetch_list=[gen_out], mode="test")[0])
    np.testing.assert_array_equal(got, seq)


def test_unstacked_dense_weights_generate_via_stacking():
    """A dense model trained on the per-layer path (how tp/sp configs
    train) also serves through stack_generator_weights."""
    from paddle_tpu.models.llama import stack_generator_weights

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        tokens = fluid.layers.data(name="tokens", shape=[-1, 16],
                                   dtype="int64", append_batch_size=False)
        targets = fluid.layers.data(name="targets", shape=[-1, 16],
                                    dtype="int64",
                                    append_batch_size=False)
        _, loss = build_llama(CFG, tokens, targets)   # unstacked path
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    fwd_p = fluid.Program()
    with fluid.program_guard(fwd_p, fluid.Program()):
        ftok = fluid.layers.data(name="ftok", shape=[-1, -1],
                                 dtype="int64", append_batch_size=False)
        logits, _ = build_llama(CFG, ftok, None)
    gen_p = fluid.Program()
    with fluid.program_guard(gen_p, fluid.Program()):
        ptok = fluid.layers.data(name="ptok", shape=[-1, PROMPT],
                                 dtype="int64", append_batch_size=False)
        gen_out = build_llama_generator(CFG, ptok, max_new_tokens=NEW)

    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(11)
    with fluid.scope_guard(scope):
        exe.run(startup)
        for step in range(3):
            toks = rng.randint(0, CFG.vocab_size, (4, 16)).astype(
                np.int64)
            exe.run(main, feed={"tokens": toks,
                                "targets": np.roll(toks, -1, 1)},
                    fetch_list=[loss])
        prompt = rng.randint(0, CFG.vocab_size, (2, PROMPT)).astype(
            np.int64)
        seq = prompt.copy()
        for _ in range(NEW):
            lg = np.asarray(exe.run(fwd_p, feed={"ftok": seq},
                                    fetch_list=[logits],
                                    mode="test")[0])
            nxt = lg[:, -1, :].argmax(-1).astype(np.int64)
            seq = np.concatenate([seq, nxt[:, None]], axis=1)
        stack_generator_weights(CFG, scope)
        got = np.asarray(exe.run(gen_p, feed={"ptok": prompt},
                                 fetch_list=[gen_out], mode="test")[0])
    np.testing.assert_array_equal(got, seq)


def test_quantized_generation_on_dp_mesh():
    """Serving combo: the weight-only int8 generator also runs under a
    dp mesh and matches its own single-device tokens."""
    from paddle_tpu.models.llama import quantize_generator_weights
    from paddle_tpu.parallel import make_mesh

    main, startup, loss, _, _, _, _ = _train_and_programs()
    qgen_p = fluid.Program()
    with fluid.program_guard(qgen_p, fluid.Program()):
        qtok = fluid.layers.data(name="qtok", shape=[-1, PROMPT],
                                 dtype="int64", append_batch_size=False)
        qgen_out = build_llama_generator(CFG, qtok, max_new_tokens=NEW,
                                         quantize=True, shard_dp=True)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(13)
    with fluid.scope_guard(scope):
        exe.run(startup)
        toks = rng.randint(0, CFG.vocab_size, (4, 16)).astype(np.int64)
        exe.run(main, feed={"tokens": toks,
                            "targets": np.roll(toks, -1, 1)},
                fetch_list=[loss])
        quantize_generator_weights(scope)
        prompt = rng.randint(0, CFG.vocab_size, (8, PROMPT)).astype(
            np.int64)
        ref = np.asarray(exe.run(qgen_p, feed={"qtok": prompt},
                                 fetch_list=[qgen_out],
                                 mode="test")[0])
        pe = fluid.ParallelExecutor(main_program=qgen_p, scope=scope,
                                    mesh=make_mesh({"dp": 8}))
        got = np.asarray(pe.run(feed={"qtok": prompt},
                                fetch_list=[qgen_out.name])[0])
    np.testing.assert_array_equal(got, ref)


def test_unrolled_decode_matches_scan_decode():
    """unroll_layers / decode_unroll are pure schedule knobs (round-3
    decode restructure for per-scan-iteration overhead): the emitted
    tokens must be bit-identical to the default nested-scan form."""
    outs = {}
    for label, kw in [("base", {}),
                      ("unrolled", dict(unroll_layers=True,
                                        decode_unroll=3))]:
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            gen_p, startup_p = fluid.Program(), fluid.Program()
            with fluid.program_guard(gen_p, startup_p):
                toks = fluid.layers.data(name="toks",
                                         shape=[-1, PROMPT],
                                         dtype="int64",
                                         append_batch_size=False)
                out = build_llama_generator(CFG, toks,
                                            max_new_tokens=NEW, **kw)
            gen_p.random_seed = startup_p.random_seed = 7
            exe = fluid.Executor()
            exe.run(startup_p)
            pv = np.random.RandomState(0).randint(
                0, CFG.vocab_size, (2, PROMPT)).astype(np.int64)
            outs[label] = exe.run(gen_p, feed={"toks": pv},
                                  fetch_list=[out], mode="test")[0]
    np.testing.assert_array_equal(outs["base"], outs["unrolled"])


def test_moe_quantized_generation_close_to_float():
    """MoE x int8 (VERDICT r3 #8): the expert FFN stacks quantize
    per-expert (W8A8 native dot, router kept float) and the quantized
    generator's greedy tokens overwhelmingly agree with the float MoE
    generator on a briefly-trained model."""
    from paddle_tpu.models.llama import (quantize_generator_weights,
                                         stack_generator_weights)

    mcfg = LlamaConfig(vocab_size=64, dim=32, n_layers=2, n_heads=4,
                       n_kv_heads=2, ffn_hidden=48, dtype="float32",
                       moe_experts=4, moe_top_k=2)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        tokens = fluid.layers.data(name="tokens", shape=[-1, 16],
                                   dtype="int64", append_batch_size=False)
        targets = fluid.layers.data(name="targets", shape=[-1, 16],
                                    dtype="int64",
                                    append_batch_size=False)
        _, loss = build_llama(mcfg, tokens, targets)
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    gen_p = fluid.Program()
    with fluid.program_guard(gen_p, fluid.Program()):
        ptok = fluid.layers.data(name="ptok", shape=[-1, PROMPT],
                                 dtype="int64", append_batch_size=False)
        gen_out = build_llama_generator(mcfg, ptok, max_new_tokens=NEW)
    qgen_p = fluid.Program()
    with fluid.program_guard(qgen_p, fluid.Program()):
        qtok = fluid.layers.data(name="qtok", shape=[-1, PROMPT],
                                 dtype="int64", append_batch_size=False)
        qgen_out = build_llama_generator(mcfg, qtok, max_new_tokens=NEW,
                                         quantize=True)

    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(5)
    with fluid.scope_guard(scope):
        exe.run(startup)
        for step in range(20):
            toks = rng.randint(0, mcfg.vocab_size, (4, 16)).astype(
                np.int64)
            exe.run(main, feed={"tokens": toks,
                                "targets": np.roll(toks, -1, 1)},
                    fetch_list=[loss])
        prompt = rng.randint(0, mcfg.vocab_size, (6, PROMPT)).astype(
            np.int64)
        stack_generator_weights(mcfg, scope)
        ref = np.asarray(exe.run(gen_p, feed={"ptok": prompt},
                                 fetch_list=[gen_out], mode="test")[0])

        quantize_generator_weights(scope)
        wq = np.asarray(scope.find_var("blocks.moe_w_gate"))
        assert wq.dtype == np.int8 and wq.ndim == 4
        sc = np.asarray(scope.find_var("blocks.moe_w_gate@scale"))
        assert sc.shape == (2, 4, 1, 48)        # [L, E, 1, H]
        # router stays float
        assert np.asarray(
            scope.find_var("blocks.moe_router")).dtype == np.float32
        got = np.asarray(exe.run(qgen_p, feed={"qtok": prompt},
                                 fetch_list=[qgen_out], mode="test")[0])

    np.testing.assert_array_equal(got[:, :PROMPT], prompt)
    agree = (got == ref).mean()
    assert agree >= 0.9, (agree, got, ref)


def test_kv_int8_generation_matches_bf16_cache():
    """int8 KV cache (round 5): per-(position, kv-head) scales, both
    attention contractions natively int8. On a sharpened model the
    greedy tokens must track the full-precision-cache generator (the
    int8 noise floor is ~0.4% of absmax per element); the prompt echo
    must be exact and the first generated token — computed entirely
    from the quantized prefill cache — must agree.

    Token agreement alone can't catch a quality regression that keeps
    ~80% overlap (ADVICE round 5), so the first decode step's full
    next-token DISTRIBUTION (return_probs — softmax over the
    prefill-cache logits) is additionally pinned at the probability
    level: max |p_int8 - p_bf16| and per-row KL(p_bf16 || p_int8) must
    stay near the int8 noise floor (measured ~1.3e-3 / ~1.3e-5 on this
    config; the bounds carry >10x headroom)."""
    import paddle_tpu as fluid
    from paddle_tpu.models.llama import build_llama_generator

    p_ref, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(p_ref, startup):
        t = fluid.layers.data(name="t", shape=[-1, PROMPT],
                              dtype="int64", append_batch_size=False)
        out_ref, probs_ref = build_llama_generator(CFG, t, 12,
                                                   return_probs=True)
    p_q8 = fluid.Program()
    with fluid.program_guard(p_q8, fluid.Program()):
        t2 = fluid.layers.data(name="t", shape=[-1, PROMPT],
                               dtype="int64", append_batch_size=False)
        out_q8, probs_q8 = build_llama_generator(CFG, t2, 12,
                                                 kv_int8=True,
                                                 return_probs=True)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, CFG.vocab_size, (4, PROMPT)).astype(np.int64)
    with fluid.scope_guard(scope):
        exe.run(startup)
        # sharp logits: argmax stable under the int8 cache noise
        scope.set("lm_head", np.asarray(scope.find_var("lm_head")) * 40)
        ref, p_bf16 = (np.asarray(x) for x in exe.run(
            p_ref, feed={"t": prompt},
            fetch_list=[out_ref, probs_ref], mode="test"))
        q8, p_int8 = (np.asarray(x) for x in exe.run(
            p_q8, feed={"t": prompt},
            fetch_list=[out_q8, probs_q8], mode="test"))
    np.testing.assert_array_equal(q8[:, :PROMPT], prompt)
    np.testing.assert_array_equal(q8[:, PROMPT], ref[:, PROMPT])
    agree = (ref == q8).mean()
    assert agree > 0.8, (agree, ref[0], q8[0])
    # probability-level closeness on the first decode step
    assert p_bf16.shape == p_int8.shape == (4, CFG.vocab_size)
    np.testing.assert_allclose(p_bf16.sum(-1), 1.0, atol=1e-5)
    np.testing.assert_allclose(p_int8.sum(-1), 1.0, atol=1e-5)
    max_dp = np.abs(p_int8 - p_bf16).max()
    assert max_dp < 0.02, f"int8 KV shifted first-step probs by {max_dp}"
    kl = (p_bf16 * (np.log(p_bf16 + 1e-12)
                    - np.log(p_int8 + 1e-12))).sum(-1)
    assert kl.max() < 1e-3, f"KL(bf16||int8) per row: {kl}"
