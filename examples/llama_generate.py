"""Train a tiny Llama on synthetic text, then generate from it with the
fused KV-cache program — the whole prefill + decode loop is ONE XLA
executable (no host round trip per token).

Run:  python examples/llama_generate.py  [--cpu]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np                                      # noqa: E402

import paddle_tpu as fluid                              # noqa: E402
from paddle_tpu.models.llama import (                   # noqa: E402
    LlamaConfig, build_llama, build_llama_generator,
    build_llama_spec_generator, copy_weights_as_draft)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--new-tokens", type=int, default=16)
    args = ap.parse_args()
    if args.cpu:
        fluid.force_cpu()   # BEFORE any device op

    cfg = LlamaConfig(vocab_size=256, dim=128, n_layers=4, n_heads=8,
                      n_kv_heads=4, ffn_hidden=256, dtype="float32")
    seq, prompt_len = 32, 8

    train_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(train_p, startup):
        toks = fluid.layers.data(name="toks", shape=[-1, seq],
                                 dtype="int64", append_batch_size=False)
        tgts = fluid.layers.data(name="tgts", shape=[-1, seq],
                                 dtype="int64", append_batch_size=False)
        _, loss = build_llama(cfg, toks, tgts, shard_pp=True)
        fluid.optimizer.Adam(learning_rate=3e-3).minimize(loss)

    gen_p = fluid.Program()
    with fluid.program_guard(gen_p, fluid.Program()):
        ptok = fluid.layers.data(name="ptok", shape=[-1, prompt_len],
                                 dtype="int64", append_batch_size=False)
        gen = build_llama_generator(cfg, ptok,
                                    max_new_tokens=args.new_tokens)

    place = fluid.CPUPlace() if args.cpu else fluid.TPUPlace()
    exe = fluid.Executor(place)
    exe.run(startup)

    # learnable synthetic language: arithmetic sequences mod vocab
    rng = np.random.RandomState(0)
    for step in range(args.steps):
        start = rng.randint(0, 256, (8, 1))
        stride = rng.randint(1, 4, (8, 1))
        seqs = (start + stride * np.arange(seq + 1)) % 256
        out = exe.run(train_p,
                      feed={"toks": seqs[:, :-1], "tgts": seqs[:, 1:]},
                      fetch_list=[loss])
        if step % 20 == 0:
            print(f"step {step}: "
                  f"loss={float(np.asarray(out[0]).reshape(())):.3f}")

    start = np.arange(4).reshape(4, 1) * 7
    prompts = (start + 2 * np.arange(prompt_len)) % 256
    toks_out = exe.run(gen_p, feed={"ptok": prompts.astype(np.int64)},
                       fetch_list=[gen], mode="test")[0]
    for row in np.asarray(toks_out):
        print("prompt", row[:prompt_len].tolist(),
              "->", row[prompt_len:].tolist())

    # --- speculative decoding: a draft proposes, the target verifies;
    # output is EXACTLY the target's greedy tokens. Here the "draft" is
    # the same trained weights copied under draft.* names (perfect
    # acceptance); a real deployment trains a smaller draft_cfg model.
    spec_p = fluid.Program()
    with fluid.program_guard(spec_p, fluid.Program()):
        ptok = fluid.layers.data(name="sptok", shape=[-1, prompt_len],
                                 dtype="int64", append_batch_size=False)
        spec = build_llama_spec_generator(cfg, cfg, ptok,
                                          max_new_tokens=args.new_tokens,
                                          gamma=4)
    copy_weights_as_draft(fluid.global_scope())
    spec_out = np.asarray(exe.run(
        spec_p, feed={"sptok": prompts.astype(np.int64)},
        fetch_list=[spec], mode="test")[0])
    same = np.array_equal(spec_out, np.asarray(toks_out))
    print(f"speculative == greedy: {same}")

    # --- sampled speculative decoding: same machinery at
    # temperature > 0 (rejection resampling) — each token distributed
    # exactly as the plain sampler with the same temperature/top-p
    samp_p = fluid.Program()
    with fluid.program_guard(samp_p, fluid.Program()):
        ptok = fluid.layers.data(name="mptok", shape=[-1, prompt_len],
                                 dtype="int64", append_batch_size=False)
        samp = build_llama_spec_generator(
            cfg, cfg, ptok, max_new_tokens=args.new_tokens, gamma=4,
            temperature=0.8, top_p=0.95)
    samp_out = np.asarray(exe.run(
        samp_p, feed={"mptok": prompts.astype(np.int64)},
        fetch_list=[samp], mode="test")[0])
    print("sampled speculative:", samp_out[0, prompt_len:].tolist())


if __name__ == "__main__":
    main()
