"""Benchmark: ResNet-50 train step (fwd+bwd+SGD-momentum) images/sec on
one chip — the reference's headline number (reference
benchmark/fluid/models/resnet.py run via fluid_benchmark.py) — followed
by a ladder of further rungs while the budget lasts.

Prints ONE JSON line last:
  {"metric": ..., "value": N, "unit": "images/sec", "vs_baseline": R}
vs_baseline = achieved MFU / 0.60 (the north-star 60% MFU target band),
using ~3x4.09 GFLOP per image for the ResNet-50 train step and the
v5e peak of 197 bf16 TFLOP/s per chip.

A chip belongs to one process at a time, so the parent never touches
jax: it runs one child per rung, one after another, under a hard total
wall-clock budget (BENCH_TOTAL_BUDGET, default 1080 s), and STREAMS
every child's output line by line ('# '-prefixed, flushed) so a killed
parent still leaves a diagnostic tail. There is no CPU mode: a child
whose backend is not ``tpu`` exits non-zero before it builds anything,
and a parent with a failed rung or no result exits non-zero too.

Children use JAX's persistent compilation cache
(paddle_tpu.enable_compile_cache: $JAX_COMPILATION_CACHE_DIR, else
.jax_cache in the checkout). ROADMAP Speed 1 replaces this file.
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

_T0 = time.time()
_CHILD_SCRIPT = os.path.abspath(__file__)      # patchable test seam
TOTAL_BUDGET = float(os.environ.get("BENCH_TOTAL_BUDGET", "1080"))
# per-child ceiling; the budget usually binds first
CHILD_TIMEOUT = int(os.environ.get("BENCH_CHILD_TIMEOUT", "900"))


def _bool_env(name, default="0"):
    """Boolean bench flag, validated: exactly "0" or "1". Anything else
    (true/yes/2/...) raises so stale job configs fail loudly instead of
    silently flipping a lever."""
    val = os.environ.get(name, default)
    if val not in ("0", "1"):
        raise ValueError(f"{name} must be 0 or 1, got {val!r}")
    return val == "1"


def _remaining():
    return TOTAL_BUDGET - (time.time() - _T0)


def _say(msg):
    """Parent-side progress marker: flushed immediately so the driver's
    captured tail is never empty, prefixed so it can't be mistaken for
    the final JSON record."""
    print(f"# bench[{time.time() - _T0:6.1f}s] {msg}", flush=True)


def child_main():
    import jax
    if jax.default_backend() != "tpu":
        # no CPU mode: a number from a CPU run is never written under
        # the name of a device metric
        print(f"bench child: jax backend is {jax.default_backend()!r}, "
              "not 'tpu'; refusing to run", file=sys.stderr, flush=True)
        sys.exit(1)
    import paddle_tpu as fluid
    fluid.enable_compile_cache()
    model = os.environ.get("BENCH_MODEL", "resnet50")
    if model == "transformer":
        transformer_main()
        return
    if model == "llama-decode":
        decode_main()
        return
    if model == "llama-8b-decode":
        decode_8b_main()
        return
    if model in ("seq2seq", "stacked-lstm"):
        seq_main(model)
        return
    if model == "resnet50-pipe":
        pipe_main()
        return
    if model == "deepfm":
        ctr_main()
        return
    if model == "llama-spec-decode":
        spec_main()
        return
    if model == "layout-speedup":
        layout_speedup_main()
        return
    conv_main(model)


def _conv_layout():
    """BENCH_LAYOUT, validated (default: NHWC — channels-minor,
    no per-conv activation layout copies; feeds stay NCHW, the model
    transposes once at the stem)."""
    layout = os.environ.get("BENCH_LAYOUT", "NHWC")
    if layout not in ("NCHW", "NHWC"):
        raise ValueError(f"BENCH_LAYOUT must be NCHW or NHWC, "
                         f"got {layout!r}")
    return layout


def _optimize_passes_label():
    """The active PADDLE_TPU_OPTIMIZE rewrite pipeline, for the bench
    record (alongside "layout"): "off", or the comma-joined pass list
    that the executor hook will run — so the BENCH trajectory shows
    which graph rewrites were live for each number."""
    flag = os.environ.get("PADDLE_TPU_OPTIMIZE", "0")
    if flag in ("0", "", "off", "none"):
        return "off"
    try:
        from paddle_tpu.analysis.optimize import parse_passes
        return ",".join(parse_passes(flag))
    except Exception:
        return "off"


def _executed_layout(main_p, fetch_list, declared):
    """The layout the step program ACTUALLY executes, not the
    builder's declared one: when PADDLE_TPU_OPTIMIZE includes the
    layout pass (analysis/layout.py), the executor lowers a converted
    clone — re-derive it the same way and read the conv/pool/BN format
    attrs back. Returns "NCHW"/"NHWC", or "mixed(...)" when a
    partially-converted program runs both (cost-gated regions)."""
    flag = os.environ.get("PADDLE_TPU_OPTIMIZE", "0")
    prog = main_p
    if flag not in ("0", "", "off", "none"):
        try:
            from paddle_tpu.analysis.optimize import parse_passes
            passes = parse_passes(flag)
            if "layout" in passes:
                fetch_names = [v.name if hasattr(v, "name") else v
                               for v in fetch_list]
                clone = main_p.clone(for_test=main_p._is_test)
                clone.optimize(fetch_list=fetch_names, passes=passes)
                prog = clone
        except Exception:
            prog = main_p
    fmts = {op.attrs.get("data_format",
                         op.attrs.get("data_layout", "NCHW"))
            for op in prog.global_block().ops
            if op.type in ("conv2d", "depthwise_conv2d", "pool2d",
                           "batch_norm")}
    if not fmts:
        return declared
    if len(fmts) == 1:
        return fmts.pop()
    return "mixed(" + ",".join(sorted(fmts)) + ")"


def layout_speedup_main():
    """{model}_layout_speedup: wall-clock A/B of the cost-model-driven
    NCHW→NHWC conversion pass (analysis/layout.py) on conv inference
    steps — layout-on (passes layout,fold,fuse,cse,dce) vs layout-off
    (the default pipeline), median of BENCH_TRIALS=5 ALTERNATING
    off/on trials so clock drift and cache effects hit both arms
    equally. Two configs: the mnist conv net and a tiny cifar ResNet
    (depth 8). Select with BENCH_MODEL=layout-speedup."""
    import jax
    import paddle_tpu as fluid

    backend = jax.default_backend()
    iters = int(os.environ.get("BENCH_ITERS", "20"))
    trials = int(os.environ.get("BENCH_TRIALS", "5"))
    batch = int(os.environ.get("BENCH_BATCH", "64"))

    def one_model(tag, build, feed_fn):
        main_p, startup_p = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_p, startup_p):
            fetch_names = [v.name for v in build()]
        infer = main_p.clone(for_test=True)
        off = infer.clone(for_test=True)
        off.optimize(fetch_list=fetch_names)
        on = infer.clone(for_test=True)
        on_rep = on.optimize(
            fetch_list=fetch_names,
            passes=("layout", "fold", "fuse", "cse", "dce"))

        exe = fluid.Executor(fluid.TPUPlace())
        scope = fluid.Scope()
        rng = np.random.RandomState(0)
        feed = feed_fn(rng, batch)
        times = {"off": [], "on": []}
        with fluid.scope_guard(scope):
            exe.run(startup_p)
            for prog in (off, on):       # compile both, warm
                exe.run(prog, feed=feed, fetch_list=fetch_names,
                        mode="test")
            for _ in range(trials):
                for key, prog in (("off", off), ("on", on)):
                    t0 = time.perf_counter()
                    for _ in range(iters):
                        out = exe.run(prog, feed=feed,
                                      fetch_list=fetch_names,
                                      return_numpy=False, mode="test")
                    np.asarray(out[0])   # sync point
                    times[key].append(time.perf_counter() - t0)
        t_off = float(np.median(times["off"]))
        t_on = float(np.median(times["on"]))
        print(json.dumps({
            "metric": f"{tag}_layout_speedup",
            "value": round(t_off / t_on, 4),
            "unit": "x",
            "backend": backend, "batch": batch,
            "iters": iters, "trials": trials,
            "layout_off_ms_per_step": round(1e3 * t_off / iters, 3),
            "layout_on_ms_per_step": round(1e3 * t_on / iters, 3),
            "converted": on_rep.n_converted,
            "layout_transposes": on_rep.n_layout_transposes,
        }), flush=True)

    def build_mnist():
        from paddle_tpu.models.mnist import cnn_model
        img = fluid.layers.data(name="img", shape=[1, 28, 28],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1],
                                  dtype="int64")
        _, _, pred = cnn_model(img, label)
        return [pred]

    def feed_mnist(rng, b):
        return {"img": rng.rand(b, 1, 28, 28).astype(np.float32),
                "label": rng.randint(0, 10, (b, 1)).astype(np.int64)}

    one_model("mnist_conv", build_mnist, feed_mnist)

    def build_resnet_tiny():
        from paddle_tpu.models.resnet import resnet_cifar10
        img = fluid.layers.data(name="img", shape=[3, 32, 32],
                                dtype="float32")
        return [resnet_cifar10(img, depth=8)]

    def feed_resnet_tiny(rng, b):
        return {"img": rng.rand(b, 3, 32, 32).astype(np.float32)}

    one_model("resnet_tiny", build_resnet_tiny, feed_resnet_tiny)


def _apply_train_transpiles(main_p, startup_p):
    """The shared bench train-program knobs: fused optimizer updates
    (exact; tests/test_fuse_optimizer.py) and bf16 AMP."""
    if _bool_env("BENCH_FUSE_OPT"):
        # off by default: collapses ~320 per-param update kernels but
        # re-concats/splits every param each step — measured a net LOSS
        # on the bytes-bound real-chip ResNet step (1574 vs 1897 img/s)
        from paddle_tpu.transpiler import fuse_optimizer_ops
        fuse_optimizer_ops(main_p, startup_p)
    remat = os.environ.get("BENCH_CONV_REMAT", "0")
    if remat != "0":
        # "1" = the conv-net default policy; any other value is passed
        # through as a jax.checkpoint policy name. recompute_norms:
        # save conv outputs, recompute the BN normalize + relu in the
        # backward — trades a little elementwise recompute for never
        # storing the post-norm activation
        from paddle_tpu.transpiler import memory_optimize
        memory_optimize(main_p, policy="recompute_norms"
                        if remat == "1" else remat)
    amp = os.environ.get("BENCH_AMP", "2")
    if amp not in ("0", "1", "2", "O1", "O2", "off"):
        raise ValueError(f"BENCH_AMP must be one of 0/1/2/O1/O2/off, "
                         f"got {amp!r}")
    if amp not in ("0", "off"):
        # bf16 matmuls/convs on the MXU, f32 master weights & stats;
        # "2"/"O2" (default) = O2 bf16 activation flow — halves the
        # conv nets' HBM traffic (they are bytes-bound: measured
        # 64 GB/step under O1, 42.7 GB/step under O2, real chip)
        from paddle_tpu.transpiler import amp_transpile
        amp_transpile(main_p, level="O2" if amp in ("2", "O2") else "O1")


def conv_main(model):
    """ResNet-50 (default) or VGG16 train-step images/sec."""
    import jax
    import paddle_tpu as fluid

    backend = jax.default_backend()
    vgg = model == "vgg16"
    batch = int(os.environ.get(
        "BENCH_BATCH", "128"))
    iters = int(os.environ.get("BENCH_ITERS", "20"))

    layout = _conv_layout()

    main_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup_p):
        img = fluid.layers.data(name="img", shape=[3, 224, 224],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        if vgg:
            from paddle_tpu.models.vgg import vgg16
            avg_cost, acc, _ = vgg16(img, label, layout=layout)
        else:
            from paddle_tpu.models.resnet import resnet50
            avg_cost, acc, _ = resnet50(img, label, layout=layout)
        fluid.optimizer.Momentum(learning_rate=0.1,
                                 momentum=0.9).minimize(avg_cost)
    _apply_train_transpiles(main_p, startup_p)

    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup_p)

        rng = np.random.RandomState(0)
        # stage the batch in HBM once — the loop measures compute, not the
        # host transfer (real input pipelines overlap transfer; see io/)
        imgs = jax.device_put(rng.rand(batch, 3, 224, 224).astype(np.float32))
        labels = jax.device_put(
            rng.randint(0, 1000, (batch, 1)).astype(np.int64))
        feed = {"img": imgs, "label": labels}

        # warmup / compile (synced) — with the exact repeats the timed
        # loop will use, so only ONE executable ever compiles
        reps_warm = int(os.environ.get("BENCH_REPEATS",
                                       "8"))
        exe.run(main_p, feed=feed, fetch_list=[avg_cost],
                repeats=reps_warm)
        exe.run(main_p, feed=feed, fetch_list=[avg_cost],
                repeats=reps_warm)

        # measured loop: steps are dispatched back-to-back and pipeline
        # on-device; only the LAST loss is pulled to host. Real training
        # loops do the same (fetch every N steps) — a per-step fetch
        # would bill one host<->device round trip per step to the model.
        # BENCH_REPEATS>1 additionally fuses that many optimizer steps
        # into each dispatch (Executor repeats=k, warmed above).
        reps = reps_warm
        t0 = time.perf_counter()
        for _ in range(iters):
            out = exe.run(main_p, feed=feed, fetch_list=[avg_cost],
                          return_numpy=False, repeats=reps)
        final_loss = float(np.asarray(out[0]).reshape(()))  # sync point
        dt = time.perf_counter() - t0
        assert np.isfinite(final_loss), final_loss

    ips = batch * iters * reps / dt
    # fwd GFLOP/img at 224^2: ResNet-50 ~4.09, VGG16 ~15.47; train ~3x
    train_flops_per_img = 3 * (15.47e9 if vgg else 4.09e9)
    peak = 197e12
    mfu = ips * train_flops_per_img / peak
    rec = {
        "metric": ("vgg16" if vgg else "resnet50")
                  + "_train_images_per_sec_per_chip",
        "value": round(ips, 2),
        "unit": "images/sec",
        "vs_baseline": round(mfu / 0.60, 4),
        "backend": backend,
        "batch": batch,
        "mfu": round(mfu, 4),
    }
    # the layout ACTUALLY executed (the layout pass may have converted
    # the builder's declared one — ROADMAP item 3), not just declared
    rec["layout"] = _executed_layout(main_p, [avg_cost], layout)
    rec["declared_layout"] = layout
    rec["optimize_passes"] = _optimize_passes_label()
    if _bool_env("BENCH_KSTATS"):
        with fluid.scope_guard(scope):
            rec["compiled"] = exe.compiled_stats(
                main_p, feed=feed, fetch_list=[avg_cost],
                repeats=reps_warm)
    print(json.dumps(rec))


def transformer_main():
    """Secondary headline (SURVEY §6): decoder-LM train-step tokens/sec
    on one chip, via the fused llama_decoder_stack (scan over layers).
    Select with BENCH_MODEL=transformer."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.models.llama import LlamaConfig, build_llama

    backend = jax.default_backend()
    batch = int(os.environ.get("BENCH_BATCH", "16"))
    seq = int(os.environ.get("BENCH_SEQ", "512"))
    iters = int(os.environ.get("BENCH_ITERS", "20"))
    dim = int(os.environ.get("BENCH_DIM", "1024"))
    layers_n = int(os.environ.get("BENCH_LAYERS", "8"))
    ffn = int(os.environ.get("BENCH_FFN", str(4 * dim)))
    heads = max(1, dim // 128)
    cfg = LlamaConfig(vocab_size=8192, dim=dim, n_layers=layers_n,
                      n_heads=heads, n_kv_heads=heads, ffn_hidden=ffn,
                      dtype="bfloat16")
    # shard_pp=True runs the decoder as one scan over stacked layers
    # (one compile of one layer); BENCH_UNROLL=1 unrolls the layers
    # instead — bigger executable, no per-iteration loop overhead
    unroll = _bool_env("BENCH_UNROLL")

    main_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup_p):
        tokens = fluid.layers.data(name="tokens", shape=[-1, seq],
                                   dtype="int64", append_batch_size=False)
        targets = fluid.layers.data(name="targets", shape=[-1, seq],
                                    dtype="int64", append_batch_size=False)
        # fused vocab-chunked lm-head loss avoids materializing the
        # [tokens, vocab] logits — the memory lever for big batch/seq
        fused = int(os.environ.get("BENCH_FUSED_HEAD", "2048"))
        # BENCH_SCAN_UNROLL=k replicates k layer bodies per scan
        # iteration (fewer loop iterations, bigger executable)
        scan_unroll = int(os.environ.get("BENCH_SCAN_UNROLL", "1"))
        # BENCH_REMAT=0 stores layer activations instead of
        # recomputing them in backward (~15% faster when HBM allows)
        remat = _bool_env("BENCH_REMAT", "1")
        _, loss = build_llama(cfg, tokens, targets, shard_pp=not unroll,
                              fused_head_chunk=fused,
                              scan_unroll=scan_unroll, remat=remat)
        # momentum keeps one state buffer/param instead of adam's two —
        # the HBM lever for dim-4096-class configs on a 16 GB chip
        if os.environ.get("BENCH_OPT", "adam") == "momentum":
            fluid.optimizer.Momentum(learning_rate=1e-3,
                                     momentum=0.9).minimize(loss)
        else:
            fluid.optimizer.Adam(learning_rate=1e-4).minimize(loss)

    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    # repeats>1 fuses k steps per dispatch but k-multiplies the scan
    # nesting XLA must compile (compile time not re-measured on this
    # installation), so it stays opt-in here
    reps = int(os.environ.get("BENCH_REPEATS", "1"))
    with fluid.scope_guard(scope):
        exe.run(startup_p)
        rng = np.random.RandomState(0)
        toks = jax.device_put(
            rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64))
        feed = {"tokens": toks, "targets": toks}
        exe.run(main_p, feed=feed, fetch_list=[loss], repeats=reps)
        exe.run(main_p, feed=feed, fetch_list=[loss], repeats=reps)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = exe.run(main_p, feed=feed, fetch_list=[loss],
                          return_numpy=False, repeats=reps)
        final = float(np.asarray(out[0]).reshape(()))
        dt = time.perf_counter() - t0
        assert np.isfinite(final), final

    tps = batch * seq * iters * reps / dt
    # 6 * params * tokens/sec, params excluding embeddings
    n_params = cfg.n_layers * (4 * cfg.dim * cfg.dim
                               + 3 * cfg.dim * cfg.ffn_hidden)
    peak = 197e12
    mfu = 6 * n_params * tps / peak
    rec = {
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": round(tps, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(mfu / 0.60, 4),
        "backend": backend, "batch": batch, "seq": seq,
        "dim": dim, "n_layers": layers_n,
        "mfu": round(mfu, 4),
        "optimize_passes": _optimize_passes_label(),
    }
    if _bool_env("BENCH_KSTATS"):
        # XLA's own per-step numbers (flops, kernel count) — turns the
        # per-kernel-overhead gap analysis from inference into evidence
        with fluid.scope_guard(scope):
            rec["compiled"] = exe.compiled_stats(
                main_p, feed=feed, fetch_list=[loss], repeats=reps)
    print(json.dumps(rec))


def decode_main():
    """Generation throughput: KV-cache greedy decode tokens/sec on one
    chip (whole prefill+decode loop is a single XLA program). Select
    with BENCH_MODEL=llama-decode."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.models.llama import LlamaConfig, build_llama_generator

    backend = jax.default_backend()
    batch = int(os.environ.get("BENCH_BATCH", "8"))
    prompt = int(os.environ.get("BENCH_PROMPT", "128"))
    new = int(os.environ.get("BENCH_NEW", "128"))
    iters = int(os.environ.get("BENCH_ITERS", "5"))
    quant = _bool_env("BENCH_QUANT")
    dim = int(os.environ.get("BENCH_DIM", "1024"))
    cfg = LlamaConfig(vocab_size=8192, dim=dim, n_layers=8,
                      n_heads=max(1, dim // 128),
                      n_kv_heads=max(1, dim // 128), ffn_hidden=4 * dim,
                      dtype="bfloat16")

    # unroll the per-layer inner scan (8 scan iterations -> 1
    # straight-line body) and chunk the token scan; what a lax.scan
    # iteration costs is not re-measured on this installation
    unroll_layers = os.environ.get(
        "BENCH_UNROLL_LAYERS", "1") == "1"
    decode_unroll = int(os.environ.get(
        "BENCH_DECODE_UNROLL", "16"))

    gen_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(gen_p, startup_p):
        toks = fluid.layers.data(name="toks", shape=[-1, prompt],
                                 dtype="int64", append_batch_size=False)
        out = build_llama_generator(cfg, toks, max_new_tokens=new,
                                    unroll_layers=unroll_layers,
                                    decode_unroll=decode_unroll)
    if quant:
        # weight-only int8 serving form: same scope, int8 weights
        # resident in HBM, dequant fused into the decode matmuls.
        # The float gen_p above is NOT wasted: its startup_p is what
        # initializes the float scope (the stand-in for a trained
        # checkpoint) that quantize_generator_weights then converts —
        # an int8-declared program cannot be float-initialized.
        # Only the quantized program is ever compiled or run.
        qgen_p = fluid.Program()
        with fluid.program_guard(qgen_p, fluid.Program()):
            qtoks = fluid.layers.data(name="toks", shape=[-1, prompt],
                                      dtype="int64",
                                      append_batch_size=False)
            out = build_llama_generator(cfg, qtoks, max_new_tokens=new,
                                        quantize=True,
                                        unroll_layers=unroll_layers,
                                        decode_unroll=decode_unroll)
        gen_p = qgen_p

    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup_p)
        if quant:
            from paddle_tpu.models.llama import quantize_generator_weights
            quantize_generator_weights(scope)
        rng = np.random.RandomState(0)
        pv = jax.device_put(
            rng.randint(0, cfg.vocab_size, (batch, prompt)).astype(
                np.int64))
        res = exe.run(gen_p, feed={"toks": pv}, fetch_list=[out],
                      mode="test")       # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            res = exe.run(gen_p, feed={"toks": pv}, fetch_list=[out],
                          return_numpy=False, mode="test")
        final = np.asarray(res[0])
        dt = time.perf_counter() - t0
        assert final.shape == (batch, prompt + new)

    tps = batch * new * iters / dt
    # decode is bandwidth-bound: every generated token streams the
    # whole parameter set from HBM once per batch — roofline
    # steps/sec = HBM BW / param bytes, tokens/sec = batch * that.
    # vs_baseline keeps the harness convention: achieved fraction of
    # the 60%-of-roofline band.
    mat_params = (cfg.n_layers * (4 * cfg.dim * cfg.dim
                                  + 3 * cfg.dim * cfg.ffn_hidden)
                  + cfg.vocab_size * cfg.dim)            # + lm_head
    fdt = 2 if cfg.dtype == "bfloat16" else 4
    # quantize_generator_weights leaves tok_emb (and norms) float and
    # only the matmul stacks + lm_head go int8 — bill each at its real
    # streamed width. The embedding table is GATHERED (batch rows per
    # decode step), so only those rows count as streamed bytes.
    step_bytes = (mat_params * (1 if quant else fdt)
                  + batch * cfg.dim * fdt)       # gathered emb rows
    hbm_bw = 819e9                               # v5e HBM
    roofline_tps = batch * hbm_bw / step_bytes
    print(json.dumps({
        "metric": "llama_decode_tokens_per_sec_per_chip",
        "value": round(tps, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(tps / roofline_tps / 0.60, 4),
        "backend": backend, "batch": batch, "prompt": prompt,
        "new_tokens": new, "quantized": quant,
        "unroll_layers": unroll_layers, "decode_unroll": decode_unroll,
    }))


def decode_8b_main():
    """Llama-3-8B-geometry int8 serving on ONE chip (the stretch
    config): ~7.5 GB of int8 weights resident in 16 GB HBM,
    bf16 KV cache, fused prefill+decode program. Weights are
    random-initialized ON DEVICE (one tiny init program per stacked
    tensor — int8 straight out of uniform_random, no float stage, no
    multi-GB host transfer). Select with
    BENCH_MODEL=llama-8b-decode."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.models.llama import (LlamaConfig,
                                         build_llama_generator,
                                         random_int8_generator_weights)

    backend = jax.default_backend()
    batch = int(os.environ.get("BENCH_BATCH", "4"))
    prompt = int(os.environ.get("BENCH_PROMPT", "64"))
    new = int(os.environ.get("BENCH_NEW", "64"))
    iters = int(os.environ.get("BENCH_ITERS", "3"))
    cfg = LlamaConfig(dtype="bfloat16")
    unroll_layers = _bool_env("BENCH_UNROLL_LAYERS", "1")
    decode_unroll = int(os.environ.get(
        "BENCH_DECODE_UNROLL", "16"))
    # int8 KV cache (round 5): halves the per-step KV stream — the
    # binder at long generation lengths
    kv_int8 = _bool_env("BENCH_KV_INT8")

    gen_p = fluid.Program()
    with fluid.program_guard(gen_p, fluid.Program()):
        toks = fluid.layers.data(name="toks", shape=[-1, prompt],
                                 dtype="int64", append_batch_size=False)
        out = build_llama_generator(cfg, toks, max_new_tokens=new,
                                    quantize=True, kv_int8=kv_int8,
                                    unroll_layers=unroll_layers,
                                    decode_unroll=decode_unroll)

    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    hd = cfg.dim // cfg.n_heads
    L, D, V, F = cfg.n_layers, cfg.dim, cfg.vocab_size, cfg.ffn_hidden
    fdt = cfg.dtype
    random_int8_generator_weights(cfg, exe, scope)
    with fluid.scope_guard(scope):
        rng = np.random.RandomState(0)
        pv = jax.device_put(
            rng.randint(0, V, (batch, prompt)).astype(np.int64))
        res = exe.run(gen_p, feed={"toks": pv}, fetch_list=[out],
                      mode="test")                 # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            res = exe.run(gen_p, feed={"toks": pv}, fetch_list=[out],
                          return_numpy=False, mode="test")
        final = np.asarray(res[0])
        dt = time.perf_counter() - t0
        assert final.shape == (batch, prompt + new)

    tps = batch * new * iters / dt
    mat_params = (L * (D * cfg.n_heads * hd + 2 * D * cfg.n_kv_heads * hd
                       + cfg.n_heads * hd * D + 3 * D * F) + D * V)
    fw = 2 if fdt == "bfloat16" else 4
    step_bytes = mat_params + batch * D * fw      # int8 + gathered rows
    hbm_bw = 819e9
    roofline_tps = batch * hbm_bw / step_bytes
    print(json.dumps({
        "metric": "llama8b_int8_decode_tokens_per_sec_per_chip",
        "value": round(tps, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(tps / roofline_tps / 0.60, 4),
        "backend": backend, "batch": batch, "prompt": prompt,
        "new_tokens": new, "weights_gb": round(mat_params / 2**30, 2),
        "kv_int8": kv_int8,
    }))


def seq_main(model):
    """Sequence-model train throughput (the
    'Transformer / seq2seq-attention (LoDTensor variable-length path)'
    row): words/sec for stacked dynamic-LSTM sentiment
    (BENCH_MODEL=stacked-lstm) or seq2seq-with-attention
    (BENCH_MODEL=seq2seq). Both are lax.scan-bound: the LoD/recurrent
    path the reference runs as per-op interpreter loops."""
    import jax
    import paddle_tpu as fluid

    backend = jax.default_backend()
    batch = int(os.environ.get("BENCH_BATCH", "32"))
    seq = int(os.environ.get("BENCH_SEQ", "64"))
    iters = int(os.environ.get("BENCH_ITERS", "10"))
    vocab = 10000

    main_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup_p):
        if model == "seq2seq":
            from paddle_tpu.models.machine_translation import \
                seq_to_seq_net
            src = fluid.layers.data(name="src", shape=[1],
                                    dtype="int64", lod_level=1)
            trg = fluid.layers.data(name="trg", shape=[1],
                                    dtype="int64", lod_level=1)
            lbl = fluid.layers.data(name="lbl", shape=[1],
                                    dtype="int64", lod_level=1)
            avg_cost, _ = seq_to_seq_net(src, trg, lbl, vocab, vocab,
                                         embedding_dim=512,
                                         encoder_size=512,
                                         decoder_size=512)
        else:
            from paddle_tpu.models.stacked_dynamic_lstm import \
                stacked_lstm_net
            data = fluid.layers.data(name="src", shape=[1],
                                     dtype="int64", lod_level=1)
            label = fluid.layers.data(name="label", shape=[1],
                                      dtype="int64")
            avg_cost, _, _ = stacked_lstm_net(data, label, vocab,
                                              emb_dim=128, hid_dim=512)
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(avg_cost)

    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    seqs = [rng.randint(1, vocab, (seq, 1)).astype(np.int64)
            for _ in range(batch)]
    sb = fluid.to_sequence_batch(seqs)
    if model == "seq2seq":
        feed = {"src": sb, "trg": sb, "lbl": sb}
    else:
        feed = {"src": sb,
                "label": rng.randint(0, 2, (batch, 1)).astype(np.int64)}
    # Repeat-run protocol: scan-bound modes have measured run-to-run
    # variance the single-shot protocol couldn't separate from real
    # regressions (round 4's stacked-lstm 289k->254k question). Take
    # BENCH_RUNS (default 3) back-to-back timed windows and report the
    # MEDIAN, plus the per-run values and relative spread.
    n_runs = int(os.environ.get("BENCH_RUNS", "3"))
    if n_runs < 1:
        raise ValueError(f"BENCH_RUNS must be >= 1, got {n_runs}")
    run_wps = []
    with fluid.scope_guard(scope):
        exe.run(startup_p)
        exe.run(main_p, feed=feed, fetch_list=[avg_cost])
        exe.run(main_p, feed=feed, fetch_list=[avg_cost])
        for _ in range(n_runs):
            t0 = time.perf_counter()
            for _ in range(iters):
                res = exe.run(main_p, feed=feed, fetch_list=[avg_cost],
                              return_numpy=False)
            # host fetch inside the window: the timed quantity is
            # steps-to-results, same as the single-shot protocol
            final = float(np.asarray(res[0]).reshape(()))
            dt = time.perf_counter() - t0
            assert np.isfinite(final), final
            run_wps.append(batch * seq * iters / dt)

    wps = float(np.median(run_wps))
    spread = ((max(run_wps) - min(run_wps)) / wps) if wps else 0.0
    # vs_baseline keeps the harness convention (achieved MFU / 0.60)
    # using approximate analytic matmul FLOPs per word; scan-bound
    # models sit far below the MXU band by construction (per-word
    # matmuls are ~1 MFLOP)
    if model == "seq2seq":
        # enc: fc 512->2048 + lstm512 recurrent; dec/word: attention
        # projections + fc 1024->1536 + gru512 + out fc 512->vocab
        fwd_flops = (2 * 512 * 2048 + 2 * 4 * 512 * 512
                     + 2 * 512 * 512 * 2 + 2 * seq * 512 * 2
                     + 2 * 1024 * 1536 + 2 * 3 * 512 * 512
                     + 2 * 512 * vocab)
    else:
        # fc 128->512 + 3 lstm(h=128) recurrents + 2 concat-fcs 640->512
        fwd_flops = (2 * 128 * 512 + 3 * 2 * 4 * 128 * 128
                     + 2 * 2 * 640 * 512)
    peak = 197e12
    mfu = 3 * fwd_flops * wps / peak
    print(json.dumps({
        "metric": f"{model.replace('-', '_')}_train_words_per_sec_per_chip",
        "value": round(wps, 1),
        "unit": "words/sec",
        "vs_baseline": round(mfu / 0.60, 4),
        "mfu": round(mfu, 5),
        "backend": backend, "batch": batch, "seq": seq,
        "runs": [round(w, 1) for w in run_wps],
        "spread": round(spread, 4),
    }))


def spec_main():
    """Speculative-decode machinery cost/benefit on the chip: target =
    the dim-2048 bf16 decode config (plain-decode baseline ~3.9k
    tok/s), draft = dim/4 geometry by default. BENCH_SPEC_DRAFT:

      random = untrained draft, acceptance ~ 1/vocab → alpha≈0: the
               pure-overhead FLOOR (every round pays gamma draft
               forwards + one verify forward and emits ONE token);
      copy   = target weights served as their own draft (same
               geometry) → alpha≈1: the full-acceptance CEILING of the
               machinery (the draft costs a full target forward here,
               so this isolates loop/batching costs — it is not a
               deployable speedup, which needs a trained cheap draft).

    BENCH_GAMMA sweeps the draft length; BENCH_TEMP > 0 exercises the
    speculative-sampling path. Reports tok/s + rounds/emitted from the
    op's stats (tokens-per-round vs the gamma+1 ceiling IS the
    achieved acceptance).
    Unlike llama-decode there is no decode_unroll lever: the round
    loop's trip count is data-dependent (a lax.while_loop).
    Select with BENCH_MODEL=llama-spec-decode."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.models.llama import (LlamaConfig,
                                         build_llama_spec_generator)

    backend = jax.default_backend()
    batch = int(os.environ.get("BENCH_BATCH", "8"))
    prompt = int(os.environ.get("BENCH_PROMPT",
                                "128"))
    new = int(os.environ.get("BENCH_NEW", "128"))
    iters = int(os.environ.get("BENCH_ITERS", "5"))
    gamma = int(os.environ.get("BENCH_GAMMA", "4"))
    temp = float(os.environ.get("BENCH_TEMP", "0"))
    draft_mode = os.environ.get("BENCH_SPEC_DRAFT", "random")
    if draft_mode not in ("random", "copy"):
        raise ValueError(f"BENCH_SPEC_DRAFT must be random or copy, "
                         f"got {draft_mode!r}")
    dim = int(os.environ.get("BENCH_DIM", "2048"))
    cfg = LlamaConfig(vocab_size=8192, dim=dim, n_layers=8,
                      n_heads=max(1, dim // 128),
                      n_kv_heads=max(1, dim // 128), ffn_hidden=4 * dim,
                      dtype="bfloat16")
    if draft_mode == "copy":
        draft_cfg = cfg
    else:
        ddim = max(32, dim // 4)
        draft_cfg = LlamaConfig(
            vocab_size=cfg.vocab_size, dim=ddim, n_layers=2,
            n_heads=max(1, ddim // 128), n_kv_heads=max(1, ddim // 128),
            ffn_hidden=4 * ddim, dtype=cfg.dtype)

    spec_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(spec_p, startup_p):
        toks = fluid.layers.data(name="toks", shape=[-1, prompt],
                                 dtype="int64", append_batch_size=False)
        out, rounds_v, emitted_v = build_llama_spec_generator(
            cfg, draft_cfg, toks, max_new_tokens=new, gamma=gamma,
            temperature=temp, unroll_layers=True, return_stats=True)

    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup_p)
        if draft_mode == "copy":
            from paddle_tpu.models.llama import copy_weights_as_draft
            copy_weights_as_draft(scope)
        rng = np.random.RandomState(0)
        pv = jax.device_put(
            rng.randint(0, cfg.vocab_size, (batch, prompt)).astype(
                np.int64))
        res = exe.run(spec_p, feed={"toks": pv},
                      fetch_list=[out, rounds_v, emitted_v],
                      mode="test")                 # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            res = exe.run(spec_p, feed={"toks": pv},
                          fetch_list=[out, rounds_v, emitted_v],
                          return_numpy=False, mode="test")
        toks_out = np.asarray(res[0])
        rounds = int(np.asarray(res[1]))
        emitted = int(np.asarray(res[2]))
        dt = time.perf_counter() - t0
        assert toks_out.shape == (batch, prompt + new)

    tps = batch * new * iters / dt
    tokens_per_round = (emitted - 1) / max(rounds, 1)
    print(json.dumps({
        "metric": "llama_spec_decode_tokens_per_sec_per_chip",
        "value": round(tps, 1),
        "unit": "tokens/sec",
        "backend": backend, "batch": batch, "prompt": prompt,
        "new_tokens": new, "gamma": gamma, "temperature": temp,
        "draft": draft_mode, "draft_dim": draft_cfg.dim,
        "draft_layers": draft_cfg.n_layers,
        "rounds": rounds, "emitted": emitted,
        "tokens_per_round": round(tokens_per_round, 3),
        "acceptance_ceiling": gamma + 1,
    }))


def ctr_main():
    """DeepFM CTR train throughput (the reference's
    sparse parameter-server showcase, here the TPU sparse-embedding
    path): examples/sec at a realistic table size. The step is
    gather/scatter + a small MLP, so MFU is tiny by construction (like
    the scan-bound rows); the interesting costs are the embedding
    gathers, the scatter-add gradients, and the dense Adam sweep over
    the table (ARCHITECTURE.md 'Large-vocab embeddings'). Select with
    BENCH_MODEL=deepfm."""
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.models.ctr import build_deepfm

    backend = jax.default_backend()
    batch = int(os.environ.get("BENCH_BATCH", "4096"))
    iters = int(os.environ.get("BENCH_ITERS", "20"))
    vocab = int(os.environ.get("BENCH_VOCAB",
                               "1000000"))
    fields = int(os.environ.get("BENCH_FIELDS", "23"))
    embed = int(os.environ.get("BENCH_EMBED", "16"))
    hidden = (400, 400)

    main_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup_p):
        feat = fluid.layers.data(name="feat", shape=[-1, fields],
                                 dtype="int64", append_batch_size=False)
        label = fluid.layers.data(name="label", shape=[-1, 1],
                                  dtype="float32",
                                  append_batch_size=False)
        import warnings
        with warnings.catch_warnings():
            # is_sparse on one device warns that the dense Adam sweep is
            # the real cost; that cost is exactly what this row measures
            warnings.simplefilter("ignore")
            _, avg_cost = build_deepfm(feat, label, num_features=vocab,
                                       num_fields=fields,
                                       embed_size=embed,
                                       hidden_sizes=hidden)
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(avg_cost)

    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup_p)
        rng = np.random.RandomState(0)
        ids = jax.device_put(
            rng.randint(0, vocab, (batch, fields)).astype(np.int64))
        y = jax.device_put(
            (rng.rand(batch, 1) < 0.3).astype(np.float32))
        feed = {"feat": ids, "label": y}

        reps = int(os.environ.get("BENCH_REPEATS",
                                  "8"))
        exe.run(main_p, feed=feed, fetch_list=[avg_cost], repeats=reps)
        exe.run(main_p, feed=feed, fetch_list=[avg_cost], repeats=reps)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = exe.run(main_p, feed=feed, fetch_list=[avg_cost],
                          return_numpy=False, repeats=reps)
        final = float(np.asarray(out[0]).reshape(()))
        dt = time.perf_counter() - t0
        assert np.isfinite(final), final

    eps = batch * iters * reps / dt
    # analytic fwd matmul flops/example: MLP over the field embeddings
    # (fields*embed -> 400 -> 400 -> 1) + the FM second-order terms
    fwd_flops = 2 * (fields * embed * hidden[0]
                     + hidden[0] * hidden[1] + hidden[1]
                     + 3 * fields * embed)
    peak = 197e12
    mfu = 3 * fwd_flops * eps / peak
    # the honest roofline for this row is HBM bytes, not flops: per
    # step the Adam update sweeps the full table + moments
    table_mb = vocab * (embed + 1) * 4 / 2**20
    print(json.dumps({
        "metric": "deepfm_train_examples_per_sec_per_chip",
        "value": round(eps, 1),
        "unit": "examples/sec",
        "vs_baseline": round(mfu / 0.60, 4),
        "mfu": round(mfu, 6),
        "backend": backend, "batch": batch, "vocab": vocab,
        "fields": fields, "embed_size": embed,
        "table_mb": round(table_mb, 1),
    }))


def pipe_main():
    """End-to-end input-pipeline-fed ResNet-50 train: native C++
    batcher (recordio shards -> threaded shuffle/batch) -> DeviceLoader
    async host->device prefetch -> train step. Proves the native
    pipeline sustains the synthetic-feed number (the loop the
    reference's C++ reader-op stack closes). Select with
    BENCH_MODEL=resnet50-pipe."""
    # ---- synthetic dataset on disk: uint8 images (jpeg-decoded form),
    # cast to f32 on device; ~150 KB/sample like real 224^2 RGB -------
    import shutil
    import tempfile
    tmp = tempfile.mkdtemp(prefix="bench_pipe_")
    try:
        _pipe_body(tmp)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _pipe_body(tmp):
    import jax
    import paddle_tpu as fluid
    from paddle_tpu.models.resnet import resnet50
    from paddle_tpu.io.batcher import FixedBatcher, write_fixed
    from paddle_tpu.io.device_loader import DeviceLoader

    backend = jax.default_backend()
    batch = int(os.environ.get("BENCH_BATCH", "128"))
    iters = int(os.environ.get("BENCH_ITERS", "20"))
    n_shards = int(os.environ.get("BENCH_SHARDS", "4"))
    specs = [((3, 224, 224), "uint8"), ((1,), "int64")]
    rng = np.random.RandomState(0)
    n_per = max(2 * batch * (iters + 4) // n_shards, batch)
    paths = []
    for s in range(n_shards):
        path = os.path.join(tmp, f"train-{s}.rio")
        write_fixed(path,
                    ((rng.randint(0, 255, (3, 224, 224), dtype=np.uint8),
                      rng.randint(0, 1000, (1,)).astype(np.int64))
                     for _ in range(n_per)), specs)
        paths.append(path)

    layout = _conv_layout()
    main_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup_p):
        img_u8 = fluid.layers.data(name="img_u8", shape=[3, 224, 224],
                                   dtype="uint8")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        img = fluid.layers.cast(img_u8, "float32")
        img = fluid.layers.scale(img, scale=1.0 / 255.0)
        avg_cost, acc, _ = resnet50(img, label, layout=layout)
        fluid.optimizer.Momentum(learning_rate=0.1,
                                 momentum=0.9).minimize(avg_cost)
    _apply_train_transpiles(main_p, startup_p)

    def reader():
        while True:                     # loop epochs for the bench
            for arrs in FixedBatcher(paths, specs, batch_size=batch,
                                     shuffle_buf=1024, n_threads=4,
                                     drop_last=True):
                yield {"img_u8": arrs[0], "label": arrs[1]}

    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup_p)
        with DeviceLoader(reader, buffer_size=3) as dl:
            it = iter(dl)
            feed = next(it)
            exe.run(main_p, feed=feed, fetch_list=[avg_cost])  # compile
            t0 = time.perf_counter()
            for _ in range(iters):
                res = exe.run(main_p, feed=next(it),
                              fetch_list=[avg_cost], return_numpy=False)
            final = float(np.asarray(res[0]).reshape(()))
            dt = time.perf_counter() - t0
            assert np.isfinite(final), final

    ips = batch * iters / dt
    train_flops_per_img = 3 * 4.09e9
    peak = 197e12
    mfu = ips * train_flops_per_img / peak
    print(json.dumps({
        "metric": "resnet50_pipe_train_images_per_sec_per_chip",
        "value": round(ips, 2),
        "unit": "images/sec",
        "vs_baseline": round(mfu / 0.60, 4),
        "backend": backend, "batch": batch,
        "mfu": round(mfu, 4),
        "layout": _executed_layout(main_p, [avg_cost], layout),
        "declared_layout": layout,
        "optimize_passes": _optimize_passes_label(),
    }))


def _run_child(env_extra, timeout, tag="child"):
    """Run this file with --child, STREAMING its merged stdout/stderr
    line-by-line (flushed, '# '-prefixed) so a killed parent still
    leaves a diagnostic tail. A child counts only if it exits 0 inside
    its timeout AND printed a record.
    Returns (ok, json_obj_or_None, tail)."""
    timeout = max(5.0, float(timeout))
    env = dict(os.environ)
    env.update(env_extra)
    env["PYTHONUNBUFFERED"] = "1"
    proc = subprocess.Popen(
        [sys.executable, _CHILD_SCRIPT, "--child"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, errors="replace", bufsize=1)
    lines = []

    def _pump():
        for line in proc.stdout:
            line = line.rstrip("\n")
            lines.append(line)
            print(f"# [{tag}] {line}", flush=True)
        proc.stdout.close()

    t = threading.Thread(target=_pump, daemon=True)
    t.start()
    timed_out = False
    try:
        proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        timed_out = True
        proc.kill()
        proc.wait()
    t.join(timeout=10)
    tail = "\n".join(lines)[-800:]
    if timed_out:
        return False, None, f"timeout after {timeout:.0f}s; tail: {tail}"
    obj = _extract_json(lines)
    if proc.returncode != 0 or obj is None:
        return False, None, f"rc={proc.returncode}; tail: {tail}"
    return True, obj, tail


def _extract_json(lines):
    """Last parseable JSON-object line, or None (the child contract:
    the record is the last '{'-line it prints)."""
    for line in reversed(lines):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                return None
    return None


def _metric_for(model):
    if model == "transformer":
        return "llama_train_tokens_per_sec_per_chip", "tokens/sec"
    if model == "llama-decode":
        return "llama_decode_tokens_per_sec_per_chip", "tokens/sec"
    if model == "llama-8b-decode":
        return "llama8b_int8_decode_tokens_per_sec_per_chip", "tokens/sec"
    if model in ("seq2seq", "stacked-lstm"):
        return (f"{model.replace('-', '_')}_train_words_per_sec_per_chip",
                "words/sec")
    if model == "resnet50-pipe":
        return "resnet50_pipe_train_images_per_sec_per_chip", "images/sec"
    if model == "deepfm":
        return "deepfm_train_examples_per_sec_per_chip", "examples/sec"
    if model == "llama-spec-decode":
        return "llama_spec_decode_tokens_per_sec_per_chip", "tokens/sec"
    if model == "vgg16":
        return "vgg16_train_images_per_sec_per_chip", "images/sec"
    if model == "layout-speedup":
        return "mnist_conv_layout_speedup", "x"
    return "resnet50_train_images_per_sec_per_chip", "images/sec"


# Budget-aware mode ladder for the default run (BENCH_MODEL unset):
# primary headline first, then the published high-value configs while
# time remains.  `est` = pessimistic child wall-clock (compile+measure)
# used to decide whether a rung is attempted at all; with a warm
# persistent compile cache the real cost is far lower.
_LADDER = [
    ("resnet50", {}, 0),            # primary — always attempted
    ("llama-decode", {"BENCH_QUANT": "1", "BENCH_DIM": "2048",
                      "BENCH_BATCH": "8"}, 420),
    ("transformer", {"BENCH_DIM": "4096", "BENCH_LAYERS": "4",
                     "BENCH_BATCH": "32", "BENCH_SEQ": "1024",
                     "BENCH_OPT": "momentum"}, 480),
    # batch-serving throughput config (int8 KV)
    ("llama-8b-decode", {"BENCH_BATCH": "128", "BENCH_KV_INT8": "1"},
     420),
    # sparse CTR path — small graph, cheap compile
    ("deepfm", {}, 180),
    # speculative-decode machinery floor (alpha~0 random draft)
    ("llama-spec-decode", {"BENCH_GAMMA": "4"}, 420),
]


def main():
    _say(f"total budget {TOTAL_BUDGET:.0f}s; model="
         f"{os.environ.get('BENCH_MODEL', '<ladder>')}")
    errors = []
    results = []
    fixed_model = os.environ.get("BENCH_MODEL", "")
    ladder = ([(fixed_model, {}, 0)] if fixed_model else _LADDER)

    for model, env_extra, est in ladder:
        budget = _remaining() - 15
        # extras must not endanger what we already measured: a rung
        # is attempted only if its estimate fits what is left
        if budget < max(est, 60):
            _say(f"skip {model}: {budget:.0f}s left < est {est}s")
            continue
        _say(f"run {model} (timeout {min(budget, CHILD_TIMEOUT):.0f}s)")
        ok, obj, tail = _run_child(
            dict(env_extra, BENCH_MODEL=model),
            min(budget, CHILD_TIMEOUT), tag=model)
        if ok:
            results.append(obj)
        else:
            errors.append(f"{model}: {tail[-300:]}")

    if not results:
        metric, unit = _metric_for(fixed_model or "resnet50")
        print(json.dumps({"metric": metric, "unit": unit,
                          "error": " | ".join(errors)[-2000:]
                          or "no rung fit the budget"}), flush=True)
        sys.exit(1)

    # Final record: the primary (first) result, with every extra rung's
    # driver-verified number attached.  One JSON line, printed last.
    rec = dict(results[0])
    if len(results) > 1:
        rec["extra_results"] = results[1:]
    best = max(results, key=lambda r: r.get("vs_baseline", 0.0))
    if best is not results[0]:
        rec["best_vs_baseline"] = best.get("vs_baseline")
        rec["best_metric"] = best.get("metric")
    if errors:
        rec["bench_errors"] = errors[-3:]
    _say(f"done in {time.time() - _T0:.0f}s with {len(results)} result(s)"
         f", {len(errors)} failed rung(s)")
    print(json.dumps(rec), flush=True)
    if errors:
        sys.exit(1)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        child_main()
    else:
        main()
