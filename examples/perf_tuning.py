"""Performance-tuning walkthrough: measure, change one lever, re-measure.

Demonstrates the workflow docs/PERFORMANCE.md describes on a small
conv net (runs on CPU or the real chip alike):

  1. `Executor.compiled_stats` — XLA's own flops / bytes / kernel
     histogram for the EXACT executable `run()` dispatches;
  2. AMP O2 (`amp_transpile(level="O2")`) — bf16 activation flow, the
     measured ResNet-50 lever (1,897 -> 2,786 img/s on one v5e);
  3. multi-step dispatch (`run(repeats=k)`);
  4. the profiler's chrome-trace host timeline.

Run:  python examples/perf_tuning.py  [--cpu]
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np                                      # noqa: E402

import paddle_tpu as fluid                              # noqa: E402
from paddle_tpu.models.resnet import resnet_cifar10     # noqa: E402
from paddle_tpu.transpiler import amp_transpile         # noqa: E402


def build(amp_level):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        img = fluid.layers.data("img", [3, 32, 32], dtype="float32")
        label = fluid.layers.data("label", [1], dtype="int64")
        pred = resnet_cifar10(img, class_num=10, depth=20)
        loss = fluid.layers.mean(
            fluid.layers.cross_entropy(input=pred, label=label))
        fluid.optimizer.Momentum(0.1, 0.9).minimize(loss)
    if amp_level:
        amp_transpile(main, level=amp_level)
    return main, startup, loss


def measure(amp_level, repeats=4, iters=5, batch=64):
    main, startup, loss = build(amp_level)
    rng = np.random.RandomState(0)
    feed = {"img": rng.randn(batch, 3, 32, 32).astype(np.float32),
            "label": rng.randint(0, 10, (batch, 1)).astype(np.int64)}
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        # 1. compile-time evidence BEFORE timing anything
        stats = exe.compiled_stats(main, feed=feed, fetch_list=[loss],
                                   repeats=repeats, top_k=3)
        # warmup = compile
        exe.run(main, feed=feed, fetch_list=[loss], repeats=repeats)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = exe.run(main, feed=feed, fetch_list=[loss],
                          return_numpy=False, repeats=repeats)
        final = float(np.asarray(out[0]).reshape(()))
        dt = time.perf_counter() - t0
    ips = batch * iters * repeats / dt
    print(f"\n== amp={amp_level or 'off'}  {ips:,.0f} img/s  "
          f"(loss {final:.3f})")
    print(f"   kernels/dispatch={stats['n_kernels']}  "
          f"bytes/dispatch={stats['bytes_accessed']/2**30:.2f} GiB")
    for row in stats.get("kernel_histogram", [])[:3]:
        print(f"   top bucket: {row['kind']:<22} x{row['count']:<5} "
              f"{row['mbytes']:>10.1f} MB")
    return ips


def main():
    if "--cpu" in sys.argv:
        fluid.force_cpu()   # BEFORE any device op
    # the lever ladder: measure each configuration the same way
    base = measure(None)
    o1 = measure("O1")
    o2 = measure("O2")
    import jax
    print(f"\nO1 vs f32: {o1 / base:.2f}x   O2 vs O1: {o2 / o1:.2f}x")
    if jax.default_backend() == "cpu":
        print("(CPU backend emulates bf16, so amp slows things down "
              "here — compare the BYTES column instead; the speedups "
              "are TPU numbers, see docs/PERFORMANCE.md)")

    # profile the winner: the trace (device and host spans, one clock)
    # lands under ./prof/plugins/profile/<time>/
    main_p, startup_p, loss = build("O2")
    rng = np.random.RandomState(1)
    feed = {"img": rng.randn(64, 3, 32, 32).astype(np.float32),
            "label": rng.randint(0, 10, (64, 1)).astype(np.int64)}
    exe = fluid.Executor()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup_p)
        with fluid.profiler.profiler("All", sorted_key="total",
                                     profile_path="./prof"):
            for i in range(3):
                with fluid.profiler.record_event(f"step{i}"):
                    exe.run(main_p, feed=feed, fetch_list=[loss])
    print("trace: ./prof/plugins/profile/<time>/*.trace.json.gz "
          "(load in chrome://tracing or Perfetto; the .xplane.pb "
          "beside it is TensorBoard's)")


if __name__ == "__main__":
    main()
