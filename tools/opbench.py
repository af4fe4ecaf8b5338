"""Per-op microbenchmark harness for the perf work (run on the chip,
or on CPU for plumbing checks).

Times the hot shapes of the headline models — ResNet-50's convolution
spectrum, the flagship's matmul/attention shapes — each as ONE jitted
executable with a forced host-transfer sync. Prints one JSON line per
case:
  {"case": ..., "ms": ..., "tflops": ..., "backend": ...}

Usage:  python tools/opbench.py [filter-substring]
"""
import json
import sys
import time

import numpy as np


def _sync(y):
    np.asarray(y.ravel()[0:1])


def bench_case(name, fn, args, flops, inner=10, backend=""):
    """``flops`` is the TOTAL across the dispatch's ``inner``
    iterations; tflops divides by the whole dispatch time, ms reports
    the per-iteration share."""
    import jax
    f = jax.jit(fn)
    y = f(*args)
    _sync(y)
    # best-of-3: a single hiccup inside the timed window would
    # otherwise be indistinguishable from a real regression
    dt_total = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        y = f(*args)
        _sync(y)
        dt_total = min(dt_total, time.perf_counter() - t0)
    print(json.dumps({
        "case": name, "ms": round(dt_total / inner * 1e3, 3),
        "tflops": round(flops / dt_total / 1e12, 2),
        "backend": backend,
    }), flush=True)


def main(filt=""):
    import jax
    import jax.numpy as jnp
    from jax import lax

    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    dt = jnp.bfloat16 if on_tpu else jnp.float32
    key = jax.random.PRNGKey(0)
    inner = 10

    def chain(op):
        """One dispatch running `inner` dependent iterations, so the
        per-dispatch overhead amortizes. The dependency rides a
        scalar (acc) so ops whose output shape differs from their input
        still execute every iteration (nothing DCEs)."""
        def run(x, *w):
            def body(carry, _):
                c, acc = carry
                o = op(c * (1.0 + acc * 1e-20).astype(c.dtype), *w)
                return (c, acc + o.mean().astype(jnp.float32)), None
            (_, acc), _ = lax.scan(body, (x, jnp.float32(0.0)), None,
                                   length=inner)
            return acc
        return run

    cases = []

    # ResNet-50 convolution spectrum (NCHW, batch 128)
    n = 128 if on_tpu else 4
    for (cin, cout, hw, k, stride) in [
            (64, 64, 56, 3, 1), (128, 128, 28, 3, 1),
            (256, 256, 14, 3, 1), (512, 512, 7, 3, 1),
            (256, 1024, 14, 1, 1), (1024, 256, 14, 1, 1)]:
        x = jax.random.normal(key, (n, cin, hw, hw)).astype(dt) * 0.1
        w = jax.random.normal(key, (cout, cin, k, k)).astype(dt) * 0.1
        dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                        ("NCHW", "OIHW", "NCHW"))
        pad = k // 2

        def conv(c, wv, dn=dn, stride=stride, pad=pad):
            return lax.conv_general_dilated(
                c, wv, (stride, stride), [(pad, pad), (pad, pad)],
                dimension_numbers=dn)

        flops = 2 * n * (hw // stride) ** 2 * cin * cout * k * k * inner
        cases.append((f"conv{k}x{k}_{cin}->{cout}_{hw}px",
                      chain(conv), (x, w), flops))

    # flagship matmuls (batch*seq=4096 rows)
    rows = 4096 if on_tpu else 128
    for (m, kk, nn_) in [(rows, 4096, 4096), (rows, 4096, 14336),
                         (rows, 14336, 4096), (rows, 4096, 16384)]:
        if not on_tpu and max(kk, nn_) > 4096:
            continue
        a = jax.random.normal(key, (m, kk)).astype(dt) * 0.02
        b = jax.random.normal(key, (kk, nn_)).astype(dt) * 0.02

        cases.append((f"matmul_{m}x{kk}x{nn_}",
                      chain(lambda c, bv: c @ bv), (a, b),
                      2 * m * kk * nn_ * inner))

    # flash attention (flagship shape)
    from paddle_tpu.ops.pallas_attention import flash_attention
    bsz, heads, seq, hd = (4, 32, 2048, 128) if on_tpu else (1, 2, 256, 32)
    q = jax.random.normal(key, (bsz, heads, seq, hd)).astype(dt) * 0.1

    def attn(c):
        return flash_attention(c, c, c, True, None)

    # causal: ~half the s^2 score/value work actually runs
    cases.append((f"flash_attn_b{bsz}h{heads}s{seq}",
                  chain(lambda c: attn(c)), (q,),
                  2 * bsz * heads * seq * seq * hd * inner))

    for name, fn, args, flops in cases:
        if filt and filt not in name:
            continue
        try:
            bench_case(name, fn, args, flops, inner, backend)
        except Exception as e:                     # keep sweeping
            print(json.dumps({"case": name,
                              "error": f"{type(e).__name__}: {e}"[:200]}),
                  flush=True)


if __name__ == "__main__":
    sys.path.insert(0, __file__.rsplit("/", 2)[0])
    main(sys.argv[1] if len(sys.argv) > 1 else "")
