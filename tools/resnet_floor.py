"""Analytic minimum HBM bytes/step for the ResNet-50 train bench
(VERDICT r4 task 1a): what would a PERFECT compiler have to move?

The model of "minimum" (optimistic — assumes every elementwise /
batch-norm / pool / residual-add op fuses for free into an adjacent
conv's read or write pass, and nothing but the conv boundary
activations ever crosses HBM):

  forward, per conv:   read A_in, read W, write A_out
  backward, per conv:  read A_out   (recompute the BN+ReLU epilogue),
                       read dY      (written by the next layer's dX),
                       read A_in    (for dW), read W (for dX),
                       write dX, write dW
  optimizer (momentum, f32 master + velocity, bf16 compute copy):
                       read W32, read vel, read dW, write W32,
                       write vel, write W16
  input batch:         read once (uint8-decoded f32 feed cast to bf16)

Activations/grads are billed at the train dtype (bf16 under the bench
AMP-O2 default); params/grads at bf16 with the f32 master/velocity
sweep billed at f32. dY of layer L IS dX of layer L+1: each boundary
gradient is written once and read once — both passes are counted, one
on each side.

This floor is what the measured step (~37-42 GB; builder, an earlier
installation) must be compared against: measured/floor <= ~1.3x means the
bytes-bound conclusion is real, not a stopping excuse. Reference
counterpart of the question: the per-op CUDA kernels of
/root/reference/paddle/fluid/operators/conv_cudnn_op.cu.cc make every
one of these passes explicit; XLA's job is to not add more.

Run: python tools/resnet_floor.py [batch]
Prints one JSON line with the breakdown.
"""
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"      # analytic tool, no chip
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax                                                   # noqa: E402

import numpy as np                                           # noqa: E402

import paddle_tpu as fluid                                   # noqa: E402
from paddle_tpu.models.resnet import resnet50                # noqa: E402


def floor_bytes(batch=128, act_bytes=2, param_bytes=2, opt_bytes=4,
                layout="NHWC"):
    main_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup_p):
        img = fluid.layers.data(name="img", shape=[3, 224, 224],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1],
                                  dtype="int64")
        avg_cost, _, _ = resnet50(img, label, layout=layout)
        fluid.optimizer.Momentum(learning_rate=0.1,
                                 momentum=0.9).minimize(avg_cost)

    def numel(var_name):
        shape = [batch if (d is None or d < 0) else d
                 for d in main_p.global_block().var(var_name).shape]
        return int(np.prod(shape))

    convs = []
    n_params = 0
    block = main_p.global_block()
    for op in block.ops:
        if op.type in ("conv2d", "mul"):       # mul = the final fc
            x_name = op.input("Input" if op.type == "conv2d" else "X")[0]
            w_name = op.input("Filter" if op.type == "conv2d" else "Y")[0]
            out_name = op.output("Output" if op.type == "conv2d"
                                 else "Out")[0]
            convs.append({
                "op": op.type,
                "a_in": numel(x_name),
                "w": numel(w_name),
                "a_out": numel(out_name),
            })
    for name, var in block.vars.items():
        if getattr(var, "persistable", False) and name.endswith(
                (".w_0", ".b_0", ".w_1", ".w_2")):
            pass
    # parameter count from the startup program (it initializes exactly
    # the trainable params + BN stats; velocities are optimizer state)
    for op in startup_p.global_block().ops:
        for n in op.output_names():
            v = block.vars.get(n)
            # skip BN moving stats and optimizer accumulators (their
            # sweep is billed separately in `opt` below)
            if v is not None and not n.endswith(
                    (".global_0", ".global_1")) and "velocity" not in n:
                n_params += numel(n)

    fwd = sum(c["a_in"] + c["w"] + c["a_out"] for c in convs)
    bwd = sum(2 * c["a_out"]            # read A_out (epilogue) + dY
              + 2 * c["a_in"]           # read A_in (dW) + write dX
              + 2 * c["w"]              # read W (dX) + write dW
              for c in convs)
    act_gb = (fwd + bwd) * act_bytes / 2**30
    # weights billed at param dtype in fwd/bwd above — rebill their
    # share: fwd W read + bwd (W read + dW write) are param_bytes wide
    w_total = sum(c["w"] for c in convs)
    opt = n_params * (3 * opt_bytes      # read W32, vel, dW-as-f32
                      + 2 * opt_bytes    # write W32, vel
                      + param_bytes)     # write bf16 compute copy
    input_bytes = batch * 3 * 224 * 224 * act_bytes
    total = (fwd + bwd) * act_bytes + opt + input_bytes
    return {
        "batch": batch,
        "n_convs": len(convs),
        "n_params": n_params,
        "fwd_gb": round(fwd * act_bytes / 2**30, 2),
        "bwd_gb": round(bwd * act_bytes / 2**30, 2),
        "conv_weight_passes_gb": round(3 * w_total * act_bytes / 2**30,
                                       2),
        "optimizer_gb": round(opt / 2**30, 2),
        "input_gb": round(input_bytes / 2**30, 3),
        "floor_gb_per_step": round(total / 2**30, 2),
        "activation_share": round((fwd + bwd) * act_bytes / total, 3),
        "note": ("optimistic floor: perfect epilogue fusion, conv "
                 "boundary activations cross HBM exactly the passes "
                 "listed in the module docstring"),
    }


if __name__ == "__main__":
    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 128
    print(json.dumps(floor_bytes(batch)))
