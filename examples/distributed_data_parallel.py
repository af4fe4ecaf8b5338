"""Data-parallel ResNet training over a device mesh — the
ParallelExecutor flow (docs/DISTRIBUTED.md). On one host this uses all
local chips; on a pod, call paddle_tpu.parallel.init_distributed()
first and run the same script on every host.

Try it anywhere with a virtual mesh:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python examples/distributed_data_parallel.py --cpu
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=32)
    args = ap.parse_args()
    import numpy as np
    import paddle_tpu as fluid
    if args.cpu:
        fluid.force_cpu()   # BEFORE any device op
    from paddle_tpu import parallel
    from paddle_tpu.models.resnet import resnet_cifar10

    img = fluid.layers.data(name="img", shape=[3, 32, 32],
                            dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    pred = resnet_cifar10(img, class_num=10, depth=20)
    loss = fluid.layers.mean(
        fluid.layers.cross_entropy(input=pred, label=label))
    fluid.optimizer.Momentum(learning_rate=0.05,
                             momentum=0.9).minimize(loss)

    mesh = parallel.DeviceMesh({"dp": -1})   # every visible device
    print("mesh:", dict(mesh.axes))
    startup_exe = fluid.Executor(fluid.CPUPlace() if args.cpu
                                 else fluid.TPUPlace())
    startup_exe.run(fluid.default_startup_program())
    pe = fluid.ParallelExecutor(loss_name=loss.name, mesh=mesh)

    rng = np.random.RandomState(0)
    for step in range(args.steps):
        lab = rng.randint(0, 10, (args.batch, 1))
        xs = (rng.randn(args.batch, 3, 32, 32) * 0.2
              + (lab[:, :, None, None] % 3)).astype(np.float32)
        out = pe.run(fetch_list=[loss.name],
                     feed={"img": xs, "label": lab.astype(np.int64)})
        print(f"step {step}: "
              f"loss={float(np.asarray(out[0]).reshape(())):.4f}")


if __name__ == "__main__":
    main()
