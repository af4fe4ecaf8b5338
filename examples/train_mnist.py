"""Train a small MNIST classifier end to end — the chapter-2
"recognize digits" flow (reference
python/paddle/fluid/tests/book/test_recognize_digits.py) on TPU-native
execution: the whole step (forward + backward + Adam) compiles into one
XLA executable.

Run:  python examples/train_mnist.py  [--epochs N]
Uses the real MNIST files when downloaded under ~/.cache/paddle_tpu,
synthetic shape-compatible data otherwise (zero-egress default).
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np                                      # noqa: E402

import paddle_tpu as fluid                              # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--cpu", action="store_true",
                    help="force CPUPlace (default: TPUPlace)")
    args = ap.parse_args()
    if args.cpu:
        fluid.force_cpu()   # BEFORE any device op

    img = fluid.layers.data(name="img", shape=[784], dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    hidden = fluid.layers.fc(input=img, size=200, act="relu")
    predict = fluid.layers.fc(input=hidden, size=10, act="softmax")
    loss = fluid.layers.mean(
        fluid.layers.cross_entropy(input=predict, label=label))
    acc = fluid.layers.accuracy(input=predict, label=label)
    fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)

    place = fluid.CPUPlace() if args.cpu else fluid.TPUPlace()
    exe = fluid.Executor(place)
    exe.run(fluid.default_startup_program())

    reader = fluid.batch(
        fluid.reader.shuffle(fluid.dataset.mnist.train(), buf_size=2048),
        batch_size=args.batch)
    feeder = fluid.DataFeeder(feed_list=[img, label], place=place)

    for epoch in range(args.epochs):
        for step, batch in enumerate(reader()):
            out = exe.run(feed=feeder.feed(batch),
                          fetch_list=[loss, acc])
            if step % 100 == 0:
                print(f"epoch {epoch} step {step}: "
                      f"loss={float(np.asarray(out[0]).reshape(())):.4f} "
                      f"acc={float(np.asarray(out[1]).reshape(())):.3f}")
            if step >= 300:
                break
    print("done")


if __name__ == "__main__":
    main()
