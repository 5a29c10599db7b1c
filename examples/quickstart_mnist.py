#!/usr/bin/env python
"""Quickstart: train LeNet on MNIST three ways — the three front ends a
reference (Fluid-era PaddlePaddle) user would reach for, unchanged:

  1. hapi  — `paddle.Model(...).fit(...)`  (2.0 high-level API)
  2. dygraph — eager loop with `loss.backward()` + optimizer.step()
  3. static — fluid Program + Executor (whole block compiles to ONE
     XLA computation on TPU)

Runs on whatever jax backend is attached (TPU if available, CPU
otherwise).  Data is SYNTHETIC (random images/labels — this image has
no dataset downloads); to train on real MNIST, replace
synthetic_batches with paddle.vision.datasets.MNIST pointed at local
IDX files.

Usage: python examples/quickstart_mnist.py [hapi|dygraph|static]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # run from anywhere in the repo

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F


def synthetic_batches(n_batches=40, batch=64, seed=0):
    r = np.random.RandomState(seed)
    for _ in range(n_batches):
        x = r.rand(batch, 1, 28, 28).astype("float32")
        y = r.randint(0, 10, (batch, 1)).astype("int64")
        yield x, y


def run_hapi():
    import paddle_tpu.io as pio
    from paddle_tpu.vision.models import LeNet

    x = np.concatenate([b[0] for b in synthetic_batches(8)])
    y = np.concatenate([b[1] for b in synthetic_batches(8)])

    class Samples(pio.Dataset):
        def __len__(self):
            return len(x)

        def __getitem__(self, i):
            return x[i], y[i]

    model = paddle.Model(LeNet())
    model.prepare(paddle.optimizer.Adam(learning_rate=1e-3,
                                        parameters=model.parameters()),
                  nn.CrossEntropyLoss(),
                  paddle.metric.Accuracy())
    model.fit(Samples(), batch_size=64, epochs=1, verbose=1)


def run_dygraph():
    from paddle_tpu.fluid import dygraph
    from paddle_tpu.vision.models import LeNet

    with dygraph.guard():
        net = LeNet()
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=net.parameters())
        for i, (x, y) in enumerate(synthetic_batches()):
            logits = net(paddle.to_tensor(x))
            loss = F.cross_entropy(logits, paddle.to_tensor(y))
            loss.backward()
            opt.step()
            opt.clear_grad()
            if i % 10 == 0:
                print(f"step {i}: loss {float(loss.numpy()):.4f}")


def run_static():
    import paddle_tpu.fluid as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [-1, 1, 28, 28], "float32")
        y = fluid.data("y", [-1, 1], "int64")
        h = fluid.layers.conv2d(x, 6, 5, act="relu")
        h = fluid.layers.pool2d(h, 2, pool_stride=2)
        h = fluid.layers.conv2d(h, 16, 5, act="relu")
        h = fluid.layers.pool2d(h, 2, pool_stride=2)
        h = fluid.layers.fc(h, 120, act="relu")
        h = fluid.layers.fc(h, 84, act="relu")
        logits = fluid.layers.fc(h, 10)
        loss = fluid.layers.reduce_mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.Adam(1e-3).minimize(loss)

    exe = fluid.Executor()
    exe.run(startup)
    for i, (xb, yb) in enumerate(synthetic_batches()):
        (lv,) = exe.run(main, feed={"x": xb, "y": yb},
                        fetch_list=[loss])
        if i % 10 == 0:
            print(f"step {i}: loss {float(lv):.4f}")


if __name__ == "__main__":
    mode = sys.argv[1] if len(sys.argv) > 1 else "dygraph"
    {"hapi": run_hapi, "dygraph": run_dygraph,
     "static": run_static}[mode]()
