#!/usr/bin/env python
"""ImageNet-class training entry point (the reference's north-star command:
`train_imagenet.py --kv-store tpu`).

Reference: example/image-classification/train_imagenet.py + common/fit.py.
TPU-native: with --kv-store tpu the whole step (fwd+bwd+allreduce+update)
is ONE pjit'd XLA program over a dp mesh (parallel.TrainStep) and the job
refuses to start without a TPU; `device` is the same compiled path on
whatever backend jax found; `local` runs the eager Gluon Trainer path.
One process drives every chip of the host. Data comes from an ImageRecordIter
.rec file when --data-train is given, else a synthetic stream (for
benchmarking and smoke tests, like benchmark_score.py's dummy data).
"""
import argparse
import logging
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as np


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="train imagenet",
                                formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    p.add_argument("--network", default="resnet50_v1",
                   help="gluon.model_zoo.vision model name")
    p.add_argument("--batch-size", type=int, default=128)
    p.add_argument("--num-epochs", type=int, default=1)
    p.add_argument("--num-batches", type=int, default=50,
                   help="batches per epoch for synthetic data")
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--mom", type=float, default=0.9)
    p.add_argument("--wd", type=float, default=1e-4)
    p.add_argument("--kv-store", default="tpu",
                   choices=["local", "device", "tpu", "dist_sync"])
    p.add_argument("--dtype", default="bfloat16",
                   choices=["float32", "bfloat16"])
    p.add_argument("--image-shape", default="3,224,224")
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--data-train", default=None, help=".rec file (optional)")
    p.add_argument("--dp", type=int, default=0,
                   help="data-parallel mesh size (0 = all devices)")
    p.add_argument("--disp-batches", type=int, default=10)
    return p.parse_args(argv)


def get_data(args, shape):
    import incubator_mxnet_tpu as mx
    if args.data_train:
        return mx.io.ImageRecordIter(
            path_imgrec=args.data_train, data_shape=shape,
            batch_size=args.batch_size, shuffle=True)
    rs = np.random.RandomState(0)
    X = rs.normal(0, 1, (args.batch_size,) + shape).astype(np.float32)
    Y = rs.randint(0, args.num_classes, args.batch_size).astype(np.float32)

    class Synthetic:
        def __iter__(self):
            for _ in range(args.num_batches):
                yield mx.nd.array(X), mx.nd.array(Y)

        def reset(self):
            pass

    return Synthetic()


def main(argv=None):
    """Run the job; returns (step_or_trainer, records) with one
    (epoch, batch, loss, seconds-since-start) record per displayed batch —
    what chip_smoke.py checks after driving this entry point."""
    args = parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    t_start = time.time()
    import jax
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.model_zoo import vision

    platform = jax.devices()[0].platform
    if args.kv_store == "tpu" and platform != "tpu":
        raise SystemExit(
            f"--kv-store tpu needs a TPU, but jax came up on {platform!r}; "
            "--kv-store device runs the same compiled step on whatever "
            "backend is present")

    shape = tuple(int(x) for x in args.image_shape.split(","))
    net = getattr(vision, args.network)(classes=args.num_classes)
    net.initialize(mx.init.Xavier(magnitude=2.0))
    data = get_data(args, shape)
    records = []

    def show(epoch, i, loss, n, tic):
        loss = float(loss.asnumpy() if hasattr(loss, "asnumpy") else loss)
        records.append((epoch, i + 1, loss, time.time() - t_start))
        logging.info("epoch %d batch %d loss %.4f  %.1f img/s",
                     epoch, i + 1, loss, n / (time.time() - tic))

    if args.kv_store in ("tpu", "device"):
        # compiled SPMD path: dp mesh over all chips, ONE XLA program/step
        from incubator_mxnet_tpu.parallel import TrainStep, make_mesh

        ndev = args.dp or len(jax.devices())
        mesh = make_mesh({"dp": ndev}) if ndev > 1 else None

        def loss_fn(out, label):
            logp = jax.nn.log_softmax(out.astype(jnp.float32), -1)
            return -jnp.mean(jnp.take_along_axis(
                logp, label.astype(jnp.int32)[:, None], 1))

        x0 = mx.nd.array(np.zeros((args.batch_size,) + shape, np.float32))
        step = TrainStep(net, loss_fn, optimizer="sgd",
                         optimizer_params={"learning_rate": args.lr,
                                           "momentum": args.mom,
                                           "wd": args.wd},
                         mesh=mesh, example_inputs=[x0],
                         dtype=None if args.dtype == "float32" else args.dtype)
        for epoch in range(args.num_epochs):
            tic = time.time()
            n = 0
            for i, (x, y) in enumerate(data):
                loss = step(x, y)
                n += args.batch_size
                if (i + 1) % args.disp_batches == 0:
                    show(epoch, i, loss, n, tic)
            data.reset()
            step.sync()
            logging.info("epoch %d done: %.1f img/s", epoch,
                         n / (time.time() - tic))
        return step, records
    else:
        from incubator_mxnet_tpu import autograd, gluon
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": args.lr,
                                 "momentum": args.mom, "wd": args.wd},
                                kvstore=args.kv_store)
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        for epoch in range(args.num_epochs):
            tic = time.time()
            n = 0
            for i, (x, y) in enumerate(data):
                with autograd.record():
                    loss = loss_fn(net(x), y).mean()
                loss.backward()
                trainer.step(args.batch_size)
                n += args.batch_size
                if (i + 1) % args.disp_batches == 0:
                    show(epoch, i, loss, n, tic)
            data.reset()
            logging.info("epoch %d done: %.1f img/s", epoch,
                         n / (time.time() - tic))
        return trainer, records


if __name__ == "__main__":
    main()
