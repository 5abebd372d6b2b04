"""ResNet v1 from the Gluon model zoo: the training job that
`train_imagenet.py --kv-store tpu` builds, and the exported net behind
`Predictor` + `DynamicBatcher` as `ModelServer` wires them.
"""
from __future__ import annotations

import itertools
import os
import re
import tempfile

from .. import flops, traffic as gen
from ..reference import compare, resnet_v1

# Largest |system - reference| logit over the largest |reference| logit, on
# the sample. Measured at full width (ResNet-50, CPU rehearsal, seeds 1-2):
#   training forward pass in bfloat16 (53 BatchNorms on the batch's own
#     statistics, each renormalizing the rounding of the layers before it):
#     0.083-0.115, the same at 4, 16 and 32 images; in float32: 2.4e-5
#   exported net in float32 on running statistics: 5e-7; in bfloat16: 1.5e-2
#   a forward pass that skipped BatchNorm: 17
# So bfloat16 training gets three times what it measured, which any missing
# layer exceeds many times over, and float32 serving a bound that the same
# net computed in bfloat16 fails by two orders of magnitude.
TOLERANCE = {"bfloat16": 0.3, "float32": 2e-4}


def train_flops_per_item(config, traffic):
    return flops.resnet_train_flops_per_item(config)


def _index(name):
    return int(re.search(r"(\d+)_[a-z_]+$", name).group(1))


def reference_args(named):
    """The program's name -> array dict as the reference's (convs, bns,
    dense): layers carry a creation counter in their names."""
    convs = sorted((k for k in named if re.search(r"conv\w*_weight$", k)),
                   key=_index)
    norms = sorted({k.rsplit("_", 1)[0] for k in named
                    if k.endswith("_gamma")}, key=lambda p: _index(p + "_x"))
    dense = [k for k in named if re.search(r"dense\d+_weight$", k)]
    assert len(dense) == 1, dense
    return ([named[k] for k in convs],
            [(named[p + "_gamma"], named[p + "_beta"],
              named[p + "_running_mean"], named[p + "_running_var"])
             for p in norms],
            (named[dense[0]], named[dense[0].replace("weight", "bias")]))


def seeded_norms(named, seed):
    """Every BatchNorm leaf redrawn from the seed in one jitted call. The
    zoo initializes them to the identity (scale 1, shift 0, mean 0,
    variance 1), under which a forward pass that skipped BatchNorm would
    still pass the comparison."""
    import jax
    import jax.numpy as jnp
    names = sorted(k for k in named if re.search(
        r"_(gamma|beta|running_mean|running_var)$", k))
    shapes = [named[k].shape for k in names]

    @jax.jit
    def draw(key):
        out = []
        for i, (k, s) in enumerate(zip(names, shapes)):
            sub = jax.random.fold_in(key, i)
            if k.endswith(("gamma", "running_var")):
                out.append(jax.random.uniform(sub, s, jnp.float32, 0.5, 1.5))
            else:
                out.append(0.1 * jax.random.normal(sub, s, jnp.float32))
        return out
    return dict(zip(names, draw(jax.random.PRNGKey(seed))))


def _net(config, seed, batch):
    """The zoo's net, initialized as train_imagenet.py does, its deferred
    shapes resolved without compiling a forward pass."""
    import jax.numpy as jnp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.model_zoo import vision
    mx.random.seed(seed)
    net = getattr(vision, config["network"])(classes=config["classes"])
    net.initialize(mx.init.Xavier(magnitude=2.0))
    x0 = mx.nd.NDArray(jnp.zeros((batch,) + tuple(config["image_shape"]),
                                 jnp.float32))
    return net, x0


class TrainJob:
    """SGD-momentum training through `parallel.TrainStep`, one dispatch a
    step, fed from a ring of host float32 batches through
    `io.prefetch_to_device`."""

    def __init__(self, cell, seed, spans):
        import jax
        import jax.numpy as jnp
        from incubator_mxnet_tpu.io.prefetch import prefetch_to_device
        from incubator_mxnet_tpu.parallel import TrainStep, make_mesh
        config, mix = cell.config, cell.traffic
        self.config, self.seed, self.spans = config, seed, spans
        self.chips = cell.chips
        batch = mix["batch_per_chip"] * cell.chips
        self.items_per_step = batch
        self.net, x0 = _net(config, seed, batch)
        mesh = make_mesh({"dp": cell.chips}, jax.devices()[:cell.chips]) \
            if cell.chips > 1 else None

        def loss_fn(out, label):        # train_imagenet.py's
            logp = jax.nn.log_softmax(out.astype(jnp.float32), -1)
            return -jnp.mean(jnp.take_along_axis(
                logp, label.astype(jnp.int32)[:, None], 1))

        self.dtype = mix["dtype"]
        self.step = TrainStep(
            self.net, loss_fn, optimizer="sgd",
            optimizer_params={"learning_rate": mix["lr"],
                              "momentum": mix["momentum"], "wd": mix["wd"]},
            mesh=mesh, example_inputs=[x0],
            dtype=None if self.dtype == "float32" else self.dtype)
        for k, v in seeded_norms(self.step.params, seed).items():
            old = self.step.params[k]
            self.step.params[k] = jax.device_put(v.astype(old.dtype),
                                                 old.sharding)
        images = gen.image_ring(mix["ring"], batch, config["image_shape"],
                                seed)
        labels = gen.label_ring(mix["ring"], batch, config["classes"], seed)
        self.prefetch = prefetch_to_device(
            itertools.cycle(zip(images, labels)), size=mix["prefetch"],
            mesh=mesh)

    def dispatch(self):
        with self.spans("wait_input"):
            x, y = next(self.prefetch)
        with self.spans("dispatch"):
            return self.step(x, y)

    def counters(self):
        s = self.prefetch.stats()
        return {"prefetch.wait_ms_total": s["wait_ms_total"],
                "prefetch.batches": s["batches"],
                "prefetch.h2d_bytes": s["h2d_bytes"],
                "trainstep.preplaced_hits": self.step.preplaced_hits}

    def check(self, n):
        """The net's logits in the step's dtype, with the batch's own
        BatchNorm statistics as in a training forward pass, against the
        reference on `n` seeded images; on one device, XLA candidates only
        (the sample's shapes are not the cell's, and racing kernels for
        them would add searches that serve no measured step)."""
        import jax
        import jax.numpy as jnp
        from incubator_mxnet_tpu import tune
        from incubator_mxnet_tpu.parallel.functional import functionalize
        x = jnp.asarray(gen.image_ring(1, n, self.config["image_shape"],
                                       self.seed + 1)[0])
        one = jax.devices()[0]
        params = {k: jax.device_put(v, one)
                  for k, v in self.step.params.items()}
        _, apply_fn = functionalize(self.net, [x], training=True)

        def system(p, x_):
            with tune.xla_only("perfbench's correctness sample"):
                return apply_fn(p, jax.random.PRNGKey(0), x_)[0][0]
        got = jax.jit(system)(params, x.astype(self.dtype))
        want = jax.jit(lambda p, x_: resnet_v1.forward(
            *reference_args(p), x_, self.config, training=True))(params, x)
        return compare(got, want, TOLERANCE[self.dtype],
                       f"{n} images, {self.dtype} against the float32 "
                       "reference")

    def close(self):
        self.prefetch.close()


class ServeJob:
    """The exported float32 net behind `Predictor.from_artifact` (ladder
    prewarmed) and the `DynamicBatcher` that `ModelServer` constructs, with
    the server's defaults, called in process through `submit()`."""

    def __init__(self, cell, seed, spans):
        from incubator_mxnet_tpu.parallel.functional import functionalize
        from incubator_mxnet_tpu.serve import ModelServer, Predictor
        config, mix = cell.config, cell.traffic
        self.config, self.seed = config, seed
        shape = tuple(config["image_shape"])
        self.net, x0 = _net(config, seed, 1)
        functionalize(self.net, [x0], training=False)   # deferred shapes
        named = {p.name: p.data()._data
                 for p in self.net.collect_params().values()}
        import incubator_mxnet_tpu as mx
        for k, v in seeded_norms(named, seed).items():
            self.net.collect_params()[k].set_data(mx.nd.NDArray(v))
        with tempfile.TemporaryDirectory(prefix="perfbench_") as tmp:
            prefix = os.path.join(tmp, config["network"])
            self.net.export(prefix)
            self.predictor = Predictor.from_artifact(
                prefix, input_shapes={"data": (1,) + shape},
                bucket_sizes=tuple(mix["ladder"]), prewarm=True)
        self.server = ModelServer(self.predictor)       # never start()ed:
        self.batcher = self.server.batcher.start()      # no HTTP, no JSON
        self.ring = gen.image_ring(1, mix["ring"], shape, seed)[0]

    def submit(self, i):
        """A Future for request i, or None where admission shed it."""
        from incubator_mxnet_tpu.serve import Overloaded
        try:
            return self.batcher.submit(
                {"data": self.ring[i % len(self.ring)]})
        except Overloaded:
            return None

    def warmup_bursts(self):
        return list(self.predictor.ladder.sizes)

    def counters(self):
        snap = self.server.stats.snapshot()
        return {f"serve.{k}": snap[k] for k in (
            "requests_total", "responses_ok", "shed_queue_full",
            "shed_deadline", "shed_total", "errors", "batches_total",
            "padded_rows_total")}

    def check(self, n):
        """`Predictor.predict` on `n` seeded images (a whole bucket of the
        ladder) against the reference with the running statistics."""
        import jax
        x = gen.image_ring(1, n, self.config["image_shape"],
                           self.seed + 1)[0]
        got = self.predictor.predict({"data": x})[0]
        named = {p.name: p.data()._data
                 for p in self.net.collect_params().values()}
        want = jax.jit(lambda p, x_: resnet_v1.forward(
            *reference_args(p), x_, self.config, training=False))(named, x)
        return compare(got, want, TOLERANCE["float32"],
                       f"{n} images, float32 against the float32 reference")

    def close(self):
        self.batcher.stop()
