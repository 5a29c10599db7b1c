"""Builder for ResNet training configurations (`"builder": "resnet50"`).

Builds the system under test as a user of the Program path does —
`models/resnet.build_train_program` run through transforms, verifier
and `fluid.Executor` with default flags, fed numpy every step
(`exe.run(feed=..., return_numpy=False)`), the way
`chip_smoke.executor_resnet50` proved on the chip.  On several chips
the same Program goes through
`CompiledProgram.with_data_parallel(places=tpu_places())` on a
`{data: n}` mesh, the traffic's batch being the global one.

Its checks: the system's forward pass (a forward-only twin Program
over the same scope, batch norm in training mode) against
`benchmark/reference/resnet50.py` on a seeded sample; on several
chips, the first loss against the one-device forward twin over the
same global batch, and the parameters on every device.
"""

from __future__ import annotations

import concurrent.futures
import math
import os

import jax
import numpy as np

from benchmark.lib import flops
from benchmark.reference import resnet50 as reference

# first loss of the data-parallel step against the one-device forward
# of the same global batch: both run float32 convolutions in bf16
# passes, in different orders (chip_smoke's tolerance)
DATA_PARALLEL_TOLERANCE = 2e-2
_SLICE = 128      # images drawn by one generator; fixed, so that a pool
                  # does not depend on the number of cores


def make_pool(config: dict, batch: int, n: int, seed: int) -> list:
    """`n` host batches of float32 images ~ N(0, 1) and int64 labels.
    Drawn in slices of 128 images, each from its own generator seeded
    with (seed, slice), in threads: numpy's generators release the
    interpreter lock, and 8 batches of 512 are 2.5 GB."""
    size = config["image_size"]
    images = [np.empty((batch, 3, size, size), np.float32)
              for _ in range(n)]
    slices = [(b, lo) for b in range(n) for lo in range(0, batch, _SLICE)]

    def fill(job):
        (b, lo), k = job
        hi = min(lo + _SLICE, batch)
        np.random.default_rng([seed, k]).standard_normal(
            dtype=np.float32, out=images[b][lo:hi])

    with concurrent.futures.ThreadPoolExecutor(
            min(len(slices), os.cpu_count() or 1)) as threads:
        list(threads.map(fill, zip(slices, range(len(slices)))))
    rng = np.random.default_rng([seed, len(slices)])
    return [{"image": img,
             "label": rng.integers(0, config["num_classes"], (batch, 1),
                                   dtype=np.int64)} for img in images]


class ResNetSystem:
    """The step runner the loop drives; see `BertSystem`."""

    def __init__(self, config, traffic, chips, seed, spans):
        import paddle_tpu
        import paddle_tpu.fluid as fluid
        from paddle_tpu.fluid import unique_name
        from paddle_tpu.fluid.executor import Scope
        from paddle_tpu.models import resnet

        self.spans = spans
        self.chips = chips
        self.items_per_step = traffic["batch"]
        self.flops_per_item = flops.resnet_train_flops_per_image(config)
        self.untrained_loss = math.log(config["num_classes"])
        self.first_loss_band = config["first_loss_band"]
        self.kernels = {}
        self.kernel_ops = {}
        self._net = dict(depth=config["depth"], width=config["width"],
                         class_num=config["num_classes"])
        self._shape = (3, config["image_size"], config["image_size"])
        if resnet._CONFIGS[config["depth"]] != (
                config["block"], config["stage_blocks"]):
            raise ValueError("models/resnet.py builds another layout for "
                             f"depth {config['depth']} than the file says")
        tr = config["training"]

        with spans.span("setup.pool"):
            self.pool = make_pool(config, traffic["batch"],
                                  traffic["pool_batches"], seed)
        with spans.span("setup.model"):
            paddle_tpu.seed(seed)
            with unique_name.guard():
                main, startup, _, fetches = resnet.build_train_program(
                    image_shape=self._shape, batch_size=traffic["batch"],
                    optimizer=fluid.optimizer.Momentum(
                        learning_rate=tr["learning_rate"],
                        momentum=tr["momentum"],
                        regularization=fluid.regularizer.L2Decay(
                            tr["l2_decay"])), **self._net)
            self._main, self._fetches = main, list(fetches)
            self._scope = Scope()
            place = (fluid.TPUPlace(0)
                     if jax.devices()[0].platform == "tpu"
                     else fluid.CPUPlace())
            self._exe = fluid.Executor(place)
            self._exe.run(startup, scope=self._scope)
        with spans.span("setup.reference"):
            sample = make_pool(config, traffic["reference_sample"], 1,
                               seed + 1)[0]
            self.reference = self._compare_with_reference(config, sample)
        self.data_parallel = None
        self._target = main
        if chips > 1:
            with spans.span("setup.data_parallel"):
                self.data_parallel = {
                    "twin_loss": self._twin(self.pool[0])[0]}
                self._target = self._data_parallel_target(chips)

    # -- the loop's interface ---------------------------------------------
    def step(self, batch):
        # feed and dispatch both happen inside exe.run; the profiler's
        # host_feed_ms / dispatch_ms timers split them
        with self.spans.span("bench.dispatch"):
            return self._exe.run(self._target, feed=batch,
                                 fetch_list=self._fetches,
                                 scope=self._scope, return_numpy=False)[0]

    def fetch(self, loss) -> float:
        return float(np.asarray(loss.jax()).reshape(-1)[0])

    def sync(self) -> None:
        jax.block_until_ready([
            v for v in (self._scope.get(p.name)
                        for p in self._main.list_vars()
                        if p.persistable and self._scope.has(p.name))
            if hasattr(v, "block_until_ready")])

    @property
    def memory_analysis(self) -> dict:
        """The compiler's memory analysis of every program the Executor
        compiled, as the program's own `obs.memprof` captured it."""
        from paddle_tpu.obs import memprof

        return {label: {k: int(prof.get(k, 0)) for k in (
                    "argument_bytes", "output_bytes", "alias_bytes",
                    "temp_bytes", "generated_code_bytes")}
                for label, prof in memprof.profiles().items()}

    def close(self) -> None:
        from paddle_tpu.parallel import mesh as mesh_lib

        self._exe.close()
        mesh_lib.set_current_mesh(None)

    # -- checks ------------------------------------------------------------
    def checks(self, counters_now: dict, first_loss: float) -> dict:
        out = {"reference_matches": self.reference["ok"]}
        if self.data_parallel is not None:
            twin = self.data_parallel["twin_loss"]
            self.data_parallel["first_loss"] = first_loss
            out["first_loss_matches_one_device_twin"] = (
                abs(first_loss - twin)
                <= DATA_PARALLEL_TOLERANCE * max(1.0, abs(twin)))
            param = self._scope.get("conv2d_0.w_0")
            out["parameters_on_every_chip"] = len(
                {s.device for s in param.addressable_shards}) == self.chips
        return out

    def _twin(self, batch):
        """`(loss, class probabilities)` of a forward-only twin of the
        Program — same variable names, same scope, batch norm in
        training mode — on ONE device.  The full train step at n times
        the one-chip batch does not fit one chip; its forward does."""
        import paddle_tpu.fluid as fluid
        from paddle_tpu.fluid import unique_name
        from paddle_tpu.models import resnet

        n = len(batch["label"])
        with unique_name.guard():
            main, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main, startup):
                img = fluid.data("image", [n, *self._shape], "float32")
                label = fluid.data("label", [n, 1], "int64")
                pred = resnet.resnet(img, **self._net)
                loss = fluid.layers.mean(
                    fluid.layers.loss.cross_entropy(pred, label))
        loss_v, pred_v = self._exe.run(main, feed=batch,
                                       fetch_list=[loss, pred],
                                       scope=self._scope)
        return float(np.asarray(loss_v).reshape(-1)[0]), np.asarray(pred_v)

    def _compare_with_reference(self, config, sample) -> dict:
        loss, probs = self._twin(sample)
        params = {
            p.name: self._scope.get(p.name)
            for p in self._main.list_vars()
            if p.persistable and self._scope.has(p.name)
            and p.name.startswith(("conv2d_", "batch_norm_", "fc_"))
            and "velocity" not in p.name}
        ref_loss, ref_probs = reference.forward(
            config, params, sample["image"], sample["label"])
        return reference.compare(loss, probs, float(ref_loss),
                                 np.asarray(ref_probs))

    def _data_parallel_target(self, n):
        import paddle_tpu.fluid as fluid
        from paddle_tpu.parallel.compiler import BuildStrategy

        strategy = BuildStrategy()
        strategy.mesh_axes = {"data": n}
        return fluid.CompiledProgram(self._main).with_data_parallel(
            loss_name=self._fetches[0].name, build_strategy=strategy,
            places=fluid.tpu_places(list(range(n))))


def build(config, traffic, chips, seed, spans) -> ResNetSystem:
    return ResNetSystem(config, traffic, chips, seed, spans)
