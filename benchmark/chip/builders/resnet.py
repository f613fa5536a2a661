"""Builds the program's ResNet-50 training step the way a gluon user
does (the recipe of ``chip_smoke.phase_train``): zoo network, cast,
``parallel.TrainStep(mesh=None)``; then gives it the benchmark's weights
and says which of the program's parameters is which of the reference's
leaves."""
import re


def _ref_name(name):
    """``features.5.0.body.3.weight`` -> ``s1.b0.c2.w``."""
    kinds = {"weight": "w", "gamma": "gamma", "beta": "beta",
             "bias": "b"}
    m = re.fullmatch(r"features\.(\d)\.(\w+)", name)
    if m and m.group(2) in kinds:
        return "stem." + kinds[m.group(2)]
    m = re.fullmatch(r"features\.(\d)\.(\d+)\.(body|downsample)\.(\d)\.(\w+)",
                     name)
    if m and m.group(5) in kinds:
        conv = "down" if m.group(3) == "downsample" \
            else "c%d" % (int(m.group(4)) // 3 + 1)
        return "s%d.b%s.%s.%s" % (int(m.group(1)) - 4, m.group(2), conv,
                                  kinds[m.group(5)])
    m = re.fullmatch(r"output\.(weight|bias)", name)
    if m:
        return "fc." + kinds[m.group(1)]
    return None  # running statistics: no leaf of the reference


class TrainCell:
    """The compiled step with its state: ``step(x, y)`` is
    ``TrainStep.__call__``."""

    def __init__(self, model, weights):
        import mxnet_tpu as mx
        from mxnet_tpu import gluon, parallel
        from mxnet_tpu.gluon.model_zoo import vision
        from mxnet_tpu.ndarray.ndarray import NDArray
        self._NDArray = NDArray
        net = getattr(vision, model["zoo_name"])(
            classes=model["num_classes"])
        net.cast(model["param_dtype"])
        net.initialize()
        size = model["image_size"]
        net(mx.np.zeros((1, 3, size, size), dtype=model["param_dtype"]))
        self.names = {}
        for name, p in net.collect_params().items():
            ref = _ref_name(name)
            if ref is not None and p.grad_req != "null":
                p.set_data(NDArray(weights[ref]))
                self.names[name] = ref
        missing = set(weights) - set(self.names.values())
        if missing:
            raise RuntimeError("reference leaves the program has no "
                               "parameter for: %s" % sorted(missing))
        o = model["optimizer"]
        opt = getattr(mx.optimizer, o["name"])(
            learning_rate=o["learning_rate"], momentum=o["momentum"],
            wd=o["wd"])
        self.net = net
        self.step = parallel.TrainStep(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), opt, mesh=None)

    def wrap(self, x, y):
        return self._NDArray(x), self._NDArray(y)

    def params(self):
        """Copies of the trainable leaves under the reference's names."""
        import jax.numpy as jnp
        ps = self.net.collect_params()
        return {ref: jnp.copy(ps[name].data()._data)
                for name, ref in self.names.items()}

    def momentum(self):
        import jax.numpy as jnp
        return {ref: jnp.copy(self.step._states[name][0])
                for name, ref in self.names.items()}

    def temp_bytes(self, x, y):
        """Temporaries of the compiled step program (the runtime's
        ``peak_bytes_in_use`` leaves them out)."""
        ma = self.step.lower(x, y).compile().memory_analysis()
        return int(ma.temp_size_in_bytes)

    def free(self):
        self.net = self.step = None
