"""Operations a ResNet training step needs, from the configuration's
shapes alone (He et al. 2015, Table 1): whatever implements the
convolutions, these are the multiply-accumulates the algorithm asks for.
A multiply-accumulate is two operations."""


def conv_layers(model):
    """``(cin, cout, kernel, out_size, has_input_grad)`` of every
    convolution in forward order, the classifier last as a 1x1 on a 1x1
    map.  A bottleneck stage's first block strides in its 3x3."""
    size = model["image_size"] // 2            # stem, stride 2
    layers = [(3, model["channels"][0], 7, size, False)]
    size //= 2                                  # max pool, stride 2
    cin = model["channels"][0]
    for s, (blocks, cout) in enumerate(zip(model["layers"],
                                           model["channels"][1:])):
        for b in range(blocks):
            stride = 2 if (b == 0 and s > 0) else 1
            mid = cout // 4
            layers.append((cin, mid, 1, size, True))
            layers.append((mid, mid, 3, size // stride, True))
            layers.append((mid, cout, 1, size // stride, True))
            if b == 0:
                layers.append((cin, cout, 1, size // stride, True))
            size //= stride
            cin = cout
    layers.append((cin, model["num_classes"], 1, 1, True))
    return layers


def forward_macs_per_image(model):
    return sum(cin * cout * k * k * size * size
               for cin, cout, k, size, _ in conv_layers(model))


def train_step_flops(model):
    """Forward, the gradient to the weights and the gradient to the
    input of every convolution and of the classifier, for one batch; the
    stem needs no gradient to its input."""
    per_image = sum(cin * cout * k * k * size * size * (3 if grad else 2)
                    for cin, cout, k, size, grad in conv_layers(model))
    return 2.0 * per_image * model["batch_size"]


def model_flops_per_step(model):
    """The customary model FLOPs of a step: three times the forward's."""
    return 3 * 2.0 * forward_macs_per_image(model) * model["batch_size"]
