"""ResNet V1/V2 (reference: ``gluon/model_zoo/vision/resnet.py`` — the
survey's build-config model; V1 follows the b-variant with stride on the
3x3, matching the reference).

``layout="NHWC"`` builds the channels-last variant: same architecture and
parameter *names*, weights stored OHWI, BN over the trailing axis.  On TPU
this is the MXU-native layout (PERF.md lever 1) — XLA:TPU skips the
re-layout passes the NCHW backward convs need.
"""
from __future__ import annotations

from .... import numpy_extension as npx
from ....ops.nn import channels_last as _channels_last
from ...block import HybridBlock
from ...nn import (Activation, BatchNorm, Conv2D, Dense, Flatten,
                   GlobalAvgPool2D, HybridSequential, MaxPool2D)


def _bn_axis(layout):
    return -1 if _channels_last(layout) else 1


def _conv3x3(channels, stride, in_channels, layout="NCHW"):
    return Conv2D(channels, kernel_size=3, strides=stride, padding=1,
                  use_bias=False, in_channels=in_channels, layout=layout)


class BasicBlockV1(HybridBlock):
    def __init__(self, channels, stride, downsample=False, in_channels=0,
                 layout="NCHW"):
        super().__init__()
        ax = _bn_axis(layout)
        self.body = HybridSequential()
        self.body.add(_conv3x3(channels, stride, in_channels, layout))
        self.body.add(BatchNorm(axis=ax))
        self.body.add(Activation("relu"))
        self.body.add(_conv3x3(channels, 1, channels, layout))
        self.body.add(BatchNorm(axis=ax))
        if downsample:
            self.downsample = HybridSequential()
            self.downsample.add(Conv2D(channels, kernel_size=1,
                                       strides=stride, use_bias=False,
                                       in_channels=in_channels,
                                       layout=layout))
            self.downsample.add(BatchNorm(axis=ax))
        else:
            self.downsample = None

    def forward(self, x):
        residual = x
        x = self.body(x)
        if self.downsample:
            residual = self.downsample(residual)
        return npx.activation(x + residual, "relu")


class BottleneckV1(HybridBlock):
    def __init__(self, channels, stride, downsample=False, in_channels=0,
                 layout="NCHW"):
        super().__init__()
        ax = _bn_axis(layout)
        self.body = HybridSequential()
        self.body.add(Conv2D(channels // 4, kernel_size=1, strides=1,
                             use_bias=False, layout=layout))
        self.body.add(BatchNorm(axis=ax))
        self.body.add(Activation("relu"))
        self.body.add(_conv3x3(channels // 4, stride, channels // 4, layout))
        self.body.add(BatchNorm(axis=ax))
        self.body.add(Activation("relu"))
        self.body.add(Conv2D(channels, kernel_size=1, strides=1,
                             use_bias=False, layout=layout))
        self.body.add(BatchNorm(axis=ax))
        if downsample:
            self.downsample = HybridSequential()
            self.downsample.add(Conv2D(channels, kernel_size=1,
                                       strides=stride, use_bias=False,
                                       in_channels=in_channels,
                                       layout=layout))
            self.downsample.add(BatchNorm(axis=ax))
        else:
            self.downsample = None

    def forward(self, x):
        residual = x
        x = self.body(x)
        if self.downsample:
            residual = self.downsample(residual)
        return npx.activation(x + residual, "relu")


class BasicBlockV2(HybridBlock):
    def __init__(self, channels, stride, downsample=False, in_channels=0,
                 layout="NCHW"):
        super().__init__()
        ax = _bn_axis(layout)
        self.bn1 = BatchNorm(axis=ax)
        self.conv1 = _conv3x3(channels, stride, in_channels, layout)
        self.bn2 = BatchNorm(axis=ax)
        self.conv2 = _conv3x3(channels, 1, channels, layout)
        if downsample:
            self.downsample = Conv2D(channels, 1, stride, use_bias=False,
                                     in_channels=in_channels, layout=layout)
        else:
            self.downsample = None

    def forward(self, x):
        residual = x
        x = self.bn1(x)
        x = npx.activation(x, "relu")
        if self.downsample:
            residual = self.downsample(x)
        x = self.conv1(x)
        x = self.bn2(x)
        x = npx.activation(x, "relu")
        x = self.conv2(x)
        return x + residual


class BottleneckV2(HybridBlock):
    def __init__(self, channels, stride, downsample=False, in_channels=0,
                 layout="NCHW"):
        super().__init__()
        ax = _bn_axis(layout)
        self.bn1 = BatchNorm(axis=ax)
        self.conv1 = Conv2D(channels // 4, 1, 1, use_bias=False,
                            layout=layout)
        self.bn2 = BatchNorm(axis=ax)
        self.conv2 = _conv3x3(channels // 4, stride, channels // 4, layout)
        self.bn3 = BatchNorm(axis=ax)
        self.conv3 = Conv2D(channels, 1, 1, use_bias=False, layout=layout)
        if downsample:
            self.downsample = Conv2D(channels, 1, stride, use_bias=False,
                                     in_channels=in_channels, layout=layout)
        else:
            self.downsample = None

    def forward(self, x):
        residual = x
        x = self.bn1(x)
        x = npx.activation(x, "relu")
        if self.downsample:
            residual = self.downsample(x)
        x = self.conv1(x)
        x = self.bn2(x)
        x = npx.activation(x, "relu")
        x = self.conv2(x)
        x = self.bn3(x)
        x = npx.activation(x, "relu")
        x = self.conv3(x)
        return x + residual


class ResNetV1(HybridBlock):
    def __init__(self, block, layers, channels, classes=1000, thumbnail=False,
                 layout="NCHW"):
        super().__init__()
        assert len(layers) == len(channels) - 1
        ax = _bn_axis(layout)
        self.features = HybridSequential()
        if thumbnail:
            self.features.add(_conv3x3(channels[0], 1, 0, layout))
        else:
            self.features.add(Conv2D(channels[0], 7, 2, 3, use_bias=False,
                                     layout=layout))
            self.features.add(BatchNorm(axis=ax))
            self.features.add(Activation("relu"))
            self.features.add(MaxPool2D(3, 2, 1, layout=layout))
        for i, num_layer in enumerate(layers):
            stride = 1 if i == 0 else 2
            self.features.add(self._make_layer(
                block, num_layer, channels[i + 1], stride,
                in_channels=channels[i], layout=layout))
        self.features.add(GlobalAvgPool2D(layout=layout))
        self.features.add(Flatten())
        self.output = Dense(classes, in_units=channels[-1])

    @staticmethod
    def _make_layer(block, layers, channels, stride, in_channels=0,
                    layout="NCHW"):
        layer = HybridSequential()
        layer.add(block(channels, stride, channels != in_channels,
                        in_channels=in_channels, layout=layout))
        for _ in range(layers - 1):
            layer.add(block(channels, 1, False, in_channels=channels,
                            layout=layout))
        return layer

    def forward(self, x):
        x = self.features(x)
        return self.output(x)


class ResNetV2(HybridBlock):
    def __init__(self, block, layers, channels, classes=1000, thumbnail=False,
                 layout="NCHW"):
        super().__init__()
        assert len(layers) == len(channels) - 1
        ax = _bn_axis(layout)
        self.features = HybridSequential()
        self.features.add(BatchNorm(scale=False, center=False, axis=ax))
        if thumbnail:
            self.features.add(_conv3x3(channels[0], 1, 0, layout))
        else:
            self.features.add(Conv2D(channels[0], 7, 2, 3, use_bias=False,
                                     layout=layout))
            self.features.add(BatchNorm(axis=ax))
            self.features.add(Activation("relu"))
            self.features.add(MaxPool2D(3, 2, 1, layout=layout))
        in_channels = channels[0]
        for i, num_layer in enumerate(layers):
            stride = 1 if i == 0 else 2
            self.features.add(ResNetV1._make_layer(
                block, num_layer, channels[i + 1], stride,
                in_channels=in_channels, layout=layout))
            in_channels = channels[i + 1]
        self.features.add(BatchNorm(axis=ax))
        self.features.add(Activation("relu"))
        self.features.add(GlobalAvgPool2D(layout=layout))
        self.features.add(Flatten())
        self.output = Dense(classes, in_units=in_channels)

    def forward(self, x):
        x = self.features(x)
        return self.output(x)


resnet_spec = {
    18: ("basic_block", [2, 2, 2, 2], [64, 64, 128, 256, 512]),
    34: ("basic_block", [3, 4, 6, 3], [64, 64, 128, 256, 512]),
    50: ("bottle_neck", [3, 4, 6, 3], [64, 256, 512, 1024, 2048]),
    101: ("bottle_neck", [3, 4, 23, 3], [64, 256, 512, 1024, 2048]),
    152: ("bottle_neck", [3, 8, 36, 3], [64, 256, 512, 1024, 2048]),
}
resnet_net_versions = [ResNetV1, ResNetV2]
resnet_block_versions = [
    {"basic_block": BasicBlockV1, "bottle_neck": BottleneckV1},
    {"basic_block": BasicBlockV2, "bottle_neck": BottleneckV2},
]


def get_resnet(version, num_layers, pretrained=False, ctx=None, **kwargs):
    assert num_layers in resnet_spec, \
        "Invalid resnet depth %d" % num_layers
    block_type, layers, channels = resnet_spec[num_layers]
    assert 1 <= version <= 2
    resnet_class = resnet_net_versions[version - 1]
    block_class = resnet_block_versions[version - 1][block_type]
    net = resnet_class(block_class, layers, channels, **kwargs)
    if pretrained:
        raise RuntimeError(
            "pretrained weights require network access; use "
            "load_parameters on a downloaded file instead")
    return net


def resnet18_v1(**kw):
    return get_resnet(1, 18, **kw)


def resnet34_v1(**kw):
    return get_resnet(1, 34, **kw)


def resnet50_v1(**kw):
    return get_resnet(1, 50, **kw)


def resnet101_v1(**kw):
    return get_resnet(1, 101, **kw)


def resnet152_v1(**kw):
    return get_resnet(1, 152, **kw)


def resnet18_v2(**kw):
    return get_resnet(2, 18, **kw)


def resnet34_v2(**kw):
    return get_resnet(2, 34, **kw)


def resnet50_v2(**kw):
    return get_resnet(2, 50, **kw)


def resnet101_v2(**kw):
    return get_resnet(2, 101, **kw)


def resnet152_v2(**kw):
    return get_resnet(2, 152, **kw)
