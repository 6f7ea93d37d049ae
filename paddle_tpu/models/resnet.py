"""ResNet family (ResNet-18/34/50/101/152).

Ref: the reference ships ResNet as a *model recipe* over fluid.layers
(/root/reference/python/paddle/fluid/tests/unittests/dist_se_resnext.py and
tests/book image_classification — conv_bn_layer + bottleneck patterns).
BASELINE.json flagship: ResNet-50 ImageNet throughput.

TPU-first: NCHW inputs accepted but compute can run bf16 via amp.Policy;
XLA's layout assignment handles the HWCN internals. BN state functional.
"""

import jax.numpy as jnp

from paddle_tpu import initializer as I
from paddle_tpu import nn
from paddle_tpu.core.flags import get_flag
from paddle_tpu.ops import nn as F


def _space_to_depth_nhwc(x, b=2):
    """[N,H,W,C] -> [N,H/b,W/b,b*b*C]; channel order (di, dj, c)."""
    n, h, w, c = x.shape
    if h % b or w % b:
        raise ValueError(
            f"PT_FLAGS_resnet_s2d_stem requires H and W divisible by {b}; "
            f"got {h}x{w}. Use the default 7x7 stem for odd input sizes.")
    x = x.reshape(n, h // b, b, w // b, b, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(n, h // b, w // b, b * b * c)


def _stem_s2d_weights(w):
    """Rewrite the 7x7/s2 stem kernel [7,7,cin,cout] (HWIO) into the exact
    4x4/s1 kernel over space-to-depth(2) input, [4,4,4*cin,cout].

    The 7-tap/stride-2/pad-3 window [2o-3, 2o+3] is zero-padded on the
    top/left to 8 taps covering [2o-4, 2o+3] = s2d rows o-2..o+1, i.e. a
    4-tap stride-1 conv on the halved grid with padding (2, 1). This is the
    standard TPU ResNet stem transform: a C=3 NHWC conv wastes almost the
    whole (8,128) register tile on channel padding; C=12 at half the
    spatial size quarters the padded-lane traffic. Numerically exact
    (pure index rewrite, no approximation)."""
    k, _, cin, cout = w.shape
    assert k == 7, "s2d stem transform expects the 7x7 ImageNet stem"
    w8 = jnp.pad(w, ((1, 0), (1, 0), (0, 0), (0, 0)))
    ws = w8.reshape(4, 2, 4, 2, cin, cout).transpose(0, 2, 1, 3, 4, 5)
    return ws.reshape(4, 4, 4 * cin, cout)


class ConvBN(nn.Module):
    def __init__(self, cin, cout, k, stride=1, act="relu", groups=1,
                 data_format="NCHW"):
        super().__init__()
        self.conv = nn.Conv2D(cin, cout, k, stride=stride,
                              padding=(k - 1) // 2, groups=groups, bias=False,
                              weight_init=I.msra(), data_format=data_format)
        self.bn = nn.BatchNorm(cout, act=act, data_format=data_format)

    def forward(self, x):
        return self.bn(self.conv(x))


class BasicBlock(nn.Module):
    expansion = 1

    def __init__(self, cin, cout, stride=1, data_format="NCHW"):
        super().__init__()
        self.conv1 = ConvBN(cin, cout, 3, stride, data_format=data_format)
        self.conv2 = ConvBN(cout, cout, 3, act=None, data_format=data_format)
        self.short = None
        if stride != 1 or cin != cout:
            self.short = ConvBN(cin, cout, 1, stride, act=None,
                                data_format=data_format)

    def forward(self, x):
        out = self.conv2(self.conv1(x))
        sc = self.short(x) if self.short is not None else x
        return jnp.maximum(out + sc, 0)


class Bottleneck(nn.Module):
    expansion = 4

    def __init__(self, cin, width, stride=1, data_format="NCHW"):
        super().__init__()
        cout = width * self.expansion
        self.conv1 = ConvBN(cin, width, 1, data_format=data_format)
        self.conv2 = ConvBN(width, width, 3, stride, data_format=data_format)
        self.conv3 = ConvBN(width, cout, 1, act=None, data_format=data_format)
        self.short = None
        if stride != 1 or cin != cout:
            self.short = ConvBN(cin, cout, 1, stride, act=None,
                                data_format=data_format)

    def forward(self, x):
        out = self.conv3(self.conv2(self.conv1(x)))
        sc = self.short(x) if self.short is not None else x
        return jnp.maximum(out + sc, 0)


_CONFIGS = {
    18: (BasicBlock, [2, 2, 2, 2]),
    34: (BasicBlock, [3, 4, 6, 3]),
    50: (Bottleneck, [3, 4, 6, 3]),
    101: (Bottleneck, [3, 4, 23, 3]),
    152: (Bottleneck, [3, 8, 36, 3]),
}


class ResNet(nn.Module):
    """TPU-first default is channels-last (data_format='NHWC'): convs run
    ~3x faster than NCHW on TPU (measured; see nn.Conv2D docstring). Inputs
    are still accepted as NCHW [B,3,H,W] per the reference convention and
    transposed once at the stem — one cheap transpose per step vs per-conv
    layout churn."""

    def __init__(self, depth=50, num_classes=1000, small_input=False,
                 data_format="NHWC", input_layout="NCHW"):
        super().__init__()
        block, layers = _CONFIGS[depth]
        self.small_input = small_input
        self.data_format = data_format
        # input_layout: layout of the *incoming* batch. Default NCHW per the
        # reference convention (one transpose at the stem); a TPU-first input
        # pipeline should feed NHWC directly and skip that per-step copy.
        self.input_layout = input_layout
        df = data_format
        if small_input:  # CIFAR-style stem (ref: tests/book resnet_cifar10)
            self.stem = ConvBN(3, 64, 3, data_format=df)
        else:
            self.stem = ConvBN(3, 64, 7, stride=2, data_format=df)
        stages = []
        cin = 64
        for i, n in enumerate(layers):
            width = 64 * (2 ** i)
            blocks = []
            for j in range(n):
                stride = 2 if (j == 0 and i > 0) else 1
                blocks.append(block(cin, width, stride, data_format=df))
                cin = width * block.expansion
            stages.append(nn.Sequential(blocks))
        self.stages = stages  # becomes ModuleList
        self.fc = nn.Linear(cin, num_classes,
                            weight_init=I.uniform(-0.01, 0.01))

    def forward(self, x):
        if self.data_format == "NHWC" and self.input_layout == "NCHW":
            x = jnp.transpose(x, (0, 2, 3, 1))  # NCHW input -> NHWC compute
        if (not self.small_input and self.data_format == "NHWC"
                and get_flag("resnet_s2d_stem")):
            w = _stem_s2d_weights(self.stem.conv.p("weight"))
            # through F.conv2d so the backward uses the same conv_custom_vjp
            # path as the 7x7 form — the s2d A/B on silicon must isolate the
            # layout rewrite, not switch VJPs at the same time
            x = F.conv2d(_space_to_depth_nhwc(x), w.astype(x.dtype),
                         padding=((2, 1), (2, 1)), data_format="NHWC")
            x = self.stem.bn(x)
        else:
            x = self.stem(x)
        if not self.small_input:
            x = F.pool2d(x, 3, "max", 2, padding=1,
                         data_format=self.data_format)
        for stage in self.stages:
            x = stage(x)
        x = F.pool2d(x, pool_type="avg", global_pooling=True,
                     data_format=self.data_format)
        return self.fc(x.reshape(x.shape[0], -1))


def resnet50(num_classes=1000, **kw):
    return ResNet(50, num_classes, **kw)


def resnet18(num_classes=1000, **kw):
    return ResNet(18, num_classes, **kw)
