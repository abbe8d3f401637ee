"""ResNet family (the ResNet-50/ImageNet aggregate, compiler → XLA).

The reference ships only an MNIST MLP (``examples/tinysys/modules/mlp.py``,
SURVEY.md §2.2); the CNN family is part of the capability ladder this
framework supplies.

TPU-first choices: NHWC layout (XLA:TPU's native conv layout — the MXU
consumes [spatial, channel] tiles directly), bfloat16 conv compute with
float32 normalization, and **GroupNorm instead of BatchNorm**: running
batch statistics are mutable state that would break the pure donated-step
model (``build_train_step`` donates the whole ``TrainState``) and require
cross-replica statistic sync under data parallelism; GroupNorm is the
standard stateless substitute at large batch scale and keeps the step
function identical on 1 chip and on a pod. Parallelism for CNNs is
data/FSDP (weight matrices are small relative to activations; tensor
parallelism buys nothing here), so :meth:`ResNet.partition_rules` only
splits the classifier head.
"""

from __future__ import annotations

import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from tpusystem.registry import register


class Bottleneck(nn.Module):
    """1x1 -> 3x3 -> 1x1 bottleneck with a projection shortcut on
    stride/width changes (the ResNet-50 block)."""

    features: int            # bottleneck width; block output is 4x this
    stride: int
    groups: int              # GroupNorm groups
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, hidden):
        conv = lambda features, size, stride, name: nn.Conv(
            features, (size, size), strides=(stride, stride), use_bias=False,
            dtype=self.dtype, name=name)
        norm = lambda name: nn.GroupNorm(
            num_groups=self.groups, dtype=jnp.float32, name=name)
        out_features = 4 * self.features

        shortcut = hidden
        if self.stride != 1 or hidden.shape[-1] != out_features:
            shortcut = conv(out_features, 1, self.stride, 'proj')(hidden)
            shortcut = norm('proj_norm')(shortcut)

        hidden = nn.relu(norm('norm1')(conv(self.features, 1, 1, 'conv1')(hidden)))
        hidden = nn.relu(norm('norm2')(conv(self.features, 3, self.stride, 'conv2')(hidden)))
        hidden = norm('norm3')(conv(out_features, 1, 1, 'conv3')(hidden))
        return nn.relu(hidden + shortcut)


class ResNet(nn.Module):
    """Bottleneck ResNet over NHWC images. Defaults are ResNet-50
    (stages 3-4-6-3, widths 64-128-256-512, 1000 classes)."""

    classes: int = 1000
    stages: tuple = (3, 4, 6, 3)
    width: int = 64
    groups: int = 32
    dtype: str = 'bfloat16'
    stem_stride: int = 2     # 1 for small (CIFAR-style) inputs
    stem_pool: bool = True   # max-pool after the stem (ImageNet-style)

    @nn.compact
    def __call__(self, images):
        compute_dtype = jnp.dtype(self.dtype)
        hidden = images.astype(compute_dtype)
        size = 7 if self.stem_stride == 2 else 3
        hidden = nn.Conv(self.width, (size, size),
                         strides=(self.stem_stride, self.stem_stride),
                         use_bias=False, dtype=compute_dtype, name='stem')(hidden)
        hidden = nn.relu(nn.GroupNorm(num_groups=self.groups,
                                      dtype=jnp.float32, name='stem_norm')(hidden))
        if self.stem_pool:
            hidden = nn.max_pool(hidden, (3, 3), strides=(2, 2), padding='SAME')
        for stage, blocks in enumerate(self.stages):
            for block in range(blocks):
                stride = 2 if stage > 0 and block == 0 else 1
                hidden = Bottleneck(self.width * 2 ** stage, stride,
                                    self.groups, compute_dtype,
                                    name=f's{stage}_b{block}')(hidden)
        pooled = jnp.mean(hidden, axis=(1, 2))  # global average pool
        # f32 head for a numerically stable softmax/loss
        return nn.Dense(self.classes, dtype=jnp.float32,
                        name='head')(pooled.astype(jnp.float32))

    @staticmethod
    def partition_rules():
        """Classifier head splits classes on ``model``; conv weights are
        left to the FSDP/data axes (TP buys nothing for CNN kernels)."""
        return ((r'head/kernel$', P(None, 'model')),)


register(ResNet)


def resnet50(**overrides) -> ResNet:
    return ResNet(**overrides)


def resnet_tiny(**overrides) -> ResNet:
    """Test scale: 8-group norm, 2 stages, CIFAR-style stem."""
    config = dict(classes=10, stages=(1, 1), width=16, groups=8,
                  stem_stride=1, stem_pool=False, dtype='float32')
    config.update(overrides)
    return ResNet(**config)
