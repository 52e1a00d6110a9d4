"""Plain reference model for ``resnet56_cifar10``: the CIFAR ResNet-56 of
FedML's cross-silo benchmark (He et al. 2016, section 4.2 layout with
bottleneck blocks as in FedML ``resnet56``): 3x3 stem of 16 channels, three
stages of six bottleneck blocks (1x1 -> 3x3 -> 1x1, planes 16/32/64,
expansion 4, stride 2 entering stages two and three, a 1x1 projection where
the shape changes), global average pool, dense 10.  GroupNorm (Wu & He 2018,
32 channels a group, at least one group) in place of BatchNorm, as FedML's GN
ResNets do for small federated batches; the last norm of each block starts
at scale 0.  Convolutions have no bias and start from He-normal (fan-out).
591,322 parameters.  Written in flax.linen, float32; nothing of
``fedml_tpu`` is imported.

Submodule names are given explicitly: flax derives each parameter's initial
value from its path, and the comparison is leaf by leaf, so the tree has to
be laid out as the system under test lays out its own.
"""

import flax.linen as nn
import jax.numpy as jnp

from benchmark import datagen

_he = nn.initializers.variance_scaling(2.0, "fan_out", "truncated_normal")


def _groups(channels: int, per_group: int = 32) -> int:
    g = max(1, channels // per_group)
    while channels % g:
        g -= 1
    return g


class _GN(nn.Module):
    zero: bool = False

    @nn.compact
    def __call__(self, x):
        init = nn.initializers.zeros if self.zero else nn.initializers.ones
        return nn.GroupNorm(num_groups=_groups(x.shape[-1]), epsilon=1e-5,
                            scale_init=init, name="GroupNorm_0")(x)


def _conv(ch, k, stride, name):
    return nn.Conv(ch, (k, k), strides=(stride, stride), padding="SAME",
                   use_bias=False, kernel_init=_he, name=name)


class _Block(nn.Module):
    planes: int
    stride: int

    @nn.compact
    def __call__(self, x):
        out_ch = 4 * self.planes
        y = nn.relu(_GN(name="Norm_0")(_conv(self.planes, 1, 1, "Conv_0")(x)))
        y = nn.relu(_GN(name="Norm_1")(
            _conv(self.planes, 3, self.stride, "Conv_1")(y)))
        y = _GN(zero=True, name="Norm_2")(_conv(out_ch, 1, 1, "Conv_2")(y))
        if self.stride != 1 or x.shape[-1] != out_ch:
            x = _GN(name="Norm_3")(_conv(out_ch, 1, self.stride, "Conv_3")(x))
        return nn.relu(y + x)


class Model(nn.Module):
    classes: int = 10
    blocks: tuple = (6, 6, 6)
    planes: tuple = (16, 32, 64)

    @nn.compact
    def __call__(self, x, train: bool = False):
        x = nn.relu(_GN(name="Norm_0")(_conv(16, 3, 1, "Conv_0")(x)))
        i = 0
        for stage, (p, n) in enumerate(zip(self.planes, self.blocks)):
            for b in range(n):
                stride = 2 if (stage > 0 and b == 0) else 1
                x = _Block(p, stride, name=f"Bottleneck_{i}")(x)
                i += 1
        x = jnp.mean(x, axis=(1, 2))
        return nn.Dense(self.classes, name="fc")(x)


def build_model(config: dict) -> nn.Module:
    m = config["model"]
    return Model(classes=m["classes"], blocks=tuple(m["blocks"]),
                 planes=tuple(m["planes"]))


def train_clients(arrays: dict, config: dict, program_seed: int):
    """Per-silo (x, y) under the published LDA split; the CLI draws the
    split from its one ``--seed``."""
    a = config["cli"]
    return datagen.cifar10_clients(arrays, int(a["client_num_in_total"]),
                                   float(a["partition_alpha"]),
                                   program_seed)
