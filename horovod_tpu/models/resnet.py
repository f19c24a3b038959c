"""ResNet family (v1.5) — the framework's flagship benchmark model.

BASELINE configs #2/#5 (the reference's
``examples/pytorch/pytorch_imagenet_resnet50.py`` and the Horovod paper's
headline ResNet scaling results) train ResNet-50 data-parallel. TPU-first
choices: NHWC layout (channels minor for the MXU), bfloat16 compute with
float32 variables, 3x3 stride-2 in the bottleneck's middle conv (the v1.5
variant every benchmark uses).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence

import flax.linen as nn
import jax.numpy as jnp

from ..attribution import (SCOPE_BLOCK_HEAD, SCOPE_BLOCK_STAGE,
                           SCOPE_BLOCK_STEM)
from ..profiler import annotate_collective

ModuleDef = Any


class Bottleneck(nn.Module):
    filters: int
    strides: tuple[int, int] = (1, 1)
    conv: ModuleDef = nn.Conv
    norm: ModuleDef = nn.BatchNorm

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1), use_bias=False)(x)
        y = self.norm()(y)
        y = nn.relu(y)
        # v1.5: stride lives on the 3x3, not the 1x1.
        y = self.conv(
            self.filters, (3, 3), strides=self.strides, use_bias=False,
            padding="SAME",
        )(y)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.filters * 4, (1, 1), use_bias=False)(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(
                self.filters * 4, (1, 1), strides=self.strides, use_bias=False
            )(residual)
            residual = self.norm()(residual)
        return nn.relu(residual + y)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, dtype=self.dtype)
        norm = partial(
            nn.BatchNorm,
            use_running_average=not train,
            momentum=0.9,
            epsilon=1e-5,
            dtype=self.dtype,
        )
        with annotate_collective(SCOPE_BLOCK_STEM):
            x = x.astype(self.dtype)
            x = conv(
                self.num_filters, (7, 7), strides=(2, 2), use_bias=False,
                padding=[(3, 3), (3, 3)],
            )(x)
            x = norm()(x)
            x = nn.relu(x)
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        with annotate_collective(SCOPE_BLOCK_STAGE):
            for i, block_count in enumerate(self.stage_sizes):
                for j in range(block_count):
                    strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                    x = Bottleneck(
                        self.num_filters * 2**i,
                        strides=strides,
                        conv=conv,
                        norm=norm,
                    )(x)
        with annotate_collective(SCOPE_BLOCK_HEAD):
            x = jnp.mean(x, axis=(1, 2))
            x = nn.Dense(self.num_classes, dtype=jnp.float32)(x)
        return x


ResNet50 = partial(ResNet, stage_sizes=[3, 4, 6, 3])
ResNet101 = partial(ResNet, stage_sizes=[3, 4, 23, 3])
ResNet152 = partial(ResNet, stage_sizes=[3, 8, 36, 3])
