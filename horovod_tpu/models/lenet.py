"""LeNet-5-style convnet for the MNIST end-to-end slice.

The model behind BASELINE config #1 (the reference's
``examples/pytorch/pytorch_mnist.py`` trains the same shape of network: two
convs + two dense layers). Written in flax.linen; NHWC layout (TPU-native —
the MXU wants channels minor).
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp

from ..attribution import SCOPE_BLOCK_HEAD
from ..profiler import annotate_collective


class LeNet(nn.Module):
    num_classes: int = 10

    @nn.compact
    def __call__(self, x):
        # x: (batch, 28, 28, 1)
        x = nn.Conv(32, (3, 3), padding="SAME")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = nn.Conv(64, (3, 3), padding="SAME")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(128)(x)
        x = nn.relu(x)
        x = nn.Dense(self.num_classes)(x)
        return x


def cross_entropy_loss(logits, labels, num_classes: int = 10):
    import jax.nn

    with annotate_collective(SCOPE_BLOCK_HEAD):
        one_hot = jnp.eye(num_classes, dtype=logits.dtype)[labels]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.sum(one_hot * logp, axis=-1))
