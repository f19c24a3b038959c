"""ResNet v1.5's training loss in plain ``jax.numpy`` / ``jax.lax`` and
float32 (He et al. 2015, arXiv:1512.03385; the stride of a bottleneck sits
on its 3x3): no flax, nothing of ``horovod_tpu`` but the names of its
parameter tree. The harness differentiates it and runs it under
``default_matmul_precision("highest")``.

BatchNorm normalises with the statistics of the batch it is given, as a
training step does, so the harness hands this one chip's whole batch at a
time. Each bottleneck is rematerialised in the backward pass
(``jax.checkpoint``): that changes no value, and lets a chip's batch of
float32 activations fit beside nothing else."""

from __future__ import annotations

import jax
import jax.numpy as jnp

BATCH_NORM_EPS = 1e-5


def conv(x, kernel, stride=1, padding="SAME"):
    return jax.lax.conv_general_dilated(
        x, kernel, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def batch_norm(x, p):
    mean = x.mean((0, 1, 2))
    var = jnp.square(x - mean).mean((0, 1, 2))
    return (x - mean) / jnp.sqrt(var + BATCH_NORM_EPS) * p["scale"] + p["bias"]


def bottleneck(x, p, stride):
    y = jax.nn.relu(batch_norm(conv(x, p["Conv_0"]["kernel"]),
                               p["BatchNorm_0"]))
    y = jax.nn.relu(batch_norm(conv(y, p["Conv_1"]["kernel"], stride),
                               p["BatchNorm_1"]))
    y = batch_norm(conv(y, p["Conv_2"]["kernel"]), p["BatchNorm_2"])
    if "Conv_3" in p:  # the projection shortcut
        x = batch_norm(conv(x, p["Conv_3"]["kernel"], stride),
                       p["BatchNorm_3"])
    return jax.nn.relu(x + y)


def loss(config: dict, variables, batch):
    images, labels = batch
    params = variables["params"]
    x = conv(images.astype(jnp.float32), params["Conv_0"]["kernel"], 2,
             [(3, 3), (3, 3)])
    x = jax.nn.relu(batch_norm(x, params["BatchNorm_0"]))
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    block = jax.checkpoint(bottleneck, static_argnums=2)
    index = 0
    for stage, blocks in enumerate(config["stage_sizes"]):
        # A stage's first block changes the shape; the others are alike, so
        # they are stacked and scanned and the compiler sees one of them.
        x = block(x, params[f"Bottleneck_{index}"], 2 if stage > 0 else 1)
        alike = [params[f"Bottleneck_{index + i}"] for i in range(1, blocks)]
        if alike:
            x, _ = jax.lax.scan(
                lambda x, p: (block(x, p, 1), None), x,
                jax.tree.map(lambda *leaves: jnp.stack(leaves), *alike))
        index += blocks
    x = x.mean((1, 2))
    logits = x @ params["Dense_0"]["kernel"] + params["Dense_0"]["bias"]
    log_probs = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(log_probs, labels[:, None], -1).mean()
