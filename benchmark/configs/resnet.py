"""ResNet image classification through the product's own model
(``horovod_tpu.models.resnet``), the way
``examples/jax_synthetic_benchmark.py`` takes it through the factory: the
whole ``variables`` tree is the parameter tree, so BatchNorm's running
averages ride along, get a zero gradient and are never carried forward
(``make_train_step`` has no auxiliary state). The step's arithmetic is
otherwise a training step's."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

BOTTLENECK_EXPANSION = 4  # He et al. 2015, fig. 5: 1x1, 3x3, 1x1 (x4)


def _model(config: dict):
    from horovod_tpu.models import resnet

    return resnet.ResNet(
        stage_sizes=config["stage_sizes"],
        num_classes=config["num_classes"],
        num_filters=config["num_filters"],
        dtype=jnp.dtype(config["training"]["compute_dtype"]))


def init_params(config: dict, job: dict, key):
    """The model's own initialisation, but for one thing: flax starts the
    last BatchNorm scale of every block at zero (a trick later than the
    paper), which would leave every convolution inside a block with a
    gradient of exactly zero at the seed weights and the gradient check
    with nothing to hold. They start at one, as in the paper."""
    size = config["image_size"]
    variables = _model(config).init(
        key, jnp.zeros((1, size, size, 3)), train=True)
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.ones_like(leaf)
        if path[-1].key == "scale" else leaf, variables["params"])
    return {**variables, "params": params}


def loss_fn(config: dict, job: dict):
    from horovod_tpu.models.lenet import cross_entropy_loss

    model = _model(config)

    def loss(variables, batch):
        images, labels = batch
        logits, _ = model.apply(variables, images, train=True,
                                mutable=["batch_stats"])
        return cross_entropy_loss(logits, labels,
                                  num_classes=config["num_classes"])

    return loss


def inner_optimizer(config: dict):
    training = config["training"]
    return optax.sgd(training["learning_rate"], momentum=training["momentum"])


def first_gradient(opt_state):
    """The first update's gradient is the momentum trace after it
    (``trace_1 = g_1 + momentum * 0``)."""
    is_trace = lambda s: isinstance(s, optax.TraceState)  # noqa: E731
    trace, = filter(is_trace, jax.tree.leaves(opt_state, is_leaf=is_trace))
    return trace.trace


def make_batch(config: dict, job: dict, key, rows: int):
    size = config["image_size"]
    k_images, k_labels = jax.random.split(key)
    images = jax.random.uniform(
        k_images, (rows, size, size, 3),
        jnp.dtype(config["training"]["compute_dtype"]))
    labels = jax.random.randint(k_labels, (rows,), 0, config["num_classes"])
    return images, labels.astype(jnp.int32)


def forward_macs_per_image(config: dict) -> float:
    """Multiply-accumulates of the convolutions and the classifier for one
    image, from the shapes: v1.5 puts a block's stride on its 3x3."""
    filters, size = config["num_filters"], config["image_size"]
    size = -(-size // 2)  # 7x7 stem, stride 2
    macs = size * size * 7 * 7 * 3 * filters
    size = -(-size // 2)  # 3x3 max pooling, stride 2
    channels = filters
    for stage, blocks in enumerate(config["stage_sizes"]):
        width = filters * 2 ** stage
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            out = -(-size // stride)
            macs += size * size * channels * width  # 1x1
            macs += out * out * 9 * width * width  # 3x3, strided
            macs += out * out * width * width * BOTTLENECK_EXPANSION  # 1x1
            if channels != width * BOTTLENECK_EXPANSION or stride != 1:
                macs += out * out * channels * width * BOTTLENECK_EXPANSION
            channels, size = width * BOTTLENECK_EXPANSION, out
    return float(macs + channels * config["num_classes"])


def flops_per_step(config: dict, job: dict, rows: int) -> float:
    """Two FLOPs a multiply-accumulate; a training step is three forwards
    (the backward pass costs two), nothing recomputed."""
    return rows * 3.0 * 2.0 * forward_macs_per_image(config)


def units_per_step(job: dict, rows: int) -> tuple[int, str]:
    return rows, "images"


def min_pallas_calls(config: dict) -> int:
    return 0
