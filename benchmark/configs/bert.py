"""BERT masked-LM pre-training through the product's own model
(``horovod_tpu.models.bert``): what a configuration file of this family
needs beside its sizes. The harness calls these and nothing else."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

ADAM_B1 = 0.9  # optax.adamw's default, which first_gradient() undoes


def model_config(config: dict):
    from horovod_tpu.models import bert

    return bert.BertConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        intermediate_size=config["intermediate_size"],
        max_position_embeddings=config["max_position_embeddings"],
        type_vocab_size=config["type_vocab_size"],
        dropout_rate=config["hidden_dropout_prob"],
        dtype=jnp.dtype(config["training"]["compute_dtype"]))


def init_params(config: dict, job: dict, key):
    """Random weights. They depend neither on the attention function nor
    on the input length, so they come from the plain model on a short
    input (as ``chip_smoke.init_params`` does)."""
    from horovod_tpu.models import bert

    return bert.Bert(model_config(config)).init(
        key, jnp.zeros((1, 8), jnp.int32))["params"]


def loss_fn(config: dict, job: dict):
    from horovod_tpu.models import bert

    attention = {"flash": bert.flash_attention_fn,
                 "dense": None}[config["training"]["attention"]]
    model = bert.Bert(model_config(config), attention_fn=attention)

    def loss(params, batch):
        ids, positions, labels, label_mask = batch
        _, logits = model.apply({"params": params}, ids, train=True,
                                masked_positions=positions)
        return bert.mlm_loss(logits, labels, label_mask)

    return loss


def inner_optimizer(config: dict):
    return optax.adamw(config["training"]["learning_rate"])


def first_gradient(opt_state):
    """The gradient the optimizer was handed in its first update, read
    back from Adam's first moment: ``mu_1 = (1 - b1) * g_1``."""
    is_adam = lambda s: isinstance(s, optax.ScaleByAdamState)  # noqa: E731
    adam, = filter(is_adam, jax.tree.leaves(opt_state, is_leaf=is_adam))
    return jax.tree.map(lambda mu: mu / (1.0 - ADAM_B1), adam.mu)


def make_batch(config: dict, job: dict, key, rows: int):
    """``rows`` unpadded sequences of random tokens, each with its own
    distinct masked positions and random labels there."""
    seq, masked = job["seq_len"], job["masked_positions"]
    k_ids, k_pos, k_labels = jax.random.split(key, 3)
    ids = jax.random.randint(k_ids, (rows, seq), 0, config["vocab_size"])
    positions = jax.vmap(
        lambda k: jax.random.permutation(k, seq)[:masked])(
            jax.random.split(k_pos, rows))
    labels = jax.random.randint(
        k_labels, (rows, masked), 0, config["vocab_size"])
    return (ids.astype(jnp.int32), positions.astype(jnp.int32),
            labels.astype(jnp.int32), jnp.ones((rows, masked), jnp.int32))


def flops_per_token(config: dict, seq_len: int, masked: int) -> float:
    """Model FLOPs of one training step per token (``bench.py``'s
    ``bert_flops_per_token``, copied): forward = the layers' matmuls
    2 L (4 H^2 + 2 H I), attention's two S x S products 4 L S H, and the
    masked-position head (transform + tied logits) on ``masked`` of
    ``seq_len`` positions; a training step is three forwards (the backward
    pass costs two), nothing recomputed."""
    H, I, L, V = (config["hidden_size"], config["intermediate_size"],
                  config["num_hidden_layers"], config["vocab_size"])
    layer_matmuls = 2.0 * L * (4 * H * H + 2 * H * I)
    attention = 4.0 * L * seq_len * H
    head = 2.0 * (H * H + V * H) * (masked / seq_len)
    return 3.0 * (layer_matmuls + attention + head)


def flops_per_step(config: dict, job: dict, rows: int) -> float:
    return rows * job["seq_len"] * flops_per_token(
        config, job["seq_len"], job["masked_positions"])


def units_per_step(job: dict, rows: int) -> tuple[int, str]:
    return rows * job["seq_len"], "tokens"


def min_pallas_calls(config: dict) -> int:
    """A forward and a backward kernel in every layer, or a kernel gave
    way to something else."""
    if config["training"]["attention"] != "flash":
        return 0
    return 2 * config["num_hidden_layers"]
