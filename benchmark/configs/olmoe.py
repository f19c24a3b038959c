"""OLMoE causal-LM pre-training through the product's own model
(``horovod_tpu.models.olmoe``): what a configuration file of this family
needs beside its sizes. The harness calls these and nothing else."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import optax

import cells


def model_config(config: dict):
    from horovod_tpu.models import olmoe

    training = config["training"]
    return olmoe.OlmoeConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        intermediate_size=config["intermediate_size"],
        num_experts=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        experts_here=config["experts_here"],
        first_expert=config["first_expert"],
        capacity_factor=config["capacity_factor"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        load_balance_coef=training["load_balance_coef"],
        router_z_coef=training["router_z_coef"],
        dtype=jnp.dtype(training["compute_dtype"]))


def model(config: dict):
    from horovod_tpu.models import olmoe

    attention = {
        "flash": olmoe.flash_attention_fn,
        # the toy cell's: the multi-tile causal kernels, interpreted
        "flash_interpret": partial(
            olmoe.flash_attention_fn, interpret=True,
            block=config["training"].get("attention_block")),
        "dense": None}[config["training"]["attention"]]
    return olmoe.Olmoe(model_config(config), attention_fn=attention)


def init_params(config: dict, job: dict, key):
    """Random weights: flax's initialisers from the seed. They depend
    neither on the attention function nor on the input length."""
    from horovod_tpu.models import olmoe

    return olmoe.Olmoe(model_config(config)).init(
        key, jnp.zeros((1, 8), jnp.int32))["params"]


def loss_fn(config: dict, job: dict):
    from horovod_tpu.models import olmoe

    return partial(olmoe.causal_lm_loss, model(config))


def inner_optimizer(config: dict):
    return optax.adamw(config["training"]["learning_rate"])


# AdamW, as BERT's: the first gradient is read back from its first moment.
first_gradient = cells.load_code(
    cells.HERE, "configs", "bert.py").first_gradient


def make_batch(config: dict, job: dict, key, rows: int):
    """``rows`` unpadded sequences of ``seq_len + 1`` uniform random
    tokens: the model reads the first ``seq_len``, and each is labelled
    with its successor."""
    return jax.random.randint(
        key, (rows, job["seq_len"] + 1), 0, config["vocab_size"], jnp.int32)


def macs_per_token(config: dict, seq_len: int) -> dict:
    """Multiply-adds of one forward pass per token, by part: what the
    mathematics needs and nothing the implementation adds. Attention's two
    products see, under the causal mask, (S + 1) / 2 keys a query on
    average, counted as S / 2; the experts see the pairs routed into this
    chip's window in expectation, ``top_k x experts_here / num_experts`` a
    token whatever the router does, so empty slots and masked tiles count
    for nothing and show as lost ``mfu``."""
    H, I, V = (config["hidden_size"], config["intermediate_size"],
               config["vocab_size"])
    pairs_here = (config["num_experts_per_tok"] * config["experts_here"]
                  / config["num_experts"])
    return {"projections": 4.0 * H * H,
            "causal_scores": 2.0 * (seq_len / 2) * H,
            "router": 1.0 * H * config["num_experts"],
            "experts": pairs_here * 3.0 * H * I,
            "head": 1.0 * H * V}


def flops_per_step(config: dict, job: dict, rows: int) -> float:
    """A training step is three forwards (the backward pass costs two),
    nothing recomputed; a multiply-add is two operations."""
    macs = macs_per_token(config, job["seq_len"])
    per_layer = sum(value for name, value in macs.items() if name != "head")
    per_token = config["num_hidden_layers"] * per_layer + macs["head"]
    return 3.0 * 2.0 * per_token * rows * job["seq_len"]


def units_per_step(job: dict, rows: int) -> tuple[int, str]:
    return rows * job["seq_len"], "tokens"


def min_pallas_calls(config: dict) -> int:
    """The multi-tile forward, dq and dkv kernels in every layer, or a
    kernel gave way to something else. An interpreted kernel is no custom
    call."""
    if config["training"]["attention"] != "flash":
        return 0
    return 3 * config["num_hidden_layers"]
