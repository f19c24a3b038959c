"""SmallThinker causal-LM pre-training through the product's own model
(``horovod_tpu.models.smallthinker``): what a configuration file of this
family needs beside its sizes. The harness calls these and nothing else."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import optax

import cells


def model_config(config: dict):
    from horovod_tpu.models import smallthinker

    training = config["training"]
    layers = config["num_hidden_layers"]
    return smallthinker.SmallThinkerConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=layers,
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        intermediate_size=config["moe_ffn_hidden_size"],
        num_experts=config["moe_num_primary_experts"],
        top_k=config["moe_num_active_primary_experts"],
        experts_here=config["experts_here"],
        first_expert=config["first_expert"],
        capacity_factor=config["capacity_factor"],
        window=config["sliding_window_size"],
        # the published lists run over all 52 layers; the layers kept are
        # the first ones
        sliding_window_layout=tuple(config["sliding_window_layout"][:layers]),
        rope_layout=tuple(config["rope_layout"][:layers]),
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        remat=training["remat"],
        dtype=jnp.dtype(training["compute_dtype"]))


def model(config: dict):
    from horovod_tpu.models import smallthinker

    attention = {
        "flash": smallthinker.flash_attention_fn,
        # the toy cell's: the multi-tile kernels, interpreted
        "flash_interpret": partial(
            smallthinker.flash_attention_fn, interpret=True,
            block=config["training"].get("attention_block")),
        "dense": None}[config["training"]["attention"]]
    return smallthinker.SmallThinker(model_config(config),
                                     attention_fn=attention)


def init_params(config: dict, job: dict, key):
    """Random weights: flax's initialisers from the seed. They depend
    neither on the attention function nor on the input length."""
    from horovod_tpu.models import smallthinker

    return smallthinker.SmallThinker(model_config(config)).init(
        key, jnp.zeros((1, 8), jnp.int32))["params"]


def loss_fn(config: dict, job: dict):
    from horovod_tpu.models import smallthinker

    return partial(smallthinker.causal_lm_loss, model(config))


def inner_optimizer(config: dict):
    return optax.adamw(config["training"]["learning_rate"])


# AdamW, as BERT's: the first gradient is read back from its first moment.
first_gradient = cells.load_code(
    cells.HERE, "configs", "bert.py").first_gradient


def make_batch(config: dict, job: dict, key, rows: int):
    """``rows`` unpadded sequences of ``seq_len + 1`` uniform random
    tokens from the slice of the vocabulary held: the model reads the first
    ``seq_len``, and each is labelled with its successor."""
    return jax.random.randint(
        key, (rows, job["seq_len"] + 1), 0, config["vocab_size"], jnp.int32)


def visible_pairs(seq_len: int, window: int | None) -> int:
    """(query, key) pairs one head's mask leaves: ``0 <= i - j`` and, under
    a window, ``i - j < window``."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return seq_len * window - window * (window - 1) // 2


def macs_per_token(config: dict, seq_len: int) -> dict:
    """Multiply-adds of one forward pass per token, by part, summed over
    the layers kept: what the mathematics needs and nothing the
    implementation adds or repeats (a recomputed layer counts once).
    Attention's two products see exactly the pairs its layer's mask leaves;
    keys and values are projected for 4 heads, not 28; the experts see the
    pairs routed into this chip's window in expectation, ``6 x
    experts_here / 64`` a token whatever the router does, so empty slots,
    masked parts of tiles and the recomputed forward count for nothing and
    show as lost ``mfu``."""
    H, I, V = (config["hidden_size"], config["moe_ffn_hidden_size"],
               config["vocab_size"])
    q_width = config["num_attention_heads"] * config["head_dim"]
    kv_width = config["num_key_value_heads"] * config["head_dim"]
    layers = config["num_hidden_layers"]
    pairs_here = (config["moe_num_active_primary_experts"]
                  * config["experts_here"]
                  / config["moe_num_primary_experts"])
    seen = sum(visible_pairs(seq_len, config["sliding_window_size"]
                             if windowed else None)
               for windowed in config["sliding_window_layout"][:layers])
    return {"projections": layers * 2.0 * H * (q_width + kv_width),
            "scores": 2.0 * q_width * seen / seq_len,
            "router": layers * 1.0 * H * config["moe_num_primary_experts"],
            "experts": layers * pairs_here * 3.0 * H * I,
            "head": 1.0 * H * V}


def flops_per_step(config: dict, job: dict, rows: int) -> float:
    """A training step is three forwards (the backward pass costs two),
    nothing recomputed; a multiply-add is two operations."""
    per_token = sum(macs_per_token(config, job["seq_len"]).values())
    return 3.0 * 2.0 * per_token * rows * job["seq_len"]


def units_per_step(job: dict, rows: int) -> tuple[int, str]:
    return rows * job["seq_len"], "tokens"


def min_pallas_calls(config: dict) -> int:
    """The multi-tile forward, dq and dkv kernels in every layer (the
    recomputed layer keeps the forward kernel's results and does not run it
    again), or a kernel gave way to something else. An interpreted kernel
    is no custom call."""
    if config["training"]["attention"] != "flash":
        return 0
    return 3 * config["num_hidden_layers"]
