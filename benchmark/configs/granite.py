"""Granite 4.0-H causal-LM pre-training through the product's own model
(``horovod_tpu.models.granite``): what a configuration file of this family
needs beside its sizes. The harness calls these and nothing else."""

from __future__ import annotations

from functools import partial

import jax.numpy as jnp

import cells

MAMBA, ATTENTION = "mamba", "attention"


def model_config(config: dict):
    from horovod_tpu.models import granite

    training = config["training"]
    return granite.GraniteConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        shared_intermediate_size=config["shared_intermediate_size"],
        num_layers=config["num_hidden_layers"],
        layer_types=tuple(config["layer_types"]),
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        mamba_n_heads=config["mamba_n_heads"],
        mamba_d_head=config["mamba_d_head"],
        mamba_d_state=config["mamba_d_state"],
        mamba_n_groups=config["mamba_n_groups"],
        mamba_d_conv=config["mamba_d_conv"],
        mamba_expand=config["mamba_expand"],
        mamba_chunk_size=config["mamba_chunk_size"],
        embedding_multiplier=config["embedding_multiplier"],
        residual_multiplier=config["residual_multiplier"],
        attention_multiplier=config["attention_multiplier"],
        logits_scaling=config["logits_scaling"],
        rms_norm_eps=config["rms_norm_eps"],
        remat=training["remat"],
        dtype=jnp.dtype(training["compute_dtype"]))


def model(config: dict):
    from horovod_tpu.models import granite

    attention = {
        "flash": granite.flash_attention_fn,
        # the toy cell's: the grouped multi-tile kernels, interpreted
        "flash_interpret": partial(
            granite.flash_attention_fn, interpret=True,
            block=config["training"].get("attention_block")),
        "dense": None}[config["training"]["attention"]]
    return granite.Granite(model_config(config), attention_fn=attention)


def init_params(config: dict, job: dict, key):
    """Random weights: the flax model's initialisers from the seed. They
    depend neither on the attention function nor on the input length."""
    from horovod_tpu.models import granite

    built = model_config(config)
    return granite.Granite(built).init(
        key, jnp.zeros((1, built.mamba_chunk_size), jnp.int32))["params"]


def loss_fn(config: dict, job: dict):
    from horovod_tpu.models import granite

    return partial(granite.causal_lm_loss, model(config))


# As OLMoE's: AdamW at the configuration's rate, the first gradient read
# back from its first moment, ``rows`` unpadded sequences of ``seq_len + 1``
# uniform random ids of the vocabulary (here its slice) of which the model
# reads the first ``seq_len``, each labelled with its successor.
olmoe = cells.load_code(cells.HERE, "configs", "olmoe.py")
inner_optimizer = olmoe.inner_optimizer
first_gradient = olmoe.first_gradient
make_batch = olmoe.make_batch
units_per_step = olmoe.units_per_step


def macs_per_token(config: dict, seq_len: int) -> dict:
    """Multiply-adds of one forward pass per token, by part: what the
    mathematics needs and nothing an implementation adds or repeats (a
    recomputed layer counts once). A ``mamba`` layer: the projection to
    ``[z | xBC | dt]`` and the output projection (``mamba_projections``),
    four taps a channel (``short_conv``), and the recurrence at three ``P x
    N`` products a token a head (the decay, the rank-one update ``dt x B^T``
    and the read ``h C``), whatever decay matrices and masked halves a
    chunked form computes beside them. An ``attention`` layer: its four
    projections (keys and values for 8 heads, not 32), and its two score
    products over the (S + 1) / 2 keys a query sees on average, counted as
    S / 2. Every layer's feed-forward; the tied head over the vocabulary's
    slice."""
    H, I, V = (config["hidden_size"], config["shared_intermediate_size"],
               config["vocab_size"])
    heads, width, state = (config["mamba_n_heads"], config["mamba_d_head"],
                           config["mamba_d_state"])
    inner = config["mamba_expand"] * H
    mixed = inner + 2 * config["mamba_n_groups"] * state
    kv_width = H * config["num_key_value_heads"] \
        // config["num_attention_heads"]
    return {
        "mamba_projections": 1.0 * H * (inner + mixed + heads) + inner * H,
        "short_conv": 1.0 * config["mamba_d_conv"] * mixed,
        "recurrence": 3.0 * heads * width * state,
        "attention_projections": 2.0 * H * (H + kv_width),
        "causal_scores": 2.0 * (seq_len / 2) * H,
        "feed_forward": 3.0 * H * I,
        "head": 1.0 * H * V}


def flops_per_step(config: dict, job: dict, rows: int) -> float:
    """A training step is three forwards (the backward pass costs two),
    nothing recomputed; a multiply-add is two operations."""
    macs = macs_per_token(config, job["seq_len"])
    by_kind = {
        MAMBA: (macs["mamba_projections"] + macs["short_conv"]
                + macs["recurrence"] + macs["feed_forward"]),
        ATTENTION: (macs["attention_projections"] + macs["causal_scores"]
                    + macs["feed_forward"])}
    per_token = sum(by_kind[kind] for kind in config["layer_types"]) \
        + macs["head"]
    return 3.0 * 2.0 * per_token * rows * job["seq_len"]


def min_pallas_calls(config: dict) -> int:
    """The multi-tile forward, dq and dkv kernels in every ``attention``
    layer (the recomputed layer keeps the forward kernel's results and does
    not run it again), or a kernel gave way to something else (the scan
    has no kernel yet). An interpreted kernel is no custom call."""
    if config["training"]["attention"] != "flash":
        return 0
    return 3 * config["layer_types"].count(ATTENTION)
