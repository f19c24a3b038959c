"""Olmo Hybrid causal-LM pre-training through the product's own model
(``horovod_tpu.models.olmo_hybrid``): what a configuration file of this
family needs beside its sizes. The harness calls these and nothing else."""

from __future__ import annotations

from functools import partial

import jax.numpy as jnp

import cells

LINEAR, FULL = "linear_attention", "full_attention"


def model_config(config: dict):
    from horovod_tpu.models import olmo_hybrid

    training = config["training"]
    return olmo_hybrid.OlmoHybridConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        layer_types=tuple(config["layer_types"]),
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        linear_num_key_heads=config["linear_num_key_heads"],
        linear_num_value_heads=config["linear_num_value_heads"],
        linear_key_head_dim=config["linear_key_head_dim"],
        linear_value_head_dim=config["linear_value_head_dim"],
        linear_conv_kernel_dim=config["linear_conv_kernel_dim"],
        linear_allow_neg_eigval=config["linear_allow_neg_eigval"],
        heads_here=config["heads_here"],
        first_head=config["first_head"],
        chunk=training["scan_chunk"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=config["rope_parameters"]["rope_theta"],
        dtype=jnp.dtype(training["compute_dtype"]))


def model(config: dict):
    from horovod_tpu.models import olmo_hybrid

    attention = {
        "flash": olmo_hybrid.flash_attention_fn,
        # the toy cell's: the multi-tile causal kernels, interpreted
        "flash_interpret": partial(
            olmo_hybrid.flash_attention_fn, interpret=True,
            block=config["training"].get("attention_block")),
        "dense": None}[config["training"]["attention"]]
    return olmo_hybrid.OlmoHybrid(model_config(config),
                                  attention_fn=attention)


def init_params(config: dict, job: dict, key):
    """Random weights: the flax model's initialisers from the seed. They
    depend neither on the attention function nor on the input length."""
    from horovod_tpu.models import olmo_hybrid

    built = model_config(config)
    return olmo_hybrid.OlmoHybrid(built).init(
        key, jnp.zeros((1, built.chunk), jnp.int32))["params"]


def loss_fn(config: dict, job: dict):
    from horovod_tpu.models import olmo_hybrid

    return partial(olmo_hybrid.causal_lm_loss, model(config))


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
    """Multiply-adds of one forward pass per token, by part and for the
    heads this chip holds: what the mathematics needs and nothing an
    implementation adds. A linear-attention layer: the five projections and
    the two gates' (``linear_projections``), four taps a channel
    (``short_conv``), and the recurrence at three ``d_k x d_v`` products a
    token a head (``S'^T k``, the rank-one update, ``S^T q``), whatever
    chunks, solves and masked halves a chunked form computes beside them.
    A full-attention layer: four projections, and its two score products
    over the (S + 1) / 2 keys a query sees on average, counted as S / 2.
    Every layer's feed-forward, whole; the head over the vocabulary's
    slice."""
    H, I, V = (config["hidden_size"], config["intermediate_size"],
               config["vocab_size"])
    here = config["heads_here"]
    d_k, d_v = config["linear_key_head_dim"], config["linear_value_head_dim"]
    head_dim = H // config["num_attention_heads"]
    return {
        "linear_projections": 1.0 * H * here * (2 * d_k + 3 * d_v + 2),
        "short_conv": 1.0 * config["linear_conv_kernel_dim"] * here * (
            2 * d_k + d_v),
        "recurrence": 3.0 * here * d_k * d_v,
        "full_projections": 4.0 * H * here * head_dim,
        "causal_scores": 2.0 * (seq_len / 2) * here * head_dim,
        "feed_forward": 3.0 * H * I,
        "head": 1.0 * H * V}


def flops_per_step(config: dict, job: dict, rows: int) -> float:
    """A training step is three forwards (the backward pass costs two),
    nothing recomputed; a multiply-add is two operations."""
    macs = macs_per_token(config, job["seq_len"])
    by_kind = {
        LINEAR: (macs["linear_projections"] + macs["short_conv"]
                 + macs["recurrence"] + macs["feed_forward"]),
        FULL: (macs["full_projections"] + macs["causal_scores"]
               + macs["feed_forward"])}
    per_token = sum(by_kind[kind] for kind in config["layer_types"]) \
        + macs["head"]
    return 3.0 * 2.0 * per_token * rows * job["seq_len"]


def min_pallas_calls(config: dict) -> int:
    """The multi-tile forward, dq and dkv kernels in every full-attention
    layer, or a kernel gave way to something else (linear attention has no
    kernel yet). An interpreted kernel is no custom call."""
    if config["training"]["attention"] != "flash":
        return 0
    return 3 * config["layer_types"].count(FULL)
