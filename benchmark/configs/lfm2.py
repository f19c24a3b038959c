"""LFM2 causal-LM pre-training through the product's own model
(``horovod_tpu.models.lfm2``): what a configuration file of this family
needs beside its sizes. The harness calls these and nothing else."""

from __future__ import annotations

from functools import partial

import jax.numpy as jnp

import cells

CONV, ATTENTION = "conv", "full_attention"


def model_config(config: dict):
    from horovod_tpu.models import lfm2

    training, rotary = config["training"], config["rope_parameters"]
    if config["conv_bias"] or rotary["rope_type"] != "default":
        raise ValueError(
            "lfm2: the mixer here has no bias and the rotary embedding no "
            f"rescaling; got conv_bias={config['conv_bias']}, rope_type="
            f"{rotary['rope_type']!r}")
    return lfm2.Lfm2Config(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_layers=config["num_hidden_layers"],
        layer_types=tuple(config["layer_types"]),
        num_dense_layers=config["num_dense_layers"],
        conv_L_cache=config["conv_L_cache"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        rope_theta=float(rotary["rope_theta"]),
        num_experts=config["num_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        use_expert_bias=config["use_expert_bias"],
        routed_scaling_factor=float(config["routed_scaling_factor"]),
        experts_here=config["experts_here"],
        first_expert=config["first_expert"],
        capacity_factor=config["capacity_factor"],
        norm_eps=config["norm_eps"],
        remat=training["remat"],
        dtype=jnp.dtype(training["compute_dtype"]))


def model(config: dict):
    from horovod_tpu.models import lfm2

    attention = {
        "flash": lfm2.flash_attention_fn,
        # the toy cell's: the grouped multi-tile kernels, interpreted
        "flash_interpret": partial(
            lfm2.flash_attention_fn, interpret=True,
            block=config["training"].get("attention_block")),
        "dense": None}[config["training"]["attention"]]
    return lfm2.Lfm2(model_config(config), attention_fn=attention)


def init_params(config: dict, job: dict, key):
    """Random weights: the flax model's initialisers from the seed. They
    depend neither on the attention function nor on the input length."""
    from horovod_tpu.models import lfm2

    return lfm2.Lfm2(model_config(config)).init(
        key, jnp.zeros((1, 8), jnp.int32))["params"]


def loss_fn(config: dict, job: dict):
    from horovod_tpu.models import lfm2

    return partial(lfm2.causal_lm_loss, model(config))


# As OLMoE's: AdamW at the configuration's rate, the first gradient read
# back from its first moment, ``rows`` unpadded sequences of ``seq_len + 1``
# uniform random ids of the vocabulary (here its slice) of which the model
# reads the first ``seq_len``, each labelled with its successor.
olmoe = cells.load_code(cells.HERE, "configs", "olmoe.py")
inner_optimizer = olmoe.inner_optimizer
first_gradient = olmoe.first_gradient
make_batch = olmoe.make_batch
units_per_step = olmoe.units_per_step


def kinds(config: dict) -> list:
    """``(mixer, feed-forward)`` of every layer kept, in order."""
    return [(kind, "dense" if i < config["num_dense_layers"] else "experts")
            for i, kind in enumerate(config["layer_types"])]


def macs_per_token(config: dict, seq_len: int) -> dict:
    """Multiply-adds of one forward pass per token, by part of ONE layer
    (the head: of the pass): what the mathematics needs and nothing an
    implementation adds or repeats (a recomputed layer counts once; norms
    and the rotary turn are no products). A ``conv`` mixer: its two
    projections, and a channel's ``conv_L_cache`` taps and two gates. A
    ``full_attention`` mixer: its four projections (keys and values for 8
    heads, not 32), and its two score products over the (S + 1) / 2 keys a
    query sees on average, counted as S / 2. The dense feed-forward; the
    router over all 64 experts and this chip's expected routed pairs (``4 x
    experts_here / 64`` a token whatever the router does); the tied head
    over the vocabulary's slice."""
    H, V = config["hidden_size"], config["vocab_size"]
    kv_width = H * config["num_key_value_heads"] \
        // config["num_attention_heads"]
    pairs_here = (config["num_experts_per_tok"] * config["experts_here"]
                  / config["num_experts"])
    return {
        "conv_projections": 1.0 * H * 3 * H + H * H,
        "conv_taps_and_gates": (config["conv_L_cache"] + 2.0) * H,
        "attention_projections": 2.0 * H * (H + kv_width),
        "causal_scores": 2.0 * (seq_len / 2) * H,
        "dense_feed_forward": 3.0 * H * config["intermediate_size"],
        "router": 1.0 * H * config["num_experts"],
        "routed_experts": pairs_here * 3.0 * H
        * config["moe_intermediate_size"],
        "head": 1.0 * H * V}


def flops_per_step(config: dict, job: dict, rows: int) -> float:
    """A training step is three forwards (the backward pass costs two),
    nothing recomputed; a multiply-add is two operations."""
    macs = macs_per_token(config, job["seq_len"])
    part = {
        CONV: macs["conv_projections"] + macs["conv_taps_and_gates"],
        ATTENTION: macs["attention_projections"] + macs["causal_scores"],
        "dense": macs["dense_feed_forward"],
        "experts": macs["router"] + macs["routed_experts"]}
    per_token = sum(part[mixer] + part[ffn] for mixer, ffn in kinds(config)) \
        + macs["head"]
    return 3.0 * 2.0 * per_token * rows * job["seq_len"]


def min_pallas_calls(config: dict) -> int:
    """The multi-tile forward, dq and dkv kernels in every
    ``full_attention`` layer (the recomputed layer keeps the forward
    kernel's results and does not run it again), or a kernel gave way to
    something else (the gated convolution has no kernel). An interpreted
    kernel is no custom call."""
    if config["training"]["attention"] != "flash":
        return 0
    return 3 * config["layer_types"].count(ATTENTION)
