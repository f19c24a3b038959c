"""Kimi Linear causal-LM pre-training through the product's own model
(``horovod_tpu.models.kimi_linear``): what a configuration file of this
family needs beside its sizes. The harness calls these and nothing else."""

from __future__ import annotations

from functools import partial

import jax.numpy as jnp

import cells


def model_config(config: dict):
    from horovod_tpu.models import kimi_linear

    linear, training = config["linear_attn_config"], config["training"]
    return kimi_linear.KimiLinearConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_layers=config["num_hidden_layers"],
        kda_layers=tuple(linear["kda_layers"]),
        full_attn_layers=tuple(linear["full_attn_layers"]),
        first_k_dense_replace=config["first_k_dense_replace"],
        num_attention_heads=config["num_attention_heads"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        linear_num_heads=linear["num_heads"],
        linear_head_dim=linear["head_dim"],
        short_conv_kernel_size=linear["short_conv_kernel_size"],
        num_experts=config["num_experts"],
        top_k=config["num_experts_per_token"],
        num_shared_experts=config["num_shared_experts"],
        routed_scaling_factor=config["routed_scaling_factor"],
        experts_here=config["experts_here"],
        first_expert=config["first_expert"],
        capacity_factor=config["capacity_factor"],
        chunk=training["chunk"],
        sub_chunk=training["sub_chunk"],
        rms_norm_eps=config["rms_norm_eps"],
        remat=training["remat"],
        dtype=jnp.dtype(training["compute_dtype"]))


def model(config: dict):
    from horovod_tpu.models import kimi_linear

    attention = {
        "flash": kimi_linear.flash_attention_fn,
        # the toy cell's: the two-width multi-tile kernels, interpreted
        "flash_interpret": partial(
            kimi_linear.flash_attention_fn, interpret=True,
            block=config["training"].get("attention_block")),
        "dense": None}[config["training"]["attention"]]
    return kimi_linear.KimiLinear(model_config(config),
                                  attention_fn=attention)


def init_params(config: dict, job: dict, key):
    """Random weights: the flax model's initialisers from the seed. They
    depend neither on the attention function nor on the input length."""
    from horovod_tpu.models import kimi_linear

    built = model_config(config)
    return kimi_linear.KimiLinear(built).init(
        key, jnp.zeros((1, built.chunk), jnp.int32))["params"]


def loss_fn(config: dict, job: dict):
    from horovod_tpu.models import kimi_linear

    return partial(kimi_linear.causal_lm_loss, model(config))


# As OLMoE's: AdamW at the configuration's rate, the first gradient read
# back from its first moment, ``rows`` unpadded sequences of ``seq_len + 1``
# uniform random ids of the vocabulary's slice of which the model reads the
# first ``seq_len``, each labelled with its successor.
olmoe = cells.load_code(cells.HERE, "configs", "olmoe.py")
inner_optimizer = olmoe.inner_optimizer
first_gradient = olmoe.first_gradient
make_batch = olmoe.make_batch
units_per_step = olmoe.units_per_step


def kinds(config: dict) -> list:
    """``(mixer, feed-forward)`` of every layer kept, in order."""
    linear = config["linear_attn_config"]
    return [("kda" if i + 1 in linear["kda_layers"] else "mla",
             "dense" if i < config["first_k_dense_replace"] else "experts")
            for i in range(config["num_hidden_layers"])]


def macs_per_token(config: dict, seq_len: int) -> dict:
    """Multiply-adds of one forward pass per token, by part of ONE layer
    (the head: of the model): what the mathematics needs and nothing an
    implementation adds or repeats (a recomputed layer counts once). A
    ``kda`` mixer: q, k, v and the output projection, the two low-rank
    pairs and ``beta`` (``kda_projections``), four taps a channel
    (``short_conv``), and the recurrence at four ``d x d`` products a token
    a head (the decay of the state's rows, ``S'^T k``, the rank-one update,
    ``S^T q``), whatever sub-blocks, solves and masked halves a chunked
    form computes beside them. An ``mla`` mixer: its four projections, and
    its two score products over the (S + 1) / 2 keys a query sees on
    average, counted as S / 2, the first over 192 lanes and the second over
    128. The dense feed-forward; the router over all 256 experts, the
    shared expert and this chip's expected routed pairs (``8 x experts_here
    / 256`` a token whatever the router does); the head over the
    vocabulary's slice."""
    H, V = config["hidden_size"], config["vocab_size"]
    linear = config["linear_attn_config"]
    heads, d = linear["num_heads"], linear["head_dim"]
    wide = heads * d
    a_heads = config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    nope, v_dim, rank = (config["qk_nope_head_dim"], config["v_head_dim"],
                         config["kv_lora_rank"])
    moe = config["moe_intermediate_size"]
    pairs_here = (config["num_experts_per_token"] * config["experts_here"]
                  / config["num_experts"])
    return {
        "kda_projections": 4.0 * H * wide + 2.0 * (H * d + d * wide)
        + H * heads,
        "short_conv": 1.0 * linear["short_conv_kernel_size"] * 3 * wide,
        "recurrence": 4.0 * heads * d * d,
        "mla_projections": 1.0 * H * a_heads * qk
        + H * (rank + config["qk_rope_head_dim"])
        + rank * a_heads * (nope + v_dim) + a_heads * v_dim * H,
        "causal_scores": (seq_len / 2) * a_heads * (qk + v_dim),
        "dense_feed_forward": 3.0 * H * config["intermediate_size"],
        "router": 1.0 * H * config["num_experts"],
        "shared_expert": 3.0 * H * moe * config["num_shared_experts"],
        "routed_experts": pairs_here * 3.0 * H * moe,
        "head": 1.0 * H * V}


def flops_per_step(config: dict, job: dict, rows: int) -> float:
    """A training step is three forwards (the backward pass costs two),
    nothing recomputed; a multiply-add is two operations."""
    macs = macs_per_token(config, job["seq_len"])
    part = {
        "kda": (macs["kda_projections"] + macs["short_conv"]
                + macs["recurrence"]),
        "mla": macs["mla_projections"] + macs["causal_scores"],
        "dense": macs["dense_feed_forward"],
        "experts": (macs["router"] + macs["shared_expert"]
                    + macs["routed_experts"])}
    per_token = sum(part[mixer] + part[ffn] for mixer, ffn in kinds(config)) \
        + macs["head"]
    return 3.0 * 2.0 * per_token * rows * job["seq_len"]


def min_pallas_calls(config: dict) -> int:
    """The multi-tile forward, dq and dkv kernels in every latent-attention
    layer (a recomputed layer keeps the forward kernel's results and does
    not run it again), or a kernel gave way to something else (the delta
    rule has no kernel yet). An interpreted kernel is no custom call."""
    if config["training"]["attention"] != "flash":
        return 0
    return 3 * len(config["linear_attn_config"]["full_attn_layers"])
