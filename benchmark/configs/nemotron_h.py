"""Nemotron-H causal-LM pre-training through the product's own model
(``horovod_tpu.models.nemotron_h``): what a configuration file of this
family needs beside its sizes. The harness calls these and nothing else."""

from __future__ import annotations

from functools import partial

import jax.numpy as jnp

import cells

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def model_config(config: dict):
    from horovod_tpu.models import nemotron_h

    training = config["training"]
    return nemotron_h.NemotronHConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        hybrid_override_pattern=config["hybrid_override_pattern"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        mamba_num_heads=config["mamba_num_heads"],
        mamba_head_dim=config["mamba_head_dim"],
        ssm_state_size=config["ssm_state_size"],
        n_groups=config["n_groups"],
        conv_kernel=config["conv_kernel"],
        chunk_size=config["chunk_size"],
        num_experts=config["n_routed_experts"],
        top_k=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=config[
            "moe_shared_expert_intermediate_size"],
        routed_scaling_factor=config["routed_scaling_factor"],
        experts_here=config["experts_here"],
        first_expert=config["first_expert"],
        capacity_factor=config["capacity_factor"],
        rms_norm_eps=config["layer_norm_epsilon"],
        remat=training["remat"],
        dtype=jnp.dtype(training["compute_dtype"]))


def model(config: dict):
    from horovod_tpu.models import nemotron_h

    attention = {
        "flash": nemotron_h.flash_attention_fn,
        # the toy cell's: the grouped multi-tile kernels, interpreted
        "flash_interpret": partial(
            nemotron_h.flash_attention_fn, interpret=True,
            block=config["training"].get("attention_block")),
        "dense": None}[config["training"]["attention"]]
    return nemotron_h.NemotronH(model_config(config),
                                attention_fn=attention)


def init_params(config: dict, job: dict, key):
    """Random weights: the flax model's initialisers from the seed. They
    depend neither on the attention function nor on the input length."""
    from horovod_tpu.models import nemotron_h

    built = model_config(config)
    return nemotron_h.NemotronH(built).init(
        key, jnp.zeros((1, built.chunk_size), jnp.int32))["params"]


def loss_fn(config: dict, job: dict):
    from horovod_tpu.models import nemotron_h

    return partial(nemotron_h.causal_lm_loss, model(config))


# As OLMoE's: AdamW at the configuration's rate, the first gradient read
# back from its first moment, ``rows`` unpadded sequences of ``seq_len + 1``
# uniform random ids of the vocabulary's slice of which the model reads the
# first ``seq_len``, each labelled with its successor.
olmoe = cells.load_code(cells.HERE, "configs", "olmoe.py")
inner_optimizer = olmoe.inner_optimizer
first_gradient = olmoe.first_gradient
make_batch = olmoe.make_batch
units_per_step = olmoe.units_per_step


def macs_per_token(config: dict, seq_len: int) -> dict:
    """Multiply-adds of one forward pass per token, by part of ONE layer
    (the head: of the model): what the mathematics needs and nothing an
    implementation adds or repeats (a recomputed layer counts once). An
    ``M`` layer: the projection to ``[z | xBC | dt]`` and the output
    projection (``mamba_projections``), four taps a channel
    (``short_conv``), and the recurrence at three ``P x N`` products a
    token a head (the decay, the rank-one update ``dt x B^T`` and the read
    ``h C``), whatever decay matrices and masked halves a chunked form
    computes beside them. A ``*`` layer: its four projections (keys and
    values for 2 heads, not 32), and its two score products over the
    (S + 1) / 2 keys a query sees on average, counted as S / 2. An ``E``
    layer: the router over all 128 experts, the shared expert's two
    matrices and this chip's expected routed pairs (``6 x experts_here /
    128`` a token whatever the router does) at two matrices each; the head
    over the vocabulary's slice."""
    H, V = config["hidden_size"], config["vocab_size"]
    heads, width, state = (config["mamba_num_heads"],
                           config["mamba_head_dim"], config["ssm_state_size"])
    inner = heads * width
    mixed = inner + 2 * config["n_groups"] * state
    wide = config["num_attention_heads"] * config["head_dim"]
    kv_wide = config["num_key_value_heads"] * config["head_dim"]
    pairs_here = (config["num_experts_per_tok"] * config["experts_here"]
                  / config["n_routed_experts"])
    return {
        "mamba_projections": 1.0 * H * (inner + mixed + heads) + inner * H,
        "short_conv": 1.0 * config["conv_kernel"] * mixed,
        "recurrence": 3.0 * heads * width * state,
        "attention_projections": 2.0 * H * (wide + kv_wide),
        "causal_scores": 2.0 * (seq_len / 2) * wide,
        "router": 1.0 * H * config["n_routed_experts"],
        "shared_expert": 2.0 * H * config[
            "moe_shared_expert_intermediate_size"],
        "routed_experts": pairs_here * 2.0 * H * config[
            "moe_intermediate_size"],
        "head": 1.0 * H * V}


def flops_per_step(config: dict, job: dict, rows: int) -> float:
    """A training step is three forwards (the backward pass costs two),
    nothing recomputed; a multiply-add is two operations."""
    macs = macs_per_token(config, job["seq_len"])
    by_kind = {
        MAMBA: (macs["mamba_projections"] + macs["short_conv"]
                + macs["recurrence"]),
        ATTENTION: macs["attention_projections"] + macs["causal_scores"],
        EXPERTS: (macs["router"] + macs["shared_expert"]
                  + macs["routed_experts"])}
    per_token = sum(by_kind[kind]
                    for kind in config["hybrid_override_pattern"]) \
        + macs["head"]
    return 3.0 * 2.0 * per_token * rows * job["seq_len"]


def min_pallas_calls(config: dict) -> int:
    """The multi-tile forward, dq and dkv kernels in every ``*`` layer (a
    recomputed layer keeps the forward kernel's results and does not run it
    again), or a kernel gave way to something else (the scan has no kernel
    yet). An interpreted kernel is no custom call."""
    if config["training"]["attention"] != "flash":
        return 0
    return 3 * config["hybrid_override_pattern"].count(ATTENTION)
