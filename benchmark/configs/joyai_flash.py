"""JoyAI-LLM Flash causal-LM pre-training with its multi-token-prediction
module through the product's own model
(``horovod_tpu.models.joyai_flash``): what a configuration file of this
family needs beside its sizes. The harness calls these and nothing else."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

import cells


def model_config(config: dict):
    from horovod_tpu.models import joyai_flash

    training = config["training"]
    return joyai_flash.JoyAIFlashConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_layers=config["num_hidden_layers"],
        first_k_dense_replace=config["first_k_dense_replace"],
        num_attention_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        rope_theta=float(config["rope_theta"]),
        num_experts=config["n_routed_experts"],
        top_k=config["num_experts_per_tok"],
        num_shared_experts=config["n_shared_experts"],
        routed_scaling_factor=config["routed_scaling_factor"],
        num_nextn_predict_layers=config["num_nextn_predict_layers"],
        mtp_loss_weight=config["mtp_loss_weight"],
        experts_here=config["experts_here"],
        first_expert=config["first_expert"],
        capacity_factor=config["capacity_factor"],
        rms_norm_eps=config["rms_norm_eps"],
        remat=training["remat"],
        dtype=jnp.dtype(training["compute_dtype"]))


def model(config: dict):
    from horovod_tpu.models import joyai_flash

    if not config["rope_interleave"] or config["rope_scaling"] is not None:
        raise ValueError(
            "joyai_flash: the rotary split here is the source's interleaved "
            "pairs without rescaling; got rope_interleave="
            f"{config['rope_interleave']}, rope_scaling="
            f"{config['rope_scaling']}")
    attention = {
        "flash": joyai_flash.flash_attention_fn,
        # the toy cell's: the two-width multi-tile kernels, interpreted
        "flash_interpret": partial(
            joyai_flash.flash_attention_fn, interpret=True,
            block=config["training"].get("attention_block")),
        "dense": None}[config["training"]["attention"]]
    return joyai_flash.JoyAIFlash(model_config(config),
                                  attention_fn=attention)


def init_params(config: dict, job: dict, key):
    """Random weights: the flax model's initialisers from the seed. They
    depend neither on the attention function nor on the input length."""
    from horovod_tpu.models import joyai_flash

    ids = jnp.zeros((1, 8), jnp.int32)
    return joyai_flash.JoyAIFlash(model_config(config)).init(
        key, *[ids] * (1 + config["num_nextn_predict_layers"]))["params"]


def loss_fn(config: dict, job: dict):
    from horovod_tpu.models import joyai_flash

    return partial(joyai_flash.mtp_lm_loss, model(config))


# As OLMoE's: AdamW at the configuration's rate, the first gradient read
# back from its first moment, tokens a step.
olmoe = cells.load_code(cells.HERE, "configs", "olmoe.py")
inner_optimizer = olmoe.inner_optimizer
first_gradient = olmoe.first_gradient
units_per_step = olmoe.units_per_step


def make_batch(config: dict, job: dict, key, rows: int):
    """``rows`` unpadded sequences of ``seq_len + 2`` uniform random ids of
    the vocabulary's slice: the model reads the first ``seq_len``, each is
    labelled with its successor, and the prediction module's output with
    the token after that (``seq_len + 1`` ids without the module)."""
    length = job["seq_len"] + 1 + config["num_nextn_predict_layers"]
    return jax.random.randint(
        key, (rows, length), 0, config["vocab_size"], jnp.int32)


def kinds(config: dict) -> list:
    """``(mixer, feed-forward)`` of every layer kept, in order, the
    prediction module's layer last."""
    depth = config["num_hidden_layers"] + config["num_nextn_predict_layers"]
    return [("mla",
             "dense" if i < config["first_k_dense_replace"] else "experts")
            for i in range(depth)]


def macs_per_token(config: dict, seq_len: int) -> dict:
    """Multiply-adds of one forward pass per token, by part of ONE layer
    (the head and the module's joining projection: of one pass): what the
    mathematics needs and nothing an implementation adds or repeats (a
    recomputed layer counts once; the rotary turn is no product). Latent
    attention: its five projections, and its two score products over the (S
    + 1) / 2 keys a query sees on average, counted as S / 2, the first over
    192 lanes and the second over 128. The dense feed-forward; the router
    over all 256 experts, the shared expert and this chip's expected routed
    pairs (``8 x experts_here / 256`` a token whatever the router does); the
    head over the vocabulary's slice, which the main pass and the module
    each run once."""
    H, V = config["hidden_size"], config["vocab_size"]
    heads = config["num_attention_heads"]
    nope, rope, v_dim = (config["qk_nope_head_dim"],
                         config["qk_rope_head_dim"], config["v_head_dim"])
    q_rank, kv_rank = config["q_lora_rank"], config["kv_lora_rank"]
    moe = config["moe_intermediate_size"]
    pairs_here = (config["num_experts_per_tok"] * config["experts_here"]
                  / config["n_routed_experts"])
    return {
        "mla_projections": 1.0 * H * q_rank + q_rank * heads * (nope + rope)
        + H * (kv_rank + rope) + kv_rank * heads * (nope + v_dim)
        + heads * v_dim * H,
        "causal_scores": (seq_len / 2) * heads * (nope + rope + v_dim),
        "dense_feed_forward": 3.0 * H * config["intermediate_size"],
        "router": 1.0 * H * config["n_routed_experts"],
        "shared_expert": 3.0 * H * moe * config["n_shared_experts"],
        "routed_experts": pairs_here * 3.0 * H * moe,
        "mtp_projection": 2.0 * H * H,
        "head": 1.0 * H * V}


def _layer_macs(macs: dict) -> dict:
    """Multiply-adds a token of a layer's mixer or feed-forward, by kind."""
    return {
        "mla": macs["mla_projections"] + macs["causal_scores"],
        "dense": macs["dense_feed_forward"],
        "experts": (macs["router"] + macs["shared_expert"]
                    + macs["routed_experts"])}


def mtp_flops_per_step(config: dict, job: dict, rows: int) -> float:
    """The prediction module's part of ``flops_per_step``: its joining
    projection, its decoder layer and its pass through the head."""
    macs = macs_per_token(config, job["seq_len"])
    part = _layer_macs(macs)
    per_token = config["num_nextn_predict_layers"] * (
        macs["mtp_projection"] + part["mla"] + part["experts"]
        + macs["head"])
    return 3.0 * 2.0 * per_token * rows * job["seq_len"]


def flops_per_step(config: dict, job: dict, rows: int) -> float:
    """A training step is three forwards (the backward pass costs two),
    nothing recomputed; a multiply-add is two operations: the main stack's
    layers and its pass through the head, and the module's part."""
    macs = macs_per_token(config, job["seq_len"])
    part = _layer_macs(macs)
    stack = kinds(config)[:config["num_hidden_layers"]]
    per_token = sum(part[mixer] + part[ffn] for mixer, ffn in stack) \
        + macs["head"]
    return 3.0 * 2.0 * per_token * rows * job["seq_len"] \
        + mtp_flops_per_step(config, job, rows)


def min_pallas_calls(config: dict) -> int:
    """The multi-tile forward, dq and dkv kernels in every layer, the
    module's among them (a recomputed layer keeps the forward kernel's
    results and does not run it again), or a kernel gave way to something
    else. An interpreted kernel is no custom call."""
    if config["training"]["attention"] != "flash":
        return 0
    return 3 * len(kinds(config))
