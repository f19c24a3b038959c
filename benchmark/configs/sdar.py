"""SDAR block-diffusion training through the product's own model
(``horovod_tpu.models.sdar``): what a configuration file of this family
needs beside its sizes. The harness calls these and nothing else."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import optax

import cells


def model_config(config: dict):
    from horovod_tpu.models import sdar

    training = config["training"]
    return sdar.SdarConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        intermediate_size=config["moe_intermediate_size"],
        num_experts=config["num_experts"],
        top_k=config["num_experts_per_tok"],
        experts_here=config["experts_here"],
        first_expert=config["first_expert"],
        capacity_factor=config["capacity_factor"],
        block_length=config["block_length"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        remat=training["remat"],
        dtype=jnp.dtype(training["compute_dtype"]))


def model(config: dict):
    from horovod_tpu.models import sdar

    attention = {
        "flash": sdar.flash_attention_fn,
        # the toy cell's: the multi-tile kernels, interpreted
        "flash_interpret": partial(
            sdar.flash_attention_fn, interpret=True,
            block=config["training"].get("attention_block")),
        "dense": None}[config["training"]["attention"]]
    return sdar.Sdar(model_config(config), attention_fn=attention)


def init_params(config: dict, job: dict, key):
    """Random weights: flax's initialisers from the seed. They depend
    neither on the attention function nor on the input length."""
    from horovod_tpu.models import sdar

    ids = jnp.zeros((1, 2 * config["block_length"]), jnp.int32)
    return sdar.Sdar(model_config(config)).init(key, ids, ids)["params"]


def loss_fn(config: dict, job: dict):
    from horovod_tpu.models import sdar

    return partial(sdar.block_diffusion_loss, model(config))


def inner_optimizer(config: dict):
    return optax.adamw(config["training"]["learning_rate"])


# AdamW, as BERT's: the first gradient is read back from its first moment.
first_gradient = cells.load_code(
    cells.HERE, "configs", "bert.py").first_gradient


def make_batch(config: dict, job: dict, key, rows: int):
    """``rows`` unpadded sequences of ``seq_len`` uniform random tokens
    from the slice of the vocabulary held, less its last id (the mask
    token), each with its noisy twin and its per-position loss weights:
    a tree ``{"clean", "noisy", "weight"}`` of ``[rows, seq_len]`` leaves.
    The noise is the benchmark's own draw, not the program's
    (``models.sdar.noisy_batch`` follows the same recipe and is held to
    this one bit for bit in ``tests/benchmark/test_benchmark_sdar.py``),
    so that a wrong level, mask or weight there is not both sides': every
    block of ``block_length`` positions takes a level ``t ~ U[1 /
    block_length, 1]`` from the first half of the noise key, every
    position is masked where its own uniform draw, from the second half,
    is under its block's level, and a masked position weighs ``1 / t``.
    Drawn once a batch, from the seed (a job redraws it every step)."""
    ids_key, noise_key = jax.random.split(key)
    seq_len, length = job["seq_len"], config["block_length"]
    mask_id = config["vocab_size"] - 1
    clean = jax.random.randint(ids_key, (rows, seq_len), 0, mask_id,
                               jnp.int32)
    level_key, mask_key = jax.random.split(noise_key)
    level = jax.random.uniform(level_key, (rows, seq_len // length),
                               jnp.float32, 1.0 / length, 1.0)
    level = level[:, jnp.arange(seq_len) // length]  # a position's block's
    masked = jax.random.uniform(mask_key, (rows, seq_len)) < level
    return {"clean": clean,
            "noisy": jnp.where(masked, mask_id, clean),
            "weight": masked / level}


def visible_pairs(seq_len: int, block_length: int) -> dict:
    """(query, key) pairs one head's mask leaves, by term: the clean
    stream's block-causal triangle, the noisy stream's clean past and its
    own blocks. ``S (S + B)`` in all."""
    blocks = seq_len // block_length
    return {
        "clean": block_length ** 2 * blocks * (blocks + 1) // 2,
        "past": block_length ** 2 * blocks * (blocks - 1) // 2,
        "own": block_length ** 2 * blocks}


def macs_per_token(config: dict, seq_len: int) -> dict:
    """Multiply-adds of one forward pass per CLEAN token, by part, summed
    over the layers kept: what the mathematics needs and nothing the
    implementation adds or repeats (a recomputed layer counts once). A
    clean token is two stream positions: both go through projections,
    router and this chip's expected expert pairs (``8 x experts_here /
    128`` a position whatever the router does); attention's two products
    see exactly the ``S (S + B)`` pairs a query head the mask leaves; keys
    and values are projected for 4 heads, not 32; the head reads the noisy
    position only. Empty slots, masked parts of tiles and the recomputed
    forward count for nothing and show as lost ``mfu``."""
    H, I, V = (config["hidden_size"], config["moe_intermediate_size"],
               config["vocab_size"])
    q_width = config["num_attention_heads"] * config["head_dim"]
    kv_width = config["num_key_value_heads"] * config["head_dim"]
    layers = config["num_hidden_layers"]
    pairs_here = (config["num_experts_per_tok"] * config["experts_here"]
                  / config["num_experts"])
    seen = sum(visible_pairs(seq_len, config["block_length"]).values())
    return {"projections": layers * 2 * 2.0 * H * (q_width + kv_width),
            "scores": layers * 2.0 * q_width * seen / seq_len,
            "router": layers * 2 * 1.0 * H * config["num_experts"],
            "experts": layers * 2 * pairs_here * 3.0 * H * I,
            "head": 1.0 * H * V}


def flops_per_step(config: dict, job: dict, rows: int) -> float:
    """A training step is three forwards (the backward pass costs two),
    nothing recomputed; a multiply-add is two operations."""
    per_token = sum(macs_per_token(config, job["seq_len"]).values())
    return 3.0 * 2.0 * per_token * rows * job["seq_len"]


def units_per_step(job: dict, rows: int) -> tuple[int, str]:
    """Clean tokens: what a job's data holds, whatever the stream doubles."""
    return rows * job["seq_len"], "tokens"


def min_pallas_calls(config: dict) -> int:
    """Two calls of the multi-tile kernels a layer (the clean stream and
    the noisy stream's clean past), each a forward, a dq and a dkv kernel
    (the recomputed layer keeps the forward kernels' results and does not
    run them again), or a kernel gave way to something else. An
    interpreted kernel is no custom call."""
    if config["training"]["attention"] != "flash":
        return 0
    return 6 * config["num_hidden_layers"]
