"""OLMoE's training loss in plain ``jax.numpy`` and float32 (Muennighoff et
al. 2024, arXiv:2409.02060; ``transformers``' ``modeling_olmoe.py``): no
kernels, no flax, nothing of ``horovod_tpu`` but the names of its parameter
tree. The harness differentiates it and runs it under
``default_matmul_precision("highest")``.

A layer, as published: pre-norm; RMSNorm over the whole query and key
projections before the split into heads; RoPE (half-split); full causal
softmax; a float32 router whose top-k gates are the softmax's own, not
renormalised; SiLU-gated experts, no shared expert, no bias anywhere; an
untied head over every position. The loss is next-token cross entropy plus
0.01 x load balance plus 0.001 x router z-loss (the paper's coefficients).

Departures from the paper, all of them the product's and followed here so
that the two compute the same function:

* **One chip's share.** This chip holds ``experts_here`` experts from
  ``first_expert`` on; the router keeps its published width and top-k. A
  (token, pick) pair routed outside the window adds nothing here (in the
  deployment its chip computes it). Attention, router and head are whole.
* **Capacity slots.** OLMoE was trained dropless. Here one sequence is one
  routing group, pairs take an expert's slots in token order, then pick
  order, and a pair past ``ceil(capacity_factor x S x top_k /
  num_experts)`` adds nothing. The experts are computed the plain way,
  every expert of the window on every token, weighted by gate x in window
  x kept.
* The picks are ``top_k`` of the router's logits (ties to the lower index);
  ``transformers`` takes them of the probabilities, which differs only
  where two float32 probabilities round to one value.
* Both auxiliary losses are taken per sequence and per layer and averaged
  (``transformers`` concatenates all layers' tokens first, which gives the
  mean of the layers' ``f`` times the mean of their ``P``); the load
  balance is over all ``num_experts``, whoever holds them.
* ``jax.checkpoint`` around a layer changes no arithmetic and keeps one
  layer's float32 scores at a time (1.07 GB at S = 4096).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(
        jnp.square(x).mean(-1, keepdims=True) + eps) * p["scale"]


def rope(x, theta):
    """``x [B, S, H, D]``: lane ``i`` rotates with lane ``i + D/2``."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(config, x, p):
    heads = x.shape[:2] + (config["num_attention_heads"], -1)
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    q = rms_norm(x @ p["query"]["kernel"], p["q_norm"], eps).reshape(heads)
    k = rms_norm(x @ p["key"]["kernel"], p["k_norm"], eps).reshape(heads)
    v = (x @ p["value"]["kernel"]).reshape(heads)
    q, k = rope(q, theta), rope(k, theta)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    seq = x.shape[1]
    scores = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), scores, -jnp.inf)
    context = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return context.reshape(x.shape) @ p["out"]["kernel"]


def experts(config, tokens, p):
    """One sequence ``[S, D]`` through the router and this chip's window
    of the experts: ``(weighted outputs [S, D], load balance, z-loss)``."""
    num_experts, top_k = config["num_experts"], config["num_experts_per_tok"]
    first, here = config["first_expert"], config["experts_here"]
    seq = tokens.shape[0]
    capacity = math.ceil(
        config["capacity_factor"] * seq * top_k / num_experts)
    logits = tokens @ p["router"]
    probs = jax.nn.softmax(logits, -1)
    _, picks = jax.lax.top_k(logits, top_k)                      # [S, K]
    gates = jnp.take_along_axis(probs, picks, -1)
    # [S, K, here]: the pair is this window's expert e's
    mine = picks[..., None] == first + jnp.arange(here)
    # pairs before it in the same expert's queue, token then pick order
    ahead = jnp.cumsum(mine.reshape(seq * top_k, here), 0).reshape(
        seq, top_k, here) - mine
    kept = mine & (ahead < capacity)
    weight = (gates[..., None] * kept).sum(1)                    # [S, here]
    hidden = jax.nn.silu(jnp.einsum("sd,edh->seh", tokens,
                                    p["experts_gate"])) \
        * jnp.einsum("sd,edh->seh", tokens, p["experts_up"])
    out = jnp.einsum("seh,ehd,se->sd", hidden, p["experts_down"], weight)
    share = jax.nn.one_hot(picks, num_experts).sum((0, 1)) / seq
    balance = num_experts * jnp.sum(share * probs.mean(0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, -1)))
    return out, balance, z


def loss(config, params, tokens):
    """``tokens [rows, S + 1]``: positions ``0..S-1`` are read, ``1..S``
    are their labels."""
    eps = config["rms_norm_eps"]
    x = params["token_embeddings"]["embedding"][tokens[:, :-1]]

    @jax.checkpoint
    def layer(x, p):
        x = x + attention(config, rms_norm(x, p["ln_attn"], eps),
                          p["attention"])
        out, balance, z = jax.vmap(lambda t: experts(config, t, p["moe"]))(
            rms_norm(x, p["ln_moe"], eps))
        return x + out, balance.mean(), z.mean()

    balance = z = 0.0
    layers = config["num_hidden_layers"]
    for i in range(layers):
        x, layer_balance, layer_z = layer(x, params[f"layer_{i}"])
        balance, z = balance + layer_balance, z + layer_z
    logits = rms_norm(x, params["ln_out"], eps) @ params["lm_head"]
    log_probs = jax.nn.log_softmax(logits, -1)
    picked = jnp.take_along_axis(log_probs, tokens[:, 1:, None], -1)
    training = config["training"]
    return (-picked.mean()
            + training["load_balance_coef"] * balance / layers
            + training["router_z_coef"] * z / layers)
