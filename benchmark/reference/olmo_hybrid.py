"""Olmo Hybrid's training loss in plain ``jax.numpy`` and float32 (allenai
``Olmo-Hybrid-7B``, ``config.json``; the linear-attention layer is the
gated delta net of Yang et al. 2024, arXiv:2412.06464, as
``flash-linear-attention`` writes it): no kernels, no flax, no chunks,
nothing of ``horovod_tpu`` but the names of its parameter tree. The harness
differentiates it and runs it under ``default_matmul_precision("highest")``.

A layer, as the configuration file reads the published config: ``h = x +
RMSNorm(mixer(x))``, ``y = h + RMSNorm(mlp(h))``, a SiLU-gated feed-forward,
no bias. Three layers in four mix by linear attention: query, key and value
projections through a causal depth-wise convolution of 4 (here four shifted
adds) and SiLU; l2-normalised query (scaled by ``d_k ** -0.5``) and key;
``beta = 2 sigmoid(x W_b)``; ``g = -exp(A_log) softplus(x W_a + dt_bias)``;
then, per head and **token by token** from a zero state,

    S' = exp(g_t) S;  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T;  o_t = S_t^T q_t

an RMSNorm over each head's output (one scale for all heads) times ``SiLU(x
W_g)``, and the output projection. The fourth layer is causal softmax
attention with an RMSNorm over the whole query and key projections and, the
config's ``rope_theta`` being null, no rotary embedding.

Departures from the published description, all of them the product's and
followed here so that the two compute the same function:

* **One chip's share of the heads.** This chip holds ``heads_here`` heads
  from ``first_head`` on, in both kinds of layer: the parameter tree is that
  window's, and a layer's mixer gives the window's part of its output (the
  other chips' parts would be added before the norm; here nothing is). The
  feed-forward is whole. The vocabulary is a slice: ids, logits and loss are
  over ``vocab_size`` rows.
* Under the window the QK-norm's mean square is over the heads held; a
  deployment would all-reduce one number a row.
* ``jax.checkpoint`` around a layer, and around each run of 64 tokens of the
  recurrence, changes no arithmetic: it keeps one layer's float32 scores at
  a time (1 GB at S = 4096) and 64 + 64 of a layer's 4,096 states (1.1 MB a
  token at 15 heads of 96 x 192).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(
        jnp.square(x).mean(-1, keepdims=True) + eps) * p["scale"]


def causal_conv(x, w):
    """``x [B, S, C]``, ``w [C, 4]``: ``w[:, 3]`` weighs the token itself,
    ``w[:, 0]`` the one three before it; zeros before the sequence."""
    seq = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
    return (padded[:, 0:seq] * w[:, 0] + padded[:, 1:seq + 1] * w[:, 1]
            + padded[:, 2:seq + 2] * w[:, 2] + padded[:, 3:seq + 3] * w[:, 3])


def delta_rule(q, k, v, g, beta):
    """``q``, ``k`` ``[B, S, H, d_k]``, ``v [B, S, H, d_v]``, ``g``,
    ``beta`` ``[B, S, H]`` → ``o [B, S, H, d_v]``, one token at a time."""
    batch, seq, heads, d_k = q.shape

    def one_token(state, xs):
        q, k, v, g, beta = xs
        state = jnp.exp(g)[..., None, None] * state
        seen = jnp.einsum("bhkv,bhk->bhv", state, k)
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", beta[..., None] * k, v - seen)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q)

    @jax.checkpoint
    def one_run(state, xs):
        return jax.lax.scan(one_token, state, xs)

    run = math.gcd(seq, 64)
    by_run = jax.tree.map(
        lambda x: jnp.moveaxis(x, 1, 0).reshape(
            (seq // run, run) + x.shape[:1] + x.shape[2:]),
        (q, k, v, g, beta))
    state = jnp.zeros((batch, heads, d_k, v.shape[-1]), v.dtype)
    _, out = jax.lax.scan(one_run, state, by_run)
    return jnp.moveaxis(out.reshape((seq,) + out.shape[2:]), 0, 1)


def linear_attention(config, x, p):
    heads = x.shape[:2] + (config["heads_here"], -1)
    q, k, v = (
        jax.nn.silu(causal_conv(x @ p[name]["kernel"], p[name + "_conv"]))
        .reshape(heads) for name in ("query", "key", "value"))
    q = q * jax.lax.rsqrt(jnp.square(q).sum(-1, keepdims=True) + 1e-6) \
        / math.sqrt(config["linear_key_head_dim"])
    k = k * jax.lax.rsqrt(jnp.square(k).sum(-1, keepdims=True) + 1e-6)
    beta = jax.nn.sigmoid(x @ p["beta"]["kernel"])
    if config["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(
        x @ p["decay"]["kernel"] + p["dt_bias"])
    out = rms_norm(delta_rule(q, k, v, g, beta), p["o_norm"],
                   config["rms_norm_eps"])
    out = out * jax.nn.silu(x @ p["gate"]["kernel"]).reshape(heads)
    return out.reshape(x.shape[:2] + (-1,)) @ p["out"]["kernel"]


def rope(x, theta):
    """``x [B, S, H, D]``: lane ``i`` rotates with lane ``i + D/2``."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def full_attention(config, x, p):
    heads = x.shape[:2] + (config["heads_here"], -1)
    eps = config["rms_norm_eps"]
    theta = config["rope_parameters"]["rope_theta"]
    q = rms_norm(x @ p["query"]["kernel"], p["q_norm"], eps).reshape(heads)
    k = rms_norm(x @ p["key"]["kernel"], p["k_norm"], eps).reshape(heads)
    v = (x @ p["value"]["kernel"]).reshape(heads)
    if theta is not None:  # null as published: no rotary embedding
        q, k = rope(q, theta), rope(k, theta)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    seq = x.shape[1]
    scores = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), scores, -jnp.inf)
    context = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    return context.reshape(x.shape[:2] + (-1,)) @ p["out"]["kernel"]


def mlp(x, p):
    return (jax.nn.silu(x @ p["gate"]["kernel"]) * (x @ p["up"]["kernel"])) \
        @ p["down"]["kernel"]


def loss(config, params, tokens):
    """``tokens [rows, S + 1]``: positions ``0..S-1`` are read, ``1..S``
    are their labels."""
    eps = config["rms_norm_eps"]
    x = params["token_embeddings"]["embedding"][tokens[:, :-1]]
    for i, kind in enumerate(config["layer_types"]):
        @jax.checkpoint
        def layer(x, p, kind=kind):
            if kind == "linear_attention":
                mixed = linear_attention(config, x, p["linear_attention"])
            else:
                mixed = full_attention(config, x, p["attention"])
            x = x + rms_norm(mixed, p["ln_mixer"], eps)
            return x + rms_norm(mlp(x, p["mlp"]), p["ln_mlp"], eps)

        x = layer(x, params[f"layer_{i}"])
    logits = rms_norm(x, params["ln_out"], eps) @ params["lm_head"]
    log_probs = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(log_probs, tokens[:, 1:, None], -1).mean()
