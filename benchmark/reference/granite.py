"""Granite 4.0-H's training loss in plain ``jax.numpy`` and float32 (IBM
``granite-4.0-h-micro``, ``config.json``, ``model_type``
``granitemoehybrid``; the ``mamba`` layer is Mamba-2, Dao & Gu 2024,
arXiv:2405.21060, as ``transformers``' ``GraniteMoeHybridMambaLayer``
writes it): no kernels, no flax, no chunks, nothing of ``horovod_tpu`` but
the names of its parameter tree. The harness differentiates it and runs it
under ``default_matmul_precision("highest")``.

The model, as the configuration file reads the published config (``d`` =
``hidden_size``, no bias but the convolution's)::

    x = embedding_multiplier * E[ids]
    x = x + residual_multiplier * Mixer(RMSNorm(x))       a layer,
    x = x + residual_multiplier * MLP(RMSNorm(x))         pre-norm
    logits = RMSNorm(x) E^T / logits_scaling              E: the one tied leaf

``MLP``: ``[a | b] = h W_in``, ``(silu(a) * b) W_out``. A ``mamba`` mixer:
``[z | xBC | dt] = h W_in``; ``xBC = silu(conv(xBC) + bias)``, depth-wise and
causal over ``mamba_d_conv`` tokens (here shifted adds); ``x [S, H, P]``,
``B``, ``C`` ``[S, N]`` split out of it (``mamba_n_groups`` groups of heads
share a ``B`` and a ``C``: one group, all of them); ``dt = softplus(dt +
dt_bias)``, ``A = -exp(A_log)``; then, per head and **token by token** from a
zero state ``h [P, N]``,

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T;   y_t = h_t C_t + D x_t

an RMSNorm with a learned scale over all the channels of ``y * silu(z)``, and
the output projection. An ``attention`` mixer: 32 query heads on 8 key/value
heads, no positional embedding (``position_embedding_type`` ``nope``),
``softmax(causal(q k^T * attention_multiplier)) v``, output projection.

Departures from the published description, both the product's and followed
here so that the two compute the same function:

* **Ten of the forty layers and an eighth of the vocabulary**: the first
  period of ``layer_types``; ids, logits and loss over ``vocab_size`` rows.
* ``jax.checkpoint`` around a layer, around each run of 64 tokens of the
  recurrence and around each key/value head's group of query heads, and
  ``lax.map`` over those groups, change no arithmetic: they keep one
  layer's activations at a time, 64 + 64 of a layer's 4,096 states (2 MB a
  token: 64 heads of 64 x 128) and one group's float32 scores (268 MB at
  S = 4,096, not the 32 heads' 2.1 GB).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

RUN = 64  # tokens of the recurrence between two kept states


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(
        jnp.square(x).mean(-1, keepdims=True) + eps) * p["scale"]


def causal_conv(x, w, bias):
    """``x [B, S, C]``, ``w [C, taps]``: ``w[:, -1]`` weighs the token
    itself, ``w[:, 0]`` the one ``taps - 1`` before it; zeros before the
    sequence."""
    taps, seq = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return bias + sum(padded[:, i:i + seq] * w[:, i] for i in range(taps))


def state_space(x, dt, a, b, c, d):
    """``x [B, S, H, P]``, ``dt [B, S, H]``, ``a``, ``d`` ``[H]``, ``b``,
    ``c`` ``[B, S, G, N]`` → ``y [B, S, H, P]``, one token at a time."""
    batch, seq, heads, width = x.shape
    share = heads // b.shape[2]

    def one_token(state, xs):
        x, dt, b, c = xs
        b, c = jnp.repeat(b, share, 1), jnp.repeat(c, share, 1)  # [B, H, N]
        state = jnp.exp(dt * a)[..., None, None] * state + jnp.einsum(
            "bhp,bhn->bhpn", dt[..., None] * x, b)
        return state, jnp.einsum("bhpn,bhn->bhp", state, c) + d[:, None] * x

    @jax.checkpoint
    def one_run(state, xs):
        return jax.lax.scan(one_token, state, xs)

    run = math.gcd(seq, RUN)
    by_run = jax.tree.map(
        lambda t: jnp.moveaxis(t, 1, 0).reshape(
            (seq // run, run) + t.shape[:1] + t.shape[2:]),
        (x, dt, b, c))
    state = jnp.zeros((batch, heads, width, b.shape[-1]), x.dtype)
    _, out = jax.lax.scan(one_run, state, by_run)
    return jnp.moveaxis(out.reshape((seq,) + out.shape[2:]), 0, 1)


def mamba(config, x, p):
    heads, groups, state = (config["mamba_n_heads"], config["mamba_n_groups"],
                            config["mamba_d_state"])
    inner = config["mamba_expand"] * config["hidden_size"]
    z, xbc, dt = jnp.split(x @ p["in_proj"]["kernel"],
                           [inner, 2 * inner + 2 * groups * state], -1)
    xbc = jax.nn.silu(causal_conv(xbc, p["conv"], p["conv_bias"]))
    inputs, b, c = jnp.split(xbc, [inner, inner + groups * state], -1)
    out = state_space(
        inputs.reshape(x.shape[:2] + (heads, config["mamba_d_head"])),
        jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]),
        b.reshape(x.shape[:2] + (groups, state)),
        c.reshape(x.shape[:2] + (groups, state)), p["D"])
    out = rms_norm(out.reshape(z.shape) * jax.nn.silu(z), p["norm"],
                   config["rms_norm_eps"])
    return out @ p["out_proj"]["kernel"]


def attention(config, x, p):
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    seq, dim = x.shape[1], config["hidden_size"] // heads
    # [KV heads, B, S, query heads of the group, D] and [KV heads, B, S, D]
    q = jnp.moveaxis((x @ p["query"]["kernel"]).reshape(
        x.shape[:2] + (kv_heads, heads // kv_heads, dim)), 2, 0)
    k, v = (jnp.moveaxis((x @ p[name]["kernel"]).reshape(
        x.shape[:2] + (kv_heads, dim)), 2, 0) for name in ("key", "value"))
    seen = jnp.tril(jnp.ones((seq, seq), bool))

    @jax.checkpoint
    def one_group(qkv):
        q, k, v = qkv
        scores = jnp.einsum("bqgd,bkd->bgqk", q, k) \
            * config["attention_multiplier"]
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("bgqk,bkd->bqgd", weights, v)

    context = jnp.moveaxis(jax.lax.map(one_group, (q, k, v)), 0, 2)
    return context.reshape(x.shape[:2] + (-1,)) @ p["out"]["kernel"]


def mlp(x, p):
    gate, up = jnp.split(x @ p["input"]["kernel"], 2, -1)
    return (jax.nn.silu(gate) * up) @ p["output"]["kernel"]


def logits(config, params, ids):
    """``ids [rows, S]`` → ``[rows, S, vocab_size]``."""
    eps, scale = config["rms_norm_eps"], config["residual_multiplier"]
    table = params["embedding"]
    x = config["embedding_multiplier"] * table[ids]
    for i, kind in enumerate(config["layer_types"]):
        @jax.checkpoint
        def layer(x, p, kind=kind):
            normed = rms_norm(x, p["ln_mixer"], eps)
            if kind == "mamba":
                mixed = mamba(config, normed, p["mamba"])
            else:
                mixed = attention(config, normed, p["attention"])
            x = x + scale * mixed
            return x + scale * mlp(rms_norm(x, p["ln_mlp"], eps), p["mlp"])

        x = layer(x, params[f"layer_{i}"])
    return rms_norm(x, params["ln_out"], eps) @ table.T \
        / config["logits_scaling"]


def loss(config, params, tokens):
    """``tokens [rows, S + 1]``: positions ``0..S-1`` are read, ``1..S``
    are their labels."""
    log_probs = jax.nn.log_softmax(logits(config, params, tokens[:, :-1]), -1)
    return -jnp.take_along_axis(log_probs, tokens[:, 1:, None], -1).mean()
