"""Nemotron-H's training loss in plain ``jax.numpy`` and float32 (NVIDIA
``NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``, ``config.json``, ``model_type``
``nemotron_h``; the family's layers: "Nemotron-H", arXiv:2504.03624, as
``transformers`` writes ``modeling_nemotron_h.py``; the ``M`` layer is
Mamba-2, Dao & Gu 2024, arXiv:2405.21060): no kernels, no flax, no chunks, no
slots, nothing of ``horovod_tpu`` but the names of its parameter tree. The
harness differentiates it and runs it under
``default_matmul_precision("highest")``.

Every layer is one normed residual branch, ``d = hidden_size``, no bias but
the convolution's::

    x = x + Mixer_kind(RMSNorm(x))       kind: a character of
                                         hybrid_override_pattern

then a final RMSNorm, an untied head and the mean next-token cross entropy
over every position.

**``M``** (64 heads of 64, a state of 128, 8 groups of ``B`` and ``C``)::

    [z | xBC | dt] = h W_in                  4,096 | 4,096 + 2 x 8 x 128 | 64
    xBC = silu(conv4(xBC) + bias)            depth-wise, causal: shifted adds
    x [S, 64, 64], B, C [S, 8, 128]          head h reads group h // 8
    dt = softplus(dt + dt_bias);  A = -exp(A_log)

and per head, **token by token** from a zero state ``h [64, 128]``::

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T;   y_t = h_t C_t + D x_t

then ``g = y * silu(z)``, an RMSNorm of ``g`` **in 8 groups of 512 channels,
each over its own mean square**, times a learned scale of 4,096, and the
output projection 4,096 -> 2,688.

**``*``**: 32 query heads on 2 key/value heads of 128 (query head ``h`` reads
key/value head ``h // 16``), no positional embedding,
``softmax(causal(q k^T / sqrt(128))) v``, output 4,096 -> 2,688.

**``E``**: ``s = sigmoid(u W_r)`` (128 wide); the picks are the top 6 of
``s`` (one expert group); ``w_e = s_e / (sum over the picks + 1e-20) x
routed_scaling_factor``; ``y = sum_e w_e Expert_e(u) + Shared(u)``, every
expert and the shared one **two matrices**: ``W_down relu(W_up u)^2``.

Departures from the published description, all of them the product's and
followed here so that the two compute the same function:

* **Nine of the 52 layers and an eighth of the vocabulary**: the source's
  first nine characters; ids, logits and loss over ``vocab_size`` rows.
* **One chip's share of the experts.** This chip holds ``experts_here``
  experts from ``first_expert`` on; the router keeps its 128 outputs and its
  6 picks, and the gates are normalised over all six picks wherever they
  live. A (position, pick) pair routed outside the window adds nothing
  here. The mixers, the router, the shared expert and the head are whole.
* **Capacity slots** (``assumed.capacity_factor``; the source drops
  nothing). One row is one routing group; pairs take an expert's slots in
  token order, then pick order, and a pair past ``ceil(capacity_factor x S
  x 6 / 128)`` adds nothing. This reference has no slots: it goes through
  the experts held one after another, each on every position, weighted by
  gate x kept, where "kept" is that same count of the pairs ahead in the
  expert's queue.
* **No selection bias.** The source adds ``e_score_correction_bias`` to
  ``s`` for the choice alone and moves it outside the gradient; at its
  initial zero the choice is by ``s``, and so it is here.
* The picks are ``top_k`` of the scores (ties to the lower index).
* **Blocking, not a departure**: attention is mapped over query heads and
  over blocks of ``QUERY_BLOCK`` queries under ``jax.checkpoint``, the
  recurrence is checkpointed in runs of ``RUN`` tokens and every layer as a
  whole. The arithmetic of a row is that of the whole matrix.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

RUN = 64  # tokens of the recurrence between two kept states
QUERY_BLOCK = 2048  # queries a step of the map; a shorter sequence is one


def rms_norm(x, p, eps, groups=1):
    """Over the last axis, or over each of its ``groups`` equal runs."""
    runs = x.reshape(x.shape[:-1] + (groups, -1))
    runs = runs * jax.lax.rsqrt(
        jnp.square(runs).mean(-1, keepdims=True) + eps)
    return runs.reshape(x.shape) * p["scale"]


def causal_conv(x, w, bias):
    """``x [B, S, C]``, ``w [C, taps]``: ``w[:, -1]`` weighs the token
    itself, ``w[:, 0]`` the one ``taps - 1`` before it; zeros before the
    sequence."""
    taps, seq = w.shape[1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return bias + sum(padded[:, i:i + seq] * w[:, i] for i in range(taps))


def state_space(x, dt, a, b, c, d):
    """``x [B, S, H, P]``, ``dt [B, S, H]``, ``a``, ``d`` ``[H]``, ``b``,
    ``c`` ``[B, S, G, N]`` → ``y [B, S, H, P]``, one token at a time; head
    ``h`` reads group ``h // (H / G)``."""
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


def mamba(config, h, p):
    heads, width, groups, state = (
        config["mamba_num_heads"], config["mamba_head_dim"],
        config["n_groups"], config["ssm_state_size"])
    inner = heads * width
    z, xbc, dt = jnp.split(h @ p["in_proj"]["kernel"],
                           [inner, 2 * inner + 2 * groups * state], -1)
    xbc = jax.nn.silu(causal_conv(xbc, p["conv"], p["conv_bias"]))
    inputs, b, c = jnp.split(xbc, [inner, inner + groups * state], -1)
    out = state_space(
        inputs.reshape(h.shape[:2] + (heads, width)),
        jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]),
        b.reshape(h.shape[:2] + (groups, state)),
        c.reshape(h.shape[:2] + (groups, state)), p["D"])
    out = rms_norm(out.reshape(z.shape) * jax.nn.silu(z), p["norm"],
                   config["layer_norm_epsilon"], groups)
    return out @ p["out_proj"]["kernel"]


def attention(config, h, p):
    batch, seq = h.shape[:2]
    heads, kv_heads, dim = (config["num_attention_heads"],
                            config["num_key_value_heads"], config["head_dim"])
    q = (h @ p["query"]["kernel"]).reshape(batch, seq, heads, dim)
    k, v = ((h @ p[name]["kernel"]).reshape(batch, seq, kv_heads, dim)
            for name in ("key", "value"))
    block = min(seq, QUERY_BLOCK)

    @jax.checkpoint
    def one_block(q_block, first, k_head, v_head):
        scores = jnp.einsum("bqd,bkd->bqk", q_block, k_head) / math.sqrt(dim)
        ahead = (first + jnp.arange(block))[:, None] >= jnp.arange(seq)[None]
        return jnp.einsum(
            "bqk,bkd->bqd",
            jax.nn.softmax(jnp.where(ahead, scores, -jnp.inf), -1), v_head)

    def one_head(args):
        q_head, shared = args  # [B, S, D], the key/value head it reads
        k_head, v_head = k[:, :, shared], v[:, :, shared]
        blocks = q_head.reshape(batch, seq // block, block, dim)
        out = jax.lax.map(
            lambda args: one_block(args[0], args[1], k_head, v_head),
            (blocks.transpose(1, 0, 2, 3),
             jnp.arange(seq // block) * block))
        return out.transpose(1, 0, 2, 3).reshape(batch, seq, dim)

    context = jax.lax.map(one_head, (
        q.transpose(2, 0, 1, 3),
        jnp.arange(heads) // (heads // kv_heads)))  # [H, B, S, D]
    context = context.transpose(1, 2, 0, 3).reshape(batch, seq, heads * dim)
    return context @ p["out"]["kernel"]


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def plain_mlp(x, p):
    return relu2(x @ p["up"]["kernel"]) @ p["down"]["kernel"]


def experts(config, tokens, p):
    """One row ``[S, d]`` (normalised) through the router and this chip's
    window of the experts: the weighted outputs ``[S, d]``, without the
    shared expert."""
    num_experts, top_k = config["n_routed_experts"], config[
        "num_experts_per_tok"]
    first, here = config["first_expert"], config["experts_here"]
    seq = tokens.shape[0]
    capacity = math.ceil(
        config["capacity_factor"] * seq * top_k / num_experts)
    scores = jax.nn.sigmoid(tokens @ p["router"])                  # [S, 128]
    picked, picks = jax.lax.top_k(scores, top_k)                   # [S, K]
    gates = picked / (picked.sum(-1, keepdims=True) + 1e-20) \
        * config["routed_scaling_factor"]
    out = jnp.zeros_like(tokens)
    for e in range(here):  # the experts held, one after another
        mine = picks == first + e                                  # [S, K]
        # pairs before it in this expert's queue, token then pick order
        ahead = jnp.cumsum(mine.reshape(-1)).reshape(mine.shape) - mine
        weight = (gates * (mine & (ahead < capacity))).sum(-1)     # [S]
        out = out + weight[:, None] * (
            relu2(tokens @ p["experts_up"][e]) @ p["experts_down"][e])
    return out


def layer(config, kind, x, p):
    """A layer of ``kind`` on ``x [B, S, d]``."""
    h = rms_norm(x, p["ln"], config["layer_norm_epsilon"])
    if kind == "M":
        return x + mamba(config, h, p["mamba"])
    if kind == "*":
        return x + attention(config, h, p["attention"])
    routed = jax.vmap(lambda t: experts(config, t, p["moe"]))(h)
    return x + routed + plain_mlp(h, p["shared"])


def loss(config, params, tokens):
    """``tokens [rows, S + 1]``: the first ``S`` are read, each labelled
    with its successor."""
    ids, labels = tokens[:, :-1], tokens[:, 1:]
    x = params["token_embeddings"]["embedding"][ids]
    for i, kind in enumerate(config["hybrid_override_pattern"]):
        x = jax.checkpoint(
            lambda x, p, kind=kind: layer(config, kind, x, p))(
                x, params[f"layer_{i}"])
    logits = rms_norm(x, params["ln_out"],
                      config["layer_norm_epsilon"]) @ params["lm_head"]
    log_probs = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(log_probs, labels[..., None], -1).mean()
