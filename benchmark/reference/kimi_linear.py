"""Kimi Linear's training loss in plain ``jax.numpy`` and float32
(moonshotai ``Kimi-Linear-48B-A3B-Instruct``, ``config.json``, ``model_type``
``kimi_linear``; the layers: "Kimi Linear: An Expressive, Efficient Attention
Architecture", arXiv:2510.26692, as ``flash-linear-attention`` writes the
``kda`` layer): no kernels, no flax, no chunks, no slots, nothing of
``horovod_tpu`` but the names of its parameter tree. The harness
differentiates it and runs it under ``default_matmul_precision("highest")``.

Every layer, ``d = hidden_size``, no bias anywhere::

    x' = x + Mixer(RMSNorm_1(x));   x'' = x' + FFN(RMSNorm_2(x'))

then a final RMSNorm, an untied head and the mean next-token cross entropy
over every position. ``linear_attn_config`` says which mixer a layer has
(its two lists count the layers from one), ``first_k_dense_replace`` how
many leading layers have the dense SiLU-gated feed-forward of
``intermediate_size``; the others have the experts.

**Kimi Delta Attention** (``h`` the normalised input, 32 heads of 128)::

    q, k, v = silu(conv4(h W_q)), silu(conv4(h W_k)), silu(conv4(h W_v))
    q = q / |q| * 128^-1/2;  k = k / |k|        (a head's 128 lanes; the
                                                 square root over |.|^2 + 1e-6)
    g = -exp(A_log)[head] * softplus(h W_fa W_fb + dt_bias)    [S, 32, 128]
    beta = sigmoid(h W_b)                                       [S, 32]

and per head, **token by token** from a zero state ``S [128, 128]``::

    S' = Diag(exp(g_t)) S;  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

(row ``c`` of the state, the key channel ``c``, decays by ``exp(g_tc)``),
then ``(RMSNorm_128(o_t) * sigmoid(h W_ga W_gb)) W_o``, the norm with one
scale of 128 for all heads.

**Latent attention** (32 heads, no rotary embedding: ``mla_use_nope``)::

    q = h W_q [S, 32, 192];   [c | k_r] = h W_kva  (512 | 64)
    [k_n | v] = RMSNorm_512(c) W_kvb  [S, 32, 128 | 128]
    k = [k_n | k_r], the one k_r in every head
    a = softmax(causal(q k^T / sqrt(192))) v;   out = concat(a) W_o

**Experts**: ``s = sigmoid(u W_r)`` (256 wide); the picks are the top 8 of
``s`` (one expert group); ``w_e = s_e / (sum over the picks + 1e-20) *
routed_scaling_factor``; ``y = sum_e w_e Expert_e(u) + Shared(u)``, every
expert and the shared one a SiLU-gated feed-forward of
``moe_intermediate_size``.

Departures from the published description, all of them the product's and
followed here so that the two compute the same function:

* **One chip's share of the experts.** This chip holds ``experts_here``
  experts from ``first_expert`` on; the router keeps its 256 outputs and
  its 8 picks, and the gates are normalised over all eight picks wherever
  they live. A (position, pick) pair routed outside the window adds nothing
  here. Both mixers, the router, the shared expert and the head are whole
  (the head over the slice of the vocabulary held).
* **Capacity slots** (``assumed.capacity_factor``; the source drops
  nothing). One row is one routing group; pairs take an expert's slots in
  token order, then pick order, and a pair past ``ceil(capacity_factor x S
  x 8 / 256)`` adds nothing. This reference has no slots: it computes every
  expert of the window on every position and weights by gate x in window x
  kept, where "kept" is that same count of the pairs ahead in the expert's
  queue.
* **No selection bias.** The source adds ``e_score_correction_bias`` to
  ``s`` for the choice alone and moves it outside the gradient; at its
  initial zero the choice is by ``s``, and so it is here.
* The picks are ``top_k`` of the scores (ties to the lower index).
* **Blocking, not a departure**: attention is mapped over heads and over
  blocks of ``QUERY_BLOCK`` queries under ``jax.checkpoint``, the
  recurrence is checkpointed in runs of 64 tokens and every layer as a
  whole. The arithmetic of a row is that of the whole matrix.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 2048  # queries a step of the map; a shorter sequence is one


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(
        jnp.square(x).mean(-1, keepdims=True) + eps) * p["scale"]


def causal_conv(x, w):
    """``x [B, S, C]``, ``w [C, taps]``: the last tap weighs the token
    itself, the first the one ``taps - 1`` before it; zeros before the
    sequence."""
    seq, taps = x.shape[1], w.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, i:seq + i] * w[:, i] for i in range(taps))


def unit(x):
    return x * jax.lax.rsqrt(jnp.square(x).sum(-1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta):
    """``q``, ``k``, ``g`` ``[B, S, H, d_k]``, ``v [B, S, H, d_v]``,
    ``beta [B, S, H]`` → ``o [B, S, H, d_v]``, one token at a time."""
    batch, seq, heads, d_k = q.shape

    def one_token(state, xs):
        q, k, v, g, beta = xs
        state = jnp.exp(g)[..., None] * state  # row c by exp(g_c)
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
    state = jnp.zeros((batch, heads, d_k, v.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(one_run, state, by_run)
    return jnp.moveaxis(out.reshape((seq,) + out.shape[2:]), 0, 1)


def kimi_delta_attention(config, h, p):
    linear = config["linear_attn_config"]
    heads, dim = linear["num_heads"], linear["head_dim"]
    shape = h.shape[:2] + (heads, dim)
    q, k, v = (
        jax.nn.silu(causal_conv(h @ p[name]["kernel"], p[name + "_conv"]))
        .reshape(shape) for name in ("query", "key", "value"))
    q, k = unit(q) / math.sqrt(dim), unit(k)
    step = (h @ p["decay_a"]["kernel"]) @ p["decay_b"]["kernel"]
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        step + p["dt_bias"]).reshape(shape)
    beta = jax.nn.sigmoid(h @ p["beta"]["kernel"])
    out = rms_norm(delta_rule(q, k, v, g, beta), p["o_norm"],
                   config["rms_norm_eps"])
    gate = (h @ p["gate_a"]["kernel"]) @ p["gate_b"]["kernel"]
    out = out * jax.nn.sigmoid(gate).reshape(shape)
    return out.reshape(h.shape[:2] + (heads * dim,)) @ p["out"]["kernel"]


def latent_attention(config, h, p):
    batch, seq = h.shape[:2]
    heads, rank = config["num_attention_heads"], config["kv_lora_rank"]
    nope, shared, v_dim = (config["qk_nope_head_dim"],
                           config["qk_rope_head_dim"], config["v_head_dim"])
    q = (h @ p["query"]["kernel"]).reshape(batch, seq, heads, nope + shared)
    latent = h @ p["kv_a"]["kernel"]
    up = (rms_norm(latent[..., :rank], p["kv_norm"], config["rms_norm_eps"])
          @ p["kv_b"]["kernel"]).reshape(batch, seq, heads, nope + v_dim)
    k = jnp.concatenate([
        up[..., :nope],
        jnp.repeat(latent[:, :, None, rank:], heads, axis=2)], -1)
    v = up[..., nope:]
    block = min(seq, QUERY_BLOCK)

    @jax.checkpoint
    def one_block(q_block, first, k_head, v_head):
        scores = jnp.einsum("bqd,bkd->bqk", q_block, k_head) \
            / math.sqrt(nope + shared)
        ahead = (first + jnp.arange(block))[:, None] >= jnp.arange(seq)[None]
        return jnp.einsum(
            "bqk,bkd->bqd",
            jax.nn.softmax(jnp.where(ahead, scores, -jnp.inf), -1), v_head)

    def one_head(qkv):
        q_head, k_head, v_head = qkv  # [B, S, D]
        blocks = q_head.reshape(batch, seq // block, block, -1)
        out = jax.lax.map(
            lambda args: one_block(args[0], args[1], k_head, v_head),
            (blocks.transpose(1, 0, 2, 3),
             jnp.arange(seq // block) * block))
        return out.transpose(1, 0, 2, 3).reshape(batch, seq, v_dim)

    context = jax.lax.map(one_head, tuple(
        t.transpose(2, 0, 1, 3) for t in (q, k, v)))  # [H, B, S, Dv]
    context = context.transpose(1, 2, 0, 3).reshape(batch, seq, heads * v_dim)
    return context @ p["out"]["kernel"]


def gated_mlp(x, p):
    return (jax.nn.silu(x @ p["gate"]["kernel"]) * (x @ p["up"]["kernel"])) \
        @ p["down"]["kernel"]


def experts(config, tokens, p):
    """One row ``[S, d]`` (normalised) through the router and this chip's
    window of the experts: the weighted outputs ``[S, d]``, without the
    shared expert."""
    num_experts, top_k = config["num_experts"], config["num_experts_per_token"]
    first, here = config["first_expert"], config["experts_here"]
    seq = tokens.shape[0]
    capacity = math.ceil(
        config["capacity_factor"] * seq * top_k / num_experts)
    scores = jax.nn.sigmoid(tokens @ p["router"])                  # [S, 256]
    picked, picks = jax.lax.top_k(scores, top_k)                   # [S, K]
    gates = picked / (picked.sum(-1, keepdims=True) + 1e-20) \
        * config["routed_scaling_factor"]
    # [S, K, here]: the pair is this window's expert e's
    mine = picks[..., None] == first + jnp.arange(here)
    # pairs before it in the same expert's queue, token then pick order
    ahead = jnp.cumsum(mine.reshape(seq * top_k, here), 0).reshape(
        seq, top_k, here) - mine
    kept = mine & (ahead < capacity)
    weight = (gates[..., None] * kept).sum(1)                      # [S, here]
    hidden = jax.nn.silu(jnp.einsum("sd,edh->seh", tokens,
                                    p["experts_gate"])) \
        * jnp.einsum("sd,edh->seh", tokens, p["experts_up"])
    return jnp.einsum("seh,ehd,se->sd", hidden, p["experts_down"], weight)


def layer(config, index, x, p):
    """Layer ``index`` (from zero) on ``x [B, S, d]``."""
    eps = config["rms_norm_eps"]
    h = rms_norm(x, p["ln_mixer"], eps)
    if index + 1 in config["linear_attn_config"]["kda_layers"]:
        x = x + kimi_delta_attention(config, h, p["kda"])
    else:
        x = x + latent_attention(config, h, p["attention"])
    u = rms_norm(x, p["ln_ffn"], eps)
    if index < config["first_k_dense_replace"]:
        return x + gated_mlp(u, p["mlp"])
    routed = jax.vmap(lambda t: experts(config, t, p["moe"]))(u)
    return x + routed + gated_mlp(u, p["shared"])


def loss(config, params, tokens):
    """``tokens [rows, S + 1]``: the first ``S`` are read, each labelled
    with its successor."""
    ids, labels = tokens[:, :-1], tokens[:, 1:]
    x = params["token_embeddings"]["embedding"][ids]
    for i in range(config["num_hidden_layers"]):
        x = jax.checkpoint(lambda x, p, i=i: layer(config, i, x, p))(
            x, params[f"layer_{i}"])
    logits = rms_norm(x, params["ln_out"],
                      config["rms_norm_eps"]) @ params["lm_head"]
    log_probs = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(log_probs, labels[..., None], -1).mean()
