"""SmallThinker's training loss in plain ``jax.numpy`` and float32
(PowerInfer ``SmallThinker-21BA3B-Instruct``, ``config.json``): no kernels,
no flax, nothing of ``horovod_tpu`` but the names of its parameter tree.
The harness differentiates it and runs it under
``default_matmul_precision("highest")``.

Layer ``l``, with ``w_l = sliding_window_layout[l]`` and ``r_l =
rope_layout[l]``:

    h   = RMSNorm_1(x)
    rho = h W_router                      [S, 64]: from h, BEFORE attention
    q = h W_q [S, 28, 128]   k = h W_k [S, 4, 128]   v = h W_v [S, 4, 128]
    q, k = RoPE(q, k) if r_l else as they are (no positional embedding)
    a_i = sum_j softmax_j(q_i . k_j / sqrt(128)) v_j  over j <= i, and
          i - j < sliding_window_size if w_l; query head n reads key/value
          head n // 7 (the keys and values are repeated with jnp.repeat)
    x'  = x + concat(a) W_o
    u   = RMSNorm_2(x')
    P   = top-6 of rho;  g = softmax(rho[P]) over the six
    x'' = x' + sum_{e in P, e held here} g_e W_down,e (relu(W_gate,e u)
                                                       * (W_up,e u))

then a final RMSNorm and an untied head; the loss is next-token cross
entropy and nothing else. The masks are dense, built from ``i - j``.

Departures, all of them the product's and followed here so that the two
compute the same function:

* **One chip's share.** This chip holds ``experts_here`` experts from
  ``first_expert`` on; the router keeps its 64 outputs and its 6 picks, and
  the gates are normalised over all six picks wherever they live. A (token,
  pick) pair routed outside the window adds nothing here. Attention, router
  and head are whole.
* **Capacity slots** (``assumed.capacity_factor``). One sequence is one
  routing group; pairs take an expert's slots in token order, then pick
  order, and a pair past ``ceil(capacity_factor x S x 6 / 64)`` adds
  nothing. This reference has no slots: it computes every expert of the
  window on every token and weights by gate x in window x kept, where
  "kept" is that same count of the pairs ahead in the expert's queue.
* The picks are ``top_k`` of the router's logits (ties to the lower
  index).
* **Blocking, not a departure**: at 16,384 positions a ``[28, S, S]``
  float32 score tensor is 30 GB, so attention is mapped over heads and over
  blocks of ``QUERY_BLOCK`` queries (each sees every key, masked), every
  block and every layer under ``jax.checkpoint``. The arithmetic of a row
  is that of the whole matrix.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 2048  # queries a step of the map; a shorter sequence is one


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


def attention(config, x, p, layer: int):
    """``x [B, S, hidden]`` (normalised) -> the attention block's output
    before the residual."""
    batch, seq = x.shape[:2]
    heads, kv_heads, dim = (config["num_attention_heads"],
                            config["num_key_value_heads"], config["head_dim"])
    window = (config["sliding_window_size"]
              if config["sliding_window_layout"][layer] else None)
    q = (x @ p["query"]["kernel"]).reshape(batch, seq, heads, dim)
    k = (x @ p["key"]["kernel"]).reshape(batch, seq, kv_heads, dim)
    v = (x @ p["value"]["kernel"]).reshape(batch, seq, kv_heads, dim)
    if config["rope_layout"][layer]:
        q, k = rope(q, float(config["rope_theta"])), rope(
            k, float(config["rope_theta"]))
    k, v = (jnp.repeat(t, heads // kv_heads, axis=2) for t in (k, v))
    block = min(seq, QUERY_BLOCK)

    @jax.checkpoint
    def one_block(q_block, first, k_head, v_head):
        """``q_block [B, block, D]`` from position ``first`` on against one
        head's keys and values ``[B, S, D]``."""
        scores = jnp.einsum("bqd,bkd->bqk", q_block, k_head) / math.sqrt(dim)
        ahead = (first + jnp.arange(block))[:, None] - jnp.arange(seq)[None]
        seen = ahead >= 0
        if window is not None:
            seen = seen & (ahead < window)
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(scores, -1), v_head)

    def one_head(qkv):
        q_head, k_head, v_head = qkv  # [B, S, D]
        blocks = q_head.reshape(batch, seq // block, block, dim)
        out = jax.lax.map(
            lambda args: one_block(args[0], args[1], k_head, v_head),
            (blocks.transpose(1, 0, 2, 3),
             jnp.arange(seq // block) * block))
        return out.transpose(1, 0, 2, 3).reshape(batch, seq, dim)

    context = jax.lax.map(one_head, tuple(
        t.transpose(2, 0, 1, 3) for t in (q, k, v)))  # [H, B, S, D]
    context = context.transpose(1, 2, 0, 3).reshape(batch, seq, heads * dim)
    return context @ p["out"]["kernel"]


def experts(config, tokens, logits, p):
    """One sequence ``[S, D]`` (normalised) with its router logits ``[S,
    64]`` through this chip's window of the experts: the weighted outputs
    ``[S, D]``."""
    num_experts = config["moe_num_primary_experts"]
    top_k = config["moe_num_active_primary_experts"]
    first, here = config["first_expert"], config["experts_here"]
    seq = tokens.shape[0]
    capacity = math.ceil(
        config["capacity_factor"] * seq * top_k / num_experts)
    picked, picks = jax.lax.top_k(logits, top_k)                  # [S, K]
    gates = jax.nn.softmax(picked, -1)  # over the six, wherever they live
    # [S, K, here]: the pair is this window's expert e's
    mine = picks[..., None] == first + jnp.arange(here)
    # pairs before it in the same expert's queue, token then pick order
    ahead = jnp.cumsum(mine.reshape(seq * top_k, here), 0).reshape(
        seq, top_k, here) - mine
    kept = mine & (ahead < capacity)
    weight = (gates[..., None] * kept).sum(1)                     # [S, here]
    hidden = jax.nn.relu(jnp.einsum("sd,edh->seh", tokens,
                                    p["experts_gate"])) \
        * jnp.einsum("sd,edh->seh", tokens, p["experts_up"])
    return jnp.einsum("seh,ehd,se->sd", hidden, p["experts_down"], weight)


def layer(config, x, p, index: int):
    """One decoder layer on ``x [B, S, hidden]``."""
    eps = config["rms_norm_eps"]
    h = rms_norm(x, p["ln_attn"], eps)
    logits = h @ p["router"]
    x = x + attention(config, h, p["attention"], index)
    u = rms_norm(x, p["ln_moe"], eps)
    return x + jax.vmap(lambda t, r: experts(config, t, r, p["moe"]))(
        u, logits)


def loss(config, params, tokens):
    """``tokens [rows, S + 1]``: positions ``0..S-1`` are read, ``1..S``
    are their labels."""
    x = params["token_embeddings"]["embedding"][tokens[:, :-1]]
    for i in range(config["num_hidden_layers"]):
        x = jax.checkpoint(
            lambda x, p, i=i: layer(config, x, p, i))(x, params[f"layer_{i}"])
    logits = rms_norm(x, params["ln_out"],
                      config["rms_norm_eps"]) @ params["lm_head"]
    log_probs = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(log_probs, tokens[:, 1:, None], -1).mean()
