"""SDAR's block-diffusion training loss in plain ``jax.numpy`` and float32
(JetLM ``SDAR-30B-A3B-Chat``, ``config.json``, ``model_type`` ``sdar_moe``;
the objective: BD3-LMs, arXiv:2503.09573): no kernels, no flax, nothing of
``horovod_tpu`` but the names of its parameter tree. The harness
differentiates it and runs it under ``default_matmul_precision("highest")``.

The batch is ``{"clean": x0 [R, S], "noisy": xt [R, S], "weight": w [R,
S]}``: ``xt`` is ``x0`` with some positions replaced by the mask token and
``w_i = m_i / t_blk(i)`` (0 where not masked). The stream is ``[xt ; x0]``,
``2S`` positions at position ids ``[0..S-1 ; 0..S-1]``. Every layer:

    h   = RMSNorm_1(x)
    q = h W_q [2S, 32, 128]   k = h W_k [2S, 4, 128]   v = h W_v [2S, 4, 128]
    q = RMSNorm_q(q)  k = RMSNorm_k(k)   over the 128 lanes of each head,
                                         one scale of 128 for all heads
    q, k = RoPE(q, k) by position id (half-split, theta 1e6)
    a_i = sum_j softmax_j(q_i . k_j / sqrt(128)) v_j  over the keys j that
          the mask leaves; query head n reads key/value head n // 8
    x'  = x + concat(a) W_o
    u   = RMSNorm_2(x')
    rho = u W_router                                   [2S, 128]
    P   = top-8 of rho;  g = softmax(rho[P]) over the eight
    x'' = x' + sum_{e in P, e held here} g_e W_down,e (silu(W_gate,e u)
                                                       * (W_up,e u))

The mask, with ``blk(i) = pos(i) // block_length``: query ``i`` sees key
``j`` iff (both noisy and ``blk(j) == blk(i)``) or (``i`` noisy, ``j``
clean and ``blk(j) < blk(i)``) or (both clean and ``blk(j) <= blk(i)``).
It is built dense, from these three predicates. Then a final RMSNorm and an
untied head on the noisy half, and

    L = 1 / (R S) * sum_i w_i * -log softmax(z_i)[x0_i]

Departures, all of them the product's and followed here so that the two
compute the same function:

* **One chip's share.** This chip holds ``experts_here`` experts from
  ``first_expert`` on; the router keeps its 128 outputs and its 8 picks,
  and the gates are normalised over all eight picks wherever they live. A
  (position, pick) pair routed outside the window adds nothing here.
  Attention, router and head are whole (the head over the slice of the
  vocabulary held).
* **Capacity slots** (``assumed.capacity_factor``). One row of the doubled
  stream is one routing group; pairs take an expert's slots in stream
  order (the noisy half first), then pick order, and a pair past
  ``ceil(capacity_factor x 2S x 8 / 128)`` adds nothing. This reference
  has no slots: it computes every expert of the window on every position
  and weights by gate x in window x kept, where "kept" is that same count
  of the pairs ahead in the expert's queue.
* The picks are ``top_k`` of the router's logits (ties to the lower
  index).
* **Blocking, not a departure**: at 16,384 stream positions a ``[32, 2S,
  2S]`` float32 score tensor is 34 GB, so attention is mapped over heads
  and over blocks of ``QUERY_BLOCK`` queries (each sees every key of the
  stream, masked), every block and every layer under ``jax.checkpoint``.
  The arithmetic of a row is that of the whole matrix.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 2048  # queries a step of the map; a shorter stream is one


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(
        jnp.square(x).mean(-1, keepdims=True) + eps) * p["scale"]


def rope(x, positions, theta):
    """``x [B, T, H, D]`` at ``positions [T]``: lane ``i`` rotates with
    lane ``i + D/2``."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def seen(block_length, q_pos, q_noisy, k_pos, k_noisy):
    """The three predicates, for queries (rows) against keys (columns)."""
    q_blk, k_blk = (q_pos // block_length)[:, None], (
        k_pos // block_length)[None, :]
    q_noisy, k_noisy = q_noisy[:, None], k_noisy[None, :]
    own_noisy_block = q_noisy & k_noisy & (k_blk == q_blk)
    clean_past = q_noisy & ~k_noisy & (k_blk < q_blk)
    block_causal = ~q_noisy & ~k_noisy & (k_blk <= q_blk)
    return own_noisy_block | clean_past | block_causal


def attention(config, x, p, positions, noisy):
    """``x [B, 2S, hidden]`` (normalised) -> the attention block's output
    before the residual. ``positions``, ``noisy`` ``[2S]``."""
    batch, stream = x.shape[:2]
    heads, kv_heads, dim = (config["num_attention_heads"],
                            config["num_key_value_heads"], config["head_dim"])
    eps, theta = config["rms_norm_eps"], float(config["rope_theta"])
    q = (x @ p["query"]["kernel"]).reshape(batch, stream, heads, dim)
    k = (x @ p["key"]["kernel"]).reshape(batch, stream, kv_heads, dim)
    v = (x @ p["value"]["kernel"]).reshape(batch, stream, kv_heads, dim)
    q = rope(rms_norm(q, p["q_norm"], eps), positions, theta)
    k = rope(rms_norm(k, p["k_norm"], eps), positions, theta)
    k, v = (jnp.repeat(t, heads // kv_heads, axis=2) for t in (k, v))
    block = min(stream, QUERY_BLOCK)

    @jax.checkpoint
    def one_block(q_block, first, k_head, v_head):
        """``q_block [B, block, D]`` from stream index ``first`` on against
        one head's keys and values ``[B, 2S, D]``."""
        scores = jnp.einsum("bqd,bkd->bqk", q_block, k_head) / math.sqrt(dim)
        rows = first + jnp.arange(block)
        mask = seen(config["block_length"], positions[rows], noisy[rows],
                    positions, noisy)
        scores = jnp.where(mask, scores, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(scores, -1), v_head)

    def one_head(qkv):
        q_head, k_head, v_head = qkv  # [B, 2S, D]
        blocks = q_head.reshape(batch, stream // block, block, dim)
        out = jax.lax.map(
            lambda args: one_block(args[0], args[1], k_head, v_head),
            (blocks.transpose(1, 0, 2, 3),
             jnp.arange(stream // block) * block))
        return out.transpose(1, 0, 2, 3).reshape(batch, stream, dim)

    context = jax.lax.map(one_head, tuple(
        t.transpose(2, 0, 1, 3) for t in (q, k, v)))  # [H, B, 2S, D]
    context = context.transpose(1, 2, 0, 3).reshape(
        batch, stream, heads * dim)
    return context @ p["out"]["kernel"]


def experts(config, tokens, p):
    """One row of the stream ``[2S, D]`` (normalised) through the router
    and this chip's window of the experts: the weighted outputs ``[2S,
    D]``."""
    num_experts, top_k = config["num_experts"], config["num_experts_per_tok"]
    first, here = config["first_expert"], config["experts_here"]
    stream = tokens.shape[0]
    capacity = math.ceil(
        config["capacity_factor"] * stream * top_k / num_experts)
    logits = tokens @ p["router"]                                 # [2S, 128]
    picked, picks = jax.lax.top_k(logits, top_k)                  # [2S, K]
    gates = jax.nn.softmax(picked, -1)  # over the eight, wherever they live
    # [2S, K, here]: the pair is this window's expert e's
    mine = picks[..., None] == first + jnp.arange(here)
    # pairs before it in the same expert's queue, stream then pick order
    ahead = jnp.cumsum(mine.reshape(stream * top_k, here), 0).reshape(
        stream, top_k, here) - mine
    kept = mine & (ahead < capacity)
    weight = (gates[..., None] * kept).sum(1)                     # [2S, here]
    hidden = jax.nn.silu(jnp.einsum("sd,edh->seh", tokens,
                                    p["experts_gate"])) \
        * jnp.einsum("sd,edh->seh", tokens, p["experts_up"])
    return jnp.einsum("seh,ehd,se->sd", hidden, p["experts_down"], weight)


def layer(config, x, p, positions, noisy):
    """One decoder layer on ``x [B, 2S, hidden]``."""
    eps = config["rms_norm_eps"]
    h = rms_norm(x, p["ln_attn"], eps)
    x = x + attention(config, h, p["attention"], positions, noisy)
    u = rms_norm(x, p["ln_moe"], eps)
    return x + jax.vmap(lambda t: experts(config, t, p["moe"]))(u)


def loss(config, params, batch):
    """``batch``: ``clean``, ``noisy`` ids and ``weight``, each ``[rows,
    S]``."""
    seq = batch["clean"].shape[1]
    stream = jnp.concatenate([batch["noisy"], batch["clean"]], 1)
    positions = jnp.concatenate([jnp.arange(seq), jnp.arange(seq)])
    noisy = jnp.arange(2 * seq) < seq
    x = params["token_embeddings"]["embedding"][stream]
    for i in range(config["num_hidden_layers"]):
        x = jax.checkpoint(lambda x, p: layer(
            config, x, p, positions, noisy))(x, params[f"layer_{i}"])
    logits = rms_norm(x[:, :seq], params["ln_out"],
                      config["rms_norm_eps"]) @ params["lm_head"]
    log_probs = jax.nn.log_softmax(logits, -1)
    own = jnp.take_along_axis(log_probs, batch["clean"][..., None], -1)
    return -(batch["weight"] * own[..., 0]).mean()
