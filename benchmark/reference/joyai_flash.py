"""JoyAI-LLM Flash's training loss in plain ``jax.numpy`` and float32
(jdopensource ``JoyAI-LLM-Flash``, ``config.json``, ``model_type``
``joyai_llm_flash``; the layers are DeepSeek-V3's, arXiv:2412.19437, as
``transformers`` writes ``modeling_deepseek_v3.py``; the prediction module is
the report's section 2.2): no kernels, no flax, no slots, nothing of
``horovod_tpu`` but the names of its parameter tree. The harness
differentiates it and runs it under ``default_matmul_precision("highest")``.

Every layer, ``d = hidden_size``, no bias anywhere::

    x' = x + MLA(RMSNorm_1(x));   x'' = x' + FFN(RMSNorm_2(x'))

then a final RMSNorm and an untied head.

**Latent attention** (``h`` the normalised input, 32 heads)::

    q = RMSNorm_1536(h W_qa) W_qb          [S, 32, 128 | 64] = [q_n | q_r]
    [c | k_r] = h W_kva                    (512 | 64)
    [k_n | v] = RMSNorm_512(c) W_kvb       [S, 32, 128 | 128]
    q_r (every head's) and the one k_r turned by RoPE at position t
    k = [k_n | k_r], the one k_r in every head
    a = softmax(causal(q k^T / sqrt(192))) v;   out = concat(a) W_o

RoPE as the source applies it with ``rope_interleave``: the 64 lanes are
de-interleaved (the even lanes first, then the odd ones), so that the
source's pair ``(2i, 2i + 1)`` lies at ``(i, i + 32)``, and then turned half
against half (``rotate_half``) by the angle ``t * theta ** (-2i / 64)``;
``rope_scaling`` is null, so nothing is rescaled.

**Feed-forward**: the first ``first_k_dense_replace`` layers a SiLU-gated
one of ``intermediate_size``; every later one the experts: ``s = sigmoid(u
W_r)`` (256 wide); the picks are the top 8 of ``s + b`` (``topk_method``
``noaux_tc``; one expert group, so no group limit); ``w_e = s_e / (sum over
the picks + 1e-20) * routed_scaling_factor``; ``y = sum_e w_e Expert_e(u) +
Shared(u)``, every expert and the shared one SiLU-gated,
``moe_intermediate_size`` wide.

**The prediction module** (``num_nextn_predict_layers`` 1), with ``x_L`` the
main stack's output::

    z_i = [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(x_L,i)] W_eh
    z = Layer(z)            (an expert layer as above, positions 0..S-1)
    logits'_i = RMSNorm_s(z_i) W_head

``Emb`` and ``W_head`` are the main model's. The loss over a row of ``S +
2`` ids: ``mean_i CE(logits_i, t_{i+1}) + mtp_loss_weight * mean_i
CE(logits'_i, t_{i+2})``, ``i = 0..S-1``.

Departures from the published description, all of them the product's and
followed here so that the two compute the same function:

* **One chip's share of the experts.** This chip holds ``experts_here``
  experts from ``first_expert`` on; the router keeps its 256 outputs and
  its 8 picks, and the gates are normalised over all eight picks wherever
  they live. A (position, pick) pair routed outside the window adds nothing
  here. Attention, the router, the shared expert and the head are whole
  (the head over the slice of the vocabulary held).
* **Capacity slots** (``capacity_factor``; the source drops nothing). One
  row is one routing group; pairs take an expert's slots in token order,
  then pick order, and a pair past ``ceil(capacity_factor x S x 8 / 256)``
  adds nothing. This reference has no slots: it computes every expert of the
  window on every position and weights by gate x in window x kept, where
  "kept" is that same count of the pairs ahead in the expert's queue.
* **The selection bias** ``b`` is an input (``selection_bias [expert layers,
  256]``, the module's row last) that defaults to zeros, its initial value;
  the source moves it outside the gradient by the load it sees, and that
  rule is not here.
* ``x_L`` is the stack's output **before** the final norm; ``W_eh`` takes
  the embedding's half first (the released checkpoints' order);
  ``mtp_loss_weight`` is the report's 0.3: the config gives none of the
  three (the configuration file's ``assumed``).
* The picks are ``top_k`` of the scores (ties to the lower index).
* **Blocking, not a departure**: attention is mapped over heads and over
  blocks of ``QUERY_BLOCK`` queries under ``jax.checkpoint``, and every layer
  as a whole. The arithmetic of a row is that of the whole matrix.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 2048  # queries a step of the map; a shorter sequence is one


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(
        jnp.square(x).mean(-1, keepdims=True) + eps) * p["scale"]


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], -1)


def rope_interleaved(x, theta):
    """``x [B, S, ..., r]`` at positions ``0..S-1``, as
    ``apply_rotary_pos_emb_interleave``: de-interleave, then ``x cos +
    rotate_half(x) sin`` with each frequency written out twice."""
    lanes = x.shape[-1]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    inv_freq = 1.0 / theta ** (jnp.arange(0, lanes, 2, dtype=jnp.float32)
                               / lanes)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    angle = jnp.concatenate([angle, angle], -1)
    angle = angle.reshape(angle.shape[:1] + (1,) * (x.ndim - 3)
                          + angle.shape[1:])
    return x * jnp.cos(angle) + rotate_half(x) * jnp.sin(angle)


def causal_attention(q, k, v):
    """``q``, ``k [B, S, H, D]``, ``v [B, S, H, Dv]`` → ``[B, S, H x
    Dv]``."""
    batch, seq, heads, v_dim = v.shape
    block = min(seq, QUERY_BLOCK)

    @jax.checkpoint
    def one_block(q_block, first, k_head, v_head):
        scores = jnp.einsum("bqd,bkd->bqk", q_block, k_head) \
            / math.sqrt(q.shape[-1])
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
    return context.transpose(1, 2, 0, 3).reshape(batch, seq, heads * v_dim)


def latent_attention(config, h, p):
    batch, seq = h.shape[:2]
    heads, rank = config["num_attention_heads"], config["kv_lora_rank"]
    nope, rope, v_dim = (config["qk_nope_head_dim"],
                         config["qk_rope_head_dim"], config["v_head_dim"])
    eps, theta = config["rms_norm_eps"], config["rope_theta"]
    q = (rms_norm(h @ p["q_a"]["kernel"], p["q_norm"], eps)
         @ p["q_b"]["kernel"]).reshape(batch, seq, heads, nope + rope)
    latent = h @ p["kv_a"]["kernel"]
    up = (rms_norm(latent[..., :rank], p["kv_norm"], eps)
          @ p["kv_b"]["kernel"]).reshape(batch, seq, heads, nope + v_dim)
    q = jnp.concatenate(
        [q[..., :nope], rope_interleaved(q[..., nope:], theta)], -1)
    k = jnp.concatenate([
        up[..., :nope],
        jnp.repeat(rope_interleaved(latent[..., rank:], theta)[:, :, None],
                   heads, axis=2)], -1)
    return causal_attention(q, k, up[..., nope:]) @ p["out"]["kernel"]


def gated_mlp(x, p):
    return (jax.nn.silu(x @ p["gate"]["kernel"]) * (x @ p["up"]["kernel"])) \
        @ p["down"]["kernel"]


def experts(config, tokens, p, bias):
    """One row ``[S, d]`` (normalised) through the router and this chip's
    window of the experts: the weighted outputs ``[S, d]``, without the
    shared expert. ``bias [256]`` is added for the choice alone."""
    num_experts, top_k = config["n_routed_experts"], config[
        "num_experts_per_tok"]
    first, here = config["first_expert"], config["experts_here"]
    seq = tokens.shape[0]
    capacity = math.ceil(
        config["capacity_factor"] * seq * top_k / num_experts)
    scores = jax.nn.sigmoid(tokens @ p["router"])                  # [S, 256]
    _, picks = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), top_k)
    picked = jnp.take_along_axis(scores, picks, -1)                # [S, K]
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


def layer(config, dense, x, p, bias):
    eps = config["rms_norm_eps"]
    x = x + latent_attention(config, rms_norm(x, p["ln_attn"], eps),
                             p["attention"])
    u = rms_norm(x, p["ln_ffn"], eps)
    if dense:
        return x + gated_mlp(u, p["mlp"])
    routed = jax.vmap(lambda t: experts(config, t, p["moe"], bias))(u)
    return x + routed + gated_mlp(u, p["shared"])


def logits_of(config, params, ids, next_ids=None, selection_bias=None):
    """``(logits, logits')`` of the main model over ``ids [rows, S]`` and of
    the prediction module, which also reads the embeddings of ``next_ids``;
    the second is ``None`` without the module."""
    eps, dense_layers = config["rms_norm_eps"], config["first_k_dense_replace"]
    depth, modules = (config["num_hidden_layers"],
                      config["num_nextn_predict_layers"])
    if selection_bias is None:
        selection_bias = jnp.zeros(
            (depth - dense_layers + modules, config["n_routed_experts"]),
            jnp.float32)
    embedding = params["token_embeddings"]["embedding"]

    def run(i, name, x):
        dense = i < dense_layers
        return jax.checkpoint(
            lambda x, p, b: layer(config, dense, x, p, b))(
                x, params[name],
                None if dense else selection_bias[i - dense_layers])

    x = embedding[ids]
    for i in range(depth):
        x = run(i, f"layer_{i}", x)
    logits = rms_norm(x, params["ln_out"], eps) @ params["lm_head"]
    if not modules:
        return logits, None
    z = jnp.concatenate([
        rms_norm(embedding[next_ids], params["mtp_embed_norm"], eps),
        rms_norm(x, params["mtp_hidden_norm"], eps)],
        -1) @ params["mtp_proj"]["kernel"]
    z = run(depth, "mtp_layer", z)
    return logits, rms_norm(z, params["mtp_norm"], eps) @ params["lm_head"]


def cross_entropy(logits, labels):
    log_probs = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(log_probs, labels[..., None], -1).mean()


def loss(config, params, tokens, selection_bias=None):
    """``tokens [rows, S + 2]``: the first ``S`` are read; position ``i`` is
    labelled with its successor, and the module's with the one after."""
    if not config["num_nextn_predict_layers"]:
        logits, _ = logits_of(config, params, tokens[:, :-1],
                              selection_bias=selection_bias)
        return cross_entropy(logits, tokens[:, 1:])
    logits, ahead = logits_of(config, params, tokens[:, :-2],
                              tokens[:, 1:-1], selection_bias)
    return cross_entropy(logits, tokens[:, 1:-1]) \
        + config["mtp_loss_weight"] * cross_entropy(ahead, tokens[:, 2:])
