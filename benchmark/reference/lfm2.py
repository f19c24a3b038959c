"""LFM2's training loss in plain ``jax.numpy`` and float32 (LiquidAI
``LFM2-24B-A2B``, ``config.json``, ``model_type`` ``lfm2_moe``; the layers as
``transformers`` writes ``modeling_lfm2.py`` / ``modeling_lfm2_moe.py``): no
kernels, no flax, no slots, nothing of ``horovod_tpu`` but the names of its
parameter tree. The harness differentiates it and runs it under
``default_matmul_precision("highest")``.

Every layer ``l``, ``d = hidden_size``, no bias anywhere::

    x' = x + Op_l(RMSNorm_1(x));   x'' = x' + FFN_l(RMSNorm_2(x'))

then a final RMSNorm (the source's ``embedding_norm``) and the logits on the
embedding itself (one tied leaf).

**``conv``** (``h`` the normalised input)::

    [B | C | x] = h W_in                    (d -> 3 d, cut in that order)
    u = B * x
    c = Conv1d(u, groups=d, kernel=conv_L_cache, padding=conv_L_cache - 1)
        cut to the first S outputs: c_t = sum_i w[:, i] u_(t - 2 + i)
    Op = (C * c) W_out

**``full_attention``**: ``q = h W_q [S, 32, 64]``, ``k = h W_k``, ``v = h
W_v`` ``[S, 8, 64]``; ``q`` and ``k`` through an RMSNorm over a head's 64
lanes (one scale for all query heads, one for all key heads), then ``x cos +
rotate_half(x) sin`` at position ``t`` with the angle ``t theta^(-2i / 64)``
on lanes ``i`` and ``i + 32``; ``softmax(causal(q k^T / 8)) v``, query head
``j`` reading key/value head ``j // 4``; ``Op = o W_o``.

**Feed-forward**: the first ``num_dense_layers`` layers a SiLU-gated one of
``intermediate_size``; every later one the experts: ``s = sigmoid(u W_r)``
(64 wide); the picks are the top 4 of ``s + b``; ``g_e = s_e / (sum over the
picks + 1e-6) * routed_scaling_factor``; ``y = sum_e g_e Expert_e(u)``,
every expert SiLU-gated, ``moe_intermediate_size`` wide. No shared expert.

Departures from the published description, all of them the product's and
followed here so that the two compute the same function:

* **One chip's share of the experts.** This chip holds ``experts_here``
  experts from ``first_expert`` on; the router keeps its 64 outputs and its
  4 picks, and the gates are normalised over all four picks wherever they
  live. A (position, pick) pair routed outside the window adds nothing
  here. The mixers, the router and the head are whole (the head over the
  slice of the vocabulary held).
* **Capacity slots** (``capacity_factor``; the source drops nothing). One
  row is one routing group; pairs take an expert's slots in token order,
  then pick order, and a pair past ``ceil(capacity_factor x S x 4 / 64)``
  adds nothing. This reference has no slots: it computes every expert of the
  window on every position and weights by gate x in window x kept, where
  "kept" is that same count of the pairs ahead in the expert's queue.
* **The selection bias** ``b`` is an input (``selection_bias [expert layers,
  64]``) that defaults to zeros, its initial value; the source moves it
  outside the gradient by the load it sees, and that rule is not here.
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
GATE_EPS = 1e-6  # the source's, beside the sum of a token's picked scores


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(
        jnp.square(x).mean(-1, keepdims=True) + eps) * p["scale"]


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], -1)


def rope(x, theta):
    """``x [B, S, H, D]`` at positions ``0..S-1``, as
    ``apply_rotary_pos_emb``: ``x cos + rotate_half(x) sin`` with each
    frequency written out twice."""
    lanes = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, lanes, 2, dtype=jnp.float32)
                               / lanes)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    angle = jnp.concatenate([angle, angle], -1)[:, None, :]
    return x * jnp.cos(angle) + rotate_half(x) * jnp.sin(angle)


def conv1d(u, w):
    """``torch.nn.Conv1d(groups=channels, padding=taps - 1)`` of ``u [B, S,
    channels]`` with ``w [channels, taps]``, cut to the first ``S``
    outputs: zeros on both sides, every output the taps' sum over its
    window, in the source's order."""
    taps, seq = w.shape[1], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (taps - 1, taps - 1), (0, 0)))
    full = sum(w[:, i] * padded[:, i:i + seq + taps - 1]
               for i in range(taps))
    return full[:, :seq]


def short_conv(config, h, p):
    width = config["hidden_size"]
    bcx = h @ p["in_proj"]["kernel"]
    gate_in, gate_out, inner = (bcx[..., :width], bcx[..., width:2 * width],
                                bcx[..., 2 * width:])
    return (gate_out * conv1d(gate_in * inner, p["conv"])) \
        @ p["out_proj"]["kernel"]


def causal_attention(q, k, v):
    """``q``, ``k``, ``v [B, S, H, D]`` → ``[B, S, H x D]``."""
    batch, seq, heads, dim = v.shape
    block = min(seq, QUERY_BLOCK)

    @jax.checkpoint
    def one_block(q_block, first, k_head, v_head):
        scores = jnp.einsum("bqd,bkd->bqk", q_block, k_head) \
            / math.sqrt(dim)
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
        return out.transpose(1, 0, 2, 3).reshape(batch, seq, dim)

    context = jax.lax.map(one_head, tuple(
        t.transpose(2, 0, 1, 3) for t in (q, k, v)))  # [H, B, S, D]
    return context.transpose(1, 2, 0, 3).reshape(batch, seq, heads * dim)


def grouped_attention(config, h, p):
    batch, seq = h.shape[:2]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    dim = config["hidden_size"] // heads
    eps, theta = config["norm_eps"], config["rope_parameters"]["rope_theta"]
    q = (h @ p["query"]["kernel"]).reshape(batch, seq, heads, dim)
    k = (h @ p["key"]["kernel"]).reshape(batch, seq, kv_heads, dim)
    v = (h @ p["value"]["kernel"]).reshape(batch, seq, kv_heads, dim)
    q = rope(rms_norm(q, p["q_norm"], eps), theta)
    k = rope(rms_norm(k, p["k_norm"], eps), theta)
    # query head j reads key/value head j // group (repeat_kv)
    k, v = (jnp.repeat(t, heads // kv_heads, axis=2) for t in (k, v))
    return causal_attention(q, k, v) @ p["out"]["kernel"]


def gated_mlp(x, p):
    return (jax.nn.silu(x @ p["gate"]["kernel"]) * (x @ p["up"]["kernel"])) \
        @ p["down"]["kernel"]


def experts(config, tokens, p, bias):
    """One row ``[S, d]`` (normalised) through the router and this chip's
    window of the experts: the weighted outputs ``[S, d]``. ``bias [64]`` is
    added for the choice alone."""
    num_experts, top_k = config["num_experts"], config["num_experts_per_tok"]
    first, here = config["first_expert"], config["experts_here"]
    seq = tokens.shape[0]
    capacity = math.ceil(
        config["capacity_factor"] * seq * top_k / num_experts)
    scores = jax.nn.sigmoid(tokens @ p["router"])                   # [S, 64]
    _, picks = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), top_k)
    gates = jnp.take_along_axis(scores, picks, -1)                  # [S, K]
    if config["norm_topk_prob"]:
        gates = gates / (gates.sum(-1, keepdims=True) + GATE_EPS)
    gates = gates * config["routed_scaling_factor"]
    # [S, K, here]: the pair is this window's expert e's
    mine = picks[..., None] == first + jnp.arange(here)
    # pairs before it in the same expert's queue, token then pick order
    ahead = jnp.cumsum(mine.reshape(seq * top_k, here), 0).reshape(
        seq, top_k, here) - mine
    kept = mine & (ahead < capacity)
    weight = (gates[..., None] * kept).sum(1)                       # [S, here]
    hidden = jax.nn.silu(jnp.einsum("sd,edh->seh", tokens,
                                    p["experts_gate"])) \
        * jnp.einsum("sd,edh->seh", tokens, p["experts_up"])
    return jnp.einsum("seh,ehd,se->sd", hidden, p["experts_down"], weight)


def layer(config, kind, dense, x, p, bias):
    eps = config["norm_eps"]
    h = rms_norm(x, p["ln_mixer"], eps)
    if kind == "conv":
        x = x + short_conv(config, h, p["conv"])
    else:
        x = x + grouped_attention(config, h, p["attention"])
    u = rms_norm(x, p["ln_ffn"], eps)
    if dense:
        return x + gated_mlp(u, p["mlp"])
    return x + jax.vmap(lambda t: experts(config, t, p["moe"], bias))(u)


def logits_of(config, params, ids, selection_bias=None):
    """Logits ``[rows, S, V]`` over ``ids [rows, S]``."""
    dense_layers = config["num_dense_layers"]
    kinds = config["layer_types"]
    if selection_bias is None:
        selection_bias = jnp.zeros(
            (len(kinds) - dense_layers, config["num_experts"]), jnp.float32)
    embedding = params["embedding"]
    x = embedding[ids]
    for i, kind in enumerate(kinds):
        dense = i < dense_layers
        x = jax.checkpoint(
            lambda x, p, b, kind=kind, dense=dense: layer(
                config, kind, dense, x, p, b))(
                    x, params[f"layer_{i}"],
                    None if dense else selection_bias[i - dense_layers])
    return rms_norm(x, params["ln_out"], config["norm_eps"]) @ embedding.T


def cross_entropy(logits, labels):
    log_probs = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(log_probs, labels[..., None], -1).mean()


def loss(config, params, tokens, selection_bias=None):
    """``tokens [rows, S + 1]``: the first ``S`` are read; position ``i`` is
    labelled with its successor."""
    logits = logits_of(config, params, tokens[:, :-1], selection_bias)
    return cross_entropy(logits, tokens[:, 1:])
