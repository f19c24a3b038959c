"""SDAR — the framework's block-diffusion decoder: a mixture of experts
trained to denoise blocks of tokens given the clean text before them.

JetLM's ``SDAR-30B-A3B-Chat`` (``config.json``, ``model_type``
``sdar_moe``) is a Qwen3-MoE-shaped pre-norm decoder: 48 layers, hidden
2,048, 32 query heads on 4 key/value heads of 128 with a per-head QK-norm
before RoPE (theta 1e6), 128 SiLU-gated experts of width 768 of which a
token takes 8 with gates renormalised over the eight, no shared expert, an
untied head. What makes it a block-diffusion model is how it is trained
(BD3-LMs, arXiv:2503.09573). A step reads **two streams** of the same ``S``
tokens side by side, ``[x_t ; x_0]``: the noisy one, in which every block
of ``block_length`` positions has had each token replaced by the mask token
with that block's probability ``t``, then the clean one. Both count their
positions from zero. With ``blk(i) = pos(i) // block_length``, query ``i``
sees key ``j`` iff

* both noisy and ``blk(j) == blk(i)`` (its own block, noisy), or
* ``i`` noisy, ``j`` clean and ``blk(j) < blk(i)`` (the clean past), or
* both clean and ``blk(j) <= blk(i)`` (block-causal);

a clean query never sees a noisy key. Of the ``(2S)^2`` pairs that leaves
``S (S + block_length)``: ``ops.attention.block_diffusion_attention`` runs
two causal tile plans over the clean keys and never builds the square. The
head reads the noisy half only, and the loss is the cross entropy of the
masked positions' own tokens (labels in place, no shift), each weighted by
``1 / t`` of its block:

    L = 1 / (R S) * sum_i m_i / t_blk(i) * -log softmax(z_i)[x0_i]

TPU-first choices, as the other decoders (``models/parts.py`` holds the
norm, RoPE and the recomputation policy, ``models/experts.py`` the experts
module, ``SparseExperts``): bfloat16 activations with float32 parameters,
norms, RoPE and router; the experts through ``parallel/moe.py``'s slots,
one row of the doubled stream a routing group (pairs take an expert's
slots in stream order, the noisy half first, then pick order). A model may
hold a window of the experts (``experts_here`` from ``first_expert`` on),
one chip's share of expert parallelism: the router keeps its width and a
token's gates are normalised over all eight picks wherever they live, so
the shares' outputs add up to the whole layer's.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..attribution import (SCOPE_BLOCK_ATTN_PROJ, SCOPE_BLOCK_EMBED,
                           SCOPE_BLOCK_HEAD, SCOPE_BLOCK_NORM)
from ..ops.attention import block_diffusion_streams
from ..profiler import annotate_collective
from .experts import ExpertWindow, SparseExperts
from .loss import token_cross_entropy
from .parts import RMSNorm, projection, recomputed, rope, untied_head


@dataclasses.dataclass(frozen=True)
class SdarConfig(ExpertWindow):
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    intermediate_size: int = 768  # one expert's width
    num_experts: int = 128
    top_k: int = 8
    experts_here: int | None = None  # None: all from first_expert on
    first_expert: int = 0
    capacity_factor: float = 1.25
    block_length: int = 4
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    remat: bool = True
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"{self.num_heads} query heads cannot share "
                f"{self.num_kv_heads} key/value heads evenly")

    @property
    def mask_id(self) -> int:
        """The mask token: the last id of the vocabulary held, which the
        data never draws and no label is."""
        return self.vocab_size - 1


SDAR_30B_A3B = SdarConfig()
SDAR_TINY = SdarConfig(  # test-sized: 8 heads on 2, blocks of 4
    vocab_size=256, hidden_size=64, num_layers=2, num_heads=8,
    num_kv_heads=2, head_dim=8, intermediate_size=24, num_experts=8,
    top_k=2, capacity_factor=2.0,
)


def visible(block_length: int, seq_len: int):
    """The ``[2S, 2S]`` mask of the doubled stream from its three
    predicates: for the dense fallback and for tests, never for the
    kernels."""
    pos = jnp.concatenate([jnp.arange(seq_len)] * 2)
    noisy = jnp.arange(2 * seq_len) < seq_len
    q_blk, k_blk = (pos // block_length)[:, None], (pos // block_length)[None]
    q_noisy, k_noisy = noisy[:, None], noisy[None]
    return ((q_noisy & k_noisy & (k_blk == q_blk))
            | (q_noisy & ~k_noisy & (k_blk < q_blk))
            | (~q_noisy & ~k_noisy & (k_blk <= q_blk)))


def dense_block_diffusion_attention(noisy, clean, dtype, block_length):
    """Each stream's ``(q [B, S, H, D], k, v [B, S, KV heads, D])`` ->
    each stream's context ``[B, S, H, D]``; the masked softmax in float32
    over the whole square of the doubled stream, the noisy half first, the
    keys and values of a group repeated: the fallback where no kernel
    runs."""
    q, k, v = (jnp.concatenate(pair, axis=1) for pair in zip(noisy, clean))
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x.astype(jnp.float32), group, axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k) / (q.shape[-1] ** 0.5)
    seen = visible(block_length, q.shape[1] // 2)
    out = jnp.einsum("bhqk,bkhd->bqhd",
                     jax.nn.softmax(jnp.where(seen, scores, -1e30), -1), v)
    return tuple(jnp.split(out.astype(dtype), 2, axis=1))


def flash_attention_fn(noisy, clean, dtype, block_length,
                       interpret: bool = False, block: int | None = None):
    """Adapter plugging ``block_diffusion_streams`` into ``Sdar``: each
    stream's ``(q [B, S, heads, D], k, v [B, S, KV heads, D])`` ->
    transpose -> the two kernel calls and the merge, the keys and values
    with their own, smaller number of heads -> each stream's context ``[B,
    S, heads, D]``. ``block`` is for tests that want several tiles of a
    short sequence."""
    outs = block_diffusion_streams(
        *([x.transpose(0, 2, 1, 3) for x in stream]
          for stream in (noisy, clean)),
        block_length, block_q=block, block_k=block, interpret=interpret)
    return tuple(out.transpose(0, 2, 1, 3).astype(dtype) for out in outs)


class TwoStreamAttention(nn.Module):
    """``attention_fn(noisy, clean, dtype, block_length)`` is handed each
    stream's ``(q, k, v)`` and returns each stream's context."""
    config: SdarConfig
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        half = x.shape[1] // 2
        projections = [
            (projection(cfg, count * cfg.head_dim, name), count)
            for name, count in (("query", cfg.num_heads),
                                ("key", cfg.num_kv_heads),
                                ("value", cfg.num_kv_heads))]
        # QK-norm a head: over its 128 lanes, one learned scale for all
        # heads, before RoPE (OLMoE's is over the whole projection).
        norms = [RMSNorm(cfg.rms_norm_eps, name="q_norm"),
                 RMSNorm(cfg.rms_norm_eps, name="k_norm")]
        out = projection(cfg, cfg.hidden_size, "out")

        def stream(rows):
            """One stream's ``(q, k, v)``. The two are cut here, where a
            row is the hidden size wide: cut after the projections, the
            halves of q, k, v and of the context are 134 MB copies a
            layer around the kernels, which take a stream each."""
            q, k, v = (
                dense(x[:, rows]).reshape(x.shape[0], half, count,
                                          cfg.head_dim)
                for dense, count in projections)
            q, k = (rope(norm(y), cfg.rope_theta, positions[rows]).astype(
                cfg.dtype) for norm, y in zip(norms, (q, k)))
            return q, k, v

        attend = self.attention_fn or dense_block_diffusion_attention
        return jnp.concatenate([
            out(context.reshape(x.shape[0], half, -1))
            for context in attend(stream(slice(None, half)),
                                  stream(slice(half, None)), cfg.dtype,
                                  cfg.block_length)], axis=1)


class DecoderLayer(nn.Module):
    config: SdarConfig
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        with annotate_collective(SCOPE_BLOCK_NORM):
            n1 = RMSNorm(cfg.rms_norm_eps, name="ln_attn")(x).astype(
                cfg.dtype)
        with annotate_collective(SCOPE_BLOCK_ATTN_PROJ):
            attn = TwoStreamAttention(cfg, self.attention_fn,
                                      name="attention")(n1, positions)
        with annotate_collective(SCOPE_BLOCK_NORM):
            x = x + attn
            n2 = RMSNorm(cfg.rms_norm_eps, name="ln_moe")(x)
        # norm_topk_prob: softmax over the 128, top 8, renormalised, which
        # is the softmax over the eight picked logits. A row of the doubled
        # stream is one routing group.
        out = SparseExperts(cfg, gates_over_picks=True, name="moe")(n2)
        with annotate_collective(SCOPE_BLOCK_NORM):
            return x + out


class Sdar(nn.Module):
    """Call: ``model.apply(vars, noisy_ids, clean_ids)``, both ``[B, S]``
    -> the noisy positions' logits ``[B, S, V]`` in float32."""

    config: SdarConfig = SDAR_30B_A3B
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, noisy_ids, clean_ids):
        cfg = self.config
        seq_len = noisy_ids.shape[1]
        layer = recomputed(DecoderLayer, cfg)
        # Two streams side by side, each counting its positions from zero.
        positions = jnp.concatenate([jnp.arange(seq_len)] * 2)
        with annotate_collective(SCOPE_BLOCK_EMBED):
            x = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                         param_dtype=jnp.float32, name="token_embeddings")(
                jnp.concatenate([noisy_ids, clean_ids], 1)).astype(cfg.dtype)
        for i in range(cfg.num_layers):
            x = layer(cfg, self.attention_fn, name=f"layer_{i}")(
                x, positions)
        with annotate_collective(SCOPE_BLOCK_HEAD):
            # The head reads the noisy half: only its positions are scored.
            return untied_head(self, x[:, :seq_len])


def noisy_batch(key, clean_ids, block_length: int, mask_id: int):
    """One block-diffusion batch from ``clean_ids [R, S]``: ``{"clean",
    "noisy", "weight"}``, each ``[R, S]``. Every block of a row draws its
    noise level ``t ~ U[1 / block_length, 1]`` (the linear schedule
    clipped from below, under which a block masks one token in expectation
    at the least), every position is replaced by ``mask_id`` with its
    block's probability, and a masked position's loss weight is ``1 / t``
    (others 0)."""
    rows, seq_len = clean_ids.shape
    if seq_len % block_length:
        raise ValueError(
            f"{seq_len} tokens are no whole blocks of {block_length}")
    level_key, mask_key = jax.random.split(key)
    level = jax.random.uniform(
        level_key, (rows, seq_len // block_length), jnp.float32,
        1.0 / block_length, 1.0)
    level = jnp.repeat(level, block_length, axis=1)
    masked = jax.random.uniform(mask_key, (rows, seq_len)) < level
    return {"clean": clean_ids,
            "noisy": jnp.where(masked, mask_id, clean_ids),
            "weight": jnp.where(masked, 1.0 / level, 0.0)}


def block_diffusion_loss(model: Sdar, params, batch):
    """The weighted denoising cross entropy of a ``noisy_batch``: a masked
    position is scored on its own clean token (labels in place, no shift),
    weighted by ``1 / t`` of its block, and the sum is divided by all ``R
    S`` positions. The source's config has no auxiliary-loss coefficient,
    so there is none."""
    logits = model.apply({"params": params}, batch["noisy"], batch["clean"])
    return token_cross_entropy(logits, batch["clean"], batch["weight"])
