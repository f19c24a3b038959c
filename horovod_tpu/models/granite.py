"""Granite 4.0-H — the framework's first model with state-space layers.

IBM's ``granite-4.0-h-micro`` (``config.json``, ``model_type``
``granitemoehybrid``, ``num_local_experts`` 0: no experts, the shared
feed-forward is the whole one): a pre-norm causal decoder whose layers are
of two kinds by a ``layer_types`` list, nine ``mamba`` to one ``attention``
(the sixth of every ten), with muP-style multipliers on the embedding, the
residual branches, the attention scores and the logits::

    x = embedding_multiplier * E[ids]
    x = x + residual_multiplier * Mixer(RMSNorm(x))
    x = x + residual_multiplier * MLP(RMSNorm(x))
    logits = RMSNorm(x) E^T / logits_scaling          (one tied leaf E)

* **``mamba``** is a Mamba-2 mixer (Dao & Gu 2024, arXiv:2405.21060, as
  ``transformers`` writes ``GraniteMoeHybridMambaLayer``): one projection to
  ``[z | xBC | dt]``; a depth-wise causal convolution of ``mamba_d_conv``
  with a bias and SiLU over ``xBC``; ``x [S, H, P]``, ``B`` and ``C [S, G,
  N]`` split out of it; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``;
  the scan of ``ops/ssd.py`` from a zero state with the skip ``D x``; an
  RMSNorm with a learned scale over all the channels of ``y * silu(z)``
  (one group), and the output projection.
* **``attention``** is grouped-query softmax attention with **no positional
  embedding** (``position_embedding_type`` ``nope``: the state-space layers
  order the tokens) and the score scale ``attention_multiplier``, not
  ``head_dim ** -0.5``. The flash kernels fix the latter, so the queries go
  in scaled by the ratio of the two (2^-3 at the published sizes, a power
  of two: exact in bfloat16).
* The feed-forward is SiLU-gated with one input projection to ``[a | b]``:
  ``(silu(a) * b) W_out``. No bias anywhere but the convolution's.

TPU-first choices, as the other decoders: bfloat16 activations; float32
parameters, norms, steps and decays (``dt``, ``A_log``, ``D``); attention
through the framework's flash kernels (``attention_fn=``), keys and values
with their own 8 heads.

**Recomputation** (``remat``, on by default): every layer is wrapped in
``nn.remat`` with the policy SmallThinker's and SDAR's layers have
(``parts.save_kernels_and_projections``), so the forward pass keeps a
layer's input, what the flash forward kernel returned and the results of
the products without a batch dimension (a Mamba-2 layer's 8,512- and
16,384-wide rows: 0.23 GiB a layer at 4,096 tokens), and the backward pass
computes the rest again: norms, the convolution, the gates and **the whole
scan** (its products are batched over chunks and heads, so the policy keeps
none of them), whose decay matrices, 268 MB a layer in float32, are alive
for one layer's backward pass at a time. With and without ``remat`` the
parameter tree, the loss and the gradients are the same.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..attribution import (SCOPE_BLOCK_ATTN_PROJ, SCOPE_BLOCK_EMBED,
                           SCOPE_BLOCK_FFN, SCOPE_BLOCK_HEAD,
                           SCOPE_BLOCK_NORM)
from ..profiler import annotate_collective
from .loss import token_cross_entropy
from .mamba2 import mamba2_mixer
from .parts import (RMSNorm, dense_window_attention, grouped_flash_attention,
                    projection, recomputed)

flash_attention_fn = grouped_flash_attention  # benchmark/configs' name

MAMBA, ATTENTION = "mamba", "attention"
PERIOD = (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4


@dataclasses.dataclass(frozen=True)
class GraniteConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    shared_intermediate_size: int = 8192
    num_layers: int = 40
    layer_types: tuple | None = None  # None: PERIOD, repeated
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    remat: bool = True
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.hidden_size % self.num_attention_heads:
            raise ValueError(
                f"hidden_size {self.hidden_size} does not divide into "
                f"{self.num_attention_heads} heads")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads cannot share "
                f"{self.num_key_value_heads} key/value heads evenly")
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_inner:
            raise ValueError(
                f"{self.mamba_n_heads} Mamba heads of {self.mamba_d_head} "
                f"are not mamba_expand x hidden_size = {self.mamba_inner}")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError(
                f"{self.mamba_n_heads} Mamba heads do not share "
                f"{self.mamba_n_groups} groups evenly")
        kinds = self.kinds
        if len(kinds) != self.num_layers or set(kinds) - {MAMBA, ATTENTION}:
            raise ValueError(
                f"layer_types must name {self.num_layers} layers, each "
                f"{MAMBA!r} or {ATTENTION!r}; got {kinds}")

    @property
    def kinds(self) -> tuple:
        if self.layer_types is not None:
            return tuple(self.layer_types)
        return tuple(PERIOD[i % len(PERIOD)] for i in range(self.num_layers))

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def query_scale(self) -> float:
        """What the queries are multiplied by ahead of an attention function
        that scales its scores by ``head_dim ** -0.5``."""
        return self.attention_multiplier * self.head_dim ** 0.5


GRANITE_4_0_H_MICRO = GraniteConfig()
GRANITE_TINY = GraniteConfig(  # test-sized: three Mamba layers, one attention
    vocab_size=256, hidden_size=32, shared_intermediate_size=48,
    num_layers=4, layer_types=(MAMBA, MAMBA, ATTENTION, MAMBA),
    num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=8,
    mamba_d_head=8, mamba_d_state=16, mamba_chunk_size=8,
    attention_multiplier=0.25,
)


class Mamba2Mixer(nn.Module):
    """``models/mamba2.py``'s mixer at this config's sizes: one group's
    ``B`` and ``C`` in the published model, the gated norm over all the
    channels."""
    config: GraniteConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        return mamba2_mixer(
            self, cfg, x, heads=cfg.mamba_n_heads, head_dim=cfg.mamba_d_head,
            state=cfg.mamba_d_state, groups=cfg.mamba_n_groups,
            taps=cfg.mamba_d_conv, chunk=cfg.mamba_chunk_size)


class GroupedAttention(nn.Module):
    config: GraniteConfig
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config

        def heads(y, count):
            return y.reshape(x.shape[:2] + (count, cfg.head_dim))

        kv_heads = cfg.num_key_value_heads
        q = heads(projection(cfg, cfg.hidden_size, "query")(x),
                  cfg.num_attention_heads)
        k = heads(projection(cfg, kv_heads * cfg.head_dim, "key")(x), kv_heads)
        v = heads(projection(cfg, kv_heads * cfg.head_dim, "value")(x),
                  kv_heads)
        attend = self.attention_fn or dense_window_attention
        out = attend((q * cfg.query_scale).astype(cfg.dtype), k, v, cfg.dtype)
        return projection(cfg, cfg.hidden_size, "out")(
            out.reshape(x.shape[:2] + (-1,)))


class GatedMLP(nn.Module):
    config: GraniteConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        gate, up = jnp.split(
            projection(cfg, 2 * cfg.shared_intermediate_size, "input")(x), 2,
            axis=-1)
        return projection(cfg, cfg.hidden_size, "output")(
            jax.nn.silu(gate) * up)


class HybridLayer(nn.Module):
    config: GraniteConfig
    kind: str
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        scale = cfg.residual_multiplier
        with annotate_collective(SCOPE_BLOCK_NORM):
            normed = RMSNorm(cfg.rms_norm_eps, name="ln_mixer")(x).astype(
                cfg.dtype)
        with annotate_collective(SCOPE_BLOCK_ATTN_PROJ):
            if self.kind == MAMBA:
                mixed = Mamba2Mixer(cfg, name="mamba")(normed)
            else:
                mixed = GroupedAttention(cfg, self.attention_fn,
                                         name="attention")(normed)
        with annotate_collective(SCOPE_BLOCK_NORM):
            x = x + (scale * mixed).astype(cfg.dtype)
            normed = RMSNorm(cfg.rms_norm_eps, name="ln_mlp")(x).astype(
                cfg.dtype)
        with annotate_collective(SCOPE_BLOCK_FFN):
            hidden = GatedMLP(cfg, name="mlp")(normed)
        with annotate_collective(SCOPE_BLOCK_NORM):
            return x + (scale * hidden).astype(cfg.dtype)


class Granite(nn.Module):
    """Call: ``model.apply(vars, input_ids)`` → logits ``[B, S, V]`` in
    float32. ``S`` is a multiple of ``config.mamba_chunk_size``."""

    config: GraniteConfig = GRANITE_4_0_H_MICRO
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.config
        layer = recomputed(HybridLayer, cfg)
        # nn.Embed's initialiser; one leaf, read as rows here and as the
        # head's columns below (tie_word_embeddings)
        embedding = self.param(
            "embedding", nn.initializers.variance_scaling(
                1.0, "fan_in", "normal", out_axis=0),
            (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        with annotate_collective(SCOPE_BLOCK_EMBED):
            x = (cfg.embedding_multiplier
                 * jnp.take(embedding, input_ids, axis=0)).astype(cfg.dtype)
        for i, kind in enumerate(cfg.kinds):
            x = layer(cfg, kind, self.attention_fn, name=f"layer_{i}")(x)
        with annotate_collective(SCOPE_BLOCK_HEAD):
            x = RMSNorm(cfg.rms_norm_eps, name="ln_out")(x).astype(cfg.dtype)
            # bf16 in, f32 out on the MXU, as the other decoders' heads.
            logits = jax.lax.dot_general(
                x, embedding.astype(cfg.dtype),
                (((x.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            return logits / cfg.logits_scaling


def causal_lm_loss(model: Granite, params, tokens):
    """Next-token cross entropy of ``tokens [B, S + 1]``: positions
    ``0..S-1`` are read and ``1..S`` are their labels. The source's config
    has no auxiliary loss, so there is none."""
    logits = model.apply({"params": params}, tokens[:, :-1])
    return token_cross_entropy(logits, tokens[:, 1:])
