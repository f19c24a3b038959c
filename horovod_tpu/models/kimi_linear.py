"""Kimi Linear — a decoder of three kinds of layer: Kimi Delta Attention,
latent attention without positions, and a mixture of experts beside a
shared one.

moonshotai's ``Kimi-Linear-48B-A3B-Instruct`` (``config.json``,
``model_type`` ``kimi_linear``; "Kimi Linear: An Expressive, Efficient
Attention Architecture", arXiv:2510.26692) is a pre-norm causal decoder,
``x = x + Mixer(RMSNorm(x))``, ``x = x + FFN(RMSNorm(x))``, no bias
anywhere, an untied head over every position. Which layer is what is said
by two lists that count the layers from one, ``kda_layers`` and
``full_attn_layers`` (three to one), and by ``first_k_dense_replace``: the
first layer's feed-forward is dense, every later one's is the experts.

* **Kimi Delta Attention** (``flash-linear-attention``'s ``kda`` layer):
  the query, key and value projections each pass a depth-wise causal
  convolution of ``short_conv_kernel_size`` and SiLU; per head the query and
  key are l2-normalised (the query scaled by ``d ** -0.5``); ``beta =
  sigmoid(x W_b)``; the log of the decay is **one number a key channel**,
  ``g = -exp(A_log)[head] * softplus(x W_fa W_fb + dt_bias)`` through a
  low-rank pair of the head's width; the rule of
  ``ops.linear_attention.kimi_delta_rule`` from a zero state; then an
  RMSNorm over each head's output (one learned scale, shared by the heads)
  times ``sigmoid(x W_ga W_gb)``, a low-rank pair again, and the output
  projection.
* **Latent attention** (``models/latent.py``; ``q_lora_rank`` null,
  ``mla_use_nope``): the queries come straight from the input, 192 lanes a
  head; the keys and values come through a latent of ``kv_lora_rank`` with
  its RMSNorm, 128 lanes each a head, and the keys are completed by the 64
  lanes of ``k_r``
  that all heads read alike. **No rotary embedding is applied** (the
  recurrent layers order the tokens), so in training it is attention whose
  scores contract over 192 lanes and whose context is 128 wide; nothing is
  absorbed or cached. Causal softmax through ``attention_fn=``.
* **Experts**: ``SparseExperts`` with sigmoid scores, the top 8 of 256 (one
  expert group, so group-limited routing is plain top-k), the gates
  renormalised over the picks and scaled by ``routed_scaling_factor``; the
  shared expert is a dense SiLU-gated branch every token takes, under
  ``hvd.moe.shared``. The source's ``e_score_correction_bias`` (added to the
  scores for the choice only and moved by the load balancer outside the
  gradient) is held at its initial zero and is no leaf here.

A model may hold a window of the experts (``experts_here`` from
``first_expert`` on), one chip's share of expert parallelism: the router
keeps its width and a token's gates are normalised over all eight picks
wherever they live, so the shares' routed outputs, with the shared expert
counted once, add up to the whole layer's.

TPU-first choices, as the other decoders: bfloat16 activations; float32
parameters, norms, router, convolution weights and gates (``A_log``,
``dt_bias``, ``g``, ``beta``). The mixer's activations stay ``[B, S, H *
d]`` from the projections to the output's gate (the rule's kernels read
and write them so): the two l2-norms and the output's RMSNorm, each a
head at a time, sum a head's lanes and spread the factor back over them as
products with the heads' matrix of ones and zeros
(:func:`scaled_by_head`), because a reduction over part of the lanes makes
the v5e's compiler lay the array out a head a row and back.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..attribution import (SCOPE_BLOCK_ATTN_PROJ, SCOPE_BLOCK_EMBED,
                           SCOPE_BLOCK_FFN, SCOPE_BLOCK_HEAD,
                           SCOPE_BLOCK_NORM, SCOPE_LINATTN_CONV,
                           SCOPE_LINATTN_GATE, SCOPE_MOE_SHARED)
from ..ops.linear_attention import kimi_delta_rule, short_conv
from ..profiler import annotate_collective
from .experts import ExpertWindow, SparseExperts
from .latent import LatentAttention
from .loss import token_cross_entropy
from .parts import (GatedMLP, RMSNorm, decay_rate,
                    head_major_flash_attention, projection, recomputed,
                    step_bias, untied_head)

flash_attention_fn = head_major_flash_attention  # benchmark/configs' name

KDA, MLA = "kda", "mla"


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig(ExpertWindow):
    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216  # the dense layers' feed-forward
    moe_intermediate_size: int = 1024  # one expert's, and the shared one's
    num_layers: int = 27
    kda_layers: tuple = (1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                         19, 21, 22, 23, 25, 26)  # counted from one
    full_attn_layers: tuple = (4, 8, 12, 16, 20, 24, 27)
    first_k_dense_replace: int = 1
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64  # k_r's lanes; nothing rotates them
    v_head_dim: int = 128
    linear_num_heads: int = 32
    linear_head_dim: int = 128  # keys and values alike; the low-rank pairs'
    short_conv_kernel_size: int = 4
    num_experts: int = 256
    top_k: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    experts_here: int | None = None  # None: all from first_expert on
    first_expert: int = 0
    capacity_factor: float = 1.25
    chunk: int = 64  # tokens a step of the scan
    sub_chunk: int = 16  # rows of the rule's sub-blocks
    rms_norm_eps: float = 1e-5
    remat: bool = True
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        listed = sorted(self.kda_layers + self.full_attn_layers)
        if listed != list(range(1, self.num_layers + 1)):
            raise ValueError(
                f"kda_layers and full_attn_layers must name each of the "
                f"{self.num_layers} layers once, counted from one; got "
                f"{self.kda_layers} and {self.full_attn_layers}")

    @property
    def kinds(self) -> tuple:
        return tuple(KDA if i + 1 in self.kda_layers else MLA
                     for i in range(self.num_layers))

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim


KIMI_LINEAR_48B_A3B = KimiLinearConfig()
KIMI_LINEAR_TINY = KimiLinearConfig(  # test-sized: one period
    vocab_size=256, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=24, num_layers=4, kda_layers=(1, 2, 3),
    full_attn_layers=(4,), num_attention_heads=4, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    linear_num_heads=4, linear_head_dim=16, num_experts=8, top_k=2,
    capacity_factor=2.0, chunk=16, sub_chunk=4,
)


def scaled_by_head(x, heads: int, factor):
    """``x [B, S, H * d]`` in float32 times ``factor(the sum of a head's
    squares)``, every head's ``d`` lanes by their own factor, **where they
    lie**: the sums a head and the factors' way back to the lanes are two
    float32 products at ``Precision.HIGHEST`` with the heads' ``[H * d, H]``
    matrix of ones and zeros (float32's sum in another order; a factor
    times one, exactly). A reduction over part of the lanes of ``[B, S, H *
    d]`` makes the v5e's compiler lay the array out a head a row first, and
    the factor's broadcast back over the lanes is written out and laid out
    again (``PERF.md`` §6, PR 53: 16 ms of Kimi Linear's step); the MXU
    takes both as they are. The batch is the products' batch dimension,
    the matrix broadcast: a recomputed layer's policy keeps every product
    without one."""
    f32, highest = jnp.float32, jax.lax.Precision.HIGHEST
    x = x.astype(f32)
    member = jnp.broadcast_to(
        jnp.repeat(jnp.eye(heads, dtype=f32), x.shape[-1] // heads, 0),
        x.shape[:1] + (x.shape[-1], heads))
    squares = jnp.einsum("bsx,bxh->bsh", jnp.square(x), member,
                         precision=highest, preferred_element_type=f32)
    return x * jnp.einsum("bsh,bxh->bsx", factor(squares), member,
                          precision=highest, preferred_element_type=f32)


def l2norm_of_heads(x, heads: int, eps: float = 1e-6):
    """``parts.l2norm`` of every head's ``d`` lanes of ``x [B, S, H * d]``,
    float32, where they lie (:func:`scaled_by_head`)."""
    return scaled_by_head(x, heads, lambda sums: jax.lax.rsqrt(sums + eps))


class HeadsRMSNorm(nn.Module):
    """``parts.RMSNorm`` of every head's ``d`` lanes of ``x [B, S, H * d]``
    with one learned scale ``[d]`` that the heads share, float32, where they
    lie (:func:`scaled_by_head`): the tree's leaf is ``parts.RMSNorm``'s on
    ``[B, S, H, d]``."""
    eps: float
    heads: int

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1] // self.heads
        scale = self.param("scale", nn.initializers.ones, (d,), jnp.float32)
        return scaled_by_head(
            x, self.heads, lambda sums: jax.lax.rsqrt(sums / d + self.eps)
        ) * jnp.tile(scale, self.heads)


class KimiDeltaAttention(nn.Module):
    config: KimiLinearConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        heads, d = cfg.linear_num_heads, cfg.linear_head_dim
        f32 = jnp.float32

        def projected(name):
            """``x``'s projection and the convolution's weights for it
            (torch's Conv1d default: uniform within 1 / sqrt(taps))."""
            return projection(cfg, heads * d, name)(x), self.param(
                name + "_conv", nn.initializers.variance_scaling(
                    1 / 3, "fan_in", "uniform", in_axis=-1, out_axis=-2),
                (heads * d, cfg.short_conv_kernel_size), f32)

        def low_rank(name, dtype):
            """``x W_a W_b`` through the head's width."""
            return nn.Dense(heads * d, use_bias=False, dtype=dtype,
                            name=name + "_b")(
                nn.Dense(d, use_bias=False, dtype=dtype,
                         name=name + "_a")(x))

        before = [projected("query"), projected("key"), projected("value")]
        gate = low_rank("gate", cfg.dtype)
        a_log = self.param("A_log", decay_rate, (heads,), f32)
        dt_bias = self.param("dt_bias", step_bias, (heads * d,), f32)
        with annotate_collective(SCOPE_LINATTN_CONV):
            by_head = x.shape[:2] + (heads, d)
            q, k, v = (jax.nn.silu(short_conv(y, w)) for y, w in before)
            q = (l2norm_of_heads(q, heads) * d ** -0.5).astype(
                cfg.dtype).reshape(by_head)
            k = l2norm_of_heads(k, heads).astype(cfg.dtype).reshape(by_head)
            v = v.reshape(by_head)
            beta = jax.nn.sigmoid(nn.Dense(
                heads, use_bias=False, dtype=f32, name="beta")(x))
            g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
                low_rank("decay", f32) + dt_bias).reshape(q.shape)
        out = kimi_delta_rule(q, k, v, g, beta, chunk=cfg.chunk,
                              sub=cfg.sub_chunk)
        with annotate_collective(SCOPE_LINATTN_GATE):
            out = HeadsRMSNorm(cfg.rms_norm_eps, heads, name="o_norm")(
                out.reshape(x.shape[:2] + (-1,))
            ) * jax.nn.sigmoid(gate.astype(f32))
            out = out.astype(cfg.dtype)
        return projection(cfg, cfg.hidden_size, "out")(out)


class DecoderLayer(nn.Module):
    config: KimiLinearConfig
    kind: str
    dense: bool
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        with annotate_collective(SCOPE_BLOCK_NORM):
            n1 = RMSNorm(cfg.rms_norm_eps, name="ln_mixer")(x).astype(
                cfg.dtype)
        with annotate_collective(SCOPE_BLOCK_ATTN_PROJ):
            if self.kind == KDA:
                mixed = KimiDeltaAttention(cfg, name="kda")(n1)
            else:
                mixed = LatentAttention(cfg, self.attention_fn,
                                        name="attention")(n1)
        with annotate_collective(SCOPE_BLOCK_NORM):
            x = x + mixed
            n2 = RMSNorm(cfg.rms_norm_eps, name="ln_ffn")(x)
        if self.dense:
            with annotate_collective(SCOPE_BLOCK_FFN):
                out = GatedMLP(cfg, cfg.intermediate_size, name="mlp")(
                    n2.astype(cfg.dtype))
        else:
            out = SparseExperts(
                cfg, gates_over_picks=True, scores="sigmoid",
                gate_scale=cfg.routed_scaling_factor,
                width=cfg.moe_intermediate_size, name="moe")(n2)
            with annotate_collective(SCOPE_MOE_SHARED):
                out = out + GatedMLP(
                    cfg, cfg.num_shared_experts * cfg.moe_intermediate_size,
                    name="shared")(n2.astype(cfg.dtype))
        with annotate_collective(SCOPE_BLOCK_NORM):
            return x + out


class KimiLinear(nn.Module):
    """Call: ``model.apply(vars, input_ids)`` → logits ``[B, S, V]`` in
    float32. ``S`` is a multiple of ``config.chunk``."""

    config: KimiLinearConfig = KIMI_LINEAR_48B_A3B
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.config
        layer = recomputed(DecoderLayer, cfg)
        with annotate_collective(SCOPE_BLOCK_EMBED):
            x = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                         param_dtype=jnp.float32,
                         name="token_embeddings")(input_ids).astype(cfg.dtype)
        for i, kind in enumerate(cfg.kinds):
            x = layer(cfg, kind, i < cfg.first_k_dense_replace,
                      self.attention_fn, name=f"layer_{i}")(x)
        with annotate_collective(SCOPE_BLOCK_HEAD):
            return untied_head(self, x)


def causal_lm_loss(model: KimiLinear, params, tokens):
    """Next-token cross entropy of ``tokens [B, S + 1]``: positions
    ``0..S-1`` are read and ``1..S`` are their labels. The source's config
    has no auxiliary-loss coefficient, so there is none."""
    logits = model.apply({"params": params}, tokens[:, :-1])
    return token_cross_entropy(logits, tokens[:, 1:])
