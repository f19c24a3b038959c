"""SmallThinker — the framework's first model with two kinds of attention
layer, grouped keys and values, and a layer recomputed in the backward pass.

PowerInfer's ``SmallThinker-21BA3B-Instruct`` (``config.json``,
``model_type`` ``smallthinker``): a pre-norm causal decoder of 52 layers in
a period of four. ``sliding_window_layout`` and ``rope_layout`` (both
``[0, 1, 1, 1]`` repeated) say, layer by layer, whether attention is
*windowed* (query ``i`` sees the keys ``j`` with ``0 <= i - j <
sliding_window_size``) or full causal, and whether queries and keys get a
rotary embedding or no positional embedding at all. 28 query heads read 4
key/value heads, 7 to one; no bias and no QK-norm. Every layer is a
mixture of 64 ReLU-gated experts (a sparse ReGLU) of which a token takes
6, and **the router reads the pre-attention normalised input**, not the
expert block's:

    h = RMSNorm_1(x);  rho = h W_router        (float32)
    x' = x + Attention(h) W_o
    u = RMSNorm_2(x');  P = top-6 of rho;  g = softmax(rho[P])
    x'' = x' + sum_{e in P} g_e W_down,e (relu(W_gate,e u) * (W_up,e u))

The gates are the softmax over the six picked logits. A final RMSNorm and
an untied head over every position; no auxiliary loss.

TPU-first choices, as the other decoders (``models/parts.py`` holds the
norm, RoPE and the adapters, ``models/experts.py`` the capacity slots):
bfloat16 activations with float32 parameters, norms, RoPE and router;
attention through the framework's flash kernels (``attention_fn=``), which
take the window and the grouped keys and values as they are (nothing is
repeated in HBM); the experts through ``parallel/moe.py``'s slots, one
sequence a routing group. A model may hold a window of the experts
(``experts_here`` from ``first_expert`` on), one chip's share of expert
parallelism: the router keeps its width and a token's gates are normalised
over all six picks, wherever they live, so the shares' outputs add up to
the whole layer's.

**Recomputation** (``remat``, on by default): a decoder layer is wrapped in
``nn.remat`` (``jax.checkpoint``), so the forward pass keeps a layer's
input and, by the policy (``parts.save_kernels_and_projections``), the flash
kernels' output and log-sum-exp and the projections' results; the backward
pass computes the rest of the layer again, one layer's activations alive
at a time: 10.6 GiB for one sequence of the model's own 16,384 positions
on a 16 GB chip where keeping everything takes 13.3 (PERF.md). The
attention kernels are not run twice. With and without
``remat`` the parameter tree, the loss and the gradients are the same.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..attribution import (SCOPE_BLOCK_ATTN_PROJ, SCOPE_BLOCK_EMBED,
                           SCOPE_BLOCK_HEAD, SCOPE_BLOCK_NORM,
                           SCOPE_MOE_ROUTE)
from ..profiler import annotate_collective
from .experts import ExpertWindow, SparseExperts
from .loss import token_cross_entropy
from .parts import (RMSNorm, dense_window_attention, grouped_flash_attention,
                    projection, recomputed, rope_tokens_major, untied_head)

flash_attention_fn = grouped_flash_attention  # benchmark/configs' name

PERIOD = (0, 1, 1, 1)  # one period of both layouts: full + NoPE, then 3 x


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig(ExpertWindow):
    vocab_size: int = 151936
    hidden_size: int = 2560
    num_layers: int = 52
    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    intermediate_size: int = 768  # one expert's width
    num_experts: int = 64
    top_k: int = 6
    experts_here: int | None = None  # None: all from first_expert on
    first_expert: int = 0
    capacity_factor: float = 1.25
    window: int = 4096
    sliding_window_layout: tuple | None = None  # None: PERIOD, repeated
    rope_layout: tuple | None = None
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1.5e6
    remat: bool = True
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"{self.num_heads} query heads cannot share "
                f"{self.num_kv_heads} key/value heads evenly")
        for name in ("sliding_window_layout", "rope_layout"):
            layout = getattr(self, name)
            if layout is not None and len(layout) != self.num_layers:
                raise ValueError(
                    f"{name} has {len(layout)} entries for "
                    f"{self.num_layers} layers")

    def _layout(self, given) -> tuple:
        if given is not None:
            return tuple(bool(flag) for flag in given)
        return tuple(bool(PERIOD[i % len(PERIOD)])
                     for i in range(self.num_layers))

    @property
    def windowed(self) -> tuple:
        """Layer by layer: is attention windowed?"""
        return self._layout(self.sliding_window_layout)

    @property
    def rotary(self) -> tuple:
        """Layer by layer: do queries and keys get RoPE?"""
        return self._layout(self.rope_layout)


SMALLTHINKER_21B_A3B = SmallThinkerConfig()
SMALLTHINKER_TINY = SmallThinkerConfig(  # test-sized: 14 heads on 2, 7 to 1
    vocab_size=256, hidden_size=56, num_layers=4, num_heads=14,
    num_kv_heads=2, head_dim=8, intermediate_size=24, num_experts=8,
    top_k=3, capacity_factor=8 / 3, window=24,
)


class GroupedAttention(nn.Module):
    config: SmallThinkerConfig
    windowed: bool
    rotary: bool
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config

        def heads(y, count):
            return y.reshape(x.shape[:2] + (count, cfg.head_dim))

        q = projection(cfg, cfg.num_heads * cfg.head_dim, "query")(x)
        k = projection(cfg, cfg.num_kv_heads * cfg.head_dim, "key")(x)
        v = projection(cfg, cfg.num_kv_heads * cfg.head_dim, "value")(x)
        if self.rotary:
            q = rope_tokens_major(q, cfg.num_heads, cfg.rope_theta, cfg.dtype)
            k = rope_tokens_major(k, cfg.num_kv_heads, cfg.rope_theta,
                                  cfg.dtype)
        attend = self.attention_fn or dense_window_attention
        out = attend(heads(q, cfg.num_heads), heads(k, cfg.num_kv_heads),
                     heads(v, cfg.num_kv_heads), cfg.dtype,
                     cfg.window if self.windowed else None)
        return projection(cfg, cfg.hidden_size, "out")(
            out.reshape(x.shape[:2] + (-1,)))


class DecoderLayer(nn.Module):
    config: SmallThinkerConfig
    windowed: bool
    rotary: bool
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        with annotate_collective(SCOPE_BLOCK_NORM):
            n1 = RMSNorm(cfg.rms_norm_eps, name="ln_attn")(x)
        router = self.param("router", nn.initializers.lecun_normal(),
                            (cfg.hidden_size, cfg.num_experts), jnp.float32)
        # The router reads the PRE-attention normalised input, in float32
        # all the way: a TPU's default float32 matmul is one bfloat16 pass,
        # and a pick is a discontinuity.
        with annotate_collective(SCOPE_MOE_ROUTE):
            logits = jnp.matmul(n1, router,
                                precision=jax.lax.Precision.HIGHEST)
        with annotate_collective(SCOPE_BLOCK_ATTN_PROJ):
            attn = GroupedAttention(
                cfg, self.windowed, self.rotary, self.attention_fn,
                name="attention")(n1.astype(cfg.dtype))
        with annotate_collective(SCOPE_BLOCK_NORM):
            x = x + attn
            n2 = RMSNorm(cfg.rms_norm_eps, name="ln_moe")(x)
        # ReLU-gated experts on the picks made before attention, the gates
        # a softmax over the picked logits.
        out = SparseExperts(cfg, activation=jax.nn.relu,
                            gates_over_picks=True, name="moe")(n2, logits)
        with annotate_collective(SCOPE_BLOCK_NORM):
            return x + out


class SmallThinker(nn.Module):
    """Call: ``model.apply(vars, input_ids)`` -> logits ``[B, S, V]`` in
    float32."""

    config: SmallThinkerConfig = SMALLTHINKER_21B_A3B
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.config
        layer = recomputed(DecoderLayer, cfg)
        with annotate_collective(SCOPE_BLOCK_EMBED):
            x = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                         param_dtype=jnp.float32,
                         name="token_embeddings")(input_ids).astype(cfg.dtype)
        for i, (windowed, rotary) in enumerate(zip(cfg.windowed,
                                                   cfg.rotary)):
            x = layer(cfg, windowed, rotary, self.attention_fn,
                      name=f"layer_{i}")(x)
        with annotate_collective(SCOPE_BLOCK_HEAD):
            return untied_head(self, x)


def causal_lm_loss(model: SmallThinker, params, tokens):
    """Next-token cross entropy of ``tokens [B, S + 1]``: positions
    ``0..S-1`` are read and ``1..S`` are their labels. The source's config
    has no auxiliary-loss coefficient, so there is none."""
    logits = model.apply({"params": params}, tokens[:, :-1])
    return token_cross_entropy(logits, tokens[:, 1:])
