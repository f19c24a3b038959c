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

TPU-first choices, as ``models/olmoe.py`` (whose ``RMSNorm``, ``rope`` and
capacity slots this model uses): bfloat16 activations with float32
parameters, norms, RoPE and router; attention through the framework's flash
kernels (``attention_fn=``), which take the window and the grouped keys and
values as they are (nothing is repeated in HBM); the experts through
``parallel/moe.py``'s slots, one sequence a routing group. A model may hold
a window of the experts (``experts_here`` from ``first_expert`` on), one
chip's share of expert parallelism: the router keeps its width and a
token's gates are normalised over all six picks, wherever they live, so
the shares' outputs add up to the whole layer's.

**Recomputation** (``remat``, on by default): a decoder layer is wrapped in
``nn.remat`` (``jax.checkpoint``), so the forward pass keeps a layer's
input and, by the policy (``save_kernels_and_projections``), the flash
kernels' output and log-sum-exp and the projections' results; the backward
pass computes the rest of the layer again, one layer's activations alive
at a time: 10.6 GiB for one sequence of the model's own 16,384 positions
on a 16 GB chip where keeping everything takes 13.3 (PERF.md). The
attention kernels are not run twice. With and without
``remat`` the parameter tree, the loss and the gradients are the same.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..attribution import (SCOPE_BLOCK_ATTN_PROJ, SCOPE_BLOCK_EMBED,
                           SCOPE_BLOCK_HEAD, SCOPE_BLOCK_NORM,
                           SCOPE_MOE_ROUTE)
from ..ops.attention import flash_attention, flash_attention_tokens_major
from ..ops.heads import map_heads
from ..parallel import moe
from ..profiler import annotate_collective
from .loss import token_cross_entropy
from .olmoe import (RMSNorm, SparseExperts, rope,  # noqa: F401
                    routing_stats, take_expert_window)
from .recompute import save_kernels_and_projections

PERIOD = (0, 1, 1, 1)  # one period of both layouts: full + NoPE, then 3 x


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
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

    @property
    def experts_held(self) -> int:
        if self.experts_here is None:
            return self.num_experts - self.first_expert
        return self.experts_here

    def capacity(self, seq_len: int) -> int:
        return moe.expert_capacity(self.capacity_factor, seq_len, self.top_k,
                                   self.num_experts)


SMALLTHINKER_21B_A3B = SmallThinkerConfig()
SMALLTHINKER_TINY = SmallThinkerConfig(  # test-sized: 14 heads on 2, 7 to 1
    vocab_size=256, hidden_size=56, num_layers=4, num_heads=14,
    num_kv_heads=2, head_dim=8, intermediate_size=24, num_experts=8,
    top_k=3, capacity_factor=8 / 3, window=24,
)


def dense_window_attention(q, k, v, dtype, window=None):
    """``q [B, S, H, D]``, ``k``, ``v [B, S, KV heads, D]``; the banded (or
    full) causal softmax in float32, the keys and values of a group
    repeated: the fallback where no kernel runs."""
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x.astype(jnp.float32), group, axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k) / (q.shape[-1] ** 0.5)
    ahead = jnp.arange(q.shape[1])[:, None] - jnp.arange(k.shape[1])[None, :]
    seen = ahead >= 0 if window is None else (ahead >= 0) & (ahead < window)
    out = jnp.einsum("bhqk,bkhd->bqhd",
                     jax.nn.softmax(jnp.where(seen, scores, -1e30), -1), v)
    return out.astype(dtype)


def rotary_tables(dim: int, theta: float, positions):
    """``(cos, sin) [..., S, dim]`` of ``rope``'s half-split rotation at
    ``positions`` (``[S]`` or ``[B, S]``) for ``rotate_head``: each half
    written out twice, the sine's first half negated."""
    half = dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[..., None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return (jnp.concatenate([cos, cos], -1),
            jnp.concatenate([-sin, sin], -1))


def rotate_head(head, cos, sin, dtype):
    """``rope`` of one head ``[B, S, D]`` as ``dtype``, the same products
    and sums lane for lane: lane ``i`` pairs with lane ``i +- D/2``, turned
    in beside it **by the MXU**, as a product with the permutation matrix
    (a ``jnp.roll`` of the lanes is two slices of half a 128-lane tile,
    which XLA writes out padded and reads back). One 1 a column makes the
    product exact: ``HIGHEST`` sends a float32 head, and the float32
    cotangent on the way back, through as three bfloat16 pieces that sum
    to it again. Rounded to ``dtype`` here, a head at a time: the
    cotangent of a rounding of all heads at once is the whole array in
    float32 (256 MB at SDAR's shapes, live at the step's peak)."""
    dim = head.shape[-1]
    turn = jnp.asarray(np.roll(np.eye(dim, dtype=np.float32), dim // 2, 1),
                       head.dtype)
    turned = jnp.dot(head, turn, precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=jnp.float32)
    return (head * cos + turned * sin).astype(dtype)


def rope_tokens_major(x, heads: int, theta: float, dtype, positions=None):
    """``rope(...).astype(dtype)`` of ``x [B, S, heads * D]`` where a
    projection wrote it: a head after another on the lanes that hold it
    (``ops/heads.py``), nothing re-tiled on the way to the kernels."""
    if positions is None:
        positions = jnp.arange(x.shape[1])
    return map_heads(
        functools.partial(rotate_head, dtype=dtype), heads, (x,),
        constants=rotary_tables(x.shape[-1] // heads, theta, positions))


def flash_attention_fn(q, k, v, dtype, window=None, interpret: bool = False,
                       block: int | None = None):
    """Adapter plugging the causal Pallas flash kernels into
    ``SmallThinker``, the keys and values with their own, smaller number of
    heads. **A windowed layer's** ``[B, S, heads, D]`` is ``[B, S, heads *
    D]`` as the projections wrote it, and the tokens-major entry takes
    that: heads of whole 128-lane blocks reach the kernels where they lie
    (narrower ones, ``models/granite.py``'s 64, are transposed inside the
    entry). **A layer of full attention** is transposed to ``[B, heads, S,
    D]`` here, as every layer was until PR 40: fed tokens-major the kernels
    take 3 to 8% longer (a tile is 32 pieces of 4 KB where it was 128 KB in
    a row), a full layer computes twice a windowed layer's tiles, and two
    such layers took 202.8 ms where these take 192.6 (windowed ones with
    RoPE 109.5 against 118.1: PERF.md, PR 40). ``block`` is for tests that
    want several tiles of a short sequence."""
    tiles = dict(causal=True, window=window, block_q=block, block_k=block,
                 interpret=interpret)
    if window is None:
        out = flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), **tiles)
        return out.transpose(0, 2, 1, 3).astype(dtype)

    def rows(x):
        return x.reshape(x.shape[:2] + (-1,))

    out = flash_attention_tokens_major(rows(q), rows(k), rows(v),
                                       q.shape[2], **tiles)
    return out.reshape(q.shape).astype(dtype)


class GroupedAttention(nn.Module):
    config: SmallThinkerConfig
    windowed: bool
    rotary: bool
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config

        def project(name, width):
            return nn.Dense(width, use_bias=False, dtype=cfg.dtype,
                            param_dtype=jnp.float32, name=name)

        def heads(y, count):
            return y.reshape(x.shape[:2] + (count, cfg.head_dim))

        q = project("query", cfg.num_heads * cfg.head_dim)(x)
        k = project("key", cfg.num_kv_heads * cfg.head_dim)(x)
        v = project("value", cfg.num_kv_heads * cfg.head_dim)(x)
        if self.rotary:
            q = rope_tokens_major(q, cfg.num_heads, cfg.rope_theta, cfg.dtype)
            k = rope_tokens_major(k, cfg.num_kv_heads, cfg.rope_theta,
                                  cfg.dtype)
        attend = self.attention_fn or dense_window_attention
        out = attend(heads(q, cfg.num_heads), heads(k, cfg.num_kv_heads),
                     heads(v, cfg.num_kv_heads), cfg.dtype,
                     cfg.window if self.windowed else None)
        return project("out", cfg.hidden_size)(
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
        layer = DecoderLayer
        if cfg.remat:
            layer = nn.remat(DecoderLayer,
                             policy=save_kernels_and_projections)
        with annotate_collective(SCOPE_BLOCK_EMBED):
            x = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                         param_dtype=jnp.float32,
                         name="token_embeddings")(input_ids).astype(cfg.dtype)
        for i, (windowed, rotary) in enumerate(zip(cfg.windowed,
                                                   cfg.rotary)):
            x = layer(cfg, windowed, rotary, self.attention_fn,
                      name=f"layer_{i}")(x)
        with annotate_collective(SCOPE_BLOCK_HEAD):
            x = RMSNorm(cfg.rms_norm_eps, name="ln_out")(x).astype(cfg.dtype)
            # bf16 in, f32 out on the MXU, as models/bert.py's head.
            head = self.param("lm_head", nn.initializers.lecun_normal(),
                              (cfg.hidden_size, cfg.vocab_size), jnp.float32)
            return jax.lax.dot_general(
                x, head.astype(cfg.dtype), (((x.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)


def causal_lm_loss(model: SmallThinker, params, tokens):
    """Next-token cross entropy of ``tokens [B, S + 1]``: positions
    ``0..S-1`` are read and ``1..S`` are their labels. The source's config
    has no auxiliary-loss coefficient, so there is none."""
    logits = model.apply({"params": params}, tokens[:, :-1])
    return token_cross_entropy(logits, tokens[:, 1:])
