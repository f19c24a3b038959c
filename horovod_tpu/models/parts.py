"""What the decoders share beside their mixers, owned by none of them:
the norm (over all channels or in groups), the l2-norm, the projection, the
gated and the plain feed-forward, RoPE in its two forms, the dense attention
fallbacks, the two adapters onto the flash kernels, the state-space
initialisers, the recomputation policy and the untied head.

``models/olmoe.py``, ``olmo_hybrid.py``, ``smallthinker.py``, ``sdar.py``,
``granite.py``, ``kimi_linear.py``, ``nemotron_h.py``, ``joyai_flash.py`` and
``lfm2.py`` import from here, from ``models/mamba2.py``, ``models/latent.py``,
``models/experts.py`` and ``models/loss.py``, never from one another (``tests/test_decoder_imports.py``). What builds
parameters here is a function called inside the model's own ``@nn.compact``
body, not a module of its own, so every leaf keeps its name and its place
in the tree.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import flash_attention, flash_attention_tokens_major
from ..ops.heads import map_heads


class RMSNorm(nn.Module):
    """``x`` in float32 over its mean square, times a learned scale a
    channel. With ``groups`` the channels are cut into that many equal
    runs, each over its own mean square (a Mamba-2 mixer's gated norm where
    ``B`` and ``C`` come in groups); the scale stays one leaf of all the
    channels."""
    eps: float
    groups: int = 1

    @nn.compact
    def __call__(self, x):
        x = x.astype(jnp.float32)
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        if self.groups == 1:
            return x * jax.lax.rsqrt(
                jnp.mean(jnp.square(x), -1, keepdims=True) + self.eps) * scale
        runs = x.reshape(x.shape[:-1] + (self.groups, -1))
        runs = runs * jax.lax.rsqrt(
            jnp.mean(jnp.square(runs), -1, keepdims=True) + self.eps)
        return runs.reshape(x.shape) * scale


def l2norm(x, eps: float = 1e-6):
    """``x`` in float32 over its last axis' Euclidean length: a delta
    rule's queries and keys, a head at a time."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


def projection(cfg, features: int, name: str):
    """The decoders' bias-free projection: a float32 kernel, ``cfg.dtype``
    out."""
    return nn.Dense(features, use_bias=False, dtype=cfg.dtype,
                    param_dtype=jnp.float32, name=name)


class GatedMLP(nn.Module):
    """The SiLU-gated feed-forward of ``width``: ``down(silu(gate(x)) *
    up(x))``. A dense layer's, and a shared expert's."""
    config: Any
    width: int

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        hidden = jax.nn.silu(projection(cfg, self.width, "gate")(x)) \
            * projection(cfg, self.width, "up")(x)
        return projection(cfg, cfg.hidden_size, "down")(hidden)


def relu2(x):
    """``relu(x) ** 2``, Nemotron-H's ``relu2``; zero stays zero."""
    return jnp.square(jax.nn.relu(x))


class PlainMLP(nn.Module):
    """The ungated feed-forward of ``width``: ``down(activation(up(x)))``,
    two matrices. A shared expert's where the experts have no gate."""
    config: Any
    width: int
    activation: Callable = relu2

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        return projection(cfg, cfg.hidden_size, "down")(
            self.activation(projection(cfg, self.width, "up")(x)))


def head_leaf(model):
    """The head's own leaf ``lm_head`` of ``model``, ``[hidden, vocab]`` in
    float32: made once in its ``@nn.compact`` body, by whoever projects onto
    the vocabulary more than once (``models/joyai_flash.py``: the main pass
    and the prediction module)."""
    cfg = model.config
    return model.param("lm_head", nn.initializers.lecun_normal(),
                       (cfg.hidden_size, cfg.vocab_size), jnp.float32)


def head_logits(cfg, x, head):
    """``x [..., hidden]`` (normalised, ``cfg.dtype``) onto the vocabulary:
    bf16 in, f32 out on the MXU, as ``models/bert.py``'s head."""
    return jax.lax.dot_general(
        x, head.astype(cfg.dtype), (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def untied_head(model, x):
    """Final norm (``ln_out``), then the head's own leaf ``lm_head``.
    Called from ``model``'s ``@nn.compact`` body, under the scope the
    caller opened."""
    cfg = model.config
    x = RMSNorm(cfg.rms_norm_eps, name="ln_out")(x).astype(cfg.dtype)
    return head_logits(cfg, x, head_leaf(model))


def rope(x, theta: float, positions=None):
    """Rotary position embedding of ``x [B, S, H, D]`` in float32, the
    half-split form (``rotate_half``): lane ``i`` pairs with ``i + D/2``.
    ``positions`` (``[S]`` or ``[B, S]``) are the position ids where they
    are not ``0..S-1``: a stream that holds two sequences side by side
    (``models/sdar.py``) counts each from zero."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if positions is None:
        positions = jnp.arange(x.shape[1], dtype=jnp.float32)
    angle = positions.astype(jnp.float32)[..., None] * inv_freq
    cos, sin = jnp.cos(angle)[..., None, :], jnp.sin(angle)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


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


def dense_causal_attention(q, k, v, dtype):
    """``[B, S, H, D]`` inputs; full causal softmax in float32."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / (q.shape[-1] ** 0.5)
    seq = q.shape[1]
    scores = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), scores, -1e30)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                     v.astype(jnp.float32))
    return out.astype(dtype)


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


def head_major_flash_attention(q, k, v, dtype, interpret: bool = False,
                               block: int | None = None,
                               head_major: bool = False):
    """Adapter plugging the causal Pallas flash kernels into ``Olmoe`` and
    ``OlmoHybrid`` (their ``flash_attention_fn``): ``[B, S, H, D]`` ->
    transpose -> kernel. ``block`` is for tests that want several tiles of
    a short sequence. ``head_major``: ``q``, ``k``, ``v`` come ``[B, H, S,
    D]`` as the kernels read them (``models/latent.py``'s one pass writes
    them so, which it may because the adapter says ``head_major`` of
    itself); the context comes back ``[B, S, H, D]`` either way."""
    if not head_major:
        q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out = flash_attention(q, k, v, causal=True, block_q=block, block_k=block,
                          interpret=interpret)
    return out.transpose(0, 2, 1, 3).astype(dtype)


head_major_flash_attention.head_major = True


def takes_head_major(attention_fn) -> bool:
    """Whether an ``attention_fn`` says of itself (``head_major``, seen
    through ``functools.partial``) that it takes ``head_major=True`` with
    q, k, v as ``[B, H, S, D]``; any other is handed ``[B, S, H, D]``."""
    while isinstance(attention_fn, functools.partial):
        attention_fn = attention_fn.func
    return getattr(attention_fn, "head_major", False)


def grouped_flash_attention(q, k, v, dtype, window=None,
                            interpret: bool = False,
                            block: int | None = None):
    """Adapter plugging the causal Pallas flash kernels into
    ``SmallThinker`` and ``Granite`` (their ``flash_attention_fn``), the
    keys and values with their own, smaller number of heads; a layer's
    layout follows its kind. **A windowed layer's** ``[B, S, heads, D]`` is
    ``[B, S, heads * D]`` as the projections wrote it, and the tokens-major
    entry takes that: heads of whole 128-lane blocks reach the kernels
    where they lie (narrower ones, ``models/granite.py``'s 64, are
    transposed inside the entry). **A layer of full attention** is
    transposed to ``[B, heads, S, D]`` here, as every layer was until PR
    40: fed tokens-major the kernels take 3 to 8% longer (a tile is 32
    pieces of 4 KB where it was 128 KB in a row), a full layer computes
    twice a windowed layer's tiles, and two such layers took 202.8 ms where
    these take 192.6 (windowed ones with RoPE 109.5 against 118.1: PERF.md,
    PR 40). ``block`` is for tests that want several tiles of a short
    sequence."""
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


def decay_rate(key, shape, dtype=jnp.float32):
    """``A_log`` of a gated delta net or a Mamba-2 mixer: the log of a rate
    drawn from (1, 16)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def step_bias(key, shape, dtype=jnp.float32):
    """``dt_bias``: softplus's inverse of a step drawn log-uniformly from
    (0.001, 0.1), so that at the seed ``g`` is about ``-rate x step``."""
    step = jnp.exp(jax.random.uniform(
        key, shape, dtype, math.log(1e-3), math.log(1e-1)))
    return step + jnp.log(-jnp.expm1(-step))


def save_kernels_and_projections(prim, *args, **params) -> bool:
    """The ``jax.checkpoint`` policy of a recomputed layer. Beside its
    input the forward pass keeps what a Pallas kernel returned (the only
    kernel of a layer's forward pass is the flash forward kernel, whose
    output and log-sum-exp are the residuals the dq and dkv kernels want,
    so it never runs again) and the results of the matrix products without
    a batch dimension (the four attention projections and the router:
    0.2 GiB a layer at 16,384 tokens for 4 ms of recomputation each).
    Norms, RoPE, the slots' gathers, the experts' batched products and the
    combine are computed again."""
    return prim.name == "pallas_call" or (
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable(
            prim, *args, **params))


def recomputed(layer, cfg):
    """``layer`` (a module class), under ``nn.remat`` with the policy above
    where ``cfg.remat`` says so: the same parameter tree, loss and
    gradients either way."""
    if not cfg.remat:
        return layer
    return nn.remat(layer, policy=save_kernels_and_projections)
