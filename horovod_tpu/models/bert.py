"""BERT encoder family — the framework's transformer benchmark model.

BASELINE config #3 is the reference's BERT-Large TF/Keras benchmark
(Horovod's second headline model alongside ResNet). TPU-first choices:
bfloat16 activations with float32 params/layernorm accumulation, attention
via the framework's own blockwise/flash kernels
(``horovod_tpu.ops.attention``), sequence dimension ready for the
sequence-parallel schemes in ``horovod_tpu.parallel.sequence`` (pass
``attention_fn=`` to swap in ring/Ulysses inside a sharded step).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..attribution import (SCOPE_BLOCK_ATTN_PROJ, SCOPE_BLOCK_EMBED,
                           SCOPE_BLOCK_FFN, SCOPE_BLOCK_HEAD,
                           SCOPE_BLOCK_NORM)
from ..ops.attention import flash_attention_tokens_major
from ..profiler import annotate_collective


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    dropout_rate: float = 0.1
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


BERT_BASE = BertConfig()
BERT_LARGE = BertConfig(
    hidden_size=1024, num_layers=24, num_heads=16, intermediate_size=4096
)
BERT_TINY = BertConfig(  # test-sized
    vocab_size=1024, hidden_size=64, num_layers=2, num_heads=4,
    intermediate_size=128, max_position_embeddings=128,
)


def default_attention(q, k, v, mask_bias, dtype):
    """[B, S, H, D] inputs; dense attention with an additive mask bias.

    Uses the blockwise oracle math (fp32 online softmax). ``mask_bias`` is
    [B, 1, 1, S] with 0 for visible and -1e30 for padding.
    """
    B, S, H, D = q.shape
    qt = q.transpose(0, 2, 1, 3)  # [B, H, S, D]
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    scale = 1.0 / (D ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", qt.astype(jnp.float32),
                   kt.astype(jnp.float32)) * scale
    s = s + mask_bias.astype(jnp.float32)
    p = jnp.exp(s - s.max(axis=-1, keepdims=True))
    p = p / p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vt.astype(jnp.float32))
    return out.transpose(0, 2, 1, 3).astype(dtype)


class HeadsDense(nn.Module):
    """A dense layer over attention heads that keeps them in the lanes.

    Parameters, paths, shapes and initial values are
    ``nn.DenseGeneral``'s, heads apart: ``kernel_shape`` is ``[E, H, D]``
    for a projection into heads (``contract=1``: the kernel's first
    dimension is summed over) and ``[H, D, E]`` for the one out of them
    (``contract=2``); the bias has the kernel's other dimensions. The
    product is made with the kernel merged to two dimensions (a copy of
    a parameter's size at most), so the activation on the heads' side is
    ``[B, S, H * D]``, tokens major, written and read as one array of
    dense 128-lane tiles. ``"bse,ehd->bshd"`` computes the same numbers,
    but the compiler lays its ``[B, S, H, D]`` out with the heads outside
    the tokens and copies every q, k, v and output on the way to a kernel
    that takes ``[B, S, H * D]`` (PERF.md, PR 35).
    """

    kernel_shape: tuple
    contract: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        merged = (math.prod(self.kernel_shape[:self.contract]),
                  math.prod(self.kernel_shape[self.contract:]))
        kernel = self.param(
            "kernel",
            # as nn.DenseGeneral draws it: over the merged shape
            lambda key, shape, dtype: nn.linear.default_kernel_init(
                key, merged, dtype).reshape(shape),
            self.kernel_shape, jnp.float32)
        bias = self.param("bias", nn.initializers.zeros,
                          self.kernel_shape[self.contract:], jnp.float32)
        x, kernel, bias = nn.dtypes.promote_dtype(x, kernel, bias,
                                                  dtype=self.dtype)
        return x @ kernel.reshape(merged) + bias.reshape(merged[1])


def takes_tokens_major(attention_fn) -> bool:
    """Whether an ``attention_fn`` says of itself (``tokens_major``, seen
    through ``functools.partial``) that it takes q, k, v as the
    projections write them, ``[B, S, H * D]`` with ``num_heads=``, and
    returns the same; any other is handed ``[B, S, H, D]``."""
    while isinstance(attention_fn, functools.partial):
        attention_fn = attention_fn.func
    return getattr(attention_fn, "tokens_major", False)


class SelfAttention(nn.Module):
    config: BertConfig
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, x, mask_bias, deterministic: bool):
        cfg = self.config
        into_heads = functools.partial(
            HeadsDense, (cfg.hidden_size, cfg.num_heads, cfg.head_dim), 1,
            cfg.dtype)
        q = into_heads(name="query")(x)  # [B, S, H * D]
        k = into_heads(name="key")(x)
        v = into_heads(name="value")(x)
        if takes_tokens_major(self.attention_fn):
            out = self.attention_fn(q, k, v, mask_bias, cfg.dtype,
                                    num_heads=cfg.num_heads)
        else:
            attend = self.attention_fn or default_attention
            apart = x.shape[:2] + (cfg.num_heads, cfg.head_dim)
            out = attend(q.reshape(apart), k.reshape(apart),
                         v.reshape(apart), mask_bias, cfg.dtype)
            out = out.reshape(q.shape)
        out = HeadsDense((cfg.num_heads, cfg.head_dim, cfg.hidden_size), 2,
                         cfg.dtype, name="out")(out)
        out = nn.Dropout(cfg.dropout_rate)(out, deterministic=deterministic)
        return out


class TransformerLayer(nn.Module):
    config: BertConfig
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, x, mask_bias, deterministic: bool):
        cfg = self.config
        # Post-LN (original BERT): sublayer -> residual -> LayerNorm.
        with annotate_collective(SCOPE_BLOCK_ATTN_PROJ):
            attn = SelfAttention(cfg, self.attention_fn, name="attention")(
                x, mask_bias, deterministic
            )
        with annotate_collective(SCOPE_BLOCK_NORM):
            x = nn.LayerNorm(dtype=jnp.float32, name="ln_attn")(x + attn)
            x = x.astype(cfg.dtype)
        with annotate_collective(SCOPE_BLOCK_FFN):
            h = nn.Dense(cfg.intermediate_size, dtype=cfg.dtype,
                         param_dtype=jnp.float32, name="mlp_in")(x)
            h = nn.gelu(h)
            h = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                         param_dtype=jnp.float32, name="mlp_out")(h)
            h = nn.Dropout(cfg.dropout_rate)(h, deterministic=deterministic)
        with annotate_collective(SCOPE_BLOCK_NORM):
            x = nn.LayerNorm(dtype=jnp.float32, name="ln_mlp")(x + h)
            return x.astype(cfg.dtype)


class Bert(nn.Module):
    """BERT encoder with MLM head (tied embeddings).

    Call: ``model.apply(vars, input_ids, attention_mask, token_type_ids,
    train=...)`` → ``(sequence_output [B,S,E], mlm_logits [B,S,V])``.
    """

    config: BertConfig = BERT_BASE
    attention_fn: Callable | None = None
    # Rematerialize each transformer layer in backward (jax.checkpoint):
    # activations drop from O(L * tokens * hidden) to O(tokens * hidden),
    # buying batch size at ~+1/3 forward recompute — the standard TPU
    # HBM-for-FLOPs trade.
    remat: bool = False

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, token_type_ids=None,
                 train: bool = False, masked_positions=None):
        """``masked_positions`` [B, P]: when given, the MLM head runs only
        on those positions (logits [B, P, V]) — the reference BERT
        pretraining recipe (``max_predictions_per_seq``); computing the
        [B, S, V] logits for the ~85% unmasked positions is pure waste."""
        cfg = self.config
        B, S = input_ids.shape
        if attention_mask is None:
            attention_mask = jnp.ones((B, S), jnp.int32)
        if token_type_ids is None:
            token_type_ids = jnp.zeros((B, S), jnp.int32)

        tok_emb = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                           param_dtype=jnp.float32, name="token_embeddings")
        with annotate_collective(SCOPE_BLOCK_EMBED):
            x = tok_emb(input_ids)
            x = x + nn.Embed(
                cfg.max_position_embeddings, cfg.hidden_size,
                param_dtype=jnp.float32, name="position_embeddings",
            )(jnp.arange(S)[None, :])
            x = x + nn.Embed(
                cfg.type_vocab_size, cfg.hidden_size,
                param_dtype=jnp.float32, name="type_embeddings",
            )(token_type_ids)
            x = nn.LayerNorm(dtype=jnp.float32, name="ln_emb")(x)
            x = nn.Dropout(cfg.dropout_rate)(x, deterministic=not train)
            x = x.astype(cfg.dtype)

        # Additive mask bias [B, 1, 1, S]: 0 visible, -1e30 padding.
        mask_bias = (1.0 - attention_mask[:, None, None, :].astype(
            jnp.float32)) * -1e30

        layer_cls = (
            nn.remat(TransformerLayer, static_argnums=(2,))
            if self.remat
            else TransformerLayer
        )
        for i in range(cfg.num_layers):
            x = layer_cls(cfg, self.attention_fn, name=f"layer_{i}")(
                x, mask_bias, deterministic=not train
            )

        # MLM head with tied input embeddings. The [tokens, H] @ [H, V]
        # logits matmul is ~10% of model FLOPs — run it bf16-in/f32-accum
        # on the MXU (a full-f32 matmul runs at 1/4 rate and would be the
        # single biggest line in the profile).
        with annotate_collective(SCOPE_BLOCK_HEAD):
            head_in = x
            if masked_positions is not None:
                head_in = jnp.take_along_axis(
                    x, masked_positions[..., None], axis=1
                )
            h = nn.Dense(cfg.hidden_size, dtype=cfg.dtype,
                         param_dtype=jnp.float32,
                         name="mlm_transform")(head_in)
            h = nn.gelu(h)
            h = nn.LayerNorm(dtype=jnp.float32, name="mlm_ln")(h)
            logits = jax.lax.dot_general(
                h.astype(cfg.dtype),
                tok_emb.embedding.astype(cfg.dtype),
                (((h.ndim - 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            logits = logits + self.param(
                "mlm_bias", nn.initializers.zeros, (cfg.vocab_size,),
                jnp.float32
            )
        return x, logits


def mlm_loss(logits, labels, label_mask):
    """Masked-LM cross entropy: mean over positions where label_mask == 1."""
    import jax

    with annotate_collective(SCOPE_BLOCK_HEAD):
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        mask = label_mask.astype(jnp.float32)
        return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def flash_attention_fn(q, k, v, mask_bias, dtype, interpret: bool = False,
                       *, num_heads: int):
    """Adapter plugging the Pallas flash kernels into ``Bert`` for unpadded
    batches (mask_bias all-zero). It takes q, k, v where the projections
    wrote them, ``[B, S, H * D]`` with ``num_heads=H``, and returns the
    context the same way (``tokens_major``, which ``SelfAttention`` reads):
    the kernels' block maps split the heads and nothing is transposed."""
    del mask_bias  # full-visibility batches only; padded path uses default
    return flash_attention_tokens_major(
        q, k, v, num_heads, causal=False, interpret=interpret).astype(dtype)


flash_attention_fn.tokens_major = True
