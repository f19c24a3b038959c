"""OLMoE — the framework's decoder and its sparse-expert model.

Muennighoff et al. 2024 (arXiv:2409.02060; ``transformers``'
``modeling_olmoe.py``): a pre-norm causal decoder whose every layer is
plain multi-head attention with RoPE and an RMSNorm over the whole query
and key projections (QK-norm), then a mixture of SiLU-gated experts: a
float32 router over ``num_experts``, ``top_k`` picks a token whose gates
are the softmax's own (not renormalised), no shared expert. The head is
untied and reads every position.

TPU-first choices, as ``models/bert.py``: bfloat16 activations with float32
parameters, norms, RoPE and router; attention through the framework's
flash kernels (``attention_fn=``); the experts through
``parallel/moe.py``'s capacity slots, because XLA wants static shapes:
each sequence is one routing group, and an expert takes at most
``ceil(capacity_factor · S · top_k / num_experts)`` (token, pick) pairs of
it. OLMoE was trained dropless; a pair past capacity adds nothing here.

A model may hold a window of the experts (``experts_here`` from
``first_expert`` on): the layout of expert parallelism, one chip's share.
The router keeps its width, and pairs routed outside the window add
nothing on this chip.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..attribution import (SCOPE_BLOCK_ATTN_PROJ, SCOPE_BLOCK_EMBED,
                           SCOPE_BLOCK_HEAD, SCOPE_BLOCK_NORM)
from ..profiler import annotate_collective
from .experts import ExpertWindow, SparseExperts, auxiliary_losses
from .parts import (RMSNorm, dense_causal_attention,
                    head_major_flash_attention, projection, rope, untied_head)

flash_attention_fn = head_major_flash_attention  # benchmark/configs' name


@dataclasses.dataclass(frozen=True)
class OlmoeConfig(ExpertWindow):
    vocab_size: int = 50304
    hidden_size: int = 2048
    num_layers: int = 16
    num_heads: int = 16
    intermediate_size: int = 1024  # one expert's width
    num_experts: int = 64
    top_k: int = 8
    experts_here: int | None = None  # None: all of them
    first_expert: int = 0
    capacity_factor: float = 1.25
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    load_balance_coef: float = 0.01
    router_z_coef: float = 0.001
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


OLMOE_1B_7B = OlmoeConfig()
OLMOE_TINY = OlmoeConfig(  # test-sized
    vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
    intermediate_size=32, num_experts=8, top_k=2, capacity_factor=2.0,
)


class CausalSelfAttention(nn.Module):
    config: OlmoeConfig
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        width = cfg.hidden_size
        heads = x.shape[:2] + (cfg.num_heads, cfg.head_dim)
        # QK-norm over the whole projection, before the split into heads.
        q = RMSNorm(cfg.rms_norm_eps, name="q_norm")(
            projection(cfg, width, "query")(x))
        k = RMSNorm(cfg.rms_norm_eps, name="k_norm")(
            projection(cfg, width, "key")(x))
        q = rope(q.reshape(heads), cfg.rope_theta).astype(cfg.dtype)
        k = rope(k.reshape(heads), cfg.rope_theta).astype(cfg.dtype)
        v = projection(cfg, width, "value")(x).reshape(heads)
        attend = self.attention_fn or dense_causal_attention
        out = attend(q, k, v, cfg.dtype)
        return projection(cfg, width, "out")(out.reshape(x.shape))


class DecoderLayer(nn.Module):
    config: OlmoeConfig
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        with annotate_collective(SCOPE_BLOCK_NORM):
            n1 = RMSNorm(cfg.rms_norm_eps, name="ln_attn")(x).astype(
                cfg.dtype)
        with annotate_collective(SCOPE_BLOCK_ATTN_PROJ):
            attn = CausalSelfAttention(cfg, self.attention_fn,
                                       name="attention")(n1)
        with annotate_collective(SCOPE_BLOCK_NORM):
            x = x + attn
            n2 = RMSNorm(cfg.rms_norm_eps, name="ln_moe")(x)
        out, balance, z = SparseExperts(
            cfg, auxiliary=auxiliary_losses, name="moe")(n2)
        with annotate_collective(SCOPE_BLOCK_NORM):
            return x + out, balance, z


class Olmoe(nn.Module):
    """Call: ``model.apply(vars, input_ids)`` → ``(logits [B, S, V] in
    float32, load-balance loss, router z-loss)``, the two auxiliary losses
    averaged over the layers."""

    config: OlmoeConfig = OLMOE_1B_7B
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.config
        with annotate_collective(SCOPE_BLOCK_EMBED):
            x = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                         param_dtype=jnp.float32,
                         name="token_embeddings")(input_ids).astype(cfg.dtype)
        balance = z = 0.0
        for i in range(cfg.num_layers):
            x, layer_balance, layer_z = DecoderLayer(
                cfg, self.attention_fn, name=f"layer_{i}")(x)
            balance, z = balance + layer_balance, z + layer_z
        with annotate_collective(SCOPE_BLOCK_HEAD):
            logits = untied_head(self, x)
        return logits, balance / cfg.num_layers, z / cfg.num_layers


def causal_lm_loss(model: Olmoe, params, tokens):
    """Next-token cross entropy of ``tokens [B, S + 1]`` (positions
    ``0..S-1`` are read, ``1..S`` are their labels, so every position the
    model computes has one) plus the paper's two auxiliary losses."""
    cfg = model.config
    logits, balance, z = model.apply({"params": params}, tokens[:, :-1])
    with annotate_collective(SCOPE_BLOCK_HEAD):
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        return (-picked.mean() + cfg.load_balance_coef * balance
                + cfg.router_z_coef * z)
