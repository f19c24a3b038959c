"""Nemotron-H — a decoder whose layer is a mixer alone.

NVIDIA's ``NVIDIA-Nemotron-3-Nano-30B-A3B-BF16`` (``config.json``,
``model_type`` ``nemotron_h``; the family's layers: "Nemotron-H",
arXiv:2504.03624, as ``transformers`` writes ``modeling_nemotron_h.py``) is a
pre-norm causal decoder in which **every layer is one normed residual
branch**::

    x = x + Mixer_kind(RMSNorm(x))

of three kinds by the characters of ``hybrid_override_pattern``: ``M`` a
Mamba-2 mixer, ``E`` a mixture of experts, ``*`` attention. An ``E`` layer
has no token mixer and an ``M`` or ``*`` layer no feed-forward. Then a final
RMSNorm, an untied head and the mean next-token cross entropy over every
position. ``d = hidden_size`` (2,688), eps 1e-5, no bias but the
convolution's.

* **``M``** (Mamba-2, Dao & Gu 2024; ``NemotronHMamba2Mixer``; the body is
  ``models/mamba2.py``'s): ``[z | xBC | dt] = in_proj(x)`` of widths 4,096 |
  4,096 + 2·8·128 | 64; ``xBC = silu(conv1d_4(xBC) + bias)`` depth-wise and
  causal; ``x [S, 64, 64]``, ``B``, ``C [S, 8, 128]`` (head ``h`` reads group
  ``h // 8``); ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; ``h_t =
  exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t C_t + D x_t`` from a
  zero state (``ops/ssd.py``); ``g = y * silu(z)``; **an RMSNorm of ``g`` in
  8 groups of 512 channels**, each over its own mean square, times a learned
  scale ``[4096]``; ``out_proj`` 4,096 → 2,688. The mixer's width is its 64
  heads of 64, not ``expand x d``.
* **``*``**: ``q`` 2,688 → 32 × 128, ``k``, ``v`` 2,688 → 2 × 128 (sixteen
  query heads a key/value head), causal softmax attention at scale
  ``128^-1/2`` with **no positional embedding** (``NemotronHAttention``
  builds no rotary embedding; the Mamba layers order the tokens), ``o``
  4,096 → 2,688.
* **``E``**: ``s = sigmoid(x W_r)`` in float32 over 128 experts; the picks
  are the top 6 of ``s`` (``n_group`` 1, ``topk_group`` 1: no group limit);
  a pick's gate is its ``s`` over the sum of the six (``norm_topk_prob``)
  times ``routed_scaling_factor`` 2.5; ``y = sum_e gate_e W_down,e
  relu(W_up,e x)^2 + W_down,s relu(W_up,s x)^2``: every expert **two
  matrices and ``relu(.)^2``** (``mlp_hidden_act`` ``relu2``), no gate,
  routed width 1,856 and shared width 3,712. The source's selection bias
  (``e_score_correction_bias``, added to ``s`` for the choice only and moved
  by the load balancer outside the gradient) is held at its initial zero and
  is no leaf here, as ``models/kimi_linear.py``'s.

A model may hold a window of the experts (``experts_here`` from
``first_expert`` on), one chip's share of expert parallelism: the router
keeps its width and a token's gates are normalised over all six picks
wherever they live, so the shares' routed outputs, with the shared expert
counted once, add up to the whole layer's.

TPU-first choices, as the other decoders: bfloat16 activations; float32
parameters, norms, router, steps and decays; attention through the
framework's flash kernels (``attention_fn=``), keys and values with their
own 2 heads; every layer under ``parts.recomputed`` (``remat``): the same
tree, loss and gradients either way.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import flax.linen as nn
import jax.numpy as jnp

from ..attribution import (SCOPE_BLOCK_ATTN_PROJ, SCOPE_BLOCK_EMBED,
                           SCOPE_BLOCK_FFN, SCOPE_BLOCK_HEAD,
                           SCOPE_BLOCK_NORM, SCOPE_MOE_SHARED)
from ..profiler import annotate_collective
from .experts import ExpertWindow, SparseExperts
from .loss import token_cross_entropy
from .mamba2 import mamba2_mixer
from .parts import (PlainMLP, RMSNorm, dense_window_attention,
                    grouped_flash_attention, projection, recomputed, relu2,
                    untied_head)

flash_attention_fn = grouped_flash_attention  # benchmark/configs' name

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclasses.dataclass(frozen=True)
class NemotronHConfig(ExpertWindow):
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_layers: int = 52
    hybrid_override_pattern: str = PATTERN  # a character a layer
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8  # of B and C, and of the gated norm
    conv_kernel: int = 4
    chunk_size: int = 128
    num_experts: int = 128
    top_k: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    experts_here: int | None = None  # None: all from first_expert on
    first_expert: int = 0
    capacity_factor: float = 1.25
    rms_norm_eps: float = 1e-5
    remat: bool = True
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        kinds = self.kinds
        if len(kinds) != self.num_layers or set(kinds) - {
                MAMBA, EXPERTS, ATTENTION}:
            raise ValueError(
                f"hybrid_override_pattern must name {self.num_layers} "
                f"layers, each {MAMBA!r}, {EXPERTS!r} or {ATTENTION!r}; got "
                f"{self.hybrid_override_pattern!r}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads cannot share "
                f"{self.num_key_value_heads} key/value heads evenly")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError(
                f"{self.mamba_num_heads} Mamba heads do not share "
                f"{self.n_groups} groups evenly")

    @property
    def kinds(self) -> tuple:
        return tuple(self.hybrid_override_pattern)


NEMOTRON_3_NANO_30B_A3B = NemotronHConfig()
NEMOTRON_H_TINY = NemotronHConfig(  # test-sized: two of each, one attention
    vocab_size=256, hidden_size=48, num_layers=5,
    hybrid_override_pattern="MEM*E", num_attention_heads=4,
    num_key_value_heads=1, head_dim=16, mamba_num_heads=8, mamba_head_dim=8,
    ssm_state_size=16, n_groups=2, chunk_size=8, num_experts=8, top_k=2,
    moe_intermediate_size=24, moe_shared_expert_intermediate_size=40,
    capacity_factor=2.0,
)


class Mamba2Mixer(nn.Module):
    """``models/mamba2.py``'s mixer at this config's sizes: ``n_groups``
    groups of ``B`` and ``C``, and as many of the gated norm."""
    config: NemotronHConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        return mamba2_mixer(
            self, cfg, x, heads=cfg.mamba_num_heads,
            head_dim=cfg.mamba_head_dim, state=cfg.ssm_state_size,
            groups=cfg.n_groups, taps=cfg.conv_kernel, chunk=cfg.chunk_size,
            norm_groups=cfg.n_groups)


class GroupedAttention(nn.Module):
    """``attention_fn(q [B, S, H, D], k, v [B, S, KV heads, D], dtype)``
    returns the context ``[B, S, H, D]``; ``H x D`` need not be ``d``."""
    config: NemotronHConfig
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        heads, kv_heads, dim = (cfg.num_attention_heads,
                                cfg.num_key_value_heads, cfg.head_dim)

        def projected(name, count):
            return projection(cfg, count * dim, name)(x).reshape(
                x.shape[:2] + (count, dim))

        q, k, v = (projected("query", heads), projected("key", kv_heads),
                   projected("value", kv_heads))
        attend = self.attention_fn or dense_window_attention
        out = attend(q, k, v, cfg.dtype)
        return projection(cfg, cfg.hidden_size, "out")(
            out.reshape(x.shape[:2] + (heads * dim,)))


class MixerLayer(nn.Module):
    """One normed residual branch of ``kind``."""
    config: NemotronHConfig
    kind: str
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        with annotate_collective(SCOPE_BLOCK_NORM):
            normed = RMSNorm(cfg.rms_norm_eps, name="ln")(x)  # float32
            low = normed.astype(cfg.dtype)
        if self.kind == EXPERTS:
            with annotate_collective(SCOPE_BLOCK_FFN):
                # the router reads the float32 rows; the slots cast them
                out = SparseExperts(
                    cfg, activation=relu2, gates_over_picks=True,
                    scores="sigmoid", gate_scale=cfg.routed_scaling_factor,
                    width=cfg.moe_intermediate_size, gated=False,
                    name="moe")(normed)
                with annotate_collective(SCOPE_MOE_SHARED):
                    out = out + PlainMLP(
                        cfg, cfg.moe_shared_expert_intermediate_size, relu2,
                        name="shared")(low)
        else:
            with annotate_collective(SCOPE_BLOCK_ATTN_PROJ):
                if self.kind == MAMBA:
                    out = Mamba2Mixer(cfg, name="mamba")(low)
                else:
                    out = GroupedAttention(
                        cfg, self.attention_fn, name="attention")(low)
        with annotate_collective(SCOPE_BLOCK_NORM):
            return x + out


class NemotronH(nn.Module):
    """Call: ``model.apply(vars, input_ids)`` → logits ``[B, S, V]`` in
    float32. ``S`` is a multiple of ``config.chunk_size``."""

    config: NemotronHConfig = NEMOTRON_3_NANO_30B_A3B
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.config
        layer = recomputed(MixerLayer, cfg)
        with annotate_collective(SCOPE_BLOCK_EMBED):
            x = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                         param_dtype=jnp.float32,
                         name="token_embeddings")(input_ids).astype(cfg.dtype)
        for i, kind in enumerate(cfg.kinds):
            x = layer(cfg, kind, self.attention_fn, name=f"layer_{i}")(x)
        with annotate_collective(SCOPE_BLOCK_HEAD):
            return untied_head(self, x)


def causal_lm_loss(model: NemotronH, params, tokens):
    """Next-token cross entropy of ``tokens [B, S + 1]``: positions
    ``0..S-1`` are read and ``1..S`` are their labels. The source's config
    names no auxiliary loss, so there is none."""
    logits = model.apply({"params": params}, tokens[:, :-1])
    return token_cross_entropy(logits, tokens[:, 1:])
