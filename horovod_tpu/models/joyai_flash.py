"""JoyAI-LLM Flash — a DeepSeek-V3-shaped decoder: latent attention with
low-rank queries and a rotary split in every layer, sigmoid-routed experts
beside a shared one, and a multi-token-prediction module that shares the
embedding and the head.

jdopensource's ``JoyAI-LLM-Flash`` (``config.json``, ``model_type``
``joyai_llm_flash``, "48B-A2.7B"; the layers are DeepSeek-V3's,
arXiv:2412.19437, as ``transformers`` writes ``modeling_deepseek_v3.py``)
is a pre-norm causal decoder, ``x = x + MLA(RMSNorm(x))``, ``x = x +
FFN(RMSNorm(x))``, no bias anywhere, a final RMSNorm and an untied head.

* **Latent attention** (``models/latent.py``) in every layer: the queries
  through a rank of ``q_lora_rank`` with a norm of their own, 192 lanes a
  head of which the last 64 are turned by RoPE (interleaved pairs,
  ``rope_theta``), as is the one ``k_r`` all heads read; the other 128 lanes
  of queries and keys, and the values, come as they are. Causal softmax
  through ``attention_fn=``.
* **Feed-forward**: SiLU-gated and dense in the first
  ``first_k_dense_replace`` layers; in every later one ``SparseExperts`` with
  sigmoid scores, the top 8 of 256 (one expert group, so no group limit), the
  gates renormalised over the picks and scaled by
  ``routed_scaling_factor``, beside a shared expert every token takes
  (``hvd.moe.shared``). The source's selection bias (``topk_method``
  ``noaux_tc``: the choice is by ``s + b``, the gate by ``s``) is no leaf:
  ``selection_bias=`` hands one in for the forward pass, a row an expert
  layer; its update rule is the load balancer's and not here.
* **The prediction module** (``num_nextn_predict_layers`` 1; the report's
  section 2.2): with ``x_L`` the main stack's output before the final norm,
  ``z_i = [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(x_L,i)] W_eh``, one more
  whole decoder layer over positions ``0..S-1``, and ``logits'_i =
  RMSNorm_s(z_i) W_head``, scored against ``t_{i+2}``. ``Emb`` and
  ``W_head`` are the main model's own leaves (``token_embeddings``,
  ``lm_head``), so their gradients hold both passes' contributions.
  Everything the module adds runs under the scope ``hvd.mtp``.

A model may hold a window of the experts (``experts_here`` from
``first_expert`` on), one chip's share of expert parallelism, as the other
mixtures here: the router keeps its width, a token's gates are normalised
over all eight picks wherever they live.

TPU-first choices, as the other decoders: bfloat16 activations; float32
parameters, norms, router and rotary tables.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import flax.linen as nn
import jax.numpy as jnp

from ..attribution import (SCOPE_BLOCK_ATTN_PROJ, SCOPE_BLOCK_EMBED,
                           SCOPE_BLOCK_FFN, SCOPE_BLOCK_HEAD,
                           SCOPE_BLOCK_NORM, SCOPE_MOE_SHARED, SCOPE_MTP)
from ..profiler import annotate_collective
from .experts import ExpertWindow, SparseExperts
from .latent import LatentAttention
from .loss import token_cross_entropy
from .parts import (GatedMLP, RMSNorm, head_leaf, head_logits,
                    head_major_flash_attention, projection, recomputed)

flash_attention_fn = head_major_flash_attention  # benchmark/configs' name


@dataclasses.dataclass(frozen=True)
class JoyAIFlashConfig(ExpertWindow):
    vocab_size: int = 129280
    hidden_size: int = 2048
    intermediate_size: int = 7168  # the dense layers' feed-forward
    moe_intermediate_size: int = 768  # one expert's, and the shared one's
    num_layers: int = 40
    first_k_dense_replace: int = 1
    num_attention_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32000000.0
    num_experts: int = 256
    top_k: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    num_nextn_predict_layers: int = 1
    mtp_loss_weight: float = 0.3  # lambda of the second term
    experts_here: int | None = None  # None: all from first_expert on
    first_expert: int = 0
    capacity_factor: float = 1.25
    rms_norm_eps: float = 1e-6
    remat: bool = True
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError(
                "num_nextn_predict_layers must be 0 or 1 (modules chained "
                f"one to the next have no path here); got "
                f"{self.num_nextn_predict_layers}")

    @property
    def expert_layers(self) -> int:
        """Layers that route, the prediction module's among them: the rows
        of a ``selection_bias``."""
        return (self.num_layers - self.first_k_dense_replace
                + self.num_nextn_predict_layers)


JOYAI_LLM_FLASH = JoyAIFlashConfig()
JOYAI_FLASH_TINY = JoyAIFlashConfig(  # test-sized: a dense layer and two
    vocab_size=256, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=24, num_layers=3, num_attention_heads=4,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, rope_theta=10000.0, num_experts=8,
    top_k=2, capacity_factor=2.0,
)


class DecoderLayer(nn.Module):
    config: JoyAIFlashConfig
    dense: bool
    attention_fn: Callable | None = None
    selection_bias: Any = None  # [num_experts], this layer's

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        with annotate_collective(SCOPE_BLOCK_NORM):
            n1 = RMSNorm(cfg.rms_norm_eps, name="ln_attn")(x).astype(
                cfg.dtype)
        with annotate_collective(SCOPE_BLOCK_ATTN_PROJ):
            mixed = LatentAttention(
                cfg, self.attention_fn, q_lora_rank=cfg.q_lora_rank,
                rope_theta=cfg.rope_theta, name="attention")(n1)
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
                width=cfg.moe_intermediate_size,
                selection_bias=self.selection_bias, name="moe")(n2)
            with annotate_collective(SCOPE_MOE_SHARED):
                out = out + GatedMLP(
                    cfg, cfg.num_shared_experts * cfg.moe_intermediate_size,
                    name="shared")(n2.astype(cfg.dtype))
        with annotate_collective(SCOPE_BLOCK_NORM):
            return x + out


class JoyAIFlash(nn.Module):
    """Call: ``model.apply(vars, input_ids [B, S], next_ids [B, S])`` →
    ``(logits, ahead)``, both ``[B, S, V]`` in float32: the main model's
    over ``input_ids`` and the prediction module's, which reads the main
    stack's output and the embeddings of ``next_ids`` (``input_ids`` moved
    on by one token) and predicts the token after those. A config without
    the module takes ``input_ids`` alone and returns ``logits`` alone.
    ``selection_bias [config.expert_layers, num_experts]`` in float32, where
    given, is the routers' for the choice, the module's row last."""

    config: JoyAIFlashConfig = JOYAI_LLM_FLASH
    attention_fn: Callable | None = None
    selection_bias: Any = None

    @nn.compact
    def __call__(self, input_ids, next_ids=None):
        cfg = self.config
        modules = cfg.num_nextn_predict_layers
        if modules and next_ids is None:
            raise ValueError("JoyAIFlash: a config with a prediction module "
                             "is called without next_ids")
        layer = recomputed(DecoderLayer, cfg)
        embed = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                         param_dtype=jnp.float32, name="token_embeddings")
        head = head_leaf(self)

        def routed(i):
            """Layer ``i``'s row of the bias (the module's: the last)."""
            if self.selection_bias is None:
                return None
            return self.selection_bias[i - cfg.first_k_dense_replace]

        def scored(x, norm):
            with annotate_collective(SCOPE_BLOCK_HEAD):
                return head_logits(cfg, RMSNorm(
                    cfg.rms_norm_eps, name=norm)(x).astype(cfg.dtype), head)

        with annotate_collective(SCOPE_BLOCK_EMBED):
            x = embed(input_ids).astype(cfg.dtype)
        for i in range(cfg.num_layers):
            dense = i < cfg.first_k_dense_replace
            x = layer(cfg, dense, self.attention_fn,
                      None if dense else routed(i), name=f"layer_{i}")(x)
        logits = scored(x, "ln_out")
        if not modules:
            return logits
        with annotate_collective(SCOPE_MTP):
            with annotate_collective(SCOPE_BLOCK_EMBED):
                joined = jnp.concatenate([
                    RMSNorm(cfg.rms_norm_eps, name="mtp_embed_norm")(
                        embed(next_ids)),
                    RMSNorm(cfg.rms_norm_eps, name="mtp_hidden_norm")(x)],
                    -1).astype(cfg.dtype)
                z = projection(cfg, cfg.hidden_size, "mtp_proj")(joined)
            z = layer(cfg, False, self.attention_fn, routed(cfg.num_layers),
                      name="mtp_layer")(z)
            return logits, scored(z, "mtp_norm")


def mtp_lm_loss(model: JoyAIFlash, params, tokens):
    """``tokens [B, S + 2]``: positions ``0..S-1`` are read; the main model
    is scored against ``1..S``, the prediction module (which also reads the
    embeddings of ``1..S``) against ``2..S+1``: ``CE + mtp_loss_weight *
    CE'``, each the mean over every position. Without the module ``tokens``
    are ``[B, S + 1]`` and the loss is the first term. The source's config
    has no auxiliary-loss coefficient, so there is none."""
    if not model.config.num_nextn_predict_layers:
        logits = model.apply({"params": params}, tokens[:, :-1])
        return token_cross_entropy(logits, tokens[:, 1:])
    logits, ahead = model.apply({"params": params}, tokens[:, :-2],
                                tokens[:, 1:-1])
    loss = token_cross_entropy(logits, tokens[:, 1:-1])
    with annotate_collective(SCOPE_MTP):
        return loss + model.config.mtp_loss_weight * token_cross_entropy(
            ahead, tokens[:, 2:])
