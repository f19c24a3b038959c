"""LFM2 — a decoder whose mixer, in three layers of four, is a doubly gated
short convolution: the framework's first layer that mixes tokens with
neither attention nor a recurrence.

LiquidAI's ``LFM2-24B-A2B`` (``config.json``, ``model_type`` ``lfm2_moe``;
the layers as ``transformers`` writes ``modeling_lfm2.py`` /
``modeling_lfm2_moe.py``) is a pre-norm causal decoder, no bias anywhere::

    x = x + Op_l(RMSNorm(x));   x = x + FFN_l(RMSNorm(x))
    logits = RMSNorm(x) E^T                             (one tied leaf E)

with ``Op_l`` by ``layer_types[l]``:

* **``conv``** (:class:`ShortConv`): ``[B | C | x] = h W_in`` (hidden → 3 x
  hidden, cut in that order); ``u = B * x``; a depth-wise causal convolution
  of ``conv_L_cache`` taps over time, zeros left of the sequence, the last
  tap on the token itself; ``y = C * conv(u)``; ``Op = y W_out``. No
  activation: both gates are plain products. The three parts are read as
  lane slices of the one ``[B, S, 3 x hidden]`` array the projection wrote
  (each a whole number of 128-lane blocks at the published width), and the
  two gates and the taps run under the phase scope ``hvd.shortconv.mix``,
  which this module alone opens.
* **``full_attention``** (:class:`GroupedAttention`): grouped-query causal
  softmax attention, heads of ``hidden / heads`` lanes; an RMSNorm over a
  head's lanes on queries and keys (one learned scale for all query heads,
  one for all key heads), then RoPE on all of a head's lanes in half-split
  pairs (``rope_theta``; ``rope_type`` ``default``: nothing rescaled).
  Through the framework's flash kernels (``attention_fn=``), keys and
  values with their own, smaller number of heads.

``FFN_l`` is SiLU-gated and dense (``intermediate_size``) in the first
``num_dense_layers`` layers; in every later one ``SparseExperts`` with
sigmoid scores, the top ``num_experts_per_tok`` of ``num_experts`` chosen by
``s + b`` (``use_expert_bias``), the gates ``s_e / (sum over the picks +
1e-6)`` (``norm_topk_prob``) times ``routed_scaling_factor``, experts
SiLU-gated of ``moe_intermediate_size``, no shared expert. The bias ``b`` is
no leaf: ``selection_bias=`` hands one in for the forward pass, a row an
expert layer; its update rule is the load balancer's and not here.

A model may hold a window of the experts (``experts_here`` from
``first_expert`` on), one chip's share of expert parallelism, as the other
mixtures here: the router keeps its width, a token's gates are normalised
over all its picks wherever they live.

TPU-first choices, as the other decoders: bfloat16 activations; float32
parameters, norms, router, rotary angles and the mixer's element-wise
arithmetic (the two gates and the taps are one fusion between two bfloat16
arrays); every layer under ``parts.recomputed``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..attribution import (SCOPE_BLOCK_ATTN_PROJ, SCOPE_BLOCK_EMBED,
                           SCOPE_BLOCK_FFN, SCOPE_BLOCK_HEAD,
                           SCOPE_BLOCK_NORM, SCOPE_SHORTCONV_MIX)
from ..ops.linear_attention import short_conv
from ..profiler import annotate_collective
from .experts import ExpertWindow, SparseExperts
from .loss import token_cross_entropy
from .parts import (GatedMLP, RMSNorm, dense_window_attention,
                    grouped_flash_attention, projection, recomputed, rope)

flash_attention_fn = grouped_flash_attention  # benchmark/configs' name

CONV, ATTENTION = "conv", "full_attention"
GATE_EPS = 1e-6  # beside the sum of a token's picked scores, the source's


@dataclasses.dataclass(frozen=True)
class Lfm2Config(ExpertWindow):
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776  # the dense layers' feed-forward
    moe_intermediate_size: int = 1536  # one expert's
    num_layers: int = 40
    layer_types: tuple | None = None  # None: attention third of every four
    num_dense_layers: int = 2
    conv_L_cache: int = 3  # the convolution's taps
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    rope_theta: float = 1000000.0  # rope_parameters.rope_theta
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    experts_here: int | None = None  # None: all from first_expert on
    first_expert: int = 0
    capacity_factor: float = 1.25
    norm_eps: float = 1e-5
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
        kinds = self.kinds
        if len(kinds) != self.num_layers or set(kinds) - {CONV, ATTENTION}:
            raise ValueError(
                f"layer_types must name {self.num_layers} layers, each "
                f"{CONV!r} or {ATTENTION!r}; got {kinds}")

    @property
    def kinds(self) -> tuple:
        if self.layer_types is not None:
            return tuple(self.layer_types)
        return tuple(ATTENTION if i % 4 == 2 else CONV
                     for i in range(self.num_layers))

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def top_k(self) -> int:
        """``ExpertWindow``'s and ``SparseExperts``' name for it."""
        return self.num_experts_per_tok

    @property
    def expert_layers(self) -> int:
        """Layers that route: the rows of a ``selection_bias``."""
        return self.num_layers - self.num_dense_layers


LFM2_24B_A2B = Lfm2Config()
LFM2_TINY = Lfm2Config(  # test-sized: the cell's five kinds, 8 heads on 2
    vocab_size=256, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=24, num_layers=5,
    layer_types=(CONV, ATTENTION, CONV, CONV, CONV), num_dense_layers=1,
    num_attention_heads=8, num_key_value_heads=2, rope_theta=10000.0,
    num_experts=8, num_experts_per_tok=2, capacity_factor=2.0,
)


class ShortConv(nn.Module):
    """``(C * conv(B * x)) W_out`` of ``[B | C | x] = h W_in``."""
    config: Lfm2Config

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        width, f32 = cfg.hidden_size, jnp.float32
        # torch's Conv1d default: uniform within 1 / sqrt(taps)
        taps = self.param("conv", nn.initializers.variance_scaling(
            1 / 3, "fan_in", "uniform", in_axis=-1, out_axis=-2),
            (width, cfg.conv_L_cache), f32)
        bcx = projection(cfg, 3 * width, "in_proj")(x)
        with annotate_collective(SCOPE_SHORTCONV_MIX):
            # lane slices of the one array, not a [B, S, 3, width] view
            gate_in, gate_out, inner = (
                bcx[..., i * width:(i + 1) * width].astype(f32)
                for i in range(3))
            mixed = (gate_out * short_conv(gate_in * inner, taps)).astype(
                cfg.dtype)
        return projection(cfg, width, "out_proj")(mixed)


class GroupedAttention(nn.Module):
    config: Lfm2Config
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        heads, kv_heads = cfg.num_attention_heads, cfg.num_key_value_heads

        def by_head(y, count):
            return y.reshape(x.shape[:2] + (count, cfg.head_dim))

        q = by_head(projection(cfg, cfg.hidden_size, "query")(x), heads)
        k = by_head(projection(cfg, kv_heads * cfg.head_dim, "key")(x),
                    kv_heads)
        v = by_head(projection(cfg, kv_heads * cfg.head_dim, "value")(x),
                    kv_heads)
        # QK-norm a head: over its 64 lanes, one learned scale for all
        # heads, before RoPE (as models/sdar.py's)
        q, k = (rope(RMSNorm(cfg.norm_eps, name=name)(y),
                     cfg.rope_theta).astype(cfg.dtype)
                for name, y in (("q_norm", q), ("k_norm", k)))
        attend = self.attention_fn or dense_window_attention
        out = attend(q, k, v, cfg.dtype)
        return projection(cfg, cfg.hidden_size, "out")(
            out.reshape(x.shape[:2] + (-1,)))


class DecoderLayer(nn.Module):
    config: Lfm2Config
    kind: str
    dense: bool
    attention_fn: Callable | None = None
    selection_bias: Any = None  # [num_experts], this layer's

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        with annotate_collective(SCOPE_BLOCK_NORM):
            n1 = RMSNorm(cfg.norm_eps, name="ln_mixer")(x).astype(cfg.dtype)
        with annotate_collective(SCOPE_BLOCK_ATTN_PROJ):
            if self.kind == CONV:
                mixed = ShortConv(cfg, name="conv")(n1)
            else:
                mixed = GroupedAttention(cfg, self.attention_fn,
                                         name="attention")(n1)
        with annotate_collective(SCOPE_BLOCK_NORM):
            x = x + mixed
            n2 = RMSNorm(cfg.norm_eps, name="ln_ffn")(x)
        if self.dense:
            with annotate_collective(SCOPE_BLOCK_FFN):
                out = GatedMLP(cfg, cfg.intermediate_size, name="mlp")(
                    n2.astype(cfg.dtype))
        else:
            out = SparseExperts(
                cfg, gates_over_picks=cfg.norm_topk_prob, scores="sigmoid",
                gate_scale=cfg.routed_scaling_factor,
                width=cfg.moe_intermediate_size,
                selection_bias=self.selection_bias, gate_eps=GATE_EPS,
                name="moe")(n2)
        with annotate_collective(SCOPE_BLOCK_NORM):
            return x + out


class Lfm2(nn.Module):
    """Call: ``model.apply(vars, input_ids [B, S])`` → logits ``[B, S, V]``
    in float32. ``selection_bias [config.expert_layers, num_experts]`` in
    float32, where given, is the routers' for the choice."""

    config: Lfm2Config = LFM2_24B_A2B
    attention_fn: Callable | None = None
    selection_bias: Any = None

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.config
        if self.selection_bias is not None and not cfg.use_expert_bias:
            raise ValueError("Lfm2: a selection_bias for a config whose "
                             "use_expert_bias is false")
        layer = recomputed(DecoderLayer, cfg)
        # nn.Embed's initialiser; one leaf, read as rows here and as the
        # head's columns below
        embedding = self.param(
            "embedding", nn.initializers.variance_scaling(
                1.0, "fan_in", "normal", out_axis=0),
            (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        with annotate_collective(SCOPE_BLOCK_EMBED):
            x = jnp.take(embedding, input_ids, axis=0).astype(cfg.dtype)
        for i, kind in enumerate(cfg.kinds):
            dense = i < cfg.num_dense_layers
            bias = None
            if not dense and self.selection_bias is not None:
                bias = self.selection_bias[i - cfg.num_dense_layers]
            x = layer(cfg, kind, dense, self.attention_fn, bias,
                      name=f"layer_{i}")(x)
        with annotate_collective(SCOPE_BLOCK_HEAD):
            # the source's embedding_norm
            x = RMSNorm(cfg.norm_eps, name="ln_out")(x).astype(cfg.dtype)
            return tied_logits(cfg, x, embedding)


def tied_logits(cfg, x, embedding):
    """``x [..., hidden]`` (normalised, ``cfg.dtype``) onto the rows of the
    embedding ``[vocab, hidden]`` itself: bf16 in, f32 out on the MXU, as
    the other decoders' heads."""
    return jax.lax.dot_general(
        x, embedding.astype(cfg.dtype), (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def causal_lm_loss(model: Lfm2, params, tokens):
    """Next-token cross entropy of ``tokens [B, S + 1]``: positions
    ``0..S-1`` are read and ``1..S`` are their labels. The source's config
    has no auxiliary-loss coefficient, so there is none."""
    logits = model.apply({"params": params}, tokens[:, :-1])
    return token_cross_entropy(logits, tokens[:, 1:])
