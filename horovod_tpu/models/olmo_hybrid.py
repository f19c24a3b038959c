"""Olmo Hybrid — the framework's first model with linear-attention layers.

allenai's ``Olmo-Hybrid-7B`` (``config.json``, ``model_type``
``olmo_hybrid``): a causal decoder whose layers are of two kinds by a
``layer_types`` list, three ``linear_attention`` to one ``full_attention``.
Both kinds place their norms as OLMo 2 does, after the sub-layer: ``h = x +
RMSNorm(mixer(x))``, ``y = h + RMSNorm(mlp(h))``, a SiLU-gated feed-forward,
no bias anywhere, an untied head over every position.

* **Linear attention** is a gated delta net (Yang et al. 2024,
  arXiv:2412.06464, as ``flash-linear-attention`` writes the layer): the
  query, key and value projections each pass a depth-wise causal
  convolution of ``linear_conv_kernel_dim`` and SiLU; per head the query
  and key are l2-normalised (the query scaled by ``d_k ** -0.5``);
  ``beta = sigmoid(x W_b)``, doubled where ``linear_allow_neg_eigval``;
  ``g = -exp(A_log) * softplus(x W_a + dt_bias)``; the rule of
  ``ops/linear_attention.py`` from a zero state; then an RMSNorm over each
  head's output (one learned scale, shared by the heads) times ``SiLU(x
  W_g)``, and the output projection.
* **Full attention** is ``models/olmoe.py``'s block: RMSNorm over the whole
  query and key projections before the split into heads, causal softmax
  through ``attention_fn=``. ``rope_theta`` None, as the config has it,
  means no rotary embedding.

A model may hold a window of the heads (``heads_here`` from ``first_head``
on) in both kinds of layer, as ``models/olmoe.py`` holds a window of the
experts: the layout of head (tensor) parallelism, one chip's share. Its
projections are that many heads wide, its output projection gives this
window's part of the layer's output (the chips' parts add up; on one chip
there is no exchange), and the feed-forward, a width, stays whole.
:func:`take_head_window` cuts a window's parameters out of a whole model's.
One departure: under a window the QK-norm's mean square is over the heads
held, where a deployment would all-reduce one number a row.

TPU-first choices, as the other models: bfloat16 activations; float32
parameters, norms, convolution weights and gates (``A_log``, ``dt_bias``,
``g``, ``beta``).
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
                           SCOPE_LINATTN_GATE)
from ..ops.linear_attention import gated_delta_rule, short_conv
from ..profiler import annotate_collective
from .parts import (GatedMLP, RMSNorm, decay_rate, dense_causal_attention,
                    head_major_flash_attention, l2norm, projection, rope,
                    step_bias, untied_head)

flash_attention_fn = head_major_flash_attention  # benchmark/configs' name

LINEAR, FULL = "linear_attention", "full_attention"
PERIOD = (LINEAR, LINEAR, LINEAR, FULL)


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_layers: int = 32
    layer_types: tuple | None = None  # None: PERIOD, repeated
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    heads_here: int | None = None  # None: all from first_head on
    first_head: int = 0
    chunk: int = 64  # tokens a step of the scan
    rms_norm_eps: float = 1e-6
    rope_theta: float | None = None
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        heads = {self.num_attention_heads, self.num_key_value_heads,
                 self.linear_num_key_heads, self.linear_num_value_heads}
        if len(heads) != 1:
            raise ValueError(
                "OlmoHybridConfig: one head count for queries, keys and "
                "values of both kinds of layer is all this model has "
                f"(grouped heads are not supported); got {sorted(heads)}")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError(
                f"hidden_size {self.hidden_size} does not divide into "
                f"{self.num_attention_heads} heads")
        kinds = self.kinds
        if len(kinds) != self.num_layers or set(kinds) - {LINEAR, FULL}:
            raise ValueError(
                f"layer_types must name {self.num_layers} layers, each "
                f"{LINEAR!r} or {FULL!r}; got {kinds}")
        if not 0 < self.window <= self.num_attention_heads - self.first_head:
            raise ValueError(
                f"a window of {self.window} heads from {self.first_head} on "
                f"does not lie inside {self.num_attention_heads}")

    @property
    def kinds(self) -> tuple:
        if self.layer_types is not None:
            return tuple(self.layer_types)
        return tuple(PERIOD[i % len(PERIOD)] for i in range(self.num_layers))

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def window(self) -> int:
        """Heads this model holds, in either kind of layer."""
        if self.heads_here is None:
            return self.num_attention_heads - self.first_head
        return self.heads_here


OLMO_HYBRID_7B = OlmoHybridConfig()
OLMO_HYBRID_TINY = OlmoHybridConfig(  # test-sized: one period
    vocab_size=512, hidden_size=64, intermediate_size=96, num_layers=4,
    num_attention_heads=4, num_key_value_heads=4, linear_num_key_heads=4,
    linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=16, chunk=16,
)


class GatedDeltaNet(nn.Module):
    """The linear-attention mixer over this model's window of the heads."""

    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        heads, d_k, d_v = (cfg.window, cfg.linear_key_head_dim,
                           cfg.linear_value_head_dim)
        f32 = jnp.float32

        def projected(name, width):
            """``x``'s projection and the convolution's weights for it
            (torch's Conv1d default: uniform within 1 / sqrt(taps))."""
            return projection(cfg, heads * width, name)(x), self.param(
                name + "_conv", nn.initializers.variance_scaling(
                    1 / 3, "fan_in", "uniform", in_axis=-1, out_axis=-2),
                (heads * width, cfg.linear_conv_kernel_dim), f32)

        before = [projected("query", d_k), projected("key", d_k),
                  projected("value", d_v)]
        gate = projection(cfg, heads * d_v, "gate")(x)
        a_log = self.param("A_log", decay_rate, (heads,), f32)
        dt_bias = self.param("dt_bias", step_bias, (heads,), f32)
        with annotate_collective(SCOPE_LINATTN_CONV):
            q, k, v = (
                jax.nn.silu(short_conv(y, w)).reshape(
                    x.shape[:2] + (heads, -1))
                for y, w in before)
            q = (l2norm(q) * d_k ** -0.5).astype(cfg.dtype)
            k = l2norm(k).astype(cfg.dtype)
            beta = jax.nn.sigmoid(nn.Dense(
                heads, use_bias=False, dtype=f32, name="beta")(x))
            if cfg.linear_allow_neg_eigval:
                beta = 2.0 * beta
            g = -jnp.exp(a_log) * jax.nn.softplus(nn.Dense(
                heads, use_bias=False, dtype=f32, name="decay")(x) + dt_bias)
        out = gated_delta_rule(q, k, v, g, beta, chunk=cfg.chunk)
        with annotate_collective(SCOPE_LINATTN_GATE):
            out = RMSNorm(cfg.rms_norm_eps, name="o_norm")(out) \
                * jax.nn.silu(gate.astype(f32)).reshape(out.shape)
            out = out.astype(cfg.dtype).reshape(x.shape[:2] + (-1,))
        return projection(cfg, cfg.hidden_size, "out")(out)


class FullAttention(nn.Module):
    """``models/olmoe.py``'s QK-norm attention block over this model's
    window of the heads; the norms' statistic is over the heads held."""

    config: OlmoHybridConfig
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        width = cfg.window * cfg.head_dim
        heads = x.shape[:2] + (cfg.window, cfg.head_dim)
        q = RMSNorm(cfg.rms_norm_eps, name="q_norm")(
            projection(cfg, width, "query")(x)).reshape(heads)
        k = RMSNorm(cfg.rms_norm_eps, name="k_norm")(
            projection(cfg, width, "key")(x)).reshape(heads)
        if cfg.rope_theta is not None:
            q, k = rope(q, cfg.rope_theta), rope(k, cfg.rope_theta)
        v = projection(cfg, width, "value")(x).reshape(heads)
        attend = self.attention_fn or dense_causal_attention
        out = attend(q.astype(cfg.dtype), k.astype(cfg.dtype), v, cfg.dtype)
        return projection(cfg, cfg.hidden_size, "out")(
            out.reshape(x.shape[:2] + (width,)))


class HybridLayer(nn.Module):
    config: OlmoHybridConfig
    kind: str
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        with annotate_collective(SCOPE_BLOCK_ATTN_PROJ):
            if self.kind == LINEAR:
                mixed = GatedDeltaNet(cfg, name="linear_attention")(x)
            else:
                mixed = FullAttention(cfg, self.attention_fn,
                                      name="attention")(x)
        with annotate_collective(SCOPE_BLOCK_NORM):
            x = x + RMSNorm(cfg.rms_norm_eps, name="ln_mixer")(mixed).astype(
                cfg.dtype)
        with annotate_collective(SCOPE_BLOCK_FFN):
            hidden = GatedMLP(cfg, cfg.intermediate_size, name="mlp")(x)
        with annotate_collective(SCOPE_BLOCK_NORM):
            return x + RMSNorm(cfg.rms_norm_eps, name="ln_mlp")(
                hidden).astype(cfg.dtype)


class OlmoHybrid(nn.Module):
    """Call: ``model.apply(vars, input_ids)`` → logits ``[B, S, V]`` in
    float32. ``S`` is a multiple of ``config.chunk``."""

    config: OlmoHybridConfig = OLMO_HYBRID_7B
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.config
        with annotate_collective(SCOPE_BLOCK_EMBED):
            x = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                         param_dtype=jnp.float32,
                         name="token_embeddings")(input_ids).astype(cfg.dtype)
        for i, kind in enumerate(cfg.kinds):
            x = HybridLayer(cfg, kind, self.attention_fn,
                            name=f"layer_{i}")(x)
        with annotate_collective(SCOPE_BLOCK_HEAD):
            return untied_head(self, x)


def causal_lm_loss(model: OlmoHybrid, params, tokens):
    """Next-token cross entropy of ``tokens [B, S + 1]``: positions
    ``0..S-1`` are read and ``1..S`` are their labels, as
    ``models.olmoe.causal_lm_loss`` without its auxiliary losses."""
    logits = model.apply({"params": params}, tokens[:, :-1])
    with annotate_collective(SCOPE_BLOCK_HEAD):
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(
            logp, tokens[:, 1:, None], axis=-1).mean()


def take_head_window(params, whole: OlmoHybridConfig,
                     share: OlmoHybridConfig):
    """The parameters ``share`` holds (its ``heads_here`` heads from
    ``first_head`` on), cut out of the tree of ``whole``, the same model
    with all its heads. Projections lose the other heads' columns (the
    output projections their rows), the convolutions, ``A_log``,
    ``dt_bias`` and the QK-norm scales the other heads' channels;
    everything else is every window's alike."""
    def window(leaf, axis, head_width):
        return jax.lax.slice_in_dim(
            leaf, share.first_head * head_width,
            (share.first_head + share.window) * head_width, axis=axis)

    def cut(mixer, columns, channels, rows):
        mixer = dict(mixer)
        for name, width in columns.items():
            mixer[name] = {"kernel": window(mixer[name]["kernel"], 1, width)}
        for name, width in channels.items():
            mixer[name] = jax.tree.map(
                lambda leaf: window(leaf, 0, width), mixer[name])
        mixer["out"] = {"kernel": window(mixer["out"]["kernel"], 0, rows)}
        return mixer

    d_k, d_v, d = (whole.linear_key_head_dim, whole.linear_value_head_dim,
                   whole.head_dim)
    out = dict(params)
    for i, kind in enumerate(whole.kinds):
        layer = dict(params[f"layer_{i}"])
        if kind == LINEAR:
            layer["linear_attention"] = cut(
                layer["linear_attention"],
                {"query": d_k, "key": d_k, "value": d_v, "gate": d_v,
                 "beta": 1, "decay": 1},
                {"query_conv": d_k, "key_conv": d_k, "value_conv": d_v,
                 "A_log": 1, "dt_bias": 1}, d_v)
        else:
            layer["attention"] = cut(
                layer["attention"], {"query": d, "key": d, "value": d},
                {"q_norm": d, "k_norm": d}, d)
        out[f"layer_{i}"] = layer
    return out
