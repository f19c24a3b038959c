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
                           SCOPE_BLOCK_HEAD, SCOPE_BLOCK_NORM,
                           SCOPE_MOE_ROUTE)
from ..ops.attention import flash_attention
from ..parallel import moe
from ..profiler import annotate_collective


@dataclasses.dataclass(frozen=True)
class OlmoeConfig:
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

    @property
    def experts_held(self) -> int:
        """Experts this model holds."""
        if self.experts_here is None:
            return self.num_experts - self.first_expert
        return self.experts_here

    window = experts_held  # its name before the decoders shared SparseExperts

    def capacity(self, seq_len: int) -> int:
        return moe.expert_capacity(self.capacity_factor, seq_len, self.top_k,
                                   self.num_experts)


OLMOE_1B_7B = OlmoeConfig()
OLMOE_TINY = OlmoeConfig(  # test-sized
    vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
    intermediate_size=32, num_experts=8, top_k=2, capacity_factor=2.0,
)


class RMSNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        x = x.astype(jnp.float32)
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), -1, keepdims=True) + self.eps) * scale


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


def dense_causal_attention(q, k, v, dtype):
    """``[B, S, H, D]`` inputs; full causal softmax in float32."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / (q.shape[-1] ** 0.5)
    seq = q.shape[1]
    scores = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), scores, -1e30)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                     v.astype(jnp.float32))
    return out.astype(dtype)


def flash_attention_fn(q, k, v, dtype, interpret: bool = False,
                       block: int | None = None):
    """Adapter plugging the causal Pallas flash kernels into ``Olmoe``:
    ``[B, S, H, D]`` -> transpose -> kernel. ``block`` is for tests that
    want several tiles of a short sequence."""
    out = flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True, block_q=block, block_k=block,
        interpret=interpret)
    return out.transpose(0, 2, 1, 3).astype(dtype)


class CausalSelfAttention(nn.Module):
    config: OlmoeConfig
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, x):
        cfg = self.config

        def project(name):
            return nn.Dense(cfg.hidden_size, use_bias=False, dtype=cfg.dtype,
                            param_dtype=jnp.float32, name=name)

        heads = x.shape[:2] + (cfg.num_heads, cfg.head_dim)
        # QK-norm over the whole projection, before the split into heads.
        q = RMSNorm(cfg.rms_norm_eps, name="q_norm")(project("query")(x))
        k = RMSNorm(cfg.rms_norm_eps, name="k_norm")(project("key")(x))
        q = rope(q.reshape(heads), cfg.rope_theta).astype(cfg.dtype)
        k = rope(k.reshape(heads), cfg.rope_theta).astype(cfg.dtype)
        v = project("value")(x).reshape(heads)
        attend = self.attention_fn or dense_causal_attention
        out = attend(q, k, v, cfg.dtype)
        return project("out")(out.reshape(x.shape))


class SparseExperts(nn.Module):
    """A model's window of the experts in capacity slots, for every
    mixture of experts here: ``tokens [B, S, D] ->`` the experts' weighted
    outputs ``[B, S, D]`` (no residual), one row a routing group.
    ``config`` is the model's (``hidden_size``, ``intermediate_size``,
    ``num_experts``, ``top_k``, ``first_expert``, ``experts_held``,
    ``capacity``, ``dtype``). The router is this module's (``logits`` is
    ``None``: a float32 parameter ``router``) or the caller's, who then
    hands in its ``logits [B, S, num_experts]``. ``auxiliary(logits,
    expert)`` is a routing group's auxiliary losses, a tuple of scalars;
    their means over the groups are returned after the output."""

    config: Any
    activation: Callable = jax.nn.silu
    gates_over_picks: bool = False
    auxiliary: Callable | None = None

    @nn.compact
    def __call__(self, x, logits=None):
        cfg = self.config
        hidden, width, here = (cfg.hidden_size, cfg.intermediate_size,
                               cfg.experts_held)
        router = None
        if logits is None:
            router = self.param("router", nn.initializers.lecun_normal(),
                                (hidden, cfg.num_experts), jnp.float32)
        stacked = nn.initializers.lecun_normal(batch_axis=(0,))
        w_gate = self.param("experts_gate", stacked, (here, hidden, width),
                            jnp.float32)
        w_up = self.param("experts_up", stacked, (here, hidden, width),
                          jnp.float32)
        w_down = self.param("experts_down", stacked, (here, width, hidden),
                            jnp.float32)
        capacity = cfg.capacity(x.shape[1])
        _record_slots(here, capacity, cfg.top_k)

        def one_group(tokens, logits):
            if router is not None:
                # The router in float32 all the way: a TPU's default
                # float32 matmul is one bfloat16 pass, and a pick is a
                # discontinuity.
                with annotate_collective(SCOPE_MOE_ROUTE):
                    logits = jnp.matmul(tokens, router,
                                        precision=jax.lax.Precision.HIGHEST)
            send, expert, pos, keep, gate, counts = moe.route_to_capacity(
                tokens.astype(cfg.dtype), logits, cfg.num_experts, capacity,
                top_k=cfg.top_k, first_expert=cfg.first_expert,
                experts_here=here, gates_over_picks=self.gates_over_picks)
            back = moe.gated_expert_ffn(
                w_gate.astype(cfg.dtype), w_up.astype(cfg.dtype),
                w_down.astype(cfg.dtype), send[..., :hidden],
                activation=self.activation)
            out = moe.combine_top_k(back, expert, pos, keep, gate,
                                    cfg.first_expert)
            losses = ()
            if self.auxiliary is not None:
                with annotate_collective(SCOPE_MOE_ROUTE):
                    losses = self.auxiliary(logits, expert)
            in_window = (expert >= cfg.first_expert) & (
                expert < cfg.first_expert + here)
            return out, counts, jnp.sum(in_window & ~keep), losses

        out, counts, dropped, losses = jax.vmap(one_group)(x, logits)
        self.sow("intermediates", "routing",
                 {"load": counts.sum(0), "dropped": dropped.sum(),
                  "pairs": counts.sum() + dropped.sum()})
        if self.auxiliary is None:
            return out
        return (out,) + tuple(loss.mean() for loss in losses)


def _record_slots(experts_here: int, capacity: int, top_k: int) -> None:
    """At trace time, as ``optimizer._record_flush`` does for the wire:
    the step that runs computes this many slots a routing group."""
    from .. import metrics

    metrics.MOE_SLOTS_LAST.set(
        experts_here * capacity, experts_here=str(experts_here),
        capacity=str(capacity), top_k=str(top_k))


def load_balance_loss(logits, expert):
    """``num_experts · Σ_e f_e · P_e`` over one routing group (Shazeer et
    al. 2017 as ``modeling_olmoe.load_balancing_loss_func`` has it):
    ``f_e`` the picks that went to expert ``e`` per token, ``P_e`` the
    mean router probability of ``e``; ``expert [T, top_k]`` are the
    picks. Over all experts, whoever holds them; the picks carry no
    gradient."""
    num_experts = logits.shape[-1]
    picks = jax.nn.one_hot(expert, num_experts, dtype=jnp.float32)
    share = picks.reshape(-1, num_experts).sum(0) / logits.shape[0]
    return num_experts * jnp.sum(
        share * jax.nn.softmax(logits, -1).mean(0))


def router_z_loss(logits):
    """Mean squared log-partition of the router (Zoph et al. 2022)."""
    return jnp.mean(jnp.square(jax.nn.logsumexp(logits, -1)))


def auxiliary_losses(logits, expert):
    """A routing group's ``(load balance, router z)`` losses."""
    return load_balance_loss(logits, expert), router_z_loss(logits)


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
            x = RMSNorm(cfg.rms_norm_eps, name="ln_out")(x).astype(cfg.dtype)
            # bf16 in, f32 out on the MXU, as models/bert.py's head.
            head = self.param("lm_head", nn.initializers.lecun_normal(),
                              (cfg.hidden_size, cfg.vocab_size), jnp.float32)
            logits = jax.lax.dot_general(
                x, head.astype(cfg.dtype), (((x.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
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


def routing_stats(model, params, *inputs):
    """What the routing of a mixture of experts here (``Olmoe``,
    ``SmallThinker``, ``Sdar``) did with ``inputs``, what the model is
    called on, layer by layer: ``{"load": [layers, experts_here]`` kept
    pairs an expert, ``"dropped": [layers]`` pairs of this window past
    capacity, ``"dropped_share": [layers]`` of the window's pairs``}``.
    Run-time values, so a program of its own, without recomputation, and
    nothing the train step carries; jit it."""
    if getattr(model.config, "remat", False):
        model = model.clone(
            config=dataclasses.replace(model.config, remat=False))
    _, state = model.apply({"params": params}, *inputs,
                           mutable=["intermediates"])
    layers = [state["intermediates"][f"layer_{i}"]["moe"]["routing"][0]
              for i in range(model.config.num_layers)]
    load = jnp.stack([layer["load"] for layer in layers])
    dropped = jnp.stack([layer["dropped"] for layer in layers])
    pairs = jnp.stack([layer["pairs"] for layer in layers])
    return {"load": load, "dropped": dropped,
            "dropped_share": dropped / jnp.maximum(pairs, 1)}


def take_expert_window(params, share):
    """The parameters ``share`` holds (a model's config: its
    ``experts_held`` experts from ``first_expert`` on), cut out of the
    tree of the same model with all its experts: the stacked expert
    weights lose the other experts' rows; attention, router, norms,
    embedding and head are every window's alike."""
    first, last = share.first_expert, share.first_expert + share.experts_held
    out = dict(params)
    for i in range(share.num_layers):
        layer = dict(params[f"layer_{i}"])
        layer["moe"] = {
            name: leaf[first:last] if name.startswith("experts_") else leaf
            for name, leaf in layer["moe"].items()}
        out[f"layer_{i}"] = layer
    return out
