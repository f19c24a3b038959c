"""The one experts module of the mixtures of experts here (``Olmoe``,
``SmallThinker``, ``Sdar``, ``KimiLinear``, ``NemotronH``, ``JoyAIFlash``,
``Lfm2``),
owned by none of them: a model's window of the experts in
``parallel/moe.py``'s capacity slots, the auxiliary losses of a routing
group, and what reads or cuts a model by its experts
(:func:`routing_stats`, :func:`take_expert_window`).
"""

from __future__ import annotations

import dataclasses
from collections.abc import Mapping
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..attribution import SCOPE_MOE_ROUTE
from ..parallel import moe
from ..profiler import annotate_collective


class ExpertWindow:
    """What :class:`SparseExperts` asks of a config beside its fields
    (``num_experts``, ``top_k``, ``experts_here``, ``first_expert``,
    ``capacity_factor``); the three mixtures' configs inherit it."""

    @property
    def experts_held(self) -> int:
        """Experts this model holds: all from ``first_expert`` on where
        ``experts_here`` is None."""
        if self.experts_here is None:
            return self.num_experts - self.first_expert
        return self.experts_here

    def capacity(self, seq_len: int) -> int:
        """Slots an expert gets for one routing group of ``seq_len``
        positions (SDAR's is a row of both streams)."""
        return moe.expert_capacity(self.capacity_factor, seq_len, self.top_k,
                                   self.num_experts)


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
    their means over the groups are returned after the output. ``scores``
    and ``gate_scale`` are ``route_to_capacity``'s; ``width`` is an
    expert's where the config's ``intermediate_size`` is a dense layer's;
    ``gate_eps`` is ``route_to_capacity``'s too, the constant beside the sum
    of a token's picked sigmoid scores.
    An expert is three matrices, ``down(activation(gate x) * up x)``, or
    with ``gated=False`` two, ``down(activation(up x))``, and then the tree
    has no ``experts_gate``. ``selection_bias`` (float32
    ``[num_experts]``, no leaf) is ``route_to_capacity``'s: added to the
    sigmoid scores for the choice alone."""

    config: Any
    activation: Callable = jax.nn.silu
    gates_over_picks: bool = False
    auxiliary: Callable | None = None
    scores: str = "softmax"
    gate_scale: float = 1.0
    width: int | None = None
    gated: bool = True
    selection_bias: Any = None
    gate_eps: float = 1e-20

    @nn.compact
    def __call__(self, x, logits=None):
        cfg = self.config
        hidden, width, here = (cfg.hidden_size,
                               self.width or cfg.intermediate_size,
                               cfg.experts_held)
        router = None
        if logits is None:
            router = self.param("router", nn.initializers.lecun_normal(),
                                (hidden, cfg.num_experts), jnp.float32)
        stacked = nn.initializers.lecun_normal(batch_axis=(0,))
        shapes = {"experts_gate": (here, hidden, width),
                  "experts_up": (here, hidden, width),
                  "experts_down": (here, width, hidden)}
        if not self.gated:
            del shapes["experts_gate"]
        weights = [self.param(name, stacked, shape, jnp.float32)
                   for name, shape in shapes.items()]
        expert_ffn = moe.gated_expert_ffn if self.gated \
            else moe.plain_expert_ffn
        capacity = cfg.capacity(x.shape[1])

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
                experts_here=here, gates_over_picks=self.gates_over_picks,
                scores=self.scores, gate_scale=self.gate_scale,
                selection_bias=self.selection_bias, gate_eps=self.gate_eps)
            back = expert_ffn(
                *(w.astype(cfg.dtype) for w in weights), send[..., :hidden],
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
    layers = [layer["moe"]["routing"][0]  # a dense layer has no "moe"
              for layer in (state["intermediates"].get(f"layer_{i}", {})
                            for i in range(model.config.num_layers))
              if "moe" in layer]
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
    for at, layer in params.items():
        # a leaf, or a dense layer: every window's alike
        if not isinstance(layer, Mapping) or "moe" not in layer:
            continue
        out[at] = dict(layer, moe={
            name: leaf[first:last] if name.startswith("experts_") else leaf
            for name, leaf in layer["moe"].items()})
    return out
