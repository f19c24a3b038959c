"""The Mamba-2 mixer's body, owned by no decoder: ``models/granite.py`` and
``models/nemotron_h.py`` each call it from a module of their own, with
their sizes as arguments.

Dao & Gu 2024 (arXiv:2405.21060) as ``transformers`` writes
``GraniteMoeHybridMambaLayer`` and ``NemotronHMamba2Mixer``: one projection
to ``[z | xBC | dt]``; a depth-wise causal convolution of ``taps`` with a
bias and SiLU over ``xBC``; ``x [S, H, P]``, ``B`` and ``C [S, G, N]`` split
out of it (head ``h`` reads group ``h // (H / G)``); ``dt = softplus(dt +
dt_bias)``, ``A = -exp(A_log)``; the scan of ``ops/ssd.py`` from a zero
state with the skip ``D x``; an RMSNorm with a learned scale of ``y *
silu(z)``, over all the channels or in ``norm_groups`` runs of them, and the
output projection. ``inner = H x P`` is the mixer's own width and need not
be a multiple of the model's (Nemotron-H: 4,096 beside 2,688).

As what ``models/parts.py`` builds, this is a function called inside the
caller's own ``@nn.compact`` body, no module: every leaf (``in_proj``,
``conv``, ``conv_bias``, ``A_log``, ``dt_bias``, ``D``, ``norm``,
``out_proj``) keeps its name and its place in the caller's tree.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..attribution import SCOPE_SSM_CONV, SCOPE_SSM_GATE
from ..ops.linear_attention import short_conv
from ..ops.ssd import ssd_scan
from ..profiler import annotate_collective
from .parts import RMSNorm, decay_rate, projection, step_bias


def mamba2_mixer(module, cfg, x, *, heads: int, head_dim: int, state: int,
                 groups: int, taps: int, chunk: int, norm_groups: int = 1):
    """``x [B, S, hidden]`` → ``[B, S, hidden]``; ``cfg`` gives
    ``hidden_size``, ``rms_norm_eps`` and ``dtype``, ``module`` is the
    caller, whose parameters these become."""
    inner, f32 = heads * head_dim, jnp.float32
    mixed = inner + 2 * groups * state  # x, B and C pass the convolution
    z, xbc, dt = jnp.split(
        projection(cfg, inner + mixed + heads, "in_proj")(x),
        [inner, inner + mixed], axis=-1)
    # torch's Conv1d default: weights and bias uniform within
    # 1 / sqrt(taps)
    conv_init = nn.initializers.variance_scaling(
        1 / 3, "fan_in", "uniform", in_axis=-1, out_axis=-2)
    conv = module.param("conv", conv_init, (mixed, taps), f32)
    bound = taps ** -0.5
    conv_bias = module.param(
        "conv_bias", lambda key, shape, dtype: jax.random.uniform(
            key, shape, dtype, -bound, bound), (mixed,), f32)
    a_log = module.param("A_log", decay_rate, (heads,), f32)
    dt_bias = module.param("dt_bias", step_bias, (heads,), f32)
    skip = module.param("D", nn.initializers.ones, (heads,), f32)
    with annotate_collective(SCOPE_SSM_CONV):
        xbc = jax.nn.silu(short_conv(xbc, conv, conv_bias))
        inputs, b, c = jnp.split(
            xbc, [inner, inner + groups * state], axis=-1)
        steps = jax.nn.softplus(dt.astype(f32) + dt_bias)
    out = ssd_scan(
        inputs.reshape(x.shape[:2] + (heads, head_dim)), steps,
        -jnp.exp(a_log), b.reshape(x.shape[:2] + (groups, state)),
        c.reshape(x.shape[:2] + (groups, state)), skip, chunk=chunk)
    with annotate_collective(SCOPE_SSM_GATE):
        out = out.reshape(z.shape).astype(f32) * jax.nn.silu(
            z.astype(f32))
        out = RMSNorm(cfg.rms_norm_eps, norm_groups, name="norm")(
            out).astype(cfg.dtype)
    return projection(cfg, cfg.hidden_size, "out_proj")(out)

