"""Pallas backward kernels for 1x1 convolutions (VERDICT r4 #1).

What the round-5 measurements established (tools/conv_roofline.py,
tools/step_attribution.py, docs/benchmarks.md round-5 section):

- The ResNet-50 step's backward is NOT one "31% MXU conv backward"
  blob: op-level xprof attribution splits it into conv fwd+dx (+fused
  BN stats) ~25.7 ms, filter grads ~11.6 ms, BN/elementwise ~5.8 ms,
  layout copies ~2.4 ms per 46.9 ms step.
- The filter-grad (dw) class is HBM-BANDWIDTH-bound, not MXU-bound:
  dw = x^T @ dy streams x and dy once (~257 MB for the 56x56 64->256
  shape) with a tiny [Cin, Cout] output. XLA's in-model reduce-fusions
  run it at ~57% of bandwidth peak; its standalone conv-form vjp is
  5.9x off the floor.
- This kernel runs the same contraction at ~the HBM floor (0.260 ms vs
  the 0.314 ms naive floor estimate on v5e; XLA dot-form 0.341 ms,
  conv-form vjp 1.524 ms — measured with 500-rep in-graph windows).

Why it is OPT-IN rather than wired into the flagship model: inside the
full step, XLA fuses the BN-backward algebra into the dw reductions and
picks conv-friendly tiled layouts; a custom-call kernel forces row-major
operands, so XLA inserts transposes that eat the standalone win — the
dot-form (Dense) variant of the whole model measured 0.986x of
baseline, a null result. The ~34% MFU ResNet ceiling on v5e is set by
memory-bound backward passes + layout boundaries, not by conv kernel
quality (forward convs hit 56% MFU in-model; 3x3 backward convs sit at
50-100% of their shape-imposed MXU caps in isolation).

Use :func:`conv1x1` in models whose layouts are already row-major
friendly (or whose 1x1 grads dominate); it is exact (f32 accumulation)
and tested against jax autodiff.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def _dw_kernel(x_ref, dy_ref, out_ref):
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(0) == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    out_ref[:] += lax.dot_general(
        x_ref[:], dy_ref[:],
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def dw_1x1(x2d, dy2d, tile: int = 4096, interpret: bool = False):
    """Filter gradient of a 1x1 conv as a streaming Pallas matmul.

    ``x2d [K, Cin]``, ``dy2d [K, Cout]`` (K = N*H*W, padded by the
    caller to a multiple of ``tile``) -> ``dw [Cin, Cout]`` f32. Grid
    streams K in ``tile`` rows per step (double-buffered by the Pallas
    pipeline); the [Cin, Cout] accumulator lives in VMEM across steps.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    K, ci = x2d.shape
    _, co = dy2d.shape
    if K % tile:
        pad = tile - K % tile
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
        dy2d = jnp.pad(dy2d, ((0, pad), (0, 0)))
        K += pad
    return pl.pallas_call(
        _dw_kernel,
        grid=(K // tile,),
        in_specs=[
            pl.BlockSpec((tile, ci), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, co), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((ci, co), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((ci, co), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=2 * K * ci * co,
            bytes_accessed=(K * (ci + co) * jnp.dtype(x2d.dtype).itemsize
                            + ci * co * 4),
            transcendentals=0),
        interpret=interpret,
    )(x2d, dy2d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def conv1x1(x, w, strides=(1, 1), interpret=False):
    """1x1 convolution (NHWC x [1,1,Cin,Cout]) with Pallas backward.

    Forward matches ``lax.conv_general_dilated``; backward computes
    dx as one MXU matmul (dy @ w^T) and dw with :func:`dw_1x1`
    (compiled for the TPU unless ``interpret=True``).
    """
    return _conv1x1_fwd_impl(x, w, strides)


def _conv1x1_fwd_impl(x, w, strides):
    if strides != (1, 1):
        x = x[:, ::strides[0], ::strides[1], :]
    return jnp.einsum("nhwc,cd->nhwd", x, w[0, 0],
                      preferred_element_type=jnp.float32).astype(x.dtype)


def _conv1x1_fwd(x, w, strides, interpret):
    return _conv1x1_fwd_impl(x, w, strides), (x, w)


def _conv1x1_bwd(strides, interpret, res, dy):
    x, w = res
    xs = x[:, ::strides[0], ::strides[1], :] if strides != (1, 1) else x
    N, H, W_, ci = xs.shape
    co = dy.shape[-1]
    dy2 = dy.reshape(-1, co)
    # dx on the strided view: dy @ w^T (one matmul), scattered back to
    # the full input for strided convs (zeros between taps).
    dxs = lax.dot_general(
        dy2, w[0, 0],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).reshape(N, H, W_, ci).astype(x.dtype)
    if strides != (1, 1):
        dx = jnp.zeros(x.shape, x.dtype)
        dx = dx.at[:, ::strides[0], ::strides[1], :].set(dxs)
    else:
        dx = dxs
    dw = dw_1x1(xs.reshape(-1, ci), dy2, interpret=interpret)[None, None]
    return dx, dw.astype(w.dtype)


conv1x1.defvjp(_conv1x1_fwd, _conv1x1_bwd)
