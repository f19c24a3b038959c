"""What the kernel families share, and no rule owns: the choice between a
kernel and its plain form, the products' dimension numbers, the running sum
inside a chunk and the walk of a chunk grid. ``ops/linear_attention.py``,
``ops/ssd.py`` and ``ops/rotary_split.py`` build on it; what is one rule's
own (its kernels' bodies, its plain form, its table of block kinds) stays
in the rule's file. It knows no rule and no instrument: a test that asks
which form a program holds reads the primitive's name in the jaxpr or the
kernel's name in the lowered text, and one that asks a kernel's plan calls
the rule's planning function.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.extend.core import Primitive
from jax.interpreters import mlir

MASKED = -1e30  # an exponent above the diagonal: exp gives 0, never a nan
NT = (((1,), (1,)), ((), ()))  # a @ b^T
TN = (((0,), (0,)), ((), ()))  # a^T @ b
NN = (((1,), (0,)), ((), ()))  # a @ b


def dot(left, right, dims=NN):
    """A kernel's product of two blocks, accumulated in float32."""
    return lax.dot_general(left, right, dims,
                           preferred_element_type=jnp.float32)


def where_lowered(name, results, by_kernel, plain):
    """The primitive ``name`` whose lowering for a TPU is ``by_kernel`` and
    for any other platform ``plain`` (``by_kernel`` interpreted where the
    tests say ``interpret``), each called with the primitive's parameters:
    the lowering platform is what the code can observe, and a trace does
    not know it (``benchmark/aot.py`` lowers for a v5e from a CPU;
    ``jax.default_backend()`` would say ``cpu`` there). Only the chosen form
    is ever traced. ``results(*avals, **how)`` are the results' abstract
    values."""
    primitive = Primitive(name)
    primitive.multiple_results = True
    primitive.def_abstract_eval(results)

    @functools.cache
    def alone(**how):  # called outside any trace
        return jax.jit(functools.partial(primitive.bind, **how))

    primitive.def_impl(lambda *xs, **how: alone(**how)(*xs))

    def lowering(on_tpu):
        def form(*xs, interpret, **how):
            return (by_kernel if on_tpu or interpret else plain)(
                *xs, interpret=interpret, **how)

        return mlir.lower_fun(form, multiple_results=True)

    mlir.register_lowering(primitive, lowering(True), platform="tpu")
    mlir.register_lowering(primitive, lowering(False))
    return primitive


def running_sum(spec, batch, chunk: int):
    """The running sum inside each chunk of ``chunk`` rows as one float32
    product with the ``[C, C]`` lower triangle of ones at
    ``Precision.HIGHEST`` (float32's sum in another order), as the einsum
    ``spec`` says: its first operand the triangle under the product's batch
    dimensions ``batch``, its second what is summed. As ``jnp.cumsum`` over
    a chunk's rows the sum is a ``reduce-window``, which the v5e runs at a
    fourteenth of its memory's pace (50 ms of Kimi Linear's step where the
    products are 11; 0.8 ms a call of the state-space scan's at 8,192
    tokens). The triangle is broadcast over ``batch`` and XLA never writes
    it out: a recomputed layer's policy keeps every product without a batch
    dimension, and the sums would stay, 134 MB a layer. A product's batch
    dimensions lead its result, so ``spec`` decides where the sums lie in
    HBM: the callers choose theirs for what their kernels read.

    Returns the sum as a function of the second operand: the triangle is
    traced here, before the caller forms that operand."""
    f32 = jnp.float32
    ones = jnp.broadcast_to(jnp.tril(jnp.ones((chunk, chunk), f32)),
                            tuple(batch) + (chunk, chunk))
    return lambda x: jnp.einsum(spec, ones, x, precision=lax.Precision.HIGHEST,
                                preferred_element_type=f32)


def chunk_grid_call(kernel, kinds, operands, results, scratch, *, grid,
                    turned, interpret, name):
    """``kernel`` as the Pallas call ``name`` over ``grid = (B, chunks or
    groups of them, H / step)``, the heads innermost. ``operands`` and
    ``results`` are ``(kind, array or dtype)`` and ``kinds`` the caller's
    table of them: a kind is ``(the array's shape as the kernel takes it,
    a block's shape, the block's index from the grid's (i, n, h))``, and an
    operand is reshaped to its kind's shape on the way in. ``turned``: the chunks last to first
    (a backward pass: ``n`` counts down from the last chunk). ``scratch``
    are the caller's scratch shapes."""
    def at(n):
        return grid[1] - 1 - n if turned else n

    def spec(kind):
        _, block, index = kinds[kind]
        return pl.BlockSpec(block, lambda i, n, h: index(i, at(n), h))

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec(kind) for kind, _ in operands],
        out_specs=[spec(kind) for kind, _ in results],
        out_shape=[jax.ShapeDtypeStruct(kinds[kind][0], dtype)
                   for kind, dtype in results],
        scratch_shapes=scratch,
        interpret=interpret,
        name=name,
    )(*(x.reshape(kinds[kind][0]) for kind, x in operands))
