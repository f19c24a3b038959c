"""``models/loss.py::token_cross_entropy``: the decoders' cross entropy with
its own differentiation rule, against ``-take_along_axis(log_softmax(x),
y).mean()`` differentiated by JAX, and what its jaxpr may not hold."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import profiler
from horovod_tpu.models.loss import token_cross_entropy

B, S, V = 2, 24, 97
TOLERANCE = 1e-6
SCATTERS = {"scatter", "scatter-add", "scatter_add"}


def plain(logits, labels, weights=None):
    """The expression the decoders had: JAX differentiates it."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if weights is not None:
        picked = weights * picked
    return -picked.mean()


def normal_logits(scale=3.0):
    return scale * jax.random.normal(jax.random.PRNGKey(0), (B, S, V))


def labels_of(shape=(B, S)):
    return jax.random.randint(jax.random.PRNGKey(1), shape, 0, V)


def diffusion_weights():
    """SDAR's: ``1 / t`` where a position is scored, zero elsewhere."""
    level = jax.random.uniform(jax.random.PRNGKey(2), (B, S), jnp.float32,
                               0.25, 1.0)
    scored = jax.random.uniform(jax.random.PRNGKey(3), (B, S)) < level
    return jnp.where(scored, 1.0 / level, 0.0)


def one_row(row):
    """``[1, 2, V]``: ``row`` and a row of noise beside it."""
    noise = jax.random.normal(jax.random.PRNGKey(4), (V,))
    return jnp.stack([jnp.asarray(row, jnp.float32), noise])[None]


CASES = {
    "plain": lambda: (normal_logits(), labels_of(), None),
    "weights-with-zeros": lambda: (normal_logits(), labels_of(),
                                   diffusion_weights()),
    "all-weights-zero": lambda: (normal_logits(), labels_of(),
                                 jnp.zeros((B, S))),
    "two-dimensions": lambda: (normal_logits()[0], labels_of()[0], None),
    "bfloat16-rounded": lambda: (
        normal_logits().astype(jnp.bfloat16).astype(jnp.float32),
        labels_of(), None),
    "a-row-of-equal-logits": lambda: (
        one_row(jnp.full((V,), 2.5)), labels_of((1, 2)), None),
    "one-entry-at-1e4": lambda: (
        one_row(jnp.zeros((V,)).at[5].set(1e4)), jnp.array([[5, 7]]), None),
    "the-label-far-below-1e4": lambda: (
        one_row(jnp.zeros((V,)).at[5].set(1e4)), jnp.array([[6, 7]]), None),
}


def relative(got, want):
    scale = float(jnp.abs(want).max())
    return float(jnp.abs(got - want).max()) / (scale or 1.0)


@pytest.mark.parametrize("case", CASES)
class TestAgainstLogSoftmax:
    def test_the_value(self, case):
        logits, labels, weights = CASES[case]()
        got = token_cross_entropy(logits, labels, weights)
        assert got.dtype == jnp.float32 and got.shape == ()
        assert np.isfinite(got)
        assert relative(got, plain(logits, labels, weights)) <= TOLERANCE

    @pytest.mark.parametrize("cotangent", [1.0, 3.0])
    def test_the_gradient(self, case, cotangent):
        logits, labels, weights = CASES[case]()
        got = jax.jit(jax.grad(lambda x: cotangent * token_cross_entropy(
            x, labels, weights)))(logits)
        want = jax.grad(lambda x: cotangent * plain(x, labels, weights))(
            logits)
        assert got.dtype == logits.dtype and got.shape == logits.shape
        assert np.isfinite(got).all()
        assert relative(got, want) <= TOLERANCE


class TestTheOtherArguments:
    def test_bfloat16_logits_get_a_bfloat16_gradient_of_float32_work(self):
        logits = normal_logits().astype(jnp.bfloat16)
        labels = labels_of()
        value, grad = jax.value_and_grad(token_cross_entropy)(logits, labels)
        assert value.dtype == jnp.float32 and grad.dtype == jnp.bfloat16
        want = jax.grad(plain)(logits.astype(jnp.float32), labels)
        assert relative(grad.astype(jnp.float32), want) <= 2.0 ** -8

    def test_weights_get_no_gradient(self):
        grad = jax.grad(token_cross_entropy, argnums=2)(
            normal_logits(), labels_of(), diffusion_weights())
        assert not grad.any()

    def test_rows_add_up_to_zero(self):
        """softmax − one-hot: what a row's gradient sums to."""
        grad = jax.grad(token_cross_entropy)(normal_logits(), labels_of())
        assert float(jnp.abs(grad.sum(-1)).max()) <= 1e-7


def equations(jaxpr, outer=""):
    """``(equation, name stack)`` of every equation of ``jaxpr`` and of
    what it calls, the stack with the caller's in front."""
    for eqn in jaxpr.eqns:
        stack = f"{outer}/{eqn.source_info.name_stack}"
        yield eqn, stack
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else (
                    value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from equations(inner, stack)


def is_zero(atom) -> bool:
    return hasattr(atom, "val") and not np.any(np.asarray(atom.val))


def grad_equations(rule, weights, backward_only=False):
    logits, labels = normal_logits(), labels_of()
    found = equations(jax.make_jaxpr(jax.grad(
        lambda x: rule(x, labels, weights)))(logits).jaxpr)
    return [eqn for eqn, stack in found
            if "transpose" in stack or not backward_only]


def zeros_of_the_logits_shape(eqns):
    """``{primitive that reads it}`` of every scalar zero broadcast to the
    logits' shape."""
    readers = set()
    for eqn in eqns:
        if (eqn.primitive.name == "broadcast_in_dim"
                and eqn.params["shape"] == (B, S, V)
                and is_zero(eqn.invars[0])):
            readers |= {other.primitive.name for other in eqns
                        if eqn.outvars[0] in other.invars}
    return readers


@pytest.mark.parametrize("weights", [None, diffusion_weights()],
                         ids=["plain", "weighted"])
class TestWhatTheJaxprHolds:
    def test_the_plain_expression_scatters_into_zeros(self, weights):
        """What this rule is for, so the next test cannot pass blind."""
        eqns = grad_equations(plain, weights)
        assert {eqn.primitive.name for eqn in eqns} & SCATTERS
        assert zeros_of_the_logits_shape(eqns) & SCATTERS

    def test_no_scatter_and_no_zeros_of_the_logits_shape(self, weights):
        eqns = grad_equations(token_cross_entropy, weights)
        assert not {eqn.primitive.name for eqn in eqns} & (
            SCATTERS | {"gather", "dynamic_update_slice"})
        # the forward's pick is a select against a zero inside the one
        # pass that sums the exponentials; no zero is an array of its own
        assert zeros_of_the_logits_shape(eqns) <= {"select_n"}
        assert not zeros_of_the_logits_shape(
            grad_equations(token_cross_entropy, weights, backward_only=True))

    def test_one_exponential_a_pass_and_nothing_else_transcendental(
            self, weights):
        eqns = grad_equations(token_cross_entropy, weights)
        wide = [eqn.primitive.name for eqn in eqns
                if eqn.outvars[0].aval.shape == (B, S, V)]
        assert wide.count("exp") == 2 and "log" not in wide

    def test_both_halves_are_the_heads(self, weights):
        logits, labels = normal_logits(), labels_of()
        stacks = {stack for eqn, stack in equations(jax.make_jaxpr(jax.grad(
            lambda x: token_cross_entropy(x, labels, weights)))(
                logits).jaxpr) if eqn.outvars[0].aval.shape == (B, S, V)}
        assert {profiler.owner_of(stack) for stack in stacks} == {
            "hvd.block.head"}
        assert any("transpose" in stack for stack in stacks)
        assert any("transpose" not in stack for stack in stacks)


def logits_sized_bytes_kept(shape, dtype):
    """Bytes of the residuals of a traced rule that are as large as the
    logits ``shape``, and the sizes of the rest."""
    logits = jax.ShapeDtypeStruct(shape, dtype)
    labels = jax.ShapeDtypeStruct(shape[:-1], jnp.int32)
    kept = jax.tree.leaves(jax.eval_shape(
        lambda x, y: jax.vjp(token_cross_entropy, x, y)[1], logits, labels))
    wide = [leaf for leaf in kept if leaf.size >= logits.size]
    return (sum(leaf.size * leaf.dtype.itemsize for leaf in wide),
            {leaf.size for leaf in kept if leaf not in wide})


class TestWhatTheRuleKeeps:
    @pytest.mark.parametrize("dtype, itemsize", [(jnp.float32, 4),
                                                 (jnp.bfloat16, 2)])
    def test_it_keeps_the_logits_bytes_and_nothing_else_their_size(
            self, dtype, itemsize):
        """The logits as they came, and beside them only a number or two a
        position."""
        assert logits_sized_bytes_kept((B, S, V), dtype) == (
            B * S * V * itemsize, {B * S})

    def test_smallthinkers_cell(self):
        assert logits_sized_bytes_kept((1, 16384, 18992), jnp.float32)[0] == (
            1_244_659_712)
