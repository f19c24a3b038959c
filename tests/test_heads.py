"""``ops/heads.py::map_heads``: a function of each head of tokens-major
arrays in a loop, forward and in its own backward, against the same function
written out a head at a time in plain JAX (values and every gradient), and
what the loop is made of."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.heads import map_heads

HEADS, WIDTH, BATCH, SEQ = 6, 8, 2, 5


def arrays(seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    lanes = (BATCH, SEQ, HEADS * WIDTH)
    return dict(q=jax.random.normal(keys[0], lanes),
                k=jax.random.normal(keys[1], lanes),
                table=jax.random.normal(keys[2], (SEQ, WIDTH)),
                weight=jax.random.normal(keys[3], lanes))


def a_head(q, k, table):
    """Not linear in anything, so each gradient needs the other block."""
    return jnp.tanh(q) * k + table


def written_out(q, k, table):
    return jnp.concatenate([
        a_head(q[..., h * WIDTH:(h + 1) * WIDTH],
               k[..., h * WIDTH:(h + 1) * WIDTH], table)
        for h in range(HEADS)], -1)


def looped(q, k, table):
    return map_heads(a_head, HEADS, (q, k), constants=(table,))


class TestAgainstTheHeadsWrittenOut:
    def test_the_results_lie_side_by_side(self):
        a = arrays()
        args = [a[name] for name in ("q", "k", "table")]
        np.testing.assert_allclose(looped(*args), written_out(*args),
                                   rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("jit", [False, True], ids=["eager", "jitted"])
    def test_every_gradient(self, jit):
        a = arrays(1)

        def loss(fn, q, k):
            return (fn(q, k, a["table"]) * a["weight"]).sum()

        grad = jax.grad(loss, argnums=(1, 2))
        if jit:
            grad = jax.jit(grad, static_argnums=0)
        got = grad(looped, a["q"], a["k"])
        want = grad(written_out, a["q"], a["k"])
        for g, w in zip(got, want):
            assert g.shape == w.shape and g.dtype == w.dtype
            np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)

    def test_constants_get_no_gradient_and_need_none(self):
        a = arrays(2)
        got = jax.grad(lambda *args: looped(*args).sum(), argnums=2)(
            a["q"], a["k"], a["table"])
        assert not np.asarray(got).any()

    def test_rows_out_stacks_a_row_a_head(self):
        a = arrays(3)
        got = map_heads(lambda q, other: (q * other).sum(-1), HEADS,
                        (a["q"], a["weight"]), rows_out=True)
        want = (a["q"] * a["weight"]).reshape(
            BATCH, SEQ, HEADS, WIDTH).sum(-1).transpose(0, 2, 1)
        assert got.shape == (BATCH, HEADS, SEQ)
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

    def test_rows_out_is_differentiable_too(self):
        a = arrays(4)
        weight = jax.random.normal(jax.random.PRNGKey(9),
                                   (BATCH, HEADS, SEQ))

        def loss(q):
            return (map_heads(lambda q: jnp.sin(q).sum(-1), HEADS, (q,),
                              rows_out=True) * weight).sum()

        want = jnp.cos(a["q"]) * jnp.repeat(
            weight.transpose(0, 2, 1), WIDTH, axis=-1)
        np.testing.assert_allclose(jax.grad(loss)(a["q"]), want, rtol=1e-6,
                                   atol=1e-6)

    def test_a_result_of_another_width_and_dtype(self):
        a = arrays(4)
        got = map_heads(lambda q: q[..., :2].astype(jnp.bfloat16), HEADS,
                        (a["q"],))
        want = a["q"].reshape(BATCH, SEQ, HEADS, WIDTH)[..., :2].reshape(
            BATCH, SEQ, HEADS * 2).astype(jnp.bfloat16)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))

    def test_a_bfloat16_block_gets_a_bfloat16_gradient(self):
        q = arrays(5)["q"].astype(jnp.bfloat16)
        got = jax.grad(lambda q: map_heads(
            lambda head: (head.astype(jnp.float32) ** 2), HEADS,
            (q,)).sum())(q)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(got, np.float32),
            np.asarray((2 * q.astype(jnp.float32)).astype(jnp.bfloat16),
                       np.float32))

    @pytest.mark.parametrize("heads", [1, 3, 4, 5])
    def test_any_number_of_heads(self, heads):
        q = jax.random.normal(jax.random.PRNGKey(6), (1, 3, heads * WIDTH))
        np.testing.assert_allclose(
            map_heads(lambda head: 2 * head + 1, heads, (q,)), 2 * q + 1,
            rtol=1e-6)


class TestWhatTheLoopIsMadeOf:
    def jaxpr(self, fn):
        a = arrays()
        return jax.make_jaxpr(fn)(a["q"], a["k"], a["table"])

    def count(self, jaxpr, name):
        found = 0
        for eqn in jaxpr.eqns:
            found += eqn.primitive.name == name
            for value in eqn.params.values():
                for inner in (value if isinstance(value, (list, tuple))
                              else [value]):
                    inner = getattr(inner, "jaxpr", inner)
                    if hasattr(inner, "eqns"):
                        found += self.count(inner, name)
        return found

    def test_the_function_is_traced_once_forward_and_once_backward(self):
        """However many heads: one ``tanh`` in the forward loop's body, and
        one loop more for the gradient, with the function's own ``tanh``
        again beside its derivative."""
        assert self.count(self.jaxpr(looped).jaxpr, "tanh") == 1
        assert self.count(self.jaxpr(written_out).jaxpr, "tanh") == HEADS
        backward = self.jaxpr(jax.grad(
            lambda *args: looped(*args).sum(), argnums=(0, 1)))
        assert (self.count(backward.jaxpr, "while")
                + self.count(backward.jaxpr, "scan")) == 2
        assert 2 <= self.count(backward.jaxpr, "tanh") <= 3 < HEADS

    def test_nothing_of_a_head_is_kept_for_the_backward(self):
        """The residuals are the arguments as they were given: no array
        with a leading axis of ``HEADS`` (a stack a head) anywhere."""
        backward = self.jaxpr(jax.grad(
            lambda *args: looped(*args).sum(), argnums=(0, 1)))

        def shapes(jaxpr):
            for eqn in jaxpr.eqns:
                for var in eqn.outvars:
                    yield getattr(var.aval, "shape", ())
                for value in eqn.params.values():
                    inner = getattr(value, "jaxpr", value)
                    if hasattr(inner, "eqns"):
                        yield from shapes(inner)

        assert not [s for s in shapes(backward.jaxpr)
                    if len(s) == 4 and s[0] == HEADS]


class TestGuards:
    def test_lanes_the_heads_cannot_share_are_refused(self):
        with pytest.raises(ValueError, match="heads cannot share"):
            map_heads(lambda x: x, HEADS,
                      (jnp.zeros((1, 3, HEADS * WIDTH + 1)),))

    def test_blocks_of_different_widths_are_refused(self):
        with pytest.raises(ValueError, match="heads cannot share"):
            map_heads(lambda x, y: x, HEADS,
                      (jnp.zeros((1, 3, HEADS * WIDTH)),
                       jnp.zeros((1, 3, HEADS * 2 * WIDTH))))

    def test_through_a_partial(self):
        a = arrays(6)
        got = map_heads(functools.partial(lambda q, factor: q * factor,
                                          factor=3.0), HEADS, (a["q"],))
        np.testing.assert_array_equal(got, a["q"] * 3.0)
