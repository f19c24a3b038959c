"""``ops/linear_attention.py`` against its definition: the chunk-parallel
gated delta rule is the token-by-token recurrence, values and gradients,
for every chunk size, with ``beta`` up to 2 and with strong and weak
decay; its solve by block doubling is ``triangular_solve``, values and
both gradients, and leaves no ``triangular_solve`` in the program; the
loop's left operands are rounded once, outside it; the short convolution
is causal; a ragged sequence is refused; the scope and
the gauge are there."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import profiler
from horovod_tpu.ops import linear_attention
from traced import loop_trips

B, S, H, DK, DV = 2, 128, 3, 8, 16


def recurrence(q, k, v, g, beta):
    """The definition, one token at a time, all in float32."""
    def one_token(state, xs):
        q, k, v, g, beta = xs                       # [B, H, ...]
        state = jnp.exp(g)[..., None, None] * state
        seen = jnp.einsum("bhkv,bhk->bhv", state, k)
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", beta[..., None] * k, v - seen)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q)

    state = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]))
    _, out = jax.lax.scan(one_token, state, jax.tree.map(
        lambda x: jnp.moveaxis(x, 1, 0), (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1)


def inputs(decay: str, seed: int = 0):
    """``decay``: per-token log decays around -1 (a chunk forgets what
    entered it: exp(-64)) or around -0.003 (it keeps nearly all)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(keys[0], (B, S, H, DK))
    k = jax.random.normal(keys[1], (B, S, H, DK))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * DK ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (B, S, H, DV))
    scale = {"strong": 1.0, "weak": 0.003}[decay]
    g = -scale * jax.random.uniform(keys[3], (B, S, H), minval=0.5,
                                    maxval=2.0)
    # (0, 2), both ends reached: negative eigenvalues are allowed
    beta = 2.0 * jax.random.uniform(keys[4], (B, S, H))
    beta = beta.at[:, ::7].set(1.999).at[:, 3::11].set(1e-3)
    return q, k, v, g, beta


@pytest.mark.parametrize("decay", ["strong", "weak"])
@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_chunk_form_is_the_recurrence(chunk, decay):
    args = inputs(decay)
    want = recurrence(*args)
    got = jax.jit(linear_attention.gated_delta_rule,
                  static_argnames="chunk")(*args, chunk=chunk)
    assert got.shape == (B, S, H, DV) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("decay", ["strong", "weak"])
@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_chunk_forms_gradients_are_the_recurrences(chunk, decay):
    args = inputs(decay, seed=1)
    weight = jax.random.normal(jax.random.PRNGKey(9), (B, S, H, DV))

    def scalar(rule):
        return lambda *a: jnp.sum(rule(*a) * weight)

    want = jax.jit(jax.grad(scalar(recurrence), argnums=range(5)))(*args)
    got = jax.jit(jax.grad(scalar(
        lambda *a: linear_attention.gated_delta_rule(*a, chunk=chunk)),
        argnums=range(5)))(*args)
    for name, g, w in zip("q k v g beta".split(), got, want):
        assert float(jnp.abs(w).max()) > 0, name
        np.testing.assert_allclose(
            g, w, rtol=0, atol=1e-4 * float(jnp.abs(w).max()), err_msg=name)


def systems(chunk: int, keys: str):
    """Five systems ``(I + A) X = rhs`` as the rule builds them from unit
    keys: ``A_ij = beta_i (k_i . k_j)`` below the diagonal. ``random``
    keys with ``beta`` in (0, 2); all keys ``equal`` with ``beta`` = 2,
    where every entry of ``A`` is 2 and the powers of ``A`` grow without
    bound (the inverse's entries stay +-2)."""
    key = jax.random.PRNGKey(chunk)
    if keys == "random":
        k = jax.random.normal(key, (5, chunk, 8))
        beta = 2.0 * jax.random.uniform(jax.random.fold_in(key, 1),
                                        (5, chunk, 1))
    else:
        k = jnp.broadcast_to(jax.random.normal(key, (5, 1, 8)), (5, chunk, 8))
        beta = jnp.full((5, chunk, 1), 2.0)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    a = jnp.tril(beta * jnp.einsum("nic,njc->nij", k, k), -1)
    rhs = jax.random.normal(jax.random.fold_in(key, 2), (5, chunk, 12))
    weight = jax.random.normal(jax.random.fold_in(key, 3), (5, chunk, 12))
    return a, rhs, weight


def substitution(a, rhs):
    return jax.lax.linalg.triangular_solve(
        a, rhs, left_side=True, lower=True, unit_diagonal=True)


@pytest.mark.parametrize("keys", ["random", "equal"])
@pytest.mark.parametrize("chunk", [16, 32, 48, 64])
def test_the_block_doubling_solve_is_forward_substitution(chunk, keys):
    a, rhs, weight = systems(chunk, keys)

    def close(got, want, what):
        np.testing.assert_allclose(
            got, want, rtol=0, atol=1e-5 * float(jnp.abs(want).max()),
            err_msg=what)

    close(linear_attention.solve_unit_lower(a, rhs), substitution(a, rhs),
          "X")
    got = jax.grad(lambda a, rhs: jnp.sum(
        linear_attention.solve_unit_lower(a, rhs) * weight), (0, 1))(a, rhs)
    want = jax.grad(lambda a, rhs: jnp.sum(
        substitution(a, rhs) * weight), (0, 1))(a, rhs)
    # (substitution's rule also fills the triangle the solve never reads)
    close(got[0], jnp.tril(want[0], -1), "the gradient to A")
    close(got[1], want[1], "the gradient to rhs")


@pytest.mark.parametrize("program", ["forward", "gradient"])
def test_the_solve_is_products_under_the_scope_and_no_substitution(program):
    """No ``triangular_solve`` in the program. The inverse's levels are
    float32 multiply-adds; the one product that applies it, and the
    gradient's two under ``transpose``, are float32 at the highest
    precision (the rule's other products are at the default), all under
    the scope the readers sum."""
    def rule(*a):
        return jnp.sum(linear_attention.gated_delta_rule(*a, chunk=32))

    fn = rule if program == "forward" else jax.grad(rule, argnums=range(5))
    # (the primitive by its name; on this backend it lowers to LAPACK's)
    assert "triangular_solve" not in str(jax.make_jaxpr(fn)(*inputs("weak")))
    text = jax.jit(fn).lower(*inputs("weak")).as_text(debug_info=True)
    assert "stablehlo.custom_call" not in text
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    lines = [line for line in text.splitlines()
             if "stablehlo.dot_general" in line and "HIGHEST" in line]
    assert all("f32" in line and "bf16" not in line for line in lines)
    exact = [names[re.search(r"loc\((#loc\d+)\)$", line).group(1)]
             for line in lines]
    assert all("hvd.linattn.scan" in name for name in exact), exact
    backward = [name for name in exact if "transpose(" in name]
    assert len(exact) - len(backward) == 1
    assert len(backward) == (2 if program == "gradient" else 0)


@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_the_loops_left_operands_are_rounded_once_outside_it(chunk):
    """The scan takes ``W``, the chunk's scores and the decayed ``Q`` and
    ``K`` already in the compute type (beside ``U`` and the chunk's decay
    in float32), and an iteration casts none of them again: it rounds only
    what follows from the state. A cast commutes with the slice the scan
    takes, so the values are those of a cast made in every iteration."""
    q, k, v, g, beta = inputs("weak", seed=4)
    args = tuple(x.astype(jnp.bfloat16) for x in (q, k, v)) + (g, beta)
    program = jax.make_jaxpr(
        lambda *a: linear_attention.gated_delta_rule(*a, chunk=chunk))(*args)
    scan, = (e for e in program.eqns if e.primitive.name == "scan")
    fixed = scan.params["num_consts"] + scan.params["num_carry"]
    assert [str(x.aval.dtype) for x in scan.invars[fixed:]] == [
        "float32"] + ["bfloat16"] * 4 + ["float32"]
    body = scan.params["jaxpr"].jaxpr
    sliced = set(body.invars[fixed:])
    casts = [e for e in body.eqns if e.primitive.name == "convert_element_type"]
    assert casts and not any(e.invars[0] in sliced for e in casts)


def test_bfloat16_operands_keep_a_float32_state():
    """The compute type's rounding is in the products' operands only: in
    bfloat16 the output is within a few bfloat16 ulps of the float32
    recurrence's, however many chunks the state crosses."""
    args = inputs("weak", seed=2)
    want = recurrence(*args)
    q, k, v, g, beta = args
    got = linear_attention.gated_delta_rule(
        q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
        v.astype(jnp.bfloat16), g, beta, chunk=32)
    assert got.dtype == jnp.bfloat16
    off = jnp.abs(got.astype(jnp.float32) - want).max() / jnp.abs(want).max()
    assert float(off) < 0.03, float(off)


def test_a_ragged_sequence_is_refused():
    q, k, v, g, beta = (x[:, :100] for x in inputs("weak"))
    with pytest.raises(ValueError, match="no multiple of the chunk of 64"):
        linear_attention.gated_delta_rule(q, k, v, g, beta)


def test_short_conv_is_the_published_one_and_sees_no_future_token():
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (2, 12, 5))
    w = jax.random.normal(jax.random.fold_in(key, 1), (5, 4))
    y = linear_attention.short_conv(x, w)
    want = np.zeros((2, 12, 5), np.float32)
    for t in range(12):
        for i in range(4):
            if t - 3 + i >= 0:
                want[:, t] += np.asarray(w[:, i]) * np.asarray(x[:, t - 3 + i])
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)
    changed = linear_attention.short_conv(x.at[:, 7].add(1.0), w)
    np.testing.assert_array_equal(changed[:, :7], y[:, :7])
    assert float(jnp.abs(changed[:, 7:11] - y[:, 7:11]).min()) > 0
    np.testing.assert_array_equal(changed[:, 11:], y[:, 11:])  # width 4


def test_the_scope_is_on_forward_and_backward_and_a_loop_trip_is_a_chunk():
    args = inputs("weak")
    text = jax.jit(jax.grad(lambda *a: jnp.sum(
        linear_attention.gated_delta_rule(*a, chunk=32)))).lower(
            *args).compile().as_text()
    scopes = profiler.instruction_scopes(text)
    under = [s for s in scopes.values()
             if profiler.phase_of(s) == "hvd.linattn.scan"]
    assert any("transpose(" in s for s in under)
    assert any("transpose(" not in s for s in under)
    assert loop_trips(text, "hvd.linattn.scan") == [S // 32] * 2
