"""``ops/linear_attention.py::kimi_delta_rule`` against its definition: the
chunk-parallel rule with a decay a key channel is the token-by-token
recurrence ``S' = Diag(exp(g_t)) S``, values and all five gradients, at
mild decays, at the initial draw's strongest (1.6 a token) and at e^-20 a
token, where the cheap factorisation ``(k e^gamma)(k e^-gamma)^T`` would
overflow inside one sub-block: nothing here is an ``inf`` or a ``nan``.
With every channel's decay equal it is ``gated_delta_rule``. The scope, the
two gauges and the refusal of a ragged sequence are there."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import metrics
from horovod_tpu.ops import linear_attention

B, S, H, DK, DV = 2, 64, 3, 8, 16
RATES = {"mild": 0.05, "initial_draws_strongest": 1.6, "e-20_a_token": 20.0}
FORMS = [(32, 8), (16, 4), (64, 16)]  # (chunk, sub-block)


def recurrence(q, k, v, g, beta):
    """The definition, one token at a time, all in float32: row ``c`` of
    the state decays by ``exp(g_tc)``."""
    def one_token(state, xs):
        q, k, v, g, beta = xs                       # [B, H, ...]
        state = jnp.exp(g)[..., None] * state
        seen = jnp.einsum("bhkv,bhk->bhv", state, k)
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", beta[..., None] * k, v - seen)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q)

    state = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]))
    _, out = jax.lax.scan(one_token, state, jax.tree.map(
        lambda x: jnp.moveaxis(x, 1, 0), (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1)


def inputs(rate: float, seed: int = 0):
    """Log decays of ``-rate`` times a draw in (0.5, 1) a key channel."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(keys[0], (B, S, H, DK))
    k = jax.random.normal(keys[1], (B, S, H, DK))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * DK ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (B, S, H, DV))
    g = -rate * jax.random.uniform(keys[3], (B, S, H, DK), minval=0.5,
                                   maxval=1.0)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (B, S, H)))
    return q, k, v, g, beta


def rule(chunk, sub):
    return lambda *a: linear_attention.kimi_delta_rule(
        *a, chunk=chunk, sub=sub)


@pytest.mark.parametrize("rate", sorted(RATES))
@pytest.mark.parametrize("chunk,sub", FORMS)
def test_chunk_form_is_the_recurrence(chunk, sub, rate):
    args = inputs(RATES[rate])
    with jax.default_matmul_precision("highest"):
        want = jax.jit(recurrence)(*args)
        got = jax.jit(rule(chunk, sub))(*args)
    assert got.shape == (B, S, H, DV) and got.dtype == jnp.float32
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("rate", sorted(RATES))
def test_chunk_forms_gradients_are_the_recurrences(rate):
    args = inputs(RATES[rate], seed=1)
    weight = jax.random.normal(jax.random.PRNGKey(9), (B, S, H, DV))

    def scalar(fn):
        return lambda *a: jnp.sum(fn(*a) * weight)

    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(scalar(recurrence), argnums=range(5)))(*args)
        got = jax.jit(jax.grad(scalar(rule(32, 8)), argnums=range(5)))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert bool(jnp.isfinite(a).all()), name
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-5 * scale + 2e-7,
                                   err_msg=name)


def test_the_cheap_factorisation_would_have_overflowed():
    """What the sub-blocks are for: at e^-20 a token the running sum falls
    past -88 within five tokens, so ``exp(-gamma)`` is past float32 inside
    one sub-block of eight, while every exponent the rule forms is a
    difference ``gamma_i - gamma_j`` with ``j <= i``."""
    _, _, _, g, _ = inputs(RATES["e-20_a_token"])
    gamma = jnp.cumsum(g[:, :8], 1)
    assert not bool(jnp.isfinite(jnp.exp(-gamma)).all())


@pytest.mark.parametrize("chunk,sub", FORMS[:2])
def test_equal_channels_are_the_gated_delta_rule(chunk, sub):
    q, k, v, g, beta = inputs(0.3, seed=2)
    scalar = g[..., 0]
    with jax.default_matmul_precision("highest"):
        want = linear_attention.gated_delta_rule(q, k, v, scalar, beta,
                                                 chunk=chunk)
        got = rule(chunk, sub)(
            q, k, v, jnp.broadcast_to(scalar[..., None], g.shape), beta)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 * float(jnp.abs(want).max()))


def test_bfloat16_operands_keep_float32_decays_and_state():
    """As ``gated_delta_rule``: ``v``'s type is the products' and the
    result's; ``g`` stays float32 through ``gamma`` and the carried state."""
    q, k, v, g, beta = inputs(0.3, seed=3)
    low = jax.tree.map(lambda x: x.astype(jnp.bfloat16), (q, k, v))
    got = jax.jit(rule(32, 8))(*low, g, beta)
    assert got.dtype == jnp.bfloat16
    want = recurrence(*(x.astype(jnp.float32) for x in low), g, beta)
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) < (
        0.03 * float(jnp.abs(want).max()))
    text = jax.jit(rule(32, 8)).lower(*low, g, beta).as_text()
    assert "while" in text and "f32[2,3,8,16]" in text.replace(
        "tensor<2x3x8x16xf32>", "f32[2,3,8,16]")


@pytest.mark.parametrize("seq,chunk,sub", [(48, 32, 8), (64, 32, 12)])
def test_a_ragged_sequence_or_sub_block_is_refused(seq, chunk, sub):
    q, k, v, g, beta = (x[:, :seq] for x in inputs(0.3))
    with pytest.raises(ValueError, match="pad it upstream"):
        linear_attention.kimi_delta_rule(q, k, v, g, beta, chunk=chunk,
                                         sub=sub)


def test_the_scope_and_the_gauges_say_which_rule_the_step_holds():
    args = inputs(0.3)
    text = jax.jit(rule(32, 8)).lower(*args).as_text(debug_info=True)
    assert "hvd.linattn.scan" in text
    assert metrics.LINATTN_CHUNKS_LAST.labels(
        chunk="32", heads_here=str(H)).get() == S // 32
    assert metrics.LINATTN_DECAY_WIDTH_LAST.labels().get() == DK
    jax.jit(linear_attention.gated_delta_rule, static_argnames="chunk").lower(
        *args[:3], args[3][..., 0], args[4], chunk=32)
    assert metrics.LINATTN_DECAY_WIDTH_LAST.labels().get() == 1
