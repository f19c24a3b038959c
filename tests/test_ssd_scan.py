"""``ops/ssd.py`` against its definition: the chunk-parallel Mamba-2 scan is
the token-by-token recurrence, values and gradients, for several chunk
sizes, with strong and weak decay, with one group of ``B`` and ``C`` shared
by all heads and with several; bfloat16 operands stay within their
rounding of it and keep the steps, the decays and the carried state in
float32; a ragged sequence and heads that do not share the groups evenly
are refused; the convolution takes a bias; the scope and the gauge are
there."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import metrics, profiler
from horovod_tpu.ops import linear_attention, ssd

B, S, H, P, N = 2, 96, 6, 8, 16


def recurrence(x, dt, a, b, c, d):
    """The definition, one token at a time, all in float32."""
    share = x.shape[2] // b.shape[2]

    def one_token(state, xs):
        x, dt, b, c = xs                             # [B, H, ...], [B, G, N]
        b, c = jnp.repeat(b, share, 1), jnp.repeat(c, share, 1)
        state = jnp.exp(dt * a)[..., None, None] * state + jnp.einsum(
            "bhp,bhn->bhpn", dt[..., None] * x, b)
        return state, jnp.einsum("bhpn,bhn->bhp", state, c) + d[:, None] * x

    state = jnp.zeros((x.shape[0], x.shape[2], x.shape[3], b.shape[3]))
    _, out = jax.lax.scan(one_token, state, jax.tree.map(
        lambda t: jnp.moveaxis(t, 1, 0), (x, dt, b, c)))
    return jnp.moveaxis(out, 0, 1)


def inputs(decay: str, groups: int = 1, seed: int = 0):
    """``decay``: steps x rates around 1 a token (a chunk forgets what
    entered it) or around 0.003 (it keeps nearly all)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (B, S, H, P))
    scale = {"strong": 1.0, "weak": 0.003}[decay]
    dt = scale * jax.random.uniform(keys[1], (B, S, H), minval=0.1,
                                    maxval=1.0)
    a = -jax.random.uniform(keys[2], (H,), minval=1.0, maxval=4.0)
    b = jax.random.normal(keys[3], (B, S, groups, N))
    c = jax.random.normal(keys[4], (B, S, groups, N))
    d = jax.random.normal(keys[5], (H,))
    return x, dt, a, b, c, d


@pytest.mark.parametrize("groups", [1, 3])
@pytest.mark.parametrize("decay", ["strong", "weak"])
@pytest.mark.parametrize("chunk", [8, 32, 96])
def test_chunk_form_is_the_recurrence(chunk, decay, groups):
    args = inputs(decay, groups)
    want = recurrence(*args)
    got = jax.jit(ssd.ssd_scan, static_argnames="chunk")(*args, chunk=chunk)
    assert got.shape == (B, S, H, P) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("groups", [1, 3])
@pytest.mark.parametrize("decay", ["strong", "weak"])
@pytest.mark.parametrize("chunk", [8, 32])
def test_chunk_forms_gradients_are_the_recurrences(chunk, decay, groups):
    args = inputs(decay, groups, seed=1)
    weight = jax.random.normal(jax.random.PRNGKey(9), (B, S, H, P))

    def scalar(scan):
        return lambda *a: jnp.sum(scan(*a) * weight)

    want = jax.jit(jax.grad(scalar(recurrence), argnums=range(6)))(*args)
    got = jax.jit(jax.grad(scalar(
        lambda *a: ssd.ssd_scan(*a, chunk=chunk)), argnums=range(6)))(*args)
    for name, g, w in zip("x dt a b c d".split(), got, want):
        np.testing.assert_allclose(
            g, w, rtol=0, atol=1e-4 * float(jnp.abs(w).max()), err_msg=name)


def test_no_skip_is_a_skip_of_zero():
    x, dt, a, b, c, d = inputs("weak")
    np.testing.assert_allclose(
        ssd.ssd_scan(x, dt, a, b, c, chunk=32),
        ssd.ssd_scan(x, dt, a, b, c, jnp.zeros_like(d), chunk=32),
        rtol=0, atol=1e-6)


def test_one_group_is_every_heads_b_and_c():
    """``mamba_n_groups`` 1: the same ``B`` and ``C`` written out a head
    (as many groups as heads) give the same output."""
    x, dt, a, b, c, d = inputs("weak", groups=1)
    shared = ssd.ssd_scan(x, dt, a, b, c, d, chunk=32)
    written_out = ssd.ssd_scan(x, dt, a, jnp.repeat(b, H, 2),
                               jnp.repeat(c, H, 2), d, chunk=32)
    np.testing.assert_allclose(shared, written_out, rtol=0, atol=1e-5)


@pytest.mark.parametrize("decay", ["strong", "weak"])
def test_bfloat16_operands_stay_within_their_rounding(decay):
    x, dt, a, b, c, d = inputs(decay)
    want = recurrence(x, dt, a, b, c, d)
    got = ssd.ssd_scan(x.astype(jnp.bfloat16), dt, a, b.astype(jnp.bfloat16),
                       c.astype(jnp.bfloat16), d, chunk=32)
    assert got.dtype == jnp.bfloat16
    off = jnp.abs(got.astype(jnp.float32) - want).max() / jnp.abs(want).max()
    assert float(off) < 3e-2


def test_steps_decays_and_the_carried_state_stay_float32():
    """In the program of a bfloat16 call the cumulative sum, every
    exponential and the loop that carries the state are float32."""
    x, dt, a, b, c, d = inputs("weak")
    jaxpr = jax.make_jaxpr(lambda *t: ssd.ssd_scan(*t, chunk=32))(
        x.astype(jnp.bfloat16), dt, a, b.astype(jnp.bfloat16),
        c.astype(jnp.bfloat16), d)
    seen = {"cumsum": [], "exp": [], "scan": []}

    def walk(eqns):
        for eqn in eqns:
            if eqn.primitive.name in seen:
                seen[eqn.primitive.name] += [
                    v.aval.dtype for v in eqn.outvars]
            elif "jaxpr" in eqn.params:  # jnp.cumsum is a jitted function
                walk(eqn.params["jaxpr"].eqns)

    walk(jaxpr.jaxpr.eqns)
    assert all(seen.values())
    for name, dtypes in seen.items():
        assert set(dtypes) == {jnp.dtype(jnp.float32)}, name


def test_a_sequence_that_is_no_multiple_of_the_chunk_is_refused():
    args = inputs("weak")
    with pytest.raises(ValueError, match="no multiple of the chunk of 64"):
        ssd.ssd_scan(*args, chunk=64)


def test_heads_that_do_not_share_the_groups_evenly_are_refused():
    x, dt, a, b, c, d = inputs("weak", groups=1)
    with pytest.raises(ValueError, match="6 heads do not share 4 groups"):
        ssd.ssd_scan(x, dt, a, jnp.repeat(b, 4, 2), jnp.repeat(c, 4, 2), d,
                     chunk=32)


def test_the_short_convolution_takes_a_bias():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (6, 4))
    bias = jax.random.normal(jax.random.PRNGKey(2), (6,))
    np.testing.assert_allclose(
        linear_attention.short_conv(x, w, bias),
        linear_attention.short_conv(x, w) + bias, rtol=0, atol=1e-6)
    # in float32, before the rounding to the input's type
    rounded = linear_attention.short_conv(x.astype(jnp.bfloat16), w, bias)
    assert rounded.dtype == jnp.bfloat16


def test_the_scope_is_on_forward_and_backward_and_the_gauge_is_set():
    args = inputs("weak")
    text = jax.jit(jax.grad(lambda *a: jnp.sum(
        ssd.ssd_scan(*a, chunk=32)))).lower(*args).compile().as_text()
    scopes = profiler.instruction_scopes(text)
    under = [s for s in scopes.values()
             if profiler.phase_of(s) == "hvd.ssm.scan"]
    assert any("transpose(" in s for s in under)
    assert any("transpose(" not in s for s in under)
    assert metrics.SSM_CHUNKS_LAST.labels(
        chunk="32", heads=str(H)).get() == S // 32


def nemotron_inputs(seed: int = 3):
    """Nemotron-H's grouping at a small width: 16 heads on 8 groups of
    ``B`` and ``C`` (two heads a group), 256 tokens, so that chunk 128 is
    two chunks and a state crosses."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    heads, groups, seq = 16, 8, 256
    x = jax.random.normal(keys[0], (1, seq, heads, 4))
    dt = 0.05 * jax.random.uniform(keys[1], (1, seq, heads), minval=0.1,
                                   maxval=1.0)
    a = -jax.random.uniform(keys[2], (heads,), minval=1.0, maxval=4.0)
    b = jax.random.normal(keys[3], (1, seq, groups, N))
    c = jax.random.normal(keys[4], (1, seq, groups, N))
    d = jax.random.normal(keys[5], (heads,))
    return x, dt, a, b, c, d


@pytest.mark.parametrize("chunk", [128, 64])
def test_eight_groups_at_chunk_128_are_the_recurrence(chunk):
    args = nemotron_inputs()
    want = recurrence(*args)
    got = jax.jit(ssd.ssd_scan, static_argnames="chunk")(*args, chunk=chunk)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))


def test_eight_groups_gradients_are_the_recurrences():
    args = nemotron_inputs(seed=4)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def scalar(scan):
        return lambda *a: jnp.sum(scan(*a) * weight)

    want = jax.jit(jax.grad(scalar(recurrence), argnums=range(6)))(*args)
    got = jax.jit(jax.grad(scalar(
        lambda *a: ssd.ssd_scan(*a, chunk=128)), argnums=range(6)))(*args)
    for name, g, w in zip("x dt a b c d".split(), got, want):
        np.testing.assert_allclose(
            g, w, rtol=0, atol=1e-4 * float(jnp.abs(w).max()), err_msg=name)


def test_a_head_reads_its_own_group():
    """Head ``h`` reads group ``h // (H / G)``: with every group's ``B``
    and ``C`` set to group 0's the output is another one."""
    x, dt, a, b, c, d = nemotron_inputs()
    own = ssd.ssd_scan(x, dt, a, b, c, d, chunk=128)
    first = ssd.ssd_scan(x, dt, a, jnp.repeat(b[:, :, :1], 8, 2),
                         jnp.repeat(c[:, :, :1], 8, 2), d, chunk=128)
    np.testing.assert_allclose(own[:, :, :2], first[:, :, :2], rtol=0,
                               atol=1e-5)  # group 0's two heads
    assert float(jnp.abs(own[:, :, 2:] - first[:, :, 2:]).max()) > 0.1


@pytest.mark.parametrize("groups", [1, 2, 8])
def test_the_grouped_norm_is_a_norm_a_group(groups):
    """``parts.RMSNorm(eps, groups)`` against a loop over the groups, each
    run of channels over its own mean square; one leaf of all the
    channels; one group is the plain norm."""
    from horovod_tpu.models.parts import RMSNorm

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 64)) * jnp.arange(
        1, 65)
    scale = jax.random.normal(jax.random.PRNGKey(1), (64,))
    params = {"params": {"scale": scale}}
    got = RMSNorm(1e-5, groups).apply(params, x)
    width = 64 // groups
    want = jnp.concatenate([
        run * jax.lax.rsqrt(jnp.mean(jnp.square(run), -1, keepdims=True)
                            + 1e-5)
        for run in (x[..., g * width:(g + 1) * width]
                    for g in range(groups))], -1) * scale
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    shapes = jax.eval_shape(RMSNorm(1e-5, groups).init,
                            jax.random.PRNGKey(0), x)["params"]
    assert shapes["scale"].shape == (64,)
    if groups > 1:
        plain = RMSNorm(1e-5).apply(params, x)
        assert float(jnp.abs(got - plain).max()) > 0.1
