"""``ops/ssd.py`` against its definition: the chunk-parallel Mamba-2 scan is
the token-by-token recurrence, values and gradients, for several chunk
sizes, with strong and weak decay, with one group of ``B`` and ``C`` shared
by all heads and with several; bfloat16 operands stay within their
rounding of it and keep the steps, the decays and the carried state in
float32; a ragged sequence and heads that do not share the groups evenly
are refused; the convolution takes a bias; the scope and the gauge are
there. At shapes that fill a TPU's tiles the two Pallas kernels
(``ssd_scan_kernel``, interpreted here) are held to the same recurrence,
value and six gradients, with the same rounding points; any other shape
takes the plain form; a recomputed layer keeps nothing the kernels
return."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import profiler
from horovod_tpu.models import parts
from horovod_tpu.ops import linear_attention, ssd
from traced import bound, loop_trips

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


def test_the_scope_is_on_forward_and_backward_and_a_loop_trip_is_a_chunk():
    args = inputs("weak")
    text = jax.jit(jax.grad(lambda *a: jnp.sum(
        ssd.ssd_scan(*a, chunk=32)))).lower(*args).compile().as_text()
    scopes = profiler.instruction_scopes(text)
    under = [s for s in scopes.values()
             if profiler.phase_of(s) == "hvd.ssm.scan"]
    assert any("transpose(" in s for s in under)
    assert any("transpose(" not in s for s in under)
    assert loop_trips(text, "hvd.ssm.scan") == [S // 32] * 2


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


# Shapes that fill a TPU's tiles, (heads, groups, head width, chunk,
# tokens): Granite's family (one group, chunk 256, two head blocks of
# eight), Nemotron-H's (eight groups of eight heads, chunk 128), a
# sequence of one chunk and of several; heads of 64 (the cells': two a lane
# block) and of 128 (one a block) beside the toys' 16 (eight a block).
TILE_FILLING = {
    "one_group_chunk_256_one_chunk": (16, 1, 16, 256, 256),
    "one_group_chunk_256_two_chunks": (16, 1, 16, 256, 512),
    "eight_groups_chunk_128_one_chunk": (64, 8, 16, 128, 128),
    "eight_groups_chunk_128_three_chunks": (64, 8, 16, 128, 384),
    "heads_of_64_two_chunks": (8, 1, 64, 128, 256),
    "heads_of_128_two_chunks": (8, 1, 128, 128, 256),
}
FORMS = {
    "plain": lambda chunk: lambda *t: ssd._chunk_form(*t, chunk),
    "kernel": lambda chunk: lambda *t: ssd.ssd_scan_kernel(*t, chunk, True),
}


def tile_filling_inputs(shape: str, seed: int = 5, dtype=jnp.float32):
    heads, groups, width, chunk, seq = TILE_FILLING[shape]
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (1, seq, heads, width)).astype(dtype)
    dt = 0.05 * jax.random.uniform(keys[1], (1, seq, heads), minval=0.1,
                                   maxval=1.0)
    a = -jax.random.uniform(keys[2], (heads,), minval=1.0, maxval=4.0)
    b, c = (jax.random.normal(key, (1, seq, groups, 128)).astype(dtype)
            for key in keys[3:5])
    return (x, dt, a, b, c, jax.random.normal(keys[5], (heads,))), chunk


@pytest.fixture
def interpreted(monkeypatch):
    """``ssd_scan`` with the kernels in the plain form's place wherever
    the shapes fill the tiles: the lowering platform decides that in the
    product, here the kernels are interpreted."""
    kernel = ssd.ssd_scan_kernel
    monkeypatch.setattr(ssd, "ssd_scan_kernel",
                        lambda *t: kernel(*t, True))


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("shape", sorted(TILE_FILLING))
def test_at_tile_filling_shapes_both_forms_are_the_recurrence(shape, form):
    args, chunk = tile_filling_inputs(shape)
    want = recurrence(*args)
    got = jax.jit(FORMS[form](chunk))(*args)
    assert got.shape == args[0].shape and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("shape", [
    "one_group_chunk_256_two_chunks", "eight_groups_chunk_128_one_chunk",
    "eight_groups_chunk_128_three_chunks", "heads_of_64_two_chunks"])
def test_at_tile_filling_shapes_both_forms_gradients_are_the_recurrences(
        shape, form):
    args, chunk = tile_filling_inputs(shape, seed=6)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def scalar(scan):
        return lambda *t: jnp.sum(scan(*t) * weight)

    want = jax.jit(jax.grad(scalar(recurrence), argnums=range(6)))(*args)
    got = jax.jit(jax.grad(scalar(FORMS[form](chunk)),
                           argnums=range(6)))(*args)
    for name, g, w in zip("x dt a b c d".split(), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        np.testing.assert_allclose(
            g, w, rtol=0, atol=1e-4 * float(jnp.abs(w).max()), err_msg=name)


@pytest.mark.parametrize("decay", ["strong", "weak"])
def test_the_kernels_bfloat16_operands_stay_within_their_rounding(decay):
    """As the plain form's, and no further from the recurrence than it by
    more than a rounding of the result: the same rounding points."""
    (x, dt, a, b, c, d), chunk = tile_filling_inputs(
        "eight_groups_chunk_128_three_chunks", dtype=jnp.bfloat16)
    dt = dt * {"strong": 20.0, "weak": 0.06}[decay]
    want = recurrence(x.astype(jnp.float32), dt, a, b.astype(jnp.float32),
                      c.astype(jnp.float32), d)
    off = {}
    for form in FORMS:
        got = FORMS[form](chunk)(x, dt, a, b, c, d)
        assert got.dtype == jnp.bfloat16
        off[form] = float(jnp.abs(got.astype(jnp.float32) - want).max()
                          / jnp.abs(want).max())
    assert off["kernel"] < 3e-2 and off["kernel"] < off["plain"] + 2 ** -8


def test_the_kernels_step_and_rate_gradients_are_as_near_as_the_plain_forms():
    """``dt_bias`` sees ``d(dt)`` summed over a head's tokens and ``A_log``
    sees ``da``: sums in which ``alpha``'s cotangent is accumulated
    backwards over each chunk, so a pair term that ``alpha_i`` adds and
    ``alpha_j`` takes away must be ONE float32 number (the plain form's
    decay matrix gives that for nothing). Rounded twice (the row sums from
    one bfloat16 product, the column sums from another) the kernels stood
    seven times further from the recurrence than the plain form in these
    sums at the initial draw's steps, and Nemotron-H's cell read ``correct:
    false`` by a ``dt_bias`` leaf (PERF.md, PR 48)."""
    heads, groups, width, chunk, seq = 16, 2, 64, 128, 1024
    keys = jax.random.split(jax.random.PRNGKey(7), 7)
    bf16, f32 = jnp.bfloat16, jnp.float32
    x = jax.random.normal(keys[0], (1, seq, heads, width)).astype(bf16)
    dt = jnp.exp(jax.random.uniform(keys[1], (1, seq, heads),
                                    minval=np.log(1e-3), maxval=np.log(0.1)))
    a = -jax.random.uniform(keys[2], (heads,), minval=1.0, maxval=16.0)
    b, c = (jax.random.normal(key, (1, seq, groups, 128)).astype(bf16)
            for key in keys[3:5])
    d = jax.random.normal(keys[5], (heads,))
    weight = jax.random.normal(keys[6], x.shape)

    def sums(scan, *args):
        dt_bar, a_bar = jax.jit(jax.grad(
            lambda *t: jnp.sum(scan(*t).astype(f32) * weight),
            argnums=(1, 2)))(*args)
        return dt_bar.sum((0, 1)), a_bar

    want = sums(recurrence, x.astype(f32), dt, a, b.astype(f32),
                c.astype(f32), d)
    off = {form: [float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
                  for g, w in zip(sums(FORMS[form](chunk), x, dt, a, b, c, d),
                                  want)]
           for form in FORMS}
    for kernel, plain in zip(off["kernel"], off["plain"]):
        assert kernel < 1.5 * plain + 1e-4, off


@pytest.mark.parametrize("shape", ["toy", "eight_groups_chunk_128_one_chunk"])
def test_no_skip_is_a_skip_of_zero_in_either_form(shape, interpreted):
    if shape == "toy":
        (x, dt, a, b, c, d), chunk = inputs("weak"), 32
    else:
        (x, dt, a, b, c, d), chunk = tile_filling_inputs(shape)
    none = ssd.ssd_scan(x, dt, a, b, c, chunk=chunk)
    np.testing.assert_allclose(
        none, ssd.ssd_scan(x, dt, a, b, c, jnp.zeros_like(d), chunk=chunk),
        rtol=0, atol=1e-6)
    np.testing.assert_allclose(
        none, recurrence(x, dt, a, b, c, jnp.zeros_like(d)), rtol=0,
        atol=2e-5 * float(jnp.abs(none).max()))
    grads = jax.grad(lambda *t: jnp.sum(
        ssd.ssd_scan(*t, chunk=chunk) ** 2), argnums=range(5))(x, dt, a, b, c)
    assert all(bool(jnp.isfinite(g).all()) for g in grads)


def kernels_rounding_points(chunk: int, *args):
    """What the forward and the backward pass of the kernel form compute
    in which type, from their programs: the running sum's product and its
    precision, the exponentials, the kernels' products, what the steps and
    ``alpha`` reach the kernels as, the states in VMEM and in HBM, and the
    per-token cotangents the backward kernel returns."""
    how = dict(chunk=chunk, step=ssd.SCAN_HEADS_A_STEP, interpret=True)
    forward = jax.make_jaxpr(
        lambda *t: ssd._forward_by_kernel(*t, states=True, **how))(*args)
    y, entering = jax.eval_shape(
        lambda *t: ssd._forward_by_kernel(*t, states=True, **how), *args)
    backward = jax.make_jaxpr(
        lambda *t: ssd._backward_by_kernel(*t, **how))(*args, entering, y)
    seen = {name: set() for name in (
        "running sum", "running sum's precision", "exp", "products' operands",
        "products' results", "steps and alpha", "states in VMEM",
        "states in HBM", "tokens' cotangents")}

    def kernel(eqn):
        body, refs = eqn.params["jaxpr"], eqn.params["jaxpr"].invars
        scratch = eqn.params["grid_mapping"].num_scratch_operands
        state = [v.aval for v in refs[len(refs) - scratch:]
                 if len(v.aval.shape) == 3]
        seen["states in VMEM"] |= {v.dtype for v in state}
        rows = args[1].shape[:1] + args[1].shape[:0:-1]  # [B, H, S]
        seen["steps and alpha"] |= {
            v.aval.dtype for v in eqn.invars if v.aval.shape == rows}
        for out in eqn.outvars:
            if len(out.aval.shape) == 4:
                seen["states in HBM"].add(out.aval.dtype)
            elif out.aval.shape == rows:
                seen["tokens' cotangents"].add(out.aval.dtype)
        walk(body.eqns)

    def walk(eqns):
        for eqn in eqns:
            name = eqn.primitive.name
            if name == "pallas_call":
                kernel(eqn)
            elif name == "exp":
                seen["exp"] |= {v.aval.dtype for v in eqn.outvars}
            elif name == "dot_general" and eqn.params["precision"]:
                seen["running sum"] |= {v.aval.dtype for v in eqn.invars}
                seen["running sum's precision"] |= set(eqn.params["precision"])
            elif name == "dot_general":
                seen["products' operands"] |= {v.aval.dtype
                                               for v in eqn.invars}
                seen["products' results"] |= {v.aval.dtype
                                              for v in eqn.outvars}
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub.eqns)

    walk(forward.jaxpr.eqns)
    walk(backward.jaxpr.eqns)
    return seen


def bfloat16_call():
    (x, dt, a, b, c, d), chunk = tile_filling_inputs(
        "eight_groups_chunk_128_three_chunks", dtype=jnp.bfloat16)
    return chunk, x, dt, a, b, c, d


def test_the_kernels_keep_the_plain_forms_rounding_points():
    """The products' operands in ``x``'s type with float32 accumulation;
    the steps, ``alpha`` (a float32 product at ``Precision.HIGHEST``, no
    ``cumsum``), every exponential, the states in VMEM and in HBM and the
    tokens' cotangents float32."""
    f32, bf16 = jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)
    seen = kernels_rounding_points(*bfloat16_call())
    assert seen.pop("products' operands") == {bf16}
    assert seen.pop("running sum's precision") == {jax.lax.Precision.HIGHEST}
    for name, dtypes in seen.items():
        assert dtypes == {f32}, name


def test_a_bfloat16_carried_state_fails_the_rounding_points(monkeypatch):
    scratch = ssd.pltpu.VMEM
    monkeypatch.setattr(
        ssd.pltpu, "VMEM", lambda shape, dtype: scratch(
            shape, jnp.bfloat16 if len(shape) == 3 else dtype))
    seen = kernels_rounding_points(*bfloat16_call())
    assert seen["states in VMEM"] == {jnp.dtype(jnp.bfloat16)}
    monkeypatch.undo()
    with pytest.raises(AssertionError, match="states in VMEM"):
        monkeypatch.setattr(
            ssd.pltpu, "VMEM", lambda shape, dtype: scratch(
                shape, jnp.bfloat16 if len(shape) == 3 else dtype))
        test_the_kernels_keep_the_plain_forms_rounding_points()


def test_a_shape_that_fills_no_tile_takes_the_plain_form():
    """The toys' widths trace the plain form whatever the platform (its
    ``cumsum`` at once); a shape that fills the tiles is a primitive whose
    lowering for this platform is the plain form (the ``cumsum`` only in
    the lowered text) and, interpreted, the kernels: eight heads a grid
    step."""
    def primitives(*args, chunk):
        return [name for name, _ in bound(
            lambda *t: ssd.ssd_scan(*t, chunk=chunk), *args, prefix="")]

    weak = inputs("weak")
    assert not ssd._heads_a_step(weak[0], weak[3], 32)
    traced = primitives(*weak, chunk=32)
    assert "hvd_ssd_chunk_scan" not in traced and "cumsum" in traced
    args, chunk = tile_filling_inputs("eight_groups_chunk_128_one_chunk")
    assert ssd._heads_a_step(args[0], args[3], chunk) == 8
    traced = primitives(*args, chunk=chunk)  # no ``cumsum``: not yet lowered
    assert "hvd_ssd_chunk_scan" in traced and "cumsum" not in traced
    text = jax.jit(lambda *t: ssd.ssd_scan(*t, chunk=chunk)).lower(
        *args).as_text()
    assert "hvd_ssd_chunk_scan" not in text and "cumsum" in text
    (_, interpreted), = bound(
        lambda *t: ssd.ssd_scan_kernel(*t, chunk, True), *args)
    assert interpreted["interpret"] and (
        interpreted["step"] == ssd.SCAN_HEADS_A_STEP == 8)
    # a state of 64, heads of 24 or seven heads a group fill no tile
    x, dt, a, b, c, d = args
    assert not ssd._heads_a_step(x, b[..., :64], chunk)
    assert not ssd._heads_a_step(x[..., :24].repeat(2, -1)[..., :24], b, chunk)
    assert not ssd._heads_a_step(x[:, :, :56], b, chunk)
    assert not ssd._heads_a_step(x, b, 64)


def test_a_recomputed_layer_keeps_nothing_the_kernels_return(interpreted):
    """Under the decoders' policy, which keeps what a ``pallas_call``
    returns: the scan is a primitive of its own, so neither ``y`` nor the
    states that enter the chunks are among a layer's saved residuals, and
    its forward pass asks for no states at all."""
    from jax._src.ad_checkpoint import saved_residuals

    (x, dt, a, b, c, d), chunk = tile_filling_inputs(
        "eight_groups_chunk_128_three_chunks")

    def layer(x, w):
        x = x * w
        return x + ssd.ssd_scan(x, dt, a, b, c, d, chunk=chunk)

    recomputed = jax.checkpoint(
        layer, policy=parts.save_kernels_and_projections)
    kept = saved_residuals(recomputed, x, jnp.ones(x.shape[-1]))
    states = (1, x.shape[1] // chunk, 128, x.shape[2] * x.shape[3])
    assert kept and all(
        source.startswith(("from the argument", "from a constant"))
        and aval.shape != states for aval, source in kept), kept
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda x, w: recomputed(x, w).sum(), argnums=(0, 1)))(
            x, jnp.ones(x.shape[-1]))
    scans = []

    def walk(eqns):
        for eqn in eqns:
            if eqn.primitive.name.startswith("hvd_ssd_chunk_scan"):
                scans.append((eqn.primitive.name, eqn.params.get("states")))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub.eqns)

    walk(jaxpr.jaxpr.eqns)
    assert sorted(scans, key=str) == [
        ("hvd_ssd_chunk_scan", False), ("hvd_ssd_chunk_scan", True),
        ("hvd_ssd_chunk_scan_backward", None)], scans
