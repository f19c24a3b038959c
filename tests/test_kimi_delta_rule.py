"""``ops/linear_attention.py::kimi_delta_rule`` against its definition: the
chunk-parallel rule with a decay a key channel is the token-by-token
recurrence ``S' = Diag(exp(g_t)) S``, values and all five gradients, at
mild decays, at the initial draw's strongest (1.6 a token) and at e^-20 a
token, where the cheap factorisation ``(k e^gamma)(k e^-gamma)^T`` would
overflow inside one sub-block: nothing here is an ``inf`` or a ``nan``.
With every channel's decay equal it is ``gated_delta_rule``. The scope, what
the trace says of the rule it holds and the refusal of a ragged sequence are
there. The running sum
``gamma`` is a float32 product with a triangle of ones at
``Precision.HIGHEST``: a float64 sum's value, and no ``reduce_window`` in
the program, forward or backward; ``gated_delta_rule`` keeps its text.

The pair terms have two forms, ``_pair_terms`` (plain JAX: any platform but
a TPU) and ``pair_terms_kernel`` (a Pallas kernel and its backward kernel:
the program lowered for a TPU). The kernel in interpret mode is the plain
form, values and the three cotangents, and every case of the rule runs
through both (``path``): on the kernel's path the test puts the kernel
where the lowering platform would have.

The kernels take ``q``, ``k``, ``v``, ``gamma`` and give their cotangents
tokens-major, ``[B, S, H, d]`` read as ``[B, S, H * d]``: the tests feed
them so and hold them to the plain forms on ``_by_head``'s view, and the
rule's jaxpr with the kernels in it holds no ``transpose`` of anything ``d``
wide."""

import contextlib
import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import linear_attention
from traced import bound, equations

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


@pytest.fixture(params=["plain", "kernel"])
def path(request, monkeypatch):
    """Which form of the pair terms the rule holds. The lowering platform
    decides that in the product (and sub-blocks that fill no tile keep the
    plain form, as all of these do); here the kernel is put in its place,
    interpreted."""
    if request.param == "kernel":
        monkeypatch.setattr(
            linear_attention, "_pair_terms_where_lowered",
            lambda *a: linear_attention.pair_terms_kernel(*a, True))
    return request.param


@pytest.mark.parametrize("rate", sorted(RATES))
@pytest.mark.parametrize("chunk,sub", FORMS)
def test_chunk_form_is_the_recurrence(chunk, sub, rate, path):
    args = inputs(RATES[rate])
    with jax.default_matmul_precision("highest"):
        want = jax.jit(recurrence)(*args)
        got = jax.jit(rule(chunk, sub))(*args)
    assert got.shape == (B, S, H, DV) and got.dtype == jnp.float32
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("rate", sorted(RATES))
def test_chunk_forms_gradients_are_the_recurrences(rate, path):
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


def test_the_cheap_factorisation_would_have_overflowed(path, monkeypatch):
    """What the sub-blocks are for: at e^-20 a token the running sum falls
    past -88 within five tokens, so ``exp(-gamma)`` is past float32 inside
    one sub-block of eight, while every exponent the rule forms is a
    difference ``gamma_i - gamma_j`` with ``j <= i``: with an exponential
    that answers ``nan`` to any positive argument the rule and its five
    gradients stay finite, through the kernels too."""
    args = inputs(RATES["e-20_a_token"])
    gamma = jnp.cumsum(args[3][:, :8], 1)
    assert not bool(jnp.isfinite(jnp.exp(-gamma)).all())

    exp = jnp.exp
    monkeypatch.setattr(
        jnp, "exp", lambda x: jnp.where(x > 0, jnp.nan, exp(x)))
    assert bool(jnp.isnan(jnp.exp(jnp.float32(1e-3))))
    out, grads = jax.value_and_grad(
        lambda *a: rule(32, 8)(*a).sum(), argnums=range(5))(*args)
    assert all(bool(jnp.isfinite(x).all()) for x in (out,) + grads)


@pytest.mark.parametrize("chunk,sub", FORMS[:2])
def test_equal_channels_are_the_gated_delta_rule(chunk, sub, path):
    q, k, v, g, beta = inputs(0.3, seed=2)
    scalar = g[..., 0]
    with jax.default_matmul_precision("highest"):
        want = linear_attention.gated_delta_rule(q, k, v, scalar, beta,
                                                 chunk=chunk)
        got = rule(chunk, sub)(
            q, k, v, jnp.broadcast_to(scalar[..., None], g.shape), beta)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 * float(jnp.abs(want).max()))


def test_bfloat16_operands_keep_float32_decays_and_state(path):
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
def test_a_ragged_sequence_or_sub_block_is_refused(seq, chunk, sub, path):
    q, k, v, g, beta = (x[:, :seq] for x in inputs(0.3))
    with pytest.raises(ValueError, match="pad it upstream"):
        linear_attention.kimi_delta_rule(q, k, v, g, beta, chunk=chunk,
                                         sub=sub)


def test_the_scope_and_the_trace_say_which_rule_the_step_holds(path):
    args = inputs(0.3)
    text = jax.jit(rule(32, 8)).lower(*args).as_text(debug_info=True)
    assert "hvd.linattn.scan" in text
    traced = jax.make_jaxpr(rule(32, 8))(*args)
    # the pair terms: no primitive where the shapes fill no tile, or, where
    # the test has put the kernel, a sequence's two chunks of its three
    # heads a grid step
    assert [(name, how["step"], how["heads"], how["sub"]) for name, how in
            bound(traced, prefix="hvd_kda_pair_terms")] == {
        "plain": [], "kernel": [("hvd_kda_pair_terms", 2, 3, 8)]}[path]
    # the chunk loop: these widths fill no tile, so one plain ``scan`` over
    # the sequence's chunks
    assert not linear_attention._scan_heads_a_step(args[1], args[2], 32)
    assert not bound(traced, prefix="hvd_kda_chunk_scan")
    assert [how["length"] for _, how in
            bound(traced, prefix="scan")] == [S // 32]

    def summed(closed):  # what each running sum is taken over
        eqns = list(equations(closed.jaxpr))
        return ([eqn.invars[0].aval.shape for eqn in eqns
                 if eqn.primitive.name == "cumsum"],
                [eqn.invars[1].aval.shape for eqn in eqns
                 if eqn.primitive.name == "dot_general"
                 and eqn.params["precision"] is not None
                 and eqn.invars[0].aval.shape == (B, S // 32, 32, 32)])

    # a decay a key channel: gamma is a product with the triangle over the
    # heads' DK lanes side by side; the scalar rule's is one number a head
    assert summed(traced) == ([], [(B, S // 32, 32, H * DK)])
    assert summed(jax.make_jaxpr(
        lambda *a: linear_attention.gated_delta_rule(*a, chunk=32))(
            *args[:3], args[3][..., 0], args[4]))[0] == [
                (B, H, S // 32, 32)]


PAIR_FORMS = [(64, 16, 128), (16, 4, 16)]  # (chunk, sub-block, d_k)


def tokens_major(x):
    """``[B, H, N, C, d]`` as the kernels take it, ``[B, S, H, d]``: the
    inverse of ``linear_attention._by_head``."""
    batch, heads, count, chunk, width = x.shape
    return jnp.moveaxis(x, 1, 3).reshape(batch, count * chunk, heads, width)


def pair_operands(rate, chunk, width, dtype, seed=5, count=3):
    """``q``, ``k`` in ``dtype`` and a float32 ``gamma`` that falls inside
    each chunk, ``[1, count * chunk, 2, width]`` as the kernels take them
    (``count`` chunks of two heads), and a cotangent for each result, ``[1,
    2, count, chunk, chunk]``."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (1, 2, count, chunk, width)
    q, k = (jax.random.normal(key, shape).astype(dtype) for key in keys[:2])
    gamma = jnp.cumsum(-rate * jax.random.uniform(
        keys[2], shape, minval=0.5, maxval=1.0), -2)
    bars = tuple(jax.random.normal(key, shape[:-1] + (chunk,))
                 for key in keys[3:])
    return tuple(tokens_major(x) for x in (q, k, gamma)), bars


def plain_pair_terms(chunk, sub, dtype):
    """``_pair_terms`` of tokens-major operands, through ``_by_head``."""
    return lambda *a: linear_attention._pair_terms(
        *(linear_attention._by_head(x, chunk) for x in a), sub, dtype)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("rate", sorted(RATES))
@pytest.mark.parametrize("chunk,sub,width", PAIR_FORMS)
def test_the_kernels_are_the_plain_pair_terms(chunk, sub, width, rate, dtype):
    """``pair_terms_kernel`` interpreted, fed ``[B, S, H, d]``, against
    ``_pair_terms`` on the head-major view of the same arrays: both
    results to float32's rounding (the same reference rows, the same
    roundings to ``dtype``), the strict upper triangle exactly zero, and
    ``dq``, ``dk``, ``dgamma`` against ``jax.vjp`` of the plain form: to
    float32's rounding for float32 operands, to the rounding of a
    bfloat16 cotangent for bfloat16 ones (the plain form rounds the far
    pairs' cotangents once more on the way)."""
    dtype = jnp.dtype(dtype)
    operands, bars = pair_operands(RATES[rate], chunk, width, dtype)
    want, plain_vjp = jax.vjp(plain_pair_terms(chunk, sub, dtype), *operands)
    got, kernel_vjp = jax.vjp(
        lambda *a: linear_attention.pair_terms_kernel(
            *a, chunk, sub, dtype, True), *operands)
    for name, a, b in zip(("inside", "a"), got, want):
        assert a.dtype == jnp.float32 and a.shape == b.shape, name
        assert bool((jnp.triu(a, 1) == 0).all()), name
        np.testing.assert_allclose(a, b, rtol=0, err_msg=name,
                                   atol=2e-6 * float(jnp.abs(b).max()))
    room = 2e-6 if dtype == jnp.float32 else 2e-2
    got_bars = kernel_vjp(bars)
    for name, a, b in zip(("dq", "dk", "dgamma"), got_bars, plain_vjp(bars)):
        assert a.dtype == b.dtype and bool(jnp.isfinite(a).all()), name
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        scale = float(jnp.abs(b).max())
        if name == "dgamma":
            # q dq + k (dk as the left side - dk as the right): the
            # diagonal's terms cancel there, to the rounding of q dq
            scale += float(jnp.abs(got_bars[0].astype(jnp.float32)).max())
        np.testing.assert_allclose(a, b, rtol=0, atol=room * scale,
                                   err_msg=name)


def test_a_grid_step_takes_the_chunks_that_divide_their_number(monkeypatch):
    """A grid step of the pair kernels is chunks x heads: four chunks of a
    sequence where four divide them, else the largest divisor under the
    limit (a sequence's six chunks go three a grid step, or all six under a
    limit of eight, or one), of as many heads as the chunk loop's kernels
    take (two here: both, or one under a limit of one); the primitive the
    call binds carries the split, and no result or cotangent depends on
    it."""
    operands, bars = pair_operands(0.3, 16, 16, jnp.float32, count=6)
    assert linear_attention.PAIR_CHUNKS_A_STEP == 4
    seen = []
    for chunks, heads, step in ((4, 8, (3, 2)), (8, 8, (6, 2)),
                                (1, 1, (1, 1))):
        monkeypatch.setattr(linear_attention, "PAIR_CHUNKS_A_STEP", chunks)
        monkeypatch.setattr(linear_attention, "SCAN_HEADS_A_STEP", heads)
        how = linear_attention._how(operands[1], 16, 4, jnp.float32, True)
        assert (how["step"], how["heads"]) == step
        got, vjp = jax.vjp(
            lambda *a: linear_attention.pair_terms_kernel(
                *a, 16, 4, jnp.float32, True), *operands)
        seen.append(tuple(got) + vjp(bars))
        (name, traced), = bound(
            lambda *a: linear_attention.pair_terms_kernel(
                *a, 16, 4, jnp.float32, True), *operands)
        assert (name, traced["step"], traced["heads"], traced["sub"]) == (
            "hvd_kda_pair_terms",) + step + (4,)
    for other in seen[1:]:
        jax.tree.map(np.testing.assert_array_equal, seen[0], other)
    want = plain_pair_terms(16, 4, jnp.float32)(*operands)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, rtol=0, atol=1e-5), seen[0][:2], want)


@pytest.mark.parametrize("fault", ["far_pairs_left_float32",
                                   "cubes_rounded_to_bfloat16"])
def test_a_moved_rounding_point_is_told(fault, monkeypatch):
    """The rounding points are the benchmark's records' margin (the worst
    recorded seed has half of its 2^-8 left): with bfloat16 operands the
    kernel stands 2e-6 of the largest entry from the plain form
    (``test_the_kernels_are_the_plain_pair_terms``), and fifty times that
    and more once the far pairs' factors are left float32 where the
    plain form rounds them to bfloat16, or a sub-block's cube is rounded
    to bfloat16 where the plain form keeps it float32."""
    sub_block = linear_attention._sub_block

    def faulty(*args):
        if fault == "far_pairs_left_float32":
            return sub_block(*args[:-1], jnp.float32)
        q, k, pieces, far = sub_block(*args)
        return q, k, [(top, cube.astype(jnp.bfloat16).astype(jnp.float32))
                      for top, cube in pieces], far

    operands, _ = pair_operands(RATES["mild"], 64, 128, jnp.bfloat16)
    want = plain_pair_terms(64, 16, jnp.bfloat16)(*operands)

    def furthest():  # (a function of its own each time: nothing is cached)
        got = jax.jit(lambda *a: linear_attention.pair_terms_kernel(
            *a, 64, 16, jnp.bfloat16, True))(*operands)
        return [float(jnp.abs(a - b).max() / jnp.abs(b).max())
                for a, b in zip(got, want)]

    assert max(furthest()) < 2e-6
    monkeypatch.setattr(linear_attention, "_sub_block", faulty)
    assert min(furthest()) > 1e-4


TEXT_CHUNK = 32  # of the cases that read the lowered text


def lowered(fn, what: str, *args) -> str:
    """``fn``'s lowered text, or that of its five gradients."""
    if what == "gradient":
        fn = jax.grad(lambda *a, fn=fn: fn(*a).sum(), argnums=range(5))
    return jax.jit(fn).lower(*args).as_text()


def running_sums_products(text: str):
    """The precisions of the ``dot_general``s that take ``g`` in chunks,
    or ``gamma``'s cotangent (a ``[B, N, C, H * d_k]`` float32 operand, as
    both lie: a chunk's rows of every head's lanes), against a ``[..., C,
    C]`` one: the running sum and its transpose. No other product of the
    rule has an operand of that shape."""
    chunk = TEXT_CHUNK
    g_dims, found = sorted([B, S // chunk, chunk, H * DK]), []
    for line in text.splitlines():
        types = re.search(
            r"stablehlo\.dot_general.*precision = \[(\w+), (\w+)\].*"
            r": \(tensor<([\dx]+)xf32>, tensor<([\dx]+)xf32>\)", line)
        if not types:
            continue
        shapes = [[int(n) for n in t.split("x")] for t in types.groups()[2:]]
        for one, other in (shapes, shapes[::-1]):
            if sorted(one) == g_dims and other[-2:] == [chunk, chunk]:
                found.append(types.groups()[:2])
    return found


@pytest.mark.parametrize("what", ["value", "gradient"])
def test_no_reduce_window_is_in_the_program(what):
    """As ``solve_unit_lower``'s "no ``triangular_solve``": a ``cumsum``
    over the rows of ``[C, d_k]`` lowers to a ``reduce_window``, which the
    v5e ran at 7 G elements a second, 50 ms of the cell's step. The
    transposed running sum of the backward pass is a product too."""
    text = lowered(rule(TEXT_CHUNK, 16), what, *inputs(0.3))
    assert "reduce_window" not in text and "cumsum" not in text
    assert len(running_sums_products(text)) == {"value": 1, "gradient": 2}[what]


@pytest.mark.parametrize("asked", [None, "bfloat16"])
def test_the_running_sums_product_is_at_full_precision(asked):
    """One bfloat16 pass would round ``g`` to 8 bits before it is summed.
    A CPU computes float32 whatever is asked, so the text is what can be
    held here: ``HIGHEST`` on both operands, forward and transposed, also
    where the caller's default is lower."""
    with (jax.default_matmul_precision(asked) if asked
          else contextlib.nullcontext()):
        text = lowered(rule(TEXT_CHUNK, 16), "gradient", *inputs(0.3))
    assert running_sums_products(text) == [("HIGHEST", "HIGHEST")] * 2


@pytest.mark.parametrize("rate", sorted(RATES))
def test_the_running_sum_is_a_float64_sums(rate, monkeypatch):
    """``gamma`` as the rule forms it (read where ``_pair_terms`` is handed
    it, with the inner ``jax.checkpoint`` out of the way so that it is an
    array) against ``numpy``'s float64 ``cumsum`` inside each chunk: float32's
    sum in another order, 1e-6 of its largest."""
    chunk, seen = 32, {}
    pair_terms = linear_attention._pair_terms

    def watched(q, k, gamma, sub, dtype):
        seen["gamma"] = gamma
        return pair_terms(q, k, gamma, sub, dtype)

    monkeypatch.setattr(linear_attention, "_pair_terms", watched)
    monkeypatch.setattr(jax, "checkpoint", lambda fn: fn)
    args = inputs(RATES[rate], seed=4)
    linear_attention.kimi_delta_rule(*args, chunk=chunk, sub=8)
    g = np.asarray(args[3], np.float64).reshape(B, S // chunk, chunk, H, DK)
    want = np.moveaxis(np.cumsum(g, 2), 3, 1)          # [B, H, N, C, d_k]
    got = np.asarray(seen["gamma"])
    assert got.dtype == np.float32 and got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-6 * np.abs(want).max())


# sha256 of jit(gated_delta_rule).lower(...).as_text(), value and gradient,
# at commit bee4942, the parent of the running sum as a product: the scalar
# rule's cumsum is [B, H, N, C] without the 128 lanes, and stays
SCALAR_RULES_TEXT = {
    "value": "ffae072f98bc93e2cb127923d64ceb225aa48b7a5f04845b5b164a7bbd7e51b2",
    "gradient": "94e46043824c91c990e077ab7372c3a8140faddcadc8b3b6c532f1951b6693e2",
}


@pytest.mark.parametrize("what", sorted(SCALAR_RULES_TEXT))
def test_the_scalar_rule_lowers_to_the_text_it_had(what):
    q, k, v, g, beta = inputs(0.3)

    def fn(*a):  # the text holds the name
        return linear_attention.gated_delta_rule(*a, chunk=TEXT_CHUNK)

    text = lowered(fn, what, q, k, v, g[..., 0], beta)
    assert "reduce_window" in text
    assert hashlib.sha256(text.encode()).hexdigest() == SCALAR_RULES_TEXT[what]


# --- the solve and the chunk loop: ``_chunk_scan`` (plain JAX: any platform
# but a TPU) and ``chunk_scan_kernel`` (a Pallas kernel and its backward
# kernel: the program lowered for a TPU), interpreted here

SCAN_FORMS = {"toy": (2, 4, 3, 16, 8, 16),  # B, H, chunks, C, d_k, d_v
              "the_cells_widths": (1, 8, 2, 64, 128, 128)}


@pytest.fixture
def scanned(monkeypatch):
    """The rule with the chunk loop's kernels where the lowering platform
    would have put them, interpreted (the pair terms stay plain)."""
    monkeypatch.setattr(
        linear_attention, "_chunk_scan_where_lowered",
        lambda *a: linear_attention.chunk_scan_kernel(*a, True))


def scan_operands(form, rate, dtype, seed=6):
    """What ``kimi_delta_rule`` hands the chunk loop (``form``: a name of
    ``SCAN_FORMS`` or such a tuple): ``q``, ``k``, ``v`` in ``dtype`` and a
    float32 ``gamma`` that falls inside each chunk, ``[B, S, H, d]``;
    ``beta [B, H, N, C, 1]`` and the pair terms of ``q``, ``k`` and
    ``gamma``, ``[B, H, N, C, C]``; and a cotangent of ``o``."""
    batch, heads, count, size, d_k, d_v = SCAN_FORMS.get(form, form)
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    lead = (batch, heads, count, size)
    q, k = (jax.random.normal(key, lead + (d_k,)) for key in keys[:2])
    q = (q / jnp.linalg.norm(q, axis=-1, keepdims=True)
         * d_k ** -0.5).astype(dtype)
    k = (k / jnp.linalg.norm(k, axis=-1, keepdims=True)).astype(dtype)
    v = jax.random.normal(keys[2], lead + (d_v,)).astype(dtype)
    gamma = jnp.cumsum(-rate * jax.random.uniform(
        keys[3], lead + (d_k,), minval=0.5, maxval=1.0), -2)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], lead + (1,)))
    inside, a = linear_attention._pair_terms(q, k, gamma, size // 4, dtype)
    o_bar = jax.random.normal(
        keys[5], (batch, count * size, heads, d_v)).astype(dtype)
    q, k, v, gamma = (tokens_major(x) for x in (q, k, v, gamma))
    return (q, k, v, gamma, beta, inside, a), o_bar


SCAN_NAMES = ("dq", "dk", "dv", "dgamma", "dbeta", "dinside", "da")


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("rate", sorted(RATES))
@pytest.mark.parametrize("form", sorted(SCAN_FORMS))
def test_the_scan_kernels_are_the_plain_chunk_form(form, rate, dtype):
    """``chunk_scan_kernel`` interpreted, fed ``[B, S, H, d]``, against
    ``_chunk_scan`` on the head-major view of the same arrays: ``o`` and
    the seven cotangents (with the pair kernels' own, the rule's five
    gradients). In float32 to float32's rounding (the solve by other sums,
    the products of three bfloat16 terms an operand). In bfloat16 the same
    rounding points: ``o`` is the plain form's to a last bit of a few
    entries (a moved rounding point moves every entry: 2^-9 of each), the
    cotangents to a bfloat16 cotangent's rounding (a CPU's plain form
    takes them unrounded into its transposed products, a TPU's and the
    kernel round them)."""
    dtype = jnp.dtype(dtype)
    operands, o_bar = scan_operands(form, RATES[rate], dtype)
    with jax.default_matmul_precision("highest"):
        want, plain_vjp = jax.vjp(linear_attention._chunk_scan_of_tokens,
                                  *operands)
        got, kernel_vjp = jax.vjp(
            lambda *a: linear_attention.chunk_scan_kernel(*a, True),
            *operands)
        want_bars, got_bars = plain_vjp(o_bar), kernel_vjp(o_bar)
    assert got.dtype == want.dtype == dtype and got.shape == want.shape
    off = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32))
    scale = float(jnp.abs(want.astype(jnp.float32)).max())
    if dtype == jnp.float32:
        assert float(off.max()) <= 2e-6 * scale
    else:
        assert float(off.max()) <= 2 ** -7 * scale
        assert float((off > 0).mean()) < 0.02
    room = 1e-5 if dtype == jnp.float32 else 2e-2
    for name, a, b in zip(SCAN_NAMES, got_bars, want_bars):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert bool(jnp.isfinite(a.astype(jnp.float32)).all()), name
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        np.testing.assert_allclose(
            a, b, rtol=0, atol=room * float(jnp.abs(b).max()) + 1e-9,
            err_msg=name)


@pytest.mark.parametrize("rate", sorted(RATES))
@pytest.mark.parametrize("chunk,sub", FORMS)
def test_the_scan_kernels_chunk_form_is_the_recurrence(chunk, sub, rate,
                                                       scanned):
    args = inputs(RATES[rate])
    with jax.default_matmul_precision("highest"):
        want = jax.jit(recurrence)(*args)
        got = jax.jit(rule(chunk, sub))(*args)
    assert got.shape == (B, S, H, DV) and got.dtype == jnp.float32
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("rate", sorted(RATES))
def test_the_scan_kernels_gradients_are_the_recurrences(rate, scanned):
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


def test_the_scan_kernels_form_no_positive_exponent(scanned, monkeypatch):
    """At e^-20 a token, with an exponential that answers ``nan`` to any
    positive argument: the kernels' ``exp(gamma)`` and ``exp(gamma_C -
    gamma)`` are of nothing positive, and the rule and its five gradients
    stay finite through them."""
    args = inputs(RATES["e-20_a_token"])
    exp = jnp.exp
    monkeypatch.setattr(
        jnp, "exp", lambda x: jnp.where(x > 0, jnp.nan, exp(x)))
    out, grads = jax.value_and_grad(
        lambda *a: rule(32, 8)(*a).sum(), argnums=range(5))(*args)
    assert all(bool(jnp.isfinite(x).all()) for x in (out,) + grads)


def test_a_grid_step_takes_two_or_eight_heads(monkeypatch):
    """Eight heads over three chunks (a count neither step divides) in grid
    steps of eight, of two and of one: the primitive the call binds says
    which, and no result or cotangent depends on the split beyond
    float32's rounding (two heads' solves share the MXU's passes where they
    lie side by side)."""
    size = 16
    operands, o_bar = scan_operands((1, 8, 3, size, 8, 16), 0.3, jnp.float32)
    seen = []
    for step in (8, 2, 1):
        monkeypatch.setattr(linear_attention, "SCAN_HEADS_A_STEP", step)
        got, vjp = jax.vjp(
            lambda *a: linear_attention.chunk_scan_kernel(*a, True),
            *operands)
        seen.append((got,) + vjp(o_bar))
        (name, traced), = bound(
            lambda *a: linear_attention.chunk_scan_kernel(*a, True),
            *operands)
        assert (name, traced["step"], traced["chunk"]) == (
            "hvd_kda_chunk_scan", step, size)
    for other in seen[1:]:
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            a, b, rtol=0, atol=1e-6 * float(jnp.abs(a).max())),
            seen[0], other)
    want = linear_attention._chunk_scan_of_tokens(*operands)
    np.testing.assert_allclose(seen[0][0], want, rtol=0, atol=1e-6)


def test_a_recomputed_layer_keeps_nothing_the_scan_kernels_return(scanned):
    """Under the decoders' policy, which keeps what a ``pallas_call``
    returns: the chunk loop is a primitive of its own, so neither ``o`` nor
    the states that enter the chunks nor the inverses are among a layer's
    saved residuals, and its forward pass asks for none of them (the
    forward primitive's DCE rule): forward without, recomputed with,
    backward."""
    from jax._src.ad_checkpoint import saved_residuals

    from horovod_tpu.models import parts

    q, k, v, g, beta = inputs(0.3)

    def layer(x, w):
        x = x * w
        return x + rule(32, 8)(q, k, x, g, beta)

    recomputed = jax.checkpoint(
        layer, policy=parts.save_kernels_and_projections)
    kept = saved_residuals(recomputed, v, jnp.ones(DV))
    states, inverses = (B, S // 32, H, DK, DV), (B, H, S // 32, 32, 32)
    assert kept and all(
        source.startswith(("from the argument", "from a constant"))
        and aval.shape not in (states, inverses) for aval, source in kept), (
            kept)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda x, w: recomputed(x, w).sum(), argnums=(0, 1)))(
            v, jnp.ones(DV))
    scans = []

    def walk(eqns):
        for eqn in eqns:
            if eqn.primitive.name.startswith("hvd_kda_chunk_scan"):
                scans.append((eqn.primitive.name, eqn.params.get("states")))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub.eqns)

    walk(jaxpr.jaxpr.eqns)
    assert sorted(scans, key=str) == [
        ("hvd_kda_chunk_scan", False), ("hvd_kda_chunk_scan", True),
        ("hvd_kda_chunk_scan_backward", None)], scans


def test_a_shape_that_fills_no_tile_takes_the_plain_chunk_loop():
    """The toys' widths trace the plain form whatever the platform: no
    primitive, a ``scan`` at once; the cell's widths are a primitive whose
    lowering for this platform is the plain form (the ``while`` is in the
    text and no kernel) and, interpreted, the kernels: eight heads a grid
    step."""
    def primitives(*operands):
        return [name for name, _ in bound(
            linear_attention._chunk_scan_where_lowered, *operands,
            prefix="")]

    fills = linear_attention._scan_heads_a_step
    toy, _ = scan_operands("toy", 0.3, jnp.float32)
    assert not fills(toy[1], toy[2], toy[-1].shape[-1])
    traced = primitives(*toy)
    assert "hvd_kda_chunk_scan" not in traced and "scan" in traced
    wide, _ = scan_operands("the_cells_widths", 0.3, jnp.bfloat16)
    traced = primitives(*wide)  # no ``scan`` yet: the lowering's choice
    assert "hvd_kda_chunk_scan" in traced and "scan" not in traced
    text = jax.jit(linear_attention._chunk_scan_where_lowered).lower(
        *wide).as_text()
    assert "tpu_custom_call" not in text and "while" in text
    (_, interpreted), = bound(
        lambda *a: linear_attention.chunk_scan_kernel(*a, True), *wide)
    assert interpreted["interpret"] and interpreted["step"] == (
        linear_attention.SCAN_HEADS_A_STEP) == 8
    # a width of 64, seven heads or a chunk of 48 fill no tile
    q, k, v = wide[:3]
    assert fills(k, v, 64) == 8
    assert not fills(k[..., :64], v, 64)
    assert not fills(k, v[..., :64], 64)
    assert not fills(k[:, :, :7], v[:, :, :7], 64)
    assert not fills(k, v, 48)


# --- the layout the kernels take: nothing ``d`` wide is transposed

KERNEL_FORMS = {  # what each primitive's lowering for a TPU traces
    "hvd_kda_pair_terms": linear_attention._forward_by_kernel,
    "hvd_kda_pair_terms_backward": linear_attention._backward_by_kernel,
    "hvd_kda_chunk_scan": linear_attention._scan_forward_by_kernel,
    "hvd_kda_chunk_scan_backward": linear_attention._scan_backward_by_kernel,
}


def under_the_scope(jaxpr, met: list, moved: list, least: int,
                    stack: str = "") -> None:
    """Walks ``jaxpr`` and everything it calls (``stack``: the name stacks
    of the calls around it), a ``hvd_kda_*`` primitive's kernel form (the
    ``pallas_call`` and what stands around it) in the primitive's place:
    ``met`` gains every such primitive's name and ``moved`` every
    ``transpose`` of ``least`` elements or more, both under
    ``hvd.linattn.scan``."""
    for eqn in jaxpr.eqns:
        here = f"{stack}/{eqn.source_info.name_stack}"
        name = eqn.primitive.name
        if "hvd.linattn.scan" not in here:
            assert name not in KERNEL_FORMS, eqn
        elif name in KERNEL_FORMS:
            met.append(name)
            form = jax.make_jaxpr(functools.partial(
                KERNEL_FORMS[name], **eqn.params))(*(
                    jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
                    for v in eqn.invars))
            under_the_scope(form.jaxpr, met, moved, least, here)
        elif name == "transpose" and eqn.invars[0].aval.size >= least:
            moved.append((eqn.invars[0].aval.shape, eqn.outvars[0].aval.shape,
                          eqn.params["permutation"]))
        for inner in jax.core.jaxprs_in_params(eqn.params):
            under_the_scope(inner, met, moved, least, here)


@pytest.mark.parametrize("what", ["value", "gradient"])
def test_nothing_d_wide_is_transposed_under_the_scope(what):
    """The jaxpr of ``kimi_delta_rule`` at the cell's shapes (one sequence
    of 8,192 in 32 heads of 128, chunks of 64 in sub-blocks of 16; traced,
    nothing allocated or run), with each of the four primitives replaced by
    what its lowering for a TPU traces: the ``pallas_call`` takes ``q``,
    ``k``, ``v``, ``gamma`` and gives ``o``, ``dq``, ``dk``, ``dv``,
    ``dgamma`` where they lie, so no ``transpose`` of an array as large as
    ``q`` is left, forward or backward (``beta``'s, one number a token, and
    nothing else). ``gamma``'s running sum and its transposed sum are
    products whose results lead with batch and chunk."""
    wide = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16)
    args = (wide, wide, wide,
            jax.ShapeDtypeStruct(wide.shape, jnp.float32),
            jax.ShapeDtypeStruct(wide.shape[:3], jnp.float32))

    def fn(*a):
        return linear_attention.kimi_delta_rule(*a, chunk=64, sub=16)

    if what == "gradient":
        fn = jax.grad(lambda *a, fn=fn: fn(*a).astype(jnp.float32).sum(),
                      argnums=range(5))
    met, moved = [], []
    under_the_scope(jax.make_jaxpr(fn)(*args).jaxpr, met, moved,
                    least=8192 * 32 * 128)
    assert sorted(met) == sorted(
        name for name in KERNEL_FORMS
        if what == "gradient" or not name.endswith("_backward")), met
    assert not moved, moved
