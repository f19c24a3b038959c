"""The multi-tile flash kernels with values of another width than the keys'
(latent attention without its rotary split scores over 192 lanes and reads
values of 128; here 24 and 16, and 16 and 24): against dense attention
forward and in all three gradients, causal and not, one tile and several,
grouped keys and a window; the context and ``dv`` come at the values'
width; the scale is the queries' width's; the kernels sit under
``hvd.attn.mla``; the gauge says both widths; a tokens-major call refuses
two widths; and a call whose widths agree lowers to the text it had before
the kernels took a second width."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import attention as att
from horovod_tpu.ops.attention import flash_attention, flash_attention_lse
from traced import pallas_calls


def dense(q, k, v, causal=True, window=None):
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / q.shape[-1] ** 0.5
    ahead = (jnp.arange(q.shape[2])[:, None] - jnp.arange(k.shape[2])[None])
    seen = ahead >= 0 if causal else jnp.ones_like(ahead, bool)
    if window is not None:
        seen &= ahead < window
    return jnp.einsum(
        "bhqk,bhkd->bhqd",
        jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1), v)


def operands(heads, kv_heads, seq, qk, v_dim, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(keys[0], (2, heads, seq, qk)),
            jax.random.normal(keys[1], (2, kv_heads, seq, qk)),
            jax.random.normal(keys[2], (2, kv_heads, seq, v_dim)),
            jax.random.normal(keys[3], (2, heads, seq, v_dim)))


# heads, kv heads, S, tile, qk lanes, v lanes, causal, window
CASES = [
    pytest.param(2, 2, 64, 16, 24, 16, True, None, id="causal-24-16"),
    pytest.param(2, 2, 64, 16, 16, 24, True, None, id="causal-16-24"),
    pytest.param(2, 2, 64, 16, 24, 16, False, None, id="full-24-16"),
    pytest.param(2, 2, 32, 32, 24, 16, True, None, id="one-tile-24-16"),
    pytest.param(4, 2, 64, 16, 24, 16, True, None, id="grouped-24-16"),
    pytest.param(4, 1, 64, 16, 24, 8, True, 20, id="grouped-window-24-8"),
]


@pytest.mark.parametrize(
    "heads,kv_heads,seq,tile,qk,v_dim,causal,window", CASES)
def test_two_widths_are_dense_attention(heads, kv_heads, seq, tile, qk,
                                        v_dim, causal, window):
    q, k, v, weight = operands(heads, kv_heads, seq, qk, v_dim)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, window=window,
                               block_q=tile, block_k=tile, interpret=True)

    got = jax.jit(flash)(q, k, v)
    want = dense(q, k, v, causal, window)
    assert got.shape == (2, heads, seq, v_dim)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6 * float(
        jnp.abs(want).max()) + 2e-6)

    def scalar(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * weight)

    got = jax.jit(jax.grad(scalar(flash), (0, 1, 2)))(q, k, v)
    want = jax.grad(scalar(lambda q, k, v: dense(q, k, v, causal, window)),
                    (0, 1, 2))(q, k, v)
    for name, a, b in zip("dq dk dv".split(), got, want):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(
            a, b, rtol=0, atol=1e-5 * float(jnp.abs(b).max()), err_msg=name)


def test_the_log_sum_exp_entry_takes_two_widths_too():
    q, k, v, _ = operands(2, 2, 64, 24, 16, seed=1)
    out, lse = flash_attention_lse(q, k, v, causal=True, block_q=16,
                                   block_k=16, interpret=True)
    assert out.shape == (2, 2, 64, 16) and lse.shape == (2, 2, 64)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / 24 ** 0.5
    seen = jnp.tril(jnp.ones((64, 64), bool))
    want = jax.nn.logsumexp(jnp.where(seen, scores, -jnp.inf), -1)
    np.testing.assert_allclose(lse, want, rtol=0, atol=1e-5)


def test_the_scope_and_the_blocks_tell_the_latent_layers_kernels():
    q, k, v, _ = operands(2, 2, 64, 24, 16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                               interpret=True).sum()

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, k, v).as_text(
        debug_info=True)
    assert "hvd.attn.mla/hvd.attn.fwd" in text
    assert "hvd.attn.mla/hvd.attn.bwd" in text
    head_major = jax.ShapeDtypeStruct((4, 64, 24), q.dtype)
    assert att._value_width(head_major, v.reshape(4, 64, 16), 24) == 16
    # every kernel reads queries and keys of 24 lanes and values of 16
    assert {(shapes[0][-1], shapes[1][-1], shapes[2][-1]) for _, shapes in
            pallas_calls(jax.grad(loss, (0, 1, 2)), q, k, v)} == {
        (24, 24, 16)}
    # equal widths: no such scope, and the values' blocks are the keys'
    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, k, k).as_text(
        debug_info=True)
    assert "hvd.attn.mla" not in text and "hvd.attn.fwd" in text
    assert att._value_width(head_major, head_major, 24) == 24


def test_grouped_tokens_major_rows_are_not_taken_for_two_widths():
    """Tokens-major, q's row is ``H * D`` lanes and k's and v's ``KV heads
    * D``: fewer key/value heads make the rows differ, not the widths. The
    kind scope is read off k and v, so a windowed grouped call stays under
    ``hvd.attn.window`` (SmallThinker's) and none under ``hvd.attn.mla``."""
    q = jnp.zeros((1, 64, 4 * 128), jnp.float32)
    kv = jnp.zeros((1, 64, 2 * 128), jnp.float32)

    def loss(q, k, v):
        return att.flash_attention_tokens_major(
            q, k, v, 4, causal=True, window=24, block_q=16, block_k=16,
            interpret=True).sum()

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, kv, kv).as_text(
        debug_info=True)
    assert "hvd.attn.window/hvd.attn.fwd" in text
    assert "hvd.attn.window/hvd.attn.bwd" in text
    assert "hvd.attn.mla" not in text


@pytest.mark.parametrize("bad", ["v_rows", "q_width", "tokens_major"])
def test_what_is_refused(bad):
    q, k, v, _ = operands(2, 2, 64, 24, 16)
    if bad == "v_rows":
        with pytest.raises(ValueError, match="v \\[B, KV heads, S, Dv\\]"):
            flash_attention(q, k, v[:, :, :32], causal=True, interpret=True)
    elif bad == "q_width":
        with pytest.raises(ValueError, match="whose heads divide"):
            flash_attention(q[..., :16], k, v, causal=True, interpret=True)
    else:
        def rows(x):  # [B, H, S, D] -> [B, S, H * D]
            return x.transpose(0, 2, 1, 3).reshape(2, 64, -1)

        wide = jnp.concatenate([q, q, q, q, q, q, q, q], -1)[..., :128]
        k128 = jnp.concatenate([k] * 8, -1)[..., :128]
        v256 = jnp.concatenate([v] * 16, -1)
        # a row of 256 lanes beside keys of 128: four key/value heads, or
        # two of twice the width; either way no such call
        with pytest.raises(ValueError, match="flash attention"):
            att.flash_attention_tokens_major(
                rows(wide), rows(k128), rows(v256), 2, causal=True,
                block_q=16, block_k=16, interpret=True)


# sha256 of jit(grad(sum(flash_attention(...)))).lower(...).as_text() at
# commit c659eac, the parent of the two-width kernels (interpreted, so no
# Mosaic body and no position is in the text)
PARENTS_TEXT = {
    "causal": "a52883de3e74dde3daca3726a75241ac374de7edddd9e5127ec28176fd4920a0",
    "grouped_window": "07b413266978868f474456d1868a4ebeab02368b23f6e152b6e10c821b8b0262",
}


@pytest.mark.parametrize("kind", sorted(PARENTS_TEXT))
def test_equal_widths_lower_to_the_text_they_had(kind):
    q = jnp.zeros((1, 2, 64, 16), jnp.float32)
    kv = q if kind == "causal" else jnp.zeros((1, 1, 64, 16), jnp.float32)
    window = None if kind == "causal" else 24

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                               interpret=True, window=window).sum()

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, kv, kv).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENTS_TEXT[kind]
