"""Sequence/context parallelism tests on the 8-device CPU mesh: ring and
Ulysses attention must match the dense single-device oracle; the Pallas
flash kernel (interpret mode on CPU) must match the blockwise reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from horovod_tpu.ops.attention import (
    blockwise_attention_reference,
    flash_attention,
)
from horovod_tpu.parallel import sequence as sp


def dense_attention(q, k, v, causal=False):
    B, H, S, D = q.shape
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) / (D ** 0.5)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def make_qkv(B=2, H=4, S=64, D=16, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (B, H, S, D)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


class TestBlockwiseOracle:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.slow
    def test_matches_dense(self, causal):
        q, k, v = make_qkv()
        out = blockwise_attention_reference(q, k, v, causal=causal,
                                            block_size=16)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(dense_attention(q, k, v, causal)),
            rtol=2e-5, atol=2e-5,
        )

    def test_cross_shard_offsets(self):
        q, k, v = make_qkv(S=16)
        # K shard entirely in the future of the Q shard: every row fully
        # masked -> zeros (not NaN). Past K shard: fully visible == plain
        # (non-causal) attention against that shard.
        masked = blockwise_attention_reference(
            q, k, v, causal=True, q_offset=0, k_offset=3 * 16)
        assert np.allclose(np.asarray(masked), 0.0)
        visible = blockwise_attention_reference(
            q, k, v, causal=True, q_offset=3 * 16, k_offset=0)
        want = blockwise_attention_reference(q, k, v, causal=False)
        np.testing.assert_allclose(
            np.asarray(visible), np.asarray(want), rtol=2e-5, atol=2e-5)


class TestFlashKernel:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        q, k, v = make_qkv(B=1, H=2, S=256, D=64)
        out = flash_attention(q, k, v, causal=causal, block_q=128,
                              block_k=128, interpret=True)
        want = dense_attention(q, k, v, causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5,
        )

    def test_rejects_ragged(self):
        q, k, v = make_qkv(S=100)
        with pytest.raises(ValueError, match="multiple"):
            flash_attention(q, k, v, block_q=128, block_k=128, interpret=True)

    def test_causal_cross_length_requires_offsets(self):
        # Regression (round-1 advisor): causal with Sq != Sk used to apply
        # a silently wrong top-left mask; now it demands explicit offsets.
        q, k, v = make_qkv(B=1, H=1, S=256, D=32)
        with pytest.raises(ValueError, match="ambiguous"):
            flash_attention(q[:, :, :128], k, v, causal=True, interpret=True)

    @pytest.mark.slow
    def test_causal_offsets_match_oracle(self):
        q, k, v = make_qkv(B=1, H=2, S=256, D=32)
        qs = q[:, :, :128]
        # Bottom-right (decode-style) alignment via q_offset = Sk - Sq.
        out = flash_attention(qs, k, v, causal=True, q_offset=128,
                              interpret=True)
        want = blockwise_attention_reference(qs, k, v, causal=True,
                                             q_offset=128)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.slow
    def test_backward_matches_reference(self, causal):
        # VERDICT r2 item 4: the kernel must be trainable — custom_vjp
        # Pallas backward vs jax.grad of the jnp oracle.
        q, k, v = make_qkv(B=1, H=2, S=256, D=64)

        def loss_flash(q, k, v):
            out = flash_attention(q, k, v, causal=causal, interpret=True)
            return jnp.sum(out * out)

        def loss_ref(q, k, v):
            out = blockwise_attention_reference(q, k, v, causal=causal)
            return jnp.sum(out * out)

        got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("causal", [False, True])
    def test_backward_two_pass_path_matches_reference(self, causal):
        """Explicit sub-sequence blocks force the TWO-PASS backward (dq +
        dkv kernels) — the default auto-block now routes every
        single-tile sequence to the fused kernel, which would otherwise
        leave the multi-tile path untested."""
        q, k, v = make_qkv(B=1, H=2, S=256, D=64)

        def loss_flash(q, k, v):
            out = flash_attention(q, k, v, causal=causal, block_q=128,
                                  block_k=128, interpret=True)
            return jnp.sum(out * out)

        def loss_ref(q, k, v):
            out = blockwise_attention_reference(q, k, v, causal=causal)
            return jnp.sum(out * out)

        got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-3, atol=2e-3)

    def test_mixed_dtype_operands_rejected(self):
        q, k, v = make_qkv(B=1, H=1, S=128, D=32)
        with pytest.raises(ValueError, match="share a dtype"):
            flash_attention(q.astype(jnp.bfloat16), k, v, interpret=True)

    def test_backward_fully_masked_rows_zero_grad(self):
        # Rows whose keys are all in the future must get zero output AND
        # zero gradient (LSE sentinel path), not NaN.
        q, k, v = make_qkv(B=1, H=1, S=128, D=32)

        def loss(q, k, v):
            out = flash_attention(q, k, v, causal=True, q_offset=0,
                                  k_offset=128, interpret=True)
            return jnp.sum(out * out)

        grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        for g in grads:
            assert np.all(np.isfinite(np.asarray(g)))
            np.testing.assert_allclose(np.asarray(g), 0.0)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, hvd, causal):
        n = hvd.size()
        B, H, S, D = 2, 4, 8 * n, 16
        q, k, v = make_qkv(B=B, H=H, S=S, D=D)
        want = dense_attention(q, k, v, causal)

        fn = sp.make_sp_attention_step(scheme="ring", causal=causal)
        got = fn(q, k, v)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4,
        )

    @pytest.mark.slow
    def test_bf16_long_sequence(self, hvd):
        # bf16 inputs, fp32 accumulation: tolerance at bf16 resolution.
        q, k, v = make_qkv(B=1, H=2, S=16 * hvd.size(), D=32,
                           dtype=jnp.bfloat16)
        want = dense_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                               v.astype(jnp.float32), causal=True)
        fn = sp.make_sp_attention_step(scheme="ring", causal=True)
        got = fn(q, k, v).astype(jnp.float32)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-2, atol=2e-2,
        )


class TestUlyssesAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, hvd, causal):
        n = hvd.size()
        B, H, S, D = 2, n, 4 * n, 16  # H == axis size (minimum legal)
        q, k, v = make_qkv(B=B, H=H, S=S, D=D)
        want = dense_attention(q, k, v, causal)
        fn = sp.make_sp_attention_step(scheme="ulysses", causal=causal)
        got = fn(q, k, v)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4,
        )


class TestShardSequence:
    def test_shard_helper(self, hvd):
        n = hvd.size()
        x = jnp.arange(2 * 3 * (4 * n) * 5, dtype=jnp.float32).reshape(
            2, 3, 4 * n, 5)
        stacked = sp.shard_sequence(x)
        assert stacked.shape == (n, 2, 3, 4, 5)
        np.testing.assert_array_equal(
            np.asarray(stacked[1]), np.asarray(x[:, :, 4:8, :]))

    def test_shard_helper_ragged(self, hvd):
        x = jnp.zeros((1, 1, 7, 2))
        with pytest.raises(ValueError, match="divisible"):
            sp.shard_sequence(x)


class TestRingFlashAttention:
    """Ring attention with the Pallas kernel per step + logsumexp merge —
    must match the dense oracle forward AND backward (trainable path)."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, hvd, causal):
        n = hvd.size()
        B, H, S, D = 1, 2, 16 * n, 32
        q, k, v = make_qkv(B=B, H=H, S=S, D=D)
        want = dense_attention(q, k, v, causal)
        fn = sp.make_sp_attention_step(scheme="ring-flash", causal=causal,
                                       interpret=True)
        got = fn(q, k, v)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)

    @pytest.mark.slow
    def test_backward_matches_dense(self, hvd):
        n = hvd.size()
        q, k, v = make_qkv(B=1, H=1, S=16 * n, D=16)
        fn = sp.make_sp_attention_step(scheme="ring-flash", causal=True,
                                       interpret=True)

        def loss_flash(q, k, v):
            return jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

        def loss_dense(q, k, v):
            return jnp.sum(
                dense_attention(q, k, v, True).astype(jnp.float32) ** 2)

        got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=5e-3, atol=5e-3)
