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
from traced import pallas_grids


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


# (Sq, Sk, block_q, block_k, q_offset, k_offset): what the multi-tile
# causal kernels must get right now that they skip the tiles the mask
# leaves nothing of and no longer fetch them.
CAUSAL_TILE_CASES = [
    pytest.param(256, 256, 32, 32, 0, 0, id="8x8-empty-last-and-first"),
    pytest.param(256, 256, 64, 32, 0, 0, id="wide-q-blocks"),
    pytest.param(256, 256, 32, 64, 0, 0, id="wide-k-blocks"),
    pytest.param(128, 256, 32, 64, 128, 0, id="decode-aligned-Sq-lt-Sk"),
    pytest.param(256, 128, 64, 32, 0, 64, id="empty-q-block"),
    pytest.param(128, 256, 32, 32, 0, 96, id="empty-k-blocks"),
    pytest.param(128, 128, 32, 32, 128, 0, id="every-tile-full"),
    pytest.param(128, 256, 32, 32, 37, 5, id="unaligned-offsets"),
    # the predicate's edge: a tile's last query IS its first key
    pytest.param(128, 128, 32, 32, 0, 31, id="one-element-visible"),
]


def dense_causal_with_lse(q, k, v, q_offset, k_offset):
    """Dense float32 causal attention and its per-row logsumexp at global
    positions; a row that sees no key gives 0 and an lse of 0 (the kernel's
    sentinel there is a constant: no gradient either way)."""
    D = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / (D ** 0.5)
    qpos = q_offset + jnp.arange(q.shape[2])
    kpos = k_offset + jnp.arange(k.shape[2])
    mask = qpos[:, None] >= kpos[None, :]
    s = jnp.where(mask, s, -1e30)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    l = p.sum(axis=-1, keepdims=True)
    seen = l > 0.0
    safe_l = jnp.where(seen, l, 1.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", p / safe_l, v)
    lse = jnp.where(seen, m + jnp.log(safe_l), 0.0)[..., 0]
    return out, lse, seen[..., 0]


class TestCausalTileSkipping:
    @pytest.mark.parametrize("Sq, Sk, bq, bk, q_off, k_off",
                             CAUSAL_TILE_CASES + [
                                 pytest.param(64, 64, 32, 32, 0, 64,
                                              id="nothing-visible"),
                                 pytest.param(96, 160, 32, 32, 1000, 1031,
                                              id="far-offsets"),
                                 pytest.param(64, 128, 16, 64, 17, 0,
                                              id="k-block-spans-q-blocks"),
                             ])
    def test_a_tile_is_skipped_iff_the_mask_leaves_nothing_of_it(
            self, Sq, Sk, bq, bk, q_off, k_off):
        from horovod_tpu.ops import attention as att

        nq, nk = Sq // bq, Sk // bk
        visible = ((q_off + np.arange(Sq))[:, None]
                   >= (k_off + np.arange(Sk))[None, :])
        computed = np.zeros((nq, nk), bool)
        for i in range(nq):
            for j in range(nk):
                tile = visible[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk]
                computed[i, j] = att._tile_visible(i, j, bq, bk, q_off,
                                                   k_off)
                assert computed[i, j] == tile.any(), (i, j)
        # The index maps stay on a tile that is computed, or at the grid's
        # edge where a whole q block / k block sees nothing: the K-innermost
        # calls stop at a row's last computed tile, the Q-innermost call
        # starts at a column's first.
        for i in range(nq):
            last = int(att._last_k_block(i, nk, bq, bk, q_off, k_off))
            if computed[i].any():
                assert computed[i, :last + 1].all()
                assert not computed[i, last + 1:].any()
            else:
                assert last == 0
        for j in range(nk):
            first = int(att._first_q_block(j, nq, bq, bk, q_off, k_off))
            if computed[:, j].any():
                assert computed[first:, j].all()
                assert not computed[:first, j].any()
            else:
                assert first == nq - 1

    @pytest.mark.parametrize("Sq, Sk, bq, bk, q_off, k_off",
                             CAUSAL_TILE_CASES)
    def test_forward_and_gradients_match_reference(self, Sq, Sk, bq, bk,
                                                   q_off, k_off):
        q, _, _ = make_qkv(B=1, H=2, S=Sq, D=32, seed=1)
        _, k, v = make_qkv(B=1, H=2, S=Sk, D=32, seed=2)

        def flash(q, k, v):
            return flash_attention(q, k, v, causal=True, block_q=bq,
                                   block_k=bk, q_offset=q_off,
                                   k_offset=k_off, interpret=True)

        def reference(q, k, v):
            return blockwise_attention_reference(
                q, k, v, causal=True, block_size=32, q_offset=q_off,
                k_offset=k_off)

        got_out, got_vjp = jax.vjp(flash, q, k, v)
        want_out, want_vjp = jax.vjp(reference, q, k, v)
        np.testing.assert_allclose(np.asarray(got_out), np.asarray(want_out),
                                   rtol=2e-5, atol=2e-5)
        g = make_qkv(B=1, H=2, S=Sq, D=32, seed=3)[0]
        for got, want in zip(got_vjp(g), want_vjp(g)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("Sq, Sk, bq, bk, q_off, k_off",
                             CAUSAL_TILE_CASES)
    def test_gradients_through_lse_match_dense(self, Sq, Sk, bq, bk, q_off,
                                               k_off):
        from horovod_tpu.ops.attention import LSE_MASKED, flash_attention_lse

        q, _, _ = make_qkv(B=1, H=1, S=Sq, D=32, seed=4)
        _, k, v = make_qkv(B=1, H=1, S=Sk, D=32, seed=5)
        g_out = make_qkv(B=1, H=1, S=Sq, D=32, seed=6)[0]
        g_lse = g_out[..., 0]

        def flash(q, k, v):
            return flash_attention_lse(q, k, v, causal=True, block_q=bq,
                                       block_k=bk, q_offset=q_off,
                                       k_offset=k_off, interpret=True)

        def dense(q, k, v):
            out, lse, seen = dense_causal_with_lse(q, k, v, q_off, k_off)
            return (out, lse), seen

        (out, lse), got_vjp = jax.vjp(flash, q, k, v)
        (want_out, want_lse), want_vjp, seen = jax.vjp(dense, q, k, v,
                                                       has_aux=True)
        seen = np.asarray(seen)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(lse)[seen],
                                   np.asarray(want_lse)[seen],
                                   rtol=2e-5, atol=2e-5)
        assert np.all(np.asarray(lse)[~seen] == LSE_MASKED)
        for got, want in zip(got_vjp((g_out, g_lse)),
                             want_vjp((g_out, g_lse))):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("causal, computed, skipped",
                             [(True, 36, 28), (False, 64, 0)])
    def test_the_plan_counts_the_tiles_a_traced_call_walks(
            self, causal, computed, skipped):
        from horovod_tpu.ops import attention as att

        q, k, v = make_qkv(B=1, H=1, S=256, D=32)

        def loss(q, k, v):
            return flash_attention(q, k, v, causal=causal, block_q=32,
                                   block_k=32, interpret=True).sum()

        pairs, band_kb, band_qb = att._tile_plan(causal, 8, 8, 32, 32, 0, 0)
        assert (pairs, 8 * 8 - pairs) == (computed, skipped)
        # Traced, not run: the forward walks the plan's grid, and the
        # backward's three kernels (the forward again, dq, dk/dv) too.
        assert pallas_grids(loss, q, k, v) == [(1, 8, band_kb)]
        assert pallas_grids(jax.grad(loss, argnums=(0, 1, 2)), q, k, v) == [
            (1, 8, band_kb), (1, 8, band_kb), (1, 8, band_qb)]


# (BH, causal): odd, prime and composite counts of (batch x head) slices.
GROUP_CASES = [
    pytest.param(bh, causal, id=f"bh{bh}-{'causal' if causal else 'full'}")
    for bh in (1, 2, 7, 12, 16, 96) for causal in (False, True)]

# A budget at which the test shapes (S=32, D=16, float32) get groups of up
# to 7 slices in the forward and 4 in the backward kernel, so that grids of
# several steps of several slices run too; the module's own gives them one
# group or two.
SMALL_BUDGET = 1280 * 1024


class TestSliceGroups:
    """A grid step of the single-tile kernels takes a group of slices. A
    slice's arithmetic is what it was, so every output and gradient equals
    the ``G = 1`` program's (budget 0: the kernels before they took
    groups) in every bit."""

    @staticmethod
    def run(monkeypatch, budget, bh, causal, q_off=0, k_off=0, S=32, D=16):
        from horovod_tpu.ops import attention as att

        monkeypatch.setattr(att, "GROUP_BUDGET_BYTES", budget)
        ks = jax.random.split(jax.random.PRNGKey(bh), 5)
        q, k, v, g_out = (jax.random.normal(key, (bh, S, D), jnp.float32)
                          for key in ks[:4])
        g_lse = jax.random.normal(ks[4], (bh, 1, S), jnp.float32)

        # not jitted: every call traces the kernels anew under the budget
        def flash(q, k, v):
            return att._flash_with_lse(q, k, v, causal, S, S, q_off, k_off,
                                       True)

        (out, lse), vjp = jax.vjp(flash, q, k, v)
        dq, dk, dv = vjp((g_out, g_lse))
        # the slices a grid step of the forward and of the fused backward
        # takes: their grids are (BH // G,)
        groups = tuple(bh // grid[0] for grid in pallas_grids(
            lambda q, k, v: jax.vjp(flash, q, k, v)[1]((g_out, g_lse)),
            q, k, v))
        return [np.asarray(x) for x in (out, lse, dq, dk, dv)], groups

    @pytest.mark.parametrize("budget", [None, SMALL_BUDGET],
                             ids=["module-budget", "small-budget"])
    @pytest.mark.parametrize("bh, causal", GROUP_CASES)
    def test_grouped_kernels_equal_the_ungrouped_bit_for_bit(
            self, monkeypatch, bh, causal, budget):
        from horovod_tpu.ops import attention as att

        budget = att.GROUP_BUDGET_BYTES if budget is None else budget
        want, ones = self.run(monkeypatch, 0, bh, causal)
        got, groups = self.run(monkeypatch, budget, bh, causal)
        assert ones == (1, 1)
        assert all(bh % g == 0 for g in groups)
        if bh > 1 and bh % 2 == 0:
            assert min(groups) > 1  # something is grouped at these sizes
        for name, g, w in zip(("out", "lse", "dq", "dk", "dv"), got, want):
            assert np.isfinite(g).all(), name
            np.testing.assert_array_equal(g, w, err_msg=name)

    @pytest.mark.parametrize("q_off, k_off", [
        pytest.param(5, 0, id="q-ahead"),
        pytest.param(0, 7, id="first-rows-masked"),
        pytest.param(100, 117, id="far-offsets"),
        pytest.param(0, 32, id="every-row-masked"),
    ])
    @pytest.mark.parametrize("bh", [7, 12])
    def test_offsets_and_masked_rows_are_kept_per_slice(self, monkeypatch,
                                                        bh, q_off, k_off):
        from horovod_tpu.ops.attention import LSE_MASKED

        want, _ = self.run(monkeypatch, 0, bh, True, q_off, k_off)
        got, groups = self.run(monkeypatch, SMALL_BUDGET, bh, True, q_off,
                               k_off)
        assert groups == ((7, 1) if bh == 7 else (6, 4))
        for name, g, w in zip(("out", "lse", "dq", "dk", "dv"), got, want):
            assert np.isfinite(g).all(), name
            np.testing.assert_array_equal(g, w, err_msg=name)
        # rows whose keys all lie ahead carry the sentinel in every slice
        masked = (q_off + np.arange(32)) < k_off
        assert (got[1][:, 0, masked] == LSE_MASKED).all()
        assert (got[1][:, 0, ~masked] < LSE_MASKED).all()
        assert (got[0][:, masked] == 0).all()
        assert (got[2][:, masked] == 0).all()

    @pytest.mark.parametrize("bh", [1, 2, 7, 12, 16, 96, 384, 1536, 1543,
                                    7919])
    @pytest.mark.parametrize("seq", [128, 512])
    @pytest.mark.parametrize("kernel", ["fwd", "bwd"])
    def test_the_group_divides_the_slices_and_fits_the_budget(self, bh, seq,
                                                              kernel):
        from horovod_tpu.ops import attention as att

        counts = att._FWD_SLICE if kernel == "fwd" else att._BWD_SLICE
        group = att._group_size(bh, seq, seq, 64, 2, **counts)
        assert group >= 1 and bh % group == 0
        if group > 1:
            assert att._group_footprint(group, seq, seq, 64, 2, **counts) \
                <= att.GROUP_BUDGET_BYTES
        # the largest such divisor: no larger one fits
        assert not any(
            bh % g == 0 and att._group_footprint(
                g, seq, seq, 64, 2, **counts) <= att.GROUP_BUDGET_BYTES
            for g in range(group + 1, bh + 1))
        if bh in (1543, 7919):  # primes past what the budget holds
            assert group == 1

    @pytest.mark.parametrize("kernel", ["fwd", "bwd"])
    def test_the_group_does_not_grow_with_the_sequence(self, kernel):
        from horovod_tpu.ops import attention as att

        counts = att._FWD_SLICE if kernel == "fwd" else att._BWD_SLICE
        groups = [att._group_size(1536, seq, seq, 64, 2, **counts)
                  for seq in (16, 64, 128, 256, 512)]
        assert groups == sorted(groups, reverse=True)
        assert groups[2] > 1  # BERT's S=128 is grouped

    def test_a_traced_call_takes_the_group_the_budget_plans(
            self, monkeypatch):
        from horovod_tpu.ops import attention as att

        q, k, v = make_qkv(B=3, H=4, S=32, D=16)

        def loss(q, k, v):
            return flash_attention(q, k, v, interpret=True).sum()

        for budget in (SMALL_BUDGET, att.GROUP_BUDGET_BYTES):
            monkeypatch.setattr(att, "GROUP_BUDGET_BYTES", budget)
            jax.clear_caches()
            want = (att._group_size(12, 32, 32, 16, 4, **att._FWD_SLICE),
                    att._group_size(12, 32, 32, 16, 4, **att._BWD_SLICE))
            # Traced, not run: the grids are (BH // G,).
            got = tuple(12 // grid[0] for grid in pallas_grids(
                jax.grad(loss, argnums=(0, 1, 2)), q, k, v))
            assert got == want
        jax.clear_caches()

    def test_a_multi_tile_call_takes_no_group(self):
        q, k, v = make_qkv(B=1, H=4, S=64, D=16)
        grids = pallas_grids(jax.grad(lambda q, k, v: flash_attention(
            q, k, v, block_q=32, block_k=32, interpret=True).sum()), q, k, v)
        # a slice a step of (BH, Q blocks, K blocks), never (BH // G,)
        assert grids == [(4, 2, 2)] * 3


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
