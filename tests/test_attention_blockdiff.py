"""The block masks of ``ops/attention.py`` (``block_length=``,
``before_block=``) and ``block_diffusion_attention``: interpreted kernels
over several tiles and grouped heads against a dense masked softmax,
forward and in all three gradients; the tile predicate and the index maps'
clamps against the elementwise mask by brute force; the tile counts at the
SDAR cell's shapes from ``_tile_plan`` alone; the gauges, the scope and the
guards."""

import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import attribution
from horovod_tpu.ops import attention
from horovod_tpu.ops.attention import (LSE_MASKED, block_diffusion_attention,
                                       block_diffusion_streams,
                                       flash_attention, flash_attention_lse)
from traced import pallas_grids


def operands(batch, heads, kv_heads, seq, dim, seed=0, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = lambda h: (batch, h, seq, dim)  # noqa: E731
    return (jax.random.normal(keys[0], shape(heads), dtype),
            jax.random.normal(keys[1], shape(kv_heads), dtype),
            jax.random.normal(keys[2], shape(kv_heads), dtype),
            jax.random.normal(keys[3], shape(heads), jnp.float32))


def block_mask(sq, sk, length, before, q_offset=0, k_offset=0):
    q_blk = (q_offset + np.arange(sq))[:, None] // length
    k_blk = (k_offset + np.arange(sk))[None, :] // length
    return k_blk < q_blk if before else k_blk <= q_blk


def dense(q, k, v, mask):
    """``(out, lse)`` of the masked softmax, the keys and values of a
    group repeated; a row that sees nothing is zero."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / q.shape[-1] ** 0.5
    s = jnp.where(mask, s, -jnp.inf)
    lse = jax.nn.logsumexp(s, -1)
    p = jnp.where(mask, jnp.exp(s - jnp.where(
        jnp.isfinite(lse), lse, 0.0)[..., None]), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v), lse


CASES = {
    # heads, kv heads, S, D, tile, block length
    "a-head-each": (4, 4, 64, 16, 16, 4),
    "grouped-by-4": (8, 2, 64, 16, 16, 4),
    "grouped-by-8-tiles-of-32": (8, 1, 128, 8, 32, 4),
    "blocks-of-8": (4, 2, 64, 16, 16, 8),
    "a-block-a-tile": (2, 2, 64, 16, 16, 16),
    "blocks-of-1-is-causal": (2, 1, 48, 16, 16, 1),
}


class TestTheBlockMasksAgainstTheDenseMask:
    @pytest.mark.parametrize("before", [False, True],
                             ids=["own-block-too", "before-the-own-block"])
    @pytest.mark.parametrize("name", sorted(CASES))
    def test_forward_and_three_gradients(self, name, before):
        heads, kv_heads, seq, dim, tile, length = CASES[name]
        q, k, v, w = operands(2, heads, kv_heads, seq, dim)
        mask = block_mask(seq, seq, length, before)
        kernel = partial(flash_attention, causal=True, block_q=tile,
                         block_k=tile, interpret=True, block_length=length,
                         before_block=before)
        np.testing.assert_allclose(kernel(q, k, v), dense(q, k, v, mask)[0],
                                   rtol=2e-5, atol=2e-6)
        got = jax.grad(lambda *a: (kernel(*a) * w).sum(), (0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: (dense(*a, mask)[0] * w).sum(),
                        (0, 1, 2))(q, k, v)
        for a, b, what in zip(got, want, "qkv"):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5,
                                       err_msg=f"d{what}")
        assert got[1].shape == k.shape  # summed over the group in the kernel

    @pytest.mark.parametrize("name", ["grouped-by-4", "blocks-of-8"])
    def test_through_the_log_sum_exp_with_a_cotangent_on_it(self, name):
        """What the merge of the two sources differentiates through."""
        heads, kv_heads, seq, dim, tile, length = CASES[name]
        q, k, v, w = operands(1, heads, kv_heads, seq, dim, seed=3)
        mask = block_mask(seq, seq, length, True)
        w_lse = jax.random.normal(jax.random.PRNGKey(9), (1, heads, seq))
        rows = mask.any(-1)  # the first block's queries see nothing

        def through(fn):
            def loss(q, k, v):
                out, lse = fn(q, k, v)
                return (out * w).sum() + (jnp.where(rows, lse, 0.0)
                                          * w_lse).sum()
            return jax.grad(loss, (0, 1, 2))(q, k, v)

        kernel = partial(flash_attention_lse, causal=True, block_q=tile,
                         block_k=tile, interpret=True, block_length=length,
                         before_block=True)
        out, lse = kernel(q, k, v)
        assert (np.asarray(lse)[..., :length] == LSE_MASKED).all()
        assert (np.asarray(out)[..., :length, :] == 0).all()
        np.testing.assert_allclose(
            np.asarray(lse)[..., length:],
            np.asarray(dense(q, k, v, mask)[1])[..., length:], rtol=2e-5)
        for a, b in zip(through(kernel),
                        through(lambda *a: dense(*a, mask))):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)

    def test_blocks_of_one_are_the_causal_call_bit_for_bit(self):
        q, k, v, _ = operands(1, 2, 1, 48, 16)
        tiles = dict(causal=True, block_q=16, block_k=16, interpret=True)
        np.testing.assert_array_equal(
            flash_attention(q, k, v, block_length=1, **tiles),
            flash_attention(q, k, v, **tiles))

    def test_offsets_of_whole_blocks(self):
        """A shard of the queries against a longer run of keys, as ring
        attention hands them over."""
        q, _, _, _ = operands(1, 2, 2, 32, 16, seed=1)
        _, k, v, _ = operands(1, 2, 2, 64, 16, seed=2)
        for before in (False, True):
            got = flash_attention(q, k, v, causal=True, q_offset=32,
                                  block_q=16, block_k=16, interpret=True,
                                  block_length=4, before_block=before)
            want = dense(q, k, v, block_mask(32, 64, 4, before, 32, 0))[0]
            np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


TABLE = [
    # block_q, block_k, q blocks, k blocks, q_offset, k_offset, length
    (16, 16, 4, 4, 0, 0, 4), (16, 16, 4, 4, 0, 0, 16), (32, 16, 2, 4, 0, 0, 4),
    (16, 32, 4, 2, 0, 0, 8), (16, 16, 2, 6, 32, 0, 4), (16, 16, 4, 4, 0, 16, 4),
    (8, 8, 4, 4, 0, 0, 8), (16, 16, 3, 3, 16, 16, 2),
]


class TestTilesAndClamps:
    @pytest.mark.parametrize("before", [False, True])
    @pytest.mark.parametrize("case", TABLE, ids=str)
    def test_the_predicate_and_the_clamps_are_the_masks(self, case, before):
        """A tile is visible exactly when the elementwise mask leaves
        something of it; the clamped index maps name the last (first)
        visible tile of a row (column) that has one."""
        block_q, block_k, num_qb, num_kb, q_off, k_off, length = case
        behind = attention._behind((length, before))
        assert behind == (length if before else 0)
        mask = block_mask(num_qb * block_q, num_kb * block_k, length, before,
                          q_off, k_off)
        tiles = mask.reshape(num_qb, block_q, num_kb, block_k).any((1, 3))
        seen = np.asarray(attention._tile_visible(
            np.arange(num_qb)[:, None], np.arange(num_kb)[None, :], block_q,
            block_k, q_off, k_off, None, behind))
        np.testing.assert_array_equal(seen, tiles)
        for i in range(num_qb):
            if tiles[i].any():
                assert int(attention._last_k_block(
                    i, num_kb, block_q, block_k, q_off, k_off,
                    behind)) == np.flatnonzero(tiles[i])[-1]
        for j in range(num_kb):
            if tiles[:, j].any():
                assert int(attention._first_q_block(
                    j, num_qb, block_q, block_k, q_off, k_off,
                    behind)) == np.flatnonzero(tiles[:, j])[0]
        pairs, band_kb, band_qb = attention._tile_plan(
            True, num_qb, num_kb, block_q, block_k, q_off, k_off, None,
            behind)
        assert (pairs, band_kb, band_qb) == (tiles.sum(), num_kb, num_qb)

    def test_the_in_tile_mask_is_the_elementwise_mask(self):
        for before in (False, True):
            for qi, kj in ((0, 0), (2, 1), (1, 2), (3, 3)):
                got = attention._causal_mask(qi, kj, 16, 16, 0, 0, None,
                                             (4, before))
                want = block_mask(64, 64, 4, before)[
                    qi * 16:(qi + 1) * 16, kj * 16:(kj + 1) * 16]
                np.testing.assert_array_equal(np.asarray(got), want)

    def test_the_cells_tile_counts_from_the_plan_alone(self):
        """SDAR's cell: 8,192 clean tokens in 16 x 16 tiles of 512, blocks
        of 4. Each of the two calls computes the causal plan's 136 tiles
        on its whole 256-step grid (120 steps empty); a 2S x 2S causal
        call would compute 528 on 1,024."""
        for before in (False, True):
            assert attention._tile_plan(
                True, 16, 16, 512, 512, 0, 0, None,
                attention._behind((4, before))) == (136, 16, 16)
        assert attention._tile_plan(True, 32, 32, 512, 512, 0, 0) == (
            528, 32, 32)

    def test_both_calls_walk_the_causal_plans_grid(self):
        q, k, v, _ = operands(1, 4, 1, 2 * 8192, 128, dtype=jnp.bfloat16)
        plans = [attention._tile_plan(
            True, 16, 16, 512, 512, 0, 0, None,
            attention._behind((4, before))) for before in (False, True)]
        computed = sum(pairs for pairs, _, _ in plans)
        steps = sum(16 * band_kb for _, band_kb, _ in plans)
        assert (plans[0][0], 16 * 16 - plans[0][0]) == (136, 120)
        # no tile outside the two triangles: twice the causal plan of S x S
        assert computed == 272 == 2 * (16 * 17 // 2)
        assert steps == 512 < 32 * 32  # not the 2S x 2S grid
        # traced: two calls, each four query heads (on the one key/value
        # head) over the S x S plan's grid
        assert pallas_grids(
            partial(block_diffusion_attention, block_length=4), q, k, v) == [
            (4, 16, plans[0][1]), (4, 16, plans[1][1])]
        shaped = jax.ShapeDtypeStruct
        assert attention._tiled_shapes(
            shaped((4, 8192, 128), q.dtype), shaped((1, 8192, 128), k.dtype),
            None)[-1] == 4


def stream_mask(seq, length):
    pos = np.concatenate([np.arange(seq)] * 2)
    noisy = np.arange(2 * seq) < seq
    q_blk, k_blk = (pos // length)[:, None], (pos // length)[None]
    q_noisy, k_noisy = noisy[:, None], noisy[None]
    return ((q_noisy & k_noisy & (k_blk == q_blk))
            | (q_noisy & ~k_noisy & (k_blk < q_blk))
            | (~q_noisy & ~k_noisy & (k_blk <= q_blk)))


class TestBlockDiffusionAttention:
    @pytest.mark.parametrize("name", ["a-head-each", "grouped-by-4",
                                      "grouped-by-8-tiles-of-32",
                                      "blocks-of-8"])
    def test_forward_and_three_gradients_over_both_streams(self, name):
        heads, kv_heads, seq, dim, tile, length = CASES[name]
        q, k, v, w = operands(2, heads, kv_heads, 2 * seq, dim, seed=5)
        mask = stream_mask(seq, length)
        assert mask.sum() == seq * (seq + length)
        call = partial(block_diffusion_attention, block_length=length,
                       block_q=tile, block_k=tile, interpret=True)
        np.testing.assert_allclose(call(q, k, v), dense(q, k, v, mask)[0],
                                   rtol=2e-5, atol=2e-6)
        got = jax.grad(lambda *a: (call(*a) * w).sum(), (0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: (dense(*a, mask)[0] * w).sum(),
                        (0, 1, 2))(q, k, v)
        for a, b, what in zip(got, want, "qkv"):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5,
                                       err_msg=f"d{what}")

    def test_in_bfloat16_as_the_cell_runs_it(self):
        q, k, v, _ = operands(1, 8, 2, 128, 16, seed=6, dtype=jnp.bfloat16)
        got = block_diffusion_attention(q, k, v, 4, block_q=16, block_k=16,
                                        interpret=True)
        assert got.dtype == jnp.bfloat16
        want = dense(*(x.astype(jnp.float32) for x in (q, k, v)),
                     stream_mask(64, 4))[0]
        np.testing.assert_allclose(got.astype(jnp.float32), want, rtol=0.05,
                                   atol=0.02)

    def test_the_own_block_is_plain_xla(self):
        q, k, v, _ = operands(2, 8, 2, 32, 16, seed=7)
        keys, values = (attention._keys_of_own_block(x, 4) for x in (k, v))
        rows = np.arange(32)
        for c in range(4):  # row i holds key c of i's block
            np.testing.assert_array_equal(keys[c], k[:, :, rows - rows % 4 + c])
        mask = (rows[:, None] // 4) == (rows[None] // 4)
        want, want_lse = dense(q, k, v, mask)
        for g in range(4):  # query head 4 kv + g of each group
            out, lse = attention._own_block_attention(
                q[:, g::4], keys, values, 4)
            np.testing.assert_allclose(out, want[:, g::4], rtol=2e-5,
                                       atol=2e-6)
            np.testing.assert_allclose(lse, want_lse[:, g::4], rtol=2e-5,
                                       atol=2e-6)

    def test_no_square_of_the_doubled_stream_is_built(self):
        """Neither scores nor a mask of 2S x 2S (or S x S) anywhere in the
        program: the kernels hold a tile, the own block ``length`` keys."""
        q, k, v, _ = operands(1, 4, 2, 2 * 256, 16)
        jaxpr = jax.make_jaxpr(jax.grad(lambda *a: block_diffusion_attention(
            *a, 4, block_q=64, block_k=64, interpret=False).sum(),
            (0, 1, 2)))(q, k, v)

        def shapes(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "pallas_call":
                    continue  # a tile at a time, in VMEM
                for var in eqn.outvars:
                    yield getattr(var.aval, "shape", ())
                for value in eqn.params.values():
                    inner = getattr(value, "jaxpr", value)
                    if hasattr(inner, "eqns"):
                        yield from shapes(inner)

        assert not [s for s in shapes(jaxpr.jaxpr)
                    if sum(d >= 256 for d in s) >= 2]

    def test_the_scope_is_around_kernels_and_merge(self):
        """Opened once, in ``block_diffusion_attention``: no name stack
        holds it twice, and it is around the kernels' phases and around
        the glue, forward and backward."""
        q, k, v, _ = operands(1, 4, 2, 128, 16)
        text = jax.jit(jax.grad(lambda *a: block_diffusion_attention(
            *a, 4, block_q=16, block_k=16, interpret=True).sum(),
            (0, 1, 2))).lower(q, k, v).compile().as_text()
        scope = attribution.SCOPE_PREFIX + attribution.SCOPE_ATTN_BLOCKDIFF
        assert scope == "hvd.attn.blockdiff"
        stacks = set(re.findall(r'op_name="([^"]*)"', text))
        assert max(stack.count(scope) for stack in stacks) == 1
        assert re.search(
            r"jvp\(hvd\.attn\.blockdiff\)/jit\(flash_attention(_lse)?\)/"
            r"hvd\.attn\.fwd", text)
        assert re.search(
            r"transpose\(jvp\(hvd\.attn\.blockdiff\)\)/"
            r"jit\(flash_attention(_lse)?\)/hvd\.attn\.bwd", text)
        # and the glue beside the kernels: the merge through the
        # log-sum-exp, forward (``jvp(scope)``) and backward
        assert re.search(r"jvp\(hvd\.attn\.blockdiff\)/exp", text)
        assert re.search(
            r"transpose\(jvp\(hvd\.attn\.blockdiff\)\)/mul", text)

    @pytest.mark.parametrize("name", ["a-head-each", "grouped-by-4"])
    def test_the_streams_given_apart_are_the_doubled_stream_cut(self, name):
        """``block_diffusion_streams`` takes each stream's q, k, v and
        returns each stream's result (PR 40: ``models/sdar.py`` cuts the
        streams before the projections); the entry over one array is that
        with a cut and a concatenation around it, bit for bit, forward
        and in the three gradients."""
        heads, kv_heads, seq, dim, tile, length = CASES[name]
        q, k, v, w = operands(2, heads, kv_heads, 2 * seq, dim, seed=8)
        call = dict(block_q=tile, block_k=tile, interpret=True)

        def apart(q, k, v):
            return jnp.concatenate(block_diffusion_streams(
                (q[:, :, :seq], k[:, :, :seq], v[:, :, :seq]),
                (q[:, :, seq:], k[:, :, seq:], v[:, :, seq:]), length,
                **call), axis=2)

        def doubled(q, k, v):
            return block_diffusion_attention(q, k, v, length, **call)

        got, pull_back = jax.vjp(apart, q, k, v)
        want, want_back = jax.vjp(doubled, q, k, v)
        for a, b in zip((got,) + pull_back(w), (want,) + want_back(w)):
            np.testing.assert_array_equal(a, b)

    def test_streams_of_another_length_are_refused(self):
        q, k, v, _ = operands(1, 2, 2, 64, 16)
        with pytest.raises(ValueError, match="whole blocks"):
            block_diffusion_streams((q[:, :, :32], k, v), (q, k, v), 4,
                                    interpret=True)

    def test_the_two_calls_plans_differ_where_a_block_is_a_tile(self):
        """Blocks of a tile's length: the noisy stream's call leaves the
        diagonal tiles out (its queries see only the blocks before their
        own), the clean stream's keeps them: two plans, each its own, on
        one grid."""
        q, k, v, _ = operands(1, 4, 2, 128, 16)
        block_diffusion_attention(q, k, v, 16, block_q=16, block_k=16,
                                  interpret=True)
        plans = [attention._tile_plan(
            True, 4, 4, 16, 16, 0, 0, None, attention._behind((16, before)))
            for before in (False, True)]
        assert [pairs for pairs, _, _ in plans] == [10, 6]
        assert 4 * 4 * 4 - (10 + 6) == 64 - 16
        assert pallas_grids(
            partial(block_diffusion_attention, block_length=16, block_q=16,
                    block_k=16, interpret=True), q, k, v) == [(4, 4, 4)] * 2


class TestGuards:
    def args(self):
        return operands(1, 2, 2, 64, 16)[:3]

    def test_a_block_mask_needs_causal_and_no_window(self):
        with pytest.raises(ValueError, match="needs causal=True"):
            flash_attention(*self.args(), block_length=4, interpret=True)
        with pytest.raises(ValueError, match="no window"):
            flash_attention(*self.args(), causal=True, window=8,
                            block_length=4, interpret=True)

    def test_tiles_and_offsets_are_whole_blocks(self):
        with pytest.raises(ValueError, match="must divide the tiles"):
            flash_attention(*self.args(), causal=True, block_q=16,
                            block_k=16, block_length=3, interpret=True)
        with pytest.raises(ValueError, match="must divide the tiles"):
            q, k, v = self.args()
            flash_attention(q[:, :, :32], k, v, causal=True, q_offset=30,
                            block_q=16, block_k=16, block_length=4,
                            interpret=True)

    def test_before_block_needs_a_block_length(self):
        with pytest.raises(ValueError, match="needs a block_length"):
            flash_attention(*self.args(), causal=True, before_block=True,
                            interpret=True)

    def test_two_halves_of_whole_blocks(self):
        q, k, v = self.args()
        with pytest.raises(ValueError, match="a noisy and a clean half"):
            block_diffusion_attention(q[:, :, :63], k, v, 4, interpret=True)
        with pytest.raises(ValueError, match="a noisy and a clean half"):
            block_diffusion_attention(q, k, v, 5, interpret=True)

    def test_a_one_tile_sequence_goes_through_the_multi_tile_kernels(self):
        """The single-tile kernels know no block mask."""
        assert attention._single_tile(64, 64, 64, 64, None, 1)
        assert not attention._single_tile(64, 64, 64, 64, None, 1, (4, True))
        q, k, v = self.args()
        got = flash_attention(q, k, v, causal=True, block_length=4,
                              interpret=True)
        np.testing.assert_allclose(
            got, dense(q, k, v, block_mask(64, 64, 4, False))[0], rtol=2e-5,
            atol=2e-6)
