"""The windowed and the grouped flash kernels (``flash_attention(...,
window=W)``, ``k`` / ``v`` with fewer heads than ``q``): against a dense
masked softmax forward and in all three gradients, the tile predicate and
the index maps' clamps against a brute-force table, what a window that
hides nothing lowers to, and the gauges. Interpret mode, small shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import metrics
from horovod_tpu.ops import attention as att
from horovod_tpu.ops.attention import flash_attention, flash_attention_lse


def dense(q, k, v, window=None, q_offset=0, k_offset=0):
    """Softmax over the keys ``j`` with ``0 <= i - j`` (``< window``), in
    global positions; keys and values repeated over their group."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / q.shape[-1] ** 0.5
    ahead = ((q_offset + jnp.arange(q.shape[2]))[:, None]
             - (k_offset + jnp.arange(k.shape[2]))[None, :])
    seen = ahead >= 0
    if window is not None:
        seen &= ahead < window
    weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    # a row that sees nothing returns zeros, as the kernels do
    weights = jnp.where(seen.any(-1)[:, None], weights, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", weights, v)


def operands(batch, heads, kv_heads, sq, sk, dim=16, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(keys[0], (batch, heads, sq, dim)),
            jax.random.normal(keys[1], (batch, kv_heads, sk, dim)),
            jax.random.normal(keys[2], (batch, kv_heads, sk, dim)),
            jax.random.normal(keys[3], (batch, heads, sq, dim)))


# heads, kv heads, S, tile, window
KERNEL_CASES = [
    pytest.param(4, 4, 128, 32, 40, id="group1-window40-tile32"),
    pytest.param(4, 4, 128, 32, 33, id="group1-window33-tile32"),
    pytest.param(8, 2, 128, 32, 50, id="group4-window50-tile32"),
    pytest.param(8, 2, 96, 16, 17, id="group4-window17-tile16"),
    pytest.param(7, 1, 128, 32, 40, id="group7-window40-tile32"),
    pytest.param(14, 2, 96, 32, 65, id="group7-window65-tile32"),
    pytest.param(14, 2, 64, 16, 1, id="group7-window1-tile16"),
    pytest.param(8, 2, 128, 32, None, id="group4-causal-tile32"),
    pytest.param(7, 1, 128, 64, None, id="group7-causal-tile64"),
    pytest.param(14, 2, 64, 64, None, id="group7-causal-one-tile"),
    pytest.param(4, 4, 64, 64, 20, id="group1-window20-one-tile"),
    pytest.param(8, 2, 64, 64, 20, id="group4-window20-one-tile"),
]


class TestAgainstDenseSoftmax:
    @pytest.mark.parametrize("heads, kv_heads, seq, tile, window",
                             KERNEL_CASES)
    def test_forward_and_all_three_gradients(self, heads, kv_heads, seq,
                                             tile, window):
        q, k, v, weight = operands(2, heads, kv_heads, seq, seq)

        def flash(q, k, v):
            return flash_attention(q, k, v, causal=True, window=window,
                                   block_q=tile, block_k=tile,
                                   interpret=True)

        got, got_vjp = jax.vjp(flash, q, k, v)
        want, want_vjp = jax.vjp(lambda q, k, v: dense(q, k, v, window),
                                 q, k, v)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
        for name, a, b in zip("qkv", got_vjp(weight), want_vjp(weight)):
            assert a.shape == b.shape, name
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5,
                                       err_msg=f"d{name}")

    @pytest.mark.parametrize("sq, sk, q_off, k_off, window", [
        pytest.param(64, 128, 64, 0, 40, id="bottom-right"),
        pytest.param(64, 128, 17, 0, 23, id="odd-offset"),
        pytest.param(96, 96, 1000, 990, 50, id="far-offsets"),
    ])
    def test_offsets_are_global_positions_under_a_window(self, sq, sk,
                                                         q_off, k_off,
                                                         window):
        q, k, v, weight = operands(1, 4, 2, sq, sk, seed=3)

        def flash(q, k, v):
            return flash_attention(q, k, v, causal=True, window=window,
                                   block_q=32, block_k=32, q_offset=q_off,
                                   k_offset=k_off, interpret=True)

        got, got_vjp = jax.vjp(flash, q, k, v)
        want, want_vjp = jax.vjp(
            lambda q, k, v: dense(q, k, v, window, q_off, k_off), q, k, v)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
        for a, b in zip(got_vjp(weight), want_vjp(weight)):
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)

    def test_the_logsumexp_and_its_cotangent_under_a_window(self):
        q, k, v, _ = operands(1, 4, 2, 64, 64, seed=5)

        def flash(q, k, v):
            out, lse = flash_attention_lse(q, k, v, causal=True, window=20,
                                           block_q=16, block_k=16,
                                           interpret=True)
            return out.sum() + (lse * lse).sum()

        def plain(q, k, v):
            kk = jnp.repeat(k, 2, 1)
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, kk) / 4.0
            ahead = jnp.arange(64)[:, None] - jnp.arange(64)[None, :]
            scores = jnp.where((ahead >= 0) & (ahead < 20), scores,
                               -jnp.inf)
            lse = jax.nn.logsumexp(scores, -1)
            return dense(q, k, v, 20).sum() + (lse * lse).sum()

        got = jax.value_and_grad(flash, (0, 1, 2))(q, k, v)
        want = jax.value_and_grad(plain, (0, 1, 2))(q, k, v)
        np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
        for a, b in zip(got[1], want[1]):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


class TestAWindowThatHidesNothing:
    @pytest.mark.parametrize("window", [128, 129, 4096])
    @pytest.mark.parametrize("tile", [32, 128])
    def test_is_the_causal_call_bit_for_bit(self, window, tile):
        q, k, v, weight = operands(1, 4, 4, 128, 128, seed=7)

        def run(window):
            out, vjp = jax.vjp(
                lambda q, k, v: flash_attention(
                    q, k, v, causal=True, window=window, block_q=tile,
                    block_k=tile, interpret=True), q, k, v)
            return (out,) + vjp(weight)

        for a, b in zip(run(window), run(None)):
            np.testing.assert_array_equal(a, b)

    def test_lowers_to_the_causal_calls_text(self):
        q, k, v, _ = operands(1, 4, 4, 128, 128)

        def text(window):
            return jax.jit(jax.grad(lambda q, k, v: flash_attention(
                q, k, v, causal=True, window=window, block_q=32, block_k=32,
                interpret=True).sum(), (0, 1, 2))).lower(q, k, v).as_text()

        assert text(128) == text(None)
        assert text(127) != text(None)

    def test_one_position_short_of_the_sequence_is_a_window(self):
        q, k, v, _ = operands(1, 2, 2, 64, 64, seed=9)
        got = flash_attention(q, k, v, causal=True, window=63, block_q=32,
                              block_k=32, interpret=True)
        np.testing.assert_allclose(got, dense(q, k, v, 63), atol=2e-5)
        # only the last query loses a key: every other row is the causal one
        causal = flash_attention(q, k, v, causal=True, block_q=32,
                                 block_k=32, interpret=True)
        np.testing.assert_array_equal(got[:, :, :63], causal[:, :, :63])
        assert not np.array_equal(got[:, :, 63], causal[:, :, 63])


# Sq, Sk, bq, bk, q_off, k_off, window
TILE_CASES = [
    pytest.param(128, 128, 32, 32, 0, 0, 40, id="square"),
    pytest.param(128, 128, 32, 32, 0, 0, 32, id="window-is-a-tile"),
    pytest.param(128, 128, 32, 32, 0, 0, 33, id="window-a-tile-and-one"),
    pytest.param(128, 128, 32, 32, 0, 0, 1, id="window-1"),
    pytest.param(128, 128, 16, 64, 0, 0, 50, id="wide-k-blocks"),
    pytest.param(128, 128, 64, 16, 0, 0, 50, id="wide-q-blocks"),
    pytest.param(64, 128, 32, 32, 64, 0, 40, id="bottom-right"),
    pytest.param(96, 160, 32, 32, 1000, 1031, 45, id="far-offsets"),
    pytest.param(64, 64, 32, 32, 500, 0, 100, id="band-left-behind"),
    pytest.param(64, 64, 32, 32, 0, 64, 10, id="nothing-visible"),
    pytest.param(16384, 16384, 512, 512, 0, 0, 4096, id="smallthinker"),
]


class TestTilePredicateAndClamps:
    @pytest.mark.parametrize("Sq, Sk, bq, bk, q_off, k_off, window",
                             TILE_CASES)
    def test_against_a_brute_force_table(self, Sq, Sk, bq, bk, q_off, k_off,
                                         window):
        nq, nk = Sq // bq, Sk // bk
        # per tile: the smallest and largest i - j it holds
        low = ((q_off + np.arange(nq) * bq)[:, None]
               - (k_off + (np.arange(nk) + 1) * bk - 1)[None, :])
        high = ((q_off + (np.arange(nq) + 1) * bq - 1)[:, None]
                - (k_off + np.arange(nk) * bk)[None, :])
        if Sq * Sk <= 1 << 16:  # small enough: from the positions themselves
            ahead = ((q_off + np.arange(Sq))[:, None]
                     - (k_off + np.arange(Sk))[None, :])
            seen = (ahead >= 0) & (ahead < window)
            table = seen.reshape(nq, bq, nk, bk).any((1, 3))
        else:
            table = (high >= 0) & (low < window)
        computed = np.asarray(att._tile_visible(
            np.arange(nq)[:, None], np.arange(nk)[None, :], bq, bk, q_off,
            k_off, window))
        np.testing.assert_array_equal(computed, table)
        # K innermost: the fetched block runs from a row's first computed
        # tile to its last, and stays there before and after
        for i in range(nq):
            first = int(att._first_k_block(i, nk, bq, bk, q_off, k_off,
                                           window))
            last = int(att._last_k_block(i, nk, bq, bk, q_off, k_off))
            assert 0 <= first <= nk - 1 and 0 <= last <= nk - 1
            if table[i].any():
                found = np.flatnonzero(table[i])
                assert (first, last) == (found[0], found[-1]), i
                assert table[i, first:last + 1].all()
        # Q innermost: the mirror
        for j in range(nk):
            first = int(att._first_q_block(j, nq, bq, bk, q_off, k_off))
            last = int(att._last_q_block(j, nq, bq, bk, q_off, k_off,
                                         window))
            assert 0 <= first <= nq - 1 and 0 <= last <= nq - 1
            if table[:, j].any():
                found = np.flatnonzero(table[:, j])
                assert (first, last) == (found[0], found[-1]), j

    def test_the_index_maps_name_only_computed_or_resident_blocks(self):
        nq = nk = 8
        args = (32, 32, 0, 0)
        kv_map = att._kv_index_map(True, nk, *args, window=70, group=7)
        q_block = att._q_block(True, nq, *args, window=70)
        visible = np.asarray(att._tile_visible(
            np.arange(nq)[:, None], np.arange(nk)[None, :], *args, 70))
        for i in range(nq):
            row = [int(kv_map(15, i, j)[1]) for j in range(nk)]
            assert all(visible[i, block] for block in row)
            # a block index only ever moves forward: nothing is fetched twice
            assert row == sorted(row)
            assert {int(kv_map(15, i, j)[0]) for j in range(nk)} == {2}
        for j in range(nk):
            column = [int(q_block(j, i)) for i in range(nq)]
            assert all(visible[block, j] for block in column)
            assert column == sorted(column)

    def test_a_group_of_one_keeps_the_slice_index_itself(self):
        assert att._kv_head(5, 1) == 5
        assert [att._kv_head(bh, 7) for bh in (0, 6, 7, 27, 28)] == [
            0, 0, 1, 3, 4]


class TestGaugesAndGuards:
    def gauge(self, name):
        return {tuple(sorted(cell["labels"].items())): cell["value"]
                for family in metrics.snapshot() if family["name"] == name
                for cell in family["samples"]}

    def test_smallthinkers_band_is_252_of_the_triangles_528_tiles(self):
        att._record_tiles(True, 32, 32, 512, 512, 0, 0, 4096, 7)
        tiles = self.gauge("hvd_attn_tiles_last")
        assert tiles[(("kind", "computed"),)] == 252
        assert tiles[(("kind", "skipped"),)] == 1024 - 252
        assert self.gauge("hvd_attn_kv_group_last")[()] == 7
        att._record_tiles(True, 32, 32, 512, 512, 0, 0, None, 1)
        assert self.gauge("hvd_attn_tiles_last")[
            (("kind", "computed"),)] == 528
        assert self.gauge("hvd_attn_kv_group_last")[()] == 1

    def test_a_traced_call_sets_both(self):
        q, k, v, _ = operands(1, 14, 2, 64, 64)
        jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=20, block_q=16, block_k=16,
            interpret=True)).lower(q, k, v)
        tiles = self.gauge("hvd_attn_tiles_last")
        # rows of 16 see 1, 2, 3, 3 tiles of 16 under a window of 20
        assert tiles[(("kind", "computed"),)] == 9
        assert self.gauge("hvd_attn_kv_group_last")[()] == 7

    def test_a_window_needs_the_causal_mask(self):
        q, k, v, _ = operands(1, 2, 2, 32, 32)
        with pytest.raises(ValueError, match="needs causal=True"):
            flash_attention(q, k, v, window=8, interpret=True)
        with pytest.raises(ValueError, match="at least 1"):
            flash_attention(q, k, v, causal=True, window=0, interpret=True)

    @pytest.mark.parametrize("kv_heads", [3, 5])
    def test_key_value_heads_must_divide_the_query_heads(self, kv_heads):
        q, k, v, _ = operands(1, 4, kv_heads, 32, 32)
        with pytest.raises(ValueError, match="heads divide"):
            flash_attention(q, k, v, causal=True, interpret=True)

    def test_grouped_heads_work_without_a_mask_too(self):
        q, k, v, _ = operands(2, 6, 2, 64, 64, seed=11)
        got = flash_attention(q, k, v, block_q=32, block_k=32,
                              interpret=True)
        kk, vv = jnp.repeat(k, 3, 1), jnp.repeat(v, 3, 1)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, kk) / 4.0
        want = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), vv)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
