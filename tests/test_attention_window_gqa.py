"""The windowed and the grouped flash kernels (``flash_attention(...,
window=W)``, ``k`` / ``v`` with fewer heads than ``q``): against a dense
masked softmax forward and in all three gradients, the tile predicate, the
band grid and the index maps against a brute-force table, what a window
that hides nothing lowers to, what the calls without a window lowered to
and the windowed ones returned before their grid ran over the band alone,
and the tile plan with the grids a traced call walks. Interpret mode, small
shapes."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import attention as att
from horovod_tpu.ops.attention import flash_attention, flash_attention_lse
from traced import pallas_grids


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

    @pytest.mark.parametrize("seq, tile", [(128, 32), (192, 64)])
    def test_granites_heads_and_its_score_scale(self, seq, tile):
        """32 query heads on 8 key/value heads of 64 through the
        multi-tile causal kernels (``models/granite.py``'s attention layer;
        BERT's D = 64 goes through the single-tile ones), with the queries
        scaled by 2^-3 ahead of the call: against a plain softmax of
        ``q k^T * 0.015625``, the model's ``attention_multiplier``, values
        and all three gradients."""
        q, k, v, weight = operands(1, 32, 8, seq, seq, dim=64, seed=3)

        def flash(q, k, v):
            return flash_attention(q * 2.0 ** -3, k, v, causal=True,
                                   block_q=tile, block_k=tile,
                                   interpret=True)

        def plain(q, k, v):
            k4, v4 = jnp.repeat(k, 4, 1), jnp.repeat(v, 4, 1)
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k4) * 0.015625
            seen = jnp.tril(jnp.ones((seq, seq), bool))
            weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return jnp.einsum("bhqk,bhkd->bhqd", weights, v4)

        got, got_vjp = jax.vjp(flash, q, k, v)
        want, want_vjp = jax.vjp(plain, q, k, v)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
        for name, a, b in zip("qkv", got_vjp(weight), want_vjp(weight)):
            assert a.shape == b.shape, name
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5,
                                       err_msg=f"d{name}")
        # four query heads a key/value head: the dk/dv grid's axis of its own
        assert (8, seq // tile, 4, seq // tile) in pallas_grids(
            jax.grad(lambda *a: flash(*a).sum(), (0, 1, 2)), q, k, v)

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
    pytest.param(128, 128, 32, 32, 0, 0, 70, id="window-no-multiple"),
    pytest.param(128, 128, 32, 32, 0, 0, 5, id="window-under-a-tile"),
    pytest.param(128, 128, 16, 64, 0, 0, 7, id="window-under-a-k-tile"),
    pytest.param(96, 96, 32, 32, 0, 32, 40, id="a-row-and-a-column-empty"),
    pytest.param(96, 96, 32, 32, 1000, 990, 50, id="queries-past-the-keys"),
    pytest.param(64, 128, 16, 32, 17, 0, 23, id="odd-offset"),
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
        # The band grid: a row's (column's) inner steps count on from its
        # first block, so step jj of q block i stands for k block
        # first(i) + jj, computed if the predicate says so and the block
        # is one of the sequence's. Every visible tile exactly once, in
        # ascending order, in the least number of steps that does it.
        pairs, band_kb, band_qb = att._tile_plan(True, nq, nk, bq, bk, q_off,
                                                 k_off, window)
        assert pairs == table.sum()
        assert band_kb == max(1, table.sum(1).max())
        assert band_qb == max(1, table.sum(0).max())
        for i in range(nq):
            first = int(att._first_k_block(i, nk, bq, bk, q_off, k_off,
                                           window))
            named = [first + jj for jj in range(band_kb)]
            computed = [j for j in named if j < nk and att._tile_visible(
                i, j, bq, bk, q_off, k_off, window)]
            assert computed == list(np.flatnonzero(table[i])), i
        for j in range(nk):
            first = int(att._first_q_block(j, nq, bq, bk, q_off, k_off))
            named = [first + ii for ii in range(band_qb)]
            computed = [i for i in named if i < nq and att._tile_visible(
                i, j, bq, bk, q_off, k_off, window)]
            assert computed == list(np.flatnonzero(table[:, j])), j

    @pytest.mark.parametrize("Sq, Sk, bq, bk, q_off, k_off, window",
                             [case for case in TILE_CASES
                              if case.values[0] <= 256])
    def test_the_index_maps_name_only_computed_or_resident_blocks(
            self, Sq, Sk, bq, bk, q_off, k_off, window):
        """Over a row's ``band_kb`` (a column's ``band_qb``) inner steps
        the fetched block is the step's own tile where that is computed
        and the last computed one after, so a step that computes nothing
        fetches nothing; and an index never goes back."""
        nq, nk = Sq // bq, Sk // bk
        args = (bq, bk, q_off, k_off)
        _, band_kb, band_qb = att._tile_plan(True, nq, nk, *args, window)
        kv_map = att._kv_index_map(True, nk, *args, window=window, group=7)
        q_block = att._q_block(True, nq, *args, window=window)
        visible = np.asarray(att._tile_visible(
            np.arange(nq)[:, None], np.arange(nk)[None, :], *args, window))
        for i in range(nq):
            row = [int(kv_map(15, i, jj)[1]) for jj in range(band_kb)]
            found = list(np.flatnonzero(visible[i]))
            if found:  # its tiles, then the last of them again
                assert row == (found + [found[-1]] * band_kb)[:band_kb], i
            else:      # one block of the sequence, the same at every step
                assert len(set(row)) == 1 and 0 <= row[0] < nk, i
            # a block index only ever moves forward: nothing is fetched twice
            assert row == sorted(row)
            assert {int(kv_map(15, i, jj)[0])
                    for jj in range(band_kb)} == {2}
        for j in range(nk):
            column = [int(q_block(j, ii)) for ii in range(band_qb)]
            found = list(np.flatnonzero(visible[:, j]))
            if found:
                assert column == (found + [found[-1]] * band_qb)[:band_qb], j
            else:
                assert len(set(column)) == 1 and 0 <= column[0] < nq, j
            assert column == sorted(column)

    def test_without_a_window_the_inner_step_is_the_block_itself(self):
        """A causal call keeps the whole row and column: the K-side index
        stops at the diagonal and the Q-side one starts there, as before
        the band grid."""
        args = (32, 32, 0, 0)
        assert att._tile_plan(True, 8, 8, *args) == (36, 8, 8)
        assert att._tile_plan(False, 8, 4, *args) == (32, 4, 8)
        kv_map = att._kv_index_map(True, 8, *args)
        q_block = att._q_block(True, 8, *args)
        for i in range(8):
            assert [int(kv_map(3, i, j)[1]) for j in range(8)] == [
                min(j, i) for j in range(8)]
            assert [int(q_block(i, j)) for j in range(8)] == [
                max(j, i) for j in range(8)]

    def test_a_group_of_one_keeps_the_slice_index_itself(self):
        assert att._kv_head(5, 1) == 5
        assert [att._kv_head(bh, 7) for bh in (0, 6, 7, 27, 28)] == [
            0, 0, 1, 3, 4]


def digest(*arrays):
    found = hashlib.sha256()
    for array in arrays:
        found.update(np.asarray(array).tobytes())
    return found.hexdigest()


# batch, heads, kv heads, Sq, Sk, bq, bk, q_off, k_off, window -> sha256 over
# (out, lse, dq, dk, dv) as the parent of the band grid (2fde1b5) returned
# them, its grid whole: one case a kernel path.
RECORDED = {
    "group1": (
        (2, 4, 4, 128, 128, 32, 32, 0, 0, 40),
        "17b13ba41bdd45c681c5842a8194809a35bf220b738efa7ae479a95d6adb266e"),
    "group7": (
        (1, 14, 2, 96, 96, 32, 32, 0, 0, 65),
        "3549f8c89b3c5f6df1e6a083afbe9a727c117d7e6938d9054ef8aeead092e71a"),
    "group7-window-under-a-tile": (
        (1, 14, 2, 64, 64, 16, 16, 0, 0, 5),
        "294d20016af64ddf3c66198f8daa10f1cad8eb59537c3f3419b027163a0955c3"),
    "group2-offsets": (
        (1, 4, 2, 64, 128, 32, 32, 64, 0, 40),
        "02a5b572e0842818700b312808cb854771bcb166af2bab2741eaca4e52eeabdc"),
    "group2-a-row-and-a-column-with-no-tile": (
        (1, 4, 2, 96, 96, 32, 32, 0, 32, 40),
        "0d37433731c084bc460cab85d8b0081de14fd4a714bfdbcd4d420dc8dcb6e285"),
    "group1-unequal-tiles": (
        (1, 4, 4, 128, 128, 16, 64, 0, 0, 50),
        "e87b7b3dc891e936f268817db4716755520bbdaaa282f96ea7be0799d7dec053"),
    "group1-nothing-visible": (
        (1, 2, 2, 64, 64, 32, 32, 500, 0, 100),
        "948f045d9c451579f1bb9670a3026cba5ee7b1c7db2d8c25c0df33b992778d1a"),
}
# Plain XLA arithmetic on like operands, recorded in the same process: where
# this machine's float32 products and sums are not the recording one's, the
# kernels' digests say nothing and the wider grid below is the witness.
CANARY = "8d718dccdf6affc9c8d702b21266a3847cfa72bd006eccd0ebd7217587fae51b"
# sha256 of jit(grad(flash_attention(...).sum())).lower(q, k, v).as_text() at
# S=128 in tiles of 32, interpreted, on the same parent: (causal, heads,
# kv heads). None has a window, so none may change.
LOWERED = {
    "causal": (
        (True, 4, 4),
        "3574fa59e8374733f81a9bb3c3c06f715326259bf50bb2a9db6b857428344863"),
    "causal-group7": (
        (True, 14, 2),
        "f9eada1fe7bad62172dfb8111a145dc59993682199c72e541a42af4d3719e9f7"),
    "not-causal": (
        (False, 4, 4),
        "ac5d999b7d588e04f4de8b9f6988556e944307eb6cbca7f375fec797ae5b3b89"),
    "not-causal-group2": (
        (False, 4, 2),
        "7201e3f21fadbf534e573ce098a2e916ab27ac748e58f3ceb46f9e9a7e2c11c5"),
}


def windowed(case, flash=flash_attention_lse):
    """``(out, lse, dq, dk, dv)`` of one windowed call, the logsumexp's
    cotangent not zero."""
    batch, heads, kv_heads, sq, sk, bq, bk, q_off, k_off, window = case
    q, k, v, weight = operands(batch, heads, kv_heads, sq, sk, seed=13)
    found, vjp = jax.vjp(
        lambda q, k, v: flash(q, k, v, True, bq, bk, q_off, k_off, True,
                              window), q, k, v)
    return tuple(found) + vjp((weight, jnp.cos(weight[..., 0])))


@pytest.fixture(scope="module")
def the_recording_machines_arithmetic():
    q, k, v, _ = operands(1, 4, 4, 128, 128, seed=13)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / 4.0
    if digest(jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v),
              jnp.exp(scores).sum(-1)) != CANARY:
        pytest.skip("another machine's float32 arithmetic than the one the "
                    "digests were recorded on")


class TestTheBandGridChangesNoBit:
    @pytest.mark.parametrize("name", sorted(RECORDED))
    def test_windowed_calls_return_what_the_whole_grid_did(
            self, name, the_recording_machines_arithmetic):
        case, recorded = RECORDED[name]
        assert digest(*windowed(case)) == recorded

    @pytest.mark.parametrize("name", sorted(RECORDED))
    def test_a_grid_wider_than_the_band_returns_the_same_bits(
            self, name, monkeypatch):
        """The whole row and column as the innermost extent, the parent's
        grid: the steps past the band compute nothing, on any machine."""
        case = RECORDED[name][0]
        band = windowed(case, att._flash)
        plan, widened = att._tile_plan, []

        def whole(causal, num_qb, num_kb, *rest):
            widened.append((num_qb, num_kb))
            return plan(causal, num_qb, num_kb, *rest)[0], num_kb, num_qb

        monkeypatch.setattr(att, "_tile_plan", whole)
        for a, b in zip(band, windowed(case, att._flash)):
            np.testing.assert_array_equal(a, b)
        assert set(widened) == {(case[3] // case[5], case[4] // case[6])}

    @pytest.mark.parametrize("name", sorted(LOWERED))
    def test_calls_without_a_window_lower_to_the_parents_text(self, name):
        (causal, heads, kv_heads), recorded = LOWERED[name]
        q, k, v, _ = operands(1, heads, kv_heads, 128, 128)
        text = jax.jit(jax.grad(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, block_q=32, block_k=32,
            interpret=True).sum(), (0, 1, 2))).lower(q, k, v).as_text()
        assert hashlib.sha256(text.encode()).hexdigest() == recorded


class TestPlansAndGuards:
    def test_smallthinkers_band_is_252_of_the_triangles_528_tiles(self):
        assert att._tile_plan(True, 32, 32, 512, 512, 0, 0, 4096)[0] == 252
        assert att._tile_plan(True, 32, 32, 512, 512, 0, 0, None)[0] == 528
        # its 28 query heads read 4 key/value heads, seven each
        shaped = jax.ShapeDtypeStruct
        assert att._tiled_shapes(shaped((28, 16384, 128), jnp.bfloat16),
                                 shaped((4, 16384, 128), jnp.bfloat16),
                                 None)[-1] == 7

    @pytest.mark.parametrize("blocks, window, grid, extents", [
        pytest.param(32, 4096, 288, (9, 9), id="smallthinker-window-layer"),
        pytest.param(32, None, 1024, (32, 32), id="smallthinker-full-layer"),
        pytest.param(8, None, 64, (8, 8), id="olmoe"),
    ])
    def test_the_grid_a_slice_runs_over(self, blocks, window, grid, extents):
        """A windowed call's grid is the band's 9 steps a row; what is
        still empty of it is ``grid - computed`` (36 of 288)."""
        computed, band_kb, band_qb = att._tile_plan(
            True, blocks, blocks, 512, 512, 0, 0, window)
        assert (band_kb, band_qb) == extents
        assert blocks * band_kb == grid
        assert computed <= grid
        if window:
            assert grid - computed == 36

    def test_a_traced_call_walks_the_plans_grid(self):
        q, k, v, _ = operands(1, 14, 2, 64, 64)

        def loss(q, k, v):
            return flash_attention(
                q, k, v, causal=True, window=20, block_q=16, block_k=16,
                interpret=True).sum()

        # rows of 16 see 1, 2, 3, 3 tiles of 16 under a window of 20
        assert att._tile_plan(True, 4, 4, 16, 16, 0, 0, 20) == (9, 3, 3)
        # forward and dq: 14 slices x 4 q blocks x the band's 3; dk/dv: 2
        # key/value heads x 4 k blocks x 7 query heads each x the band's 3
        assert sorted(pallas_grids(jax.grad(loss, (0, 1, 2)), q, k, v)) == [
            (2, 4, 7, 3), (14, 4, 3), (14, 4, 3)]

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
