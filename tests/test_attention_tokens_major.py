"""The tokens-major entry to the flash kernels (PR 35): q, k, v, the output
and every gradient ``[B, S, H * D]``, where a projection writes and reads
them. A one-tile call goes to the single-tile kernels as it lies, a block
the lanes of whole heads (two at D = 64) of a group of batch rows; any other
call transposes and is ``flash_attention``'s. CPU, interpret mode: what is
computed and which path takes it. What the chip's compiler makes of the
blocks is ``tests/test_tpu_aot.py``'s; what it costs is PERF.md's.
"""

from __future__ import annotations

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import bert
from horovod_tpu.ops import attention as att
from horovod_tpu.ops.attention import (flash_attention,
                                       flash_attention_tokens_major)
from traced import pallas_calls, pallas_grids


def operands(batch, seq, heads, kv_heads, dim, dtype, seed=0):
    """q ``[B, S, H * D]``, k, v ``[B, S, KV * D]`` and a cotangent of the
    output's shape."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    widths = (heads, kv_heads, kv_heads, heads)
    return tuple(
        jax.random.normal(key, (batch, seq, width * dim),
                          jnp.float32).astype(dtype)
        for key, width in zip(keys, widths))


def head_major(x, dim):
    return x.reshape(x.shape[:2] + (-1, dim)).transpose(0, 2, 1, 3)


def tokens_major(x):
    return x.transpose(0, 2, 1, 3).reshape(x.shape[0], x.shape[2], -1)


def single_tile_plan(fn, args, batch, heads):
    """``(group fwd, group bwd, heads a block fwd, bwd)`` of the single-tile
    kernels that ``fn(*args)`` traces, read off their grids (``(B // G, H //
    heads a block)`` of a tokens-major call, ``(B H // G,)`` of a head-major
    one, whose block holds one head); ``()`` where it traces none."""
    grids = [grid for grid in pallas_grids(fn, *args) if len(grid) < 3]
    return (tuple((batch if len(grid) == 2 else batch * heads) // grid[0]
                  for grid in grids)
            + tuple(heads // grid[1] if len(grid) == 2 else 1
                    for grid in grids))


def both(batch, seq, heads, kv_heads, dim, dtype, **call):
    """``(out, dq, dk, dv)`` of the tokens-major entry and of
    ``flash_attention`` on the transposed operands, and the first's
    :func:`single_tile_plan`."""
    q, k, v, weight = operands(batch, seq, heads, kv_heads, dim, dtype)
    entry = functools.partial(
        flash_attention_tokens_major, num_heads=heads, interpret=True,
        **call)
    out, vjp = jax.vjp(entry, q, k, v)
    here = (out,) + vjp(weight)
    gauges = single_tile_plan(
        lambda q, k, v: jax.vjp(entry, q, k, v)[1](weight), (q, k, v),
        batch, heads)
    out, vjp = jax.vjp(
        lambda q, k, v: tokens_major(flash_attention(
            head_major(q, dim), head_major(k, dim), head_major(v, dim),
            interpret=True, **call)), q, k, v)
    return here, (out,) + vjp(weight), gauges


# batch, S, H, D, dtype, causal -> heads a block. Three rows of 128: a
# group of three head pairs, an odd number.
FAST = {
    "bert-s128": ((2, 128, 16, 64, jnp.bfloat16, False), 2),
    "bert-s128-float32": ((2, 128, 16, 64, jnp.float32, False), 2),
    "bert-s512": ((2, 512, 16, 64, jnp.bfloat16, False), 2),
    "a-head-a-block": ((2, 128, 8, 128, jnp.bfloat16, False), 1),
    "a-head-a-block-float32": ((2, 128, 8, 128, jnp.float32, False), 1),
    "three-pairs-a-group": ((3, 128, 4, 64, jnp.bfloat16, False), 2),
    "three-pairs-a-group-causal": ((3, 128, 4, 64, jnp.float32, True), 2),
    "eight-heads-a-block": ((2, 256, 8, 16, jnp.float32, True), 8),
}


class TestTheSingleTileKernelsOnTokensMajorOperands:
    @pytest.mark.parametrize("name", sorted(FAST))
    def test_output_and_gradients_are_the_head_major_calls(self, name):
        (batch, seq, heads, dim, dtype, causal), per_block = FAST[name]
        here, there, gauges = both(batch, seq, heads, heads, dim, dtype,
                                   causal=causal)
        # A head contracts over its block's other lanes too, as exact
        # zeros: no sum changes but by the order its terms are taken in.
        tolerance = (dict(rtol=1e-5, atol=1e-6) if dtype == jnp.float32
                     else dict(rtol=2 ** -7, atol=2 ** -7))
        for a, b in zip(here, there):
            assert a.shape == b.shape and a.dtype == b.dtype
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                **tolerance)
        assert gauges[2:] == (per_block, per_block)
        assert all(group >= 1 and batch % group == 0
                   for group in gauges[:2])

    def test_three_rows_are_one_group_of_three_pairs(self):
        *_, gauges = both(3, 128, 4, 4, 64, jnp.bfloat16)
        assert gauges == (3, 3, 2, 2)

    def test_a_head_major_calls_block_holds_one_head(self):
        q = jnp.zeros((1, 2, 128, 64), jnp.bfloat16)
        plan = single_tile_plan(jax.grad(lambda q: flash_attention(
            q, q, q, interpret=True).astype(jnp.float32).sum()), (q,), 1, 2)
        assert plan[2:] == (1, 1)

    def test_offsets_and_fully_masked_rows(self):
        """Keys from position 64 on, queries from 0: the first 64 queries
        see nothing and return zeros, as the head-major call has it."""
        here, there, gauges = both(2, 128, 2, 2, 64, jnp.float32,
                                   causal=True, q_offset=0, k_offset=64)
        assert gauges[2:] == (2, 2)
        for a, b in zip(here, there):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
        assert not np.asarray(here[0][:, :64]).any()
        assert np.asarray(here[0][:, 64:]).any()


# batch, S, H, KV, D, call: each takes another way than the single-tile
# kernels, so the entry transposes and ``flash_attention`` computes it.
FALLBACK = {
    "two-tiles": ((2, 128, 2, 2, 64), dict(block_q=64, block_k=64)),
    "a-window": ((2, 128, 2, 2, 64), dict(causal=True, window=40)),
    "grouped-keys-and-values": ((2, 128, 4, 2, 64), dict(causal=True)),
    "a-width-that-tiles-no-lanes": ((2, 128, 4, 4, 48), dict()),
    "heads-that-leave-a-block-short": ((2, 128, 3, 3, 64), dict()),
}


class TestAnyOtherCallTransposes:
    @pytest.mark.parametrize("name", sorted(FALLBACK))
    def test_and_equals_the_transposing_call_bit_for_bit(self, name):
        (batch, seq, heads, kv_heads, dim), call = FALLBACK[name]
        here, there, gauges = both(batch, seq, heads, kv_heads, dim,
                                   jnp.float32, **call)
        for a, b in zip(here, there):
            np.testing.assert_array_equal(a, b)
        if name in ("a-width-that-tiles-no-lanes",
                    "heads-that-leave-a-block-short"):
            assert gauges[2:] == (1, 1)  # the head-major single tile
        else:
            assert gauges == ()  # no single-tile kernel at all

    def test_a_width_the_heads_do_not_divide_is_refused(self):
        q = jnp.zeros((1, 128, 100), jnp.float32)
        with pytest.raises(ValueError, match="tokens-major"):
            flash_attention_tokens_major(q, q, q, 3, interpret=True)


# ---------------------------------------------------------------------------
# PR 40: several tiles of heads that are whole 128-lane blocks go to the
# multi-tile kernels as they lie, a head the lane block the index maps find
# ---------------------------------------------------------------------------

# batch, S, H, KV heads, tile, call. D = 128 throughout: the narrowest head
# that is a lane block of its own.
TILED = {
    "full": ((2, 64, 2, 2, 16), dict()),
    "causal": ((2, 64, 2, 2, 16), dict(causal=True)),
    "causal-tiles-of-32-on-16": ((1, 64, 2, 2, (32, 16)), dict(causal=True)),
    "a-window": ((1, 64, 2, 2, 16), dict(causal=True, window=24)),
    "grouped-7-on-1": ((1, 64, 7, 1, 16), dict(causal=True)),
    "grouped-8-on-1-under-a-window": (
        (1, 64, 8, 1, 16), dict(causal=True, window=24)),
    "grouped-4-on-2-two-rows": ((2, 48, 4, 2, 16), dict(causal=True)),
    "blocks-of-4": ((1, 64, 4, 2, 16), dict(causal=True, block_length=4)),
    "before-the-own-block": (
        (1, 64, 4, 2, 16),
        dict(causal=True, block_length=4, before_block=True)),
    "offsets": ((1, 64, 2, 1, 16),
                dict(causal=True, q_offset=32, k_offset=16)),
}


def layouts(fn, *args, dim):
    """How each multi-tile kernel (forward, dq, dk/dv: grids of three axes
    and four) that ``fn(*args)`` traces is fed its queries: 1 tokens-major
    (``[B, S, H * D]``, the lanes of several heads), 0 head-major (``[BH,
    S, D]``). ``[]`` where it traces none."""
    return [int(shapes[0][-1] != dim)
            for grid, shapes in pallas_calls(fn, *args) if len(grid) >= 3]


def tiled_both(name, dtype, with_lse):
    """``(out, lse, dq, dk, dv)`` of the tokens-major entry and of the
    head-major one on the transposed operands (``lse`` left out without
    ``with_lse``), a cotangent on the log-sum-exp too where there is one,
    and each one's :func:`layouts`."""
    (batch, seq, heads, kv_heads, tile), call = TILED[name]
    block_q, block_k = tile if isinstance(tile, tuple) else (tile, tile)
    call = dict(call, block_q=block_q, block_k=block_k, interpret=True)
    dim = 128
    q, k, v, weight = operands(batch, seq, heads, kv_heads, dim, dtype)
    lse_weight = jax.random.normal(jax.random.PRNGKey(7),
                                   (batch, heads, seq), jnp.float32)

    def here(q, k, v):
        if with_lse:
            return att.flash_attention_tokens_major_lse(
                q, k, v, num_heads=heads, **call)
        return (flash_attention_tokens_major(q, k, v, num_heads=heads,
                                             **call),)

    def there(q, k, v):
        operands_ = [head_major(x, dim) for x in (q, k, v)]
        if with_lse:
            out, lse = att.flash_attention_lse(*operands_, **call)
            return tokens_major(out), lse
        return (tokens_major(flash_attention(*operands_, **call)),)

    found = []
    for fn in (here, there):
        outs, vjp = jax.vjp(fn, q, k, v)
        cotangents = (weight.astype(outs[0].dtype),) + (
            (lse_weight,) if with_lse else ())
        found.append((tuple(outs) + vjp(cotangents), layouts(
            lambda q, k, v: jax.vjp(fn, q, k, v)[1](cotangents), q, k, v,
            dim=dim)))
    return found


class TestSeveralTilesOfWholeLaneBlocksGoAsTheyLie:
    @pytest.mark.parametrize("with_lse", [False, True],
                             ids=["context", "context-and-lse"])
    @pytest.mark.parametrize("name", sorted(TILED))
    def test_every_result_is_the_head_major_calls_bit_for_bit(self, name,
                                                              with_lse):
        (here, layout), (there, head_major_layout) = tiled_both(
            name, jnp.float32, with_lse)
        assert len(here) == len(there) == (5 if with_lse else 4)
        for a, b in zip(here, there):
            assert a.shape == b.shape and a.dtype == b.dtype
            np.testing.assert_array_equal(a, b)
        assert np.asarray(here[0]).any() and np.asarray(here[-1]).any()
        assert layout == [1, 1, 1]
        assert head_major_layout == [0, 0, 0]

    @pytest.mark.parametrize("name", ["a-window", "grouped-7-on-1",
                                      "before-the-own-block"])
    def test_in_bfloat16_as_the_cells_run_it(self, name):
        (here, layout), (there, _) = tiled_both(name, jnp.bfloat16, True)
        for a, b in zip(here, there):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
        assert here[0].dtype == jnp.bfloat16 and layout == [1, 1, 1]

    def test_the_kernels_are_the_head_major_calls_but_for_the_index_maps(
            self):
        """Same three kernels on the same grids, the one tile plan's: what
        differs between the two traced programs' kernels is where a block is
        looked for."""
        q, k, v, _ = operands(1, 64, 4, 2, 128, jnp.float32)
        call = dict(causal=True, window=24, block_q=16, block_k=16,
                    interpret=True)
        lying = pallas_grids(jax.grad(
            lambda q, k, v: flash_attention_tokens_major(
                q, k, v, num_heads=4, **call).sum(), (0, 1, 2)), q, k, v)
        transposed = pallas_grids(jax.grad(lambda q, k, v: flash_attention(
            head_major(q, 128), head_major(k, 128), head_major(v, 128),
            **call).sum(), (0, 1, 2)), q, k, v)
        computed, band_kb, band_qb = att._tile_plan(
            True, 4, 4, 16, 16, 0, 0, 24)
        assert computed > 0
        assert lying == transposed == [
            (4, 4, band_kb), (4, 4, band_kb), (2, 4, 2, band_qb)]

    @pytest.mark.parametrize("name, shape, call, wanted", [
        # two heads a lane block: lane masks are the one-tile kernels' alone
        ("narrow-heads", (3, 128, 2, 2, 64), dict(block_q=64, block_k=64),
         [0, 0, 0]),
        ("narrow-grouped-heads-as-granite", (1, 128, 4, 1, 64),
         dict(causal=True, block_q=64, block_k=64), [0, 0, 0]),
        # one tile of whole lane blocks: the single-tile kernels, as since
        # PR 35: no multi-tile kernel at all
        ("one-tile", (3, 128, 2, 2, 128), dict(), []),
        ("one-tile-of-several-blocks-a-head", (1, 128, 2, 2, 256), dict(),
         []),
        ("several-blocks-a-head", (1, 64, 2, 1, 256),
         dict(causal=True, block_q=16, block_k=16), [1, 1, 1]),
    ])
    def test_which_way_a_call_goes_is_read_off_its_shapes(self, name, shape,
                                                          call, wanted):
        batch, seq, heads, kv_heads, dim = shape
        q, k, v, weight = operands(batch, seq, heads, kv_heads, dim,
                                   jnp.float32)
        entry = functools.partial(
            flash_attention_tokens_major, num_heads=heads, interpret=True,
            **call)
        out, vjp = jax.vjp(entry, q, k, v)
        here = (out,) + vjp(weight)
        assert layouts(lambda q, k, v: jax.vjp(entry, q, k, v)[1](weight),
                       q, k, v, dim=dim) == wanted
        out, vjp = jax.vjp(
            lambda q, k, v: tokens_major(flash_attention(
                head_major(q, dim), head_major(k, dim), head_major(v, dim),
                interpret=True, **call)), q, k, v)
        for a, b in zip(here, (out,) + vjp(weight)):
            if name.startswith("one-tile"):
                np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
            else:
                np.testing.assert_array_equal(a, b)

    def test_the_one_tile_call_with_a_log_sum_exp_transposes(self):
        """The single-tile tokens-major kernels return no log-sum-exp: a
        caller that wants one of a one-tile call gets the head-major
        kernels', as before."""
        q, k, v, _ = operands(1, 128, 2, 2, 128, jnp.float32)
        out, lse = att.flash_attention_tokens_major_lse(
            q, k, v, num_heads=2, interpret=True)
        want, want_lse = att.flash_attention_lse(
            head_major(q, 128), head_major(k, 128), head_major(v, 128),
            interpret=True)
        np.testing.assert_array_equal(out, tokens_major(want))
        np.testing.assert_array_equal(lse, want_lse)
        # the head-major single-tile forward (a grid over B H // G), fed
        # the transposed ``[2, 128, 128]``
        assert [shapes[0] for _, shapes in pallas_calls(
            functools.partial(att.flash_attention_tokens_major_lse,
                              num_heads=2, interpret=True), q, k, v)] == [
            (2, 128, 128)]


class TestHeadsABlockAndTheGroup:
    @pytest.mark.parametrize("dim, heads, wanted", [
        (64, 16, 2), (64, 2, 2), (64, 3, None), (128, 7, 1), (256, 2, 1),
        (32, 8, 4), (32, 6, None), (16, 8, 8), (16, 4, None),
        (96, 4, None), (48, 8, None), (192, 2, None)])
    def test_the_fewest_heads_that_fill_whole_tiles(self, dim, heads,
                                                    wanted):
        assert att._heads_per_block(dim, heads) == wanted

    @pytest.mark.parametrize("batch", [1, 3, 24, 96, 97])
    @pytest.mark.parametrize("seq", [128, 512])
    @pytest.mark.parametrize("kernel", ["fwd", "bwd"])
    def test_the_group_divides_the_rows_and_fits_the_budget(self, batch,
                                                            seq, kernel):
        counts = dict(fwd=att._FWD_SLICE, bwd=att._BWD_FROM_OUT_SLICE)[kernel]
        group = att._group_size(batch, seq, seq, 128, 2, heads=2, **counts)
        assert batch % group == 0
        fits = att._group_footprint(group, seq, seq, 128, 2, heads=2,
                                    **counts) <= att.GROUP_BUDGET_BYTES
        assert fits or group == 1
        larger = [g for g in range(group + 1, batch + 1) if batch % g == 0]
        assert not larger or att._group_footprint(
            larger[0], seq, seq, 128, 2, heads=2,
            **counts) > att.GROUP_BUDGET_BYTES

    def test_a_pair_is_charged_both_heads_scores(self):
        """Two heads in 128 lanes take the blocks one padded 64-lane head
        is charged, and the float32 scores of both."""
        one = att._group_footprint(1, 512, 512, 64, 2, **att._FWD_SLICE)
        pair = att._group_footprint(1, 512, 512, 128, 2, heads=2,
                                    **att._FWD_SLICE)
        scores = att._FWD_SLICE["temporaries"] * 512 * 512 * 4
        assert pair - one == scores

    def test_berts_groups(self):
        """BERT-Large's two shapes: 16 and 8 pairs a step at S=128 (24
        and 16 heads before), 2 and 1 at S=512 (3 and 2 heads)."""
        size = functools.partial(att._group_size, d=128, itemsize=2, heads=2)
        assert [size(96, 128, 128, **att._FWD_SLICE),
                size(96, 128, 128, **att._BWD_FROM_OUT_SLICE),
                size(24, 512, 512, **att._FWD_SLICE),
                size(24, 512, 512, **att._BWD_FROM_OUT_SLICE)] == [
                    16, 8, 2, 1]


# sha256 of jit(grad(flash_attention(...).sum())).lower(q, k, v).as_text() of
# a one-tile head-major call, interpreted, on the parent of PR 35 (8399919):
# (causal, batch, heads, S, D, dtype). The single-tile kernels gained heads a
# block; a block of one head is the program it was.
LOWERED = {
    "a-group-of-eight": (
        (False, 2, 4, 128, 64, jnp.bfloat16),
        "1feaec135bc4f8feb8207536fdbc99ed334ec2b8c7b28c922ab00fbd4784f2ec"),
    "one-slice-causal": (
        (True, 1, 1, 128, 16, jnp.float32),
        "fa13d107461154513611ffe0e20b045592813d6c67af4f77a820b0ef2f171836"),
    "s512": (
        (False, 3, 2, 512, 64, jnp.bfloat16),
        "01279a10961b6a26d766198ca5f07b6db50129d0fc079309ca913ecdb89734f9"),
}


@pytest.mark.parametrize("name", sorted(LOWERED))
def test_a_head_major_single_tile_call_lowers_to_the_parents_text(name):
    (causal, batch, heads, seq, dim, dtype), recorded = LOWERED[name]
    shape = jax.ShapeDtypeStruct((batch, heads, seq, dim), dtype)
    text = jax.jit(jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, interpret=True).astype(jnp.float32).sum(),
        (0, 1, 2))).lower(shape, shape, shape).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == recorded


# ---------------------------------------------------------------------------
# models/bert.py: the projections write [B, S, H * D] and the adapter takes it
# ---------------------------------------------------------------------------

TOY = bert.BertConfig(
    vocab_size=256, hidden_size=128, num_layers=2, num_heads=2,
    intermediate_size=256, max_position_embeddings=128, dropout_rate=0.0,
    dtype=jnp.float32)
# sha256 over (path, shape, bytes) of Bert(BERT_TINY).init(PRNGKey(0), [1, 8]
# zeros), leaves in the order of their paths, on the parent of PR 35: its
# projections were nn.DenseGeneral.
PARENTS_TINY_PARAMS = (
    "e98922a1e38e5178df8555e799363f53a38157775ef12f45304a7d4e0145f3e4")


def loss_and_gradients(attention_fn, ids):
    model = bert.Bert(TOY, attention_fn=attention_fn)
    params = bert.Bert(TOY).init(jax.random.PRNGKey(0), ids[:1, :8])["params"]

    def loss(params):
        _, logits = model.apply({"params": params}, ids, train=True)
        return bert.mlm_loss(logits, ids, jnp.ones_like(ids))

    return jax.value_and_grad(loss)(params)


class TestBertHandsTheKernelsWhatTheProjectionsWrote:
    def test_the_parameter_tree_is_the_parents(self):
        params = bert.Bert(bert.BERT_TINY).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
        attention = params["layer_0"]["attention"]
        assert {(name, leaf): array.shape
                for name, module in attention.items()
                for leaf, array in module.items()} == {
            ("query", "kernel"): (64, 4, 16), ("query", "bias"): (4, 16),
            ("key", "kernel"): (64, 4, 16), ("key", "bias"): (4, 16),
            ("value", "kernel"): (64, 4, 16), ("value", "bias"): (4, 16),
            ("out", "kernel"): (4, 16, 64), ("out", "bias"): (64,)}
        found = hashlib.sha256()
        leaves = jax.tree_util.tree_flatten_with_path(params)[0]
        for path, leaf in sorted(
                leaves, key=lambda kv: jax.tree_util.keystr(kv[0])):
            found.update(jax.tree_util.keystr(path).encode())
            found.update(str(leaf.shape).encode())
            found.update(np.asarray(leaf).tobytes())
        assert found.hexdigest() == PARENTS_TINY_PARAMS

    def test_the_adapter_says_what_it_takes_through_a_partial_too(self):
        assert bert.takes_tokens_major(bert.flash_attention_fn)
        assert bert.takes_tokens_major(functools.partial(
            functools.partial(bert.flash_attention_fn, interpret=True)))
        assert not bert.takes_tokens_major(bert.default_attention)
        assert not bert.takes_tokens_major(None)

    def test_loss_and_gradients_against_default_attention(self):
        ids = jax.random.randint(jax.random.PRNGKey(1), (3, 128), 0,
                                 TOY.vocab_size)
        seen = []

        def watched(q, k, v, mask_bias, dtype, **kw):
            seen.append((q.shape, k.shape, v.shape, kw))
            return bert.flash_attention_fn(q, k, v, mask_bias, dtype,
                                           interpret=True, **kw)

        watched.tokens_major = True
        loss, gradients = loss_and_gradients(watched, ids)
        # what the projections wrote, and the heads beside it
        assert seen == [((3, 128, 128),) * 3 + ({"num_heads": 2},)] * 2
        assert att._heads_per_block(TOY.hidden_size // TOY.num_heads,
                                    TOY.num_heads) == 2
        wanted_loss, wanted = loss_and_gradients(None, ids)
        np.testing.assert_allclose(loss, wanted_loss, rtol=1e-5)
        for (path, a), b in zip(
                jax.tree_util.tree_flatten_with_path(gradients)[0],
                jax.tree.leaves(wanted)):
            # the key bias's gradient is zero but for rounding: atol
            np.testing.assert_allclose(
                a, b, rtol=1e-4, atol=1e-7,
                err_msg=jax.tree_util.keystr(path))

    def test_any_other_attention_fn_is_handed_the_heads_apart(self):
        ids = jnp.ones((2, 16), jnp.int32)
        seen = []

        def apart(q, k, v, mask_bias, dtype):
            seen.append(q.shape)
            return bert.default_attention(q, k, v, mask_bias, dtype)

        model = bert.Bert(bert.BERT_TINY, attention_fn=apart)
        variables = bert.Bert(bert.BERT_TINY).init(jax.random.PRNGKey(0), ids)
        _, logits = model.apply(variables, ids)
        _, wanted = bert.Bert(bert.BERT_TINY).apply(variables, ids)
        assert seen == [(2, 16, 4, 16)] * 2
        np.testing.assert_array_equal(logits, wanted)

    def test_the_adapter_is_the_entry_without_a_mask(self):
        q, k, v, _ = operands(2, 128, 2, 2, 64, jnp.float32)
        found = bert.flash_attention_fn(q, k, v, None, jnp.bfloat16,
                                        interpret=True, num_heads=2)
        assert (found.shape, found.dtype) == ((2, 128, 128), jnp.bfloat16)
        np.testing.assert_array_equal(found, flash_attention_tokens_major(
            q, k, v, 2, interpret=True).astype(jnp.bfloat16))
