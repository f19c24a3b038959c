"""Top-k routing with an expert window (``parallel/moe.py``): what
``route_to_capacity`` returns for ``top_k=1`` is what it returned before
it had the argument; the picks are ``lax.top_k``'s; the gates are the
softmax's own; pairs past capacity are dropped latest first; windows that
partition the experts partition the layer; and ``routing_stats`` counts."""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from horovod_tpu.models import experts, olmoe
from horovod_tpu.parallel import moe

T, D, E = 48, 16, 8


@pytest.fixture(scope="module")
def routed():
    key = jax.random.PRNGKey(3)
    tokens = jax.random.normal(key, (T, D))
    logits = jax.random.normal(jax.random.fold_in(key, 1), (T, E))
    return tokens, logits


def route_to_capacity_before(tokens, logits, num_experts, capacity):
    """The function as it stood before ``top_k`` (PR 25's tree), copied."""
    T, D = tokens.shape
    expert = jnp.argmax(logits, axis=-1)
    gate = jax.nn.softmax(logits, axis=-1)
    gate = jnp.take_along_axis(gate, expert[:, None], axis=1)[:, 0]
    onehot = jax.nn.one_hot(expert, num_experts, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) * onehot
    pos = jnp.sum(pos, axis=1) - 1
    keep = (pos >= 0) & (pos < capacity)
    send = jnp.zeros((num_experts, capacity, D + 1), tokens.dtype)
    payload = jnp.concatenate(
        [tokens, jnp.ones((T, 1), tokens.dtype)], axis=1)
    send = send.at[expert, jnp.clip(pos, 0, capacity - 1)].add(
        jnp.where(keep[:, None], payload, 0.0))
    counts = jnp.sum(onehot * keep[:, None].astype(jnp.int32), axis=0)
    return send, expert, pos, keep, gate, counts


@pytest.mark.parametrize("capacity", [3, 6, T])
def test_top_1_is_bitwise_what_it_was(routed, capacity):
    tokens, logits = routed
    now = jax.jit(partial(moe.route_to_capacity, num_experts=E,
                          capacity=capacity))(tokens, logits)
    before = jax.jit(partial(route_to_capacity_before, num_experts=E,
                             capacity=capacity))(tokens, logits)
    for got, want in zip(now, before):
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_picks_are_top_k_and_gates_are_not_renormalised(routed):
    tokens, logits = routed
    _, expert, _, _, gate, _ = moe.route_to_capacity(
        tokens, logits, E, T, top_k=3)
    np.testing.assert_array_equal(expert, lax.top_k(logits, 3)[1])
    probs = jax.nn.softmax(logits, -1)
    np.testing.assert_array_equal(
        gate, jnp.take_along_axis(probs, expert, 1))
    # the softmax's own values over all E experts: they sum to less than 1
    assert float(gate.sum(1).max()) < 1.0


def test_a_tie_goes_to_the_lower_index_as_argmax_does():
    logits = jnp.zeros((4, E)).at[:, 5].set(1.0).at[:, 2].set(1.0)
    _, expert, *_ = moe.route_to_capacity(jnp.ones((4, D)), logits, E, 4,
                                          top_k=2)
    np.testing.assert_array_equal(expert, [[2, 5]] * 4)


def test_overflow_drops_the_latest_pairs_in_token_then_pick_order():
    # every token picks expert 1 first and expert 0 second
    logits = jnp.tile(jnp.array([1.0, 2.0, 0.0, -1.0]), (6, 1))
    tokens = jnp.arange(6.0)[:, None] + jnp.zeros((6, D))
    send, expert, pos, keep, _, counts = moe.route_to_capacity(
        tokens, logits, 4, 4, top_k=2)
    np.testing.assert_array_equal(expert, [[1, 0]] * 6)
    np.testing.assert_array_equal(pos[:, 0], np.arange(6))
    np.testing.assert_array_equal(keep, [[True, True]] * 4 + [[False] * 2] * 2)
    np.testing.assert_array_equal(counts, [4, 4, 0, 0])
    # the slots hold tokens 0..3 in order, and say so in the last channel
    np.testing.assert_array_equal(send[1, :, 0], np.arange(4.0))
    np.testing.assert_array_equal(send[0, :, -1], np.ones(4))
    assert float(jnp.abs(send[2:]).max()) == 0.0


def test_a_pair_outside_the_window_takes_no_slot(routed):
    tokens, logits = routed
    send, expert, pos, keep, _, counts = moe.route_to_capacity(
        tokens, logits, E, T, top_k=2, first_expert=2, experts_here=3)
    inside = (expert >= 2) & (expert < 5)
    np.testing.assert_array_equal(keep, inside)
    assert send.shape == (3, T, D + 1) and counts.shape == (3,)
    assert int(counts.sum()) == int(inside.sum())
    np.testing.assert_array_equal(pos[~inside], -1)
    # an expert's slots hold its tokens in token order
    for local in range(3):
        mine = np.asarray((expert == 2 + local).any(1))
        np.testing.assert_array_equal(
            send[local, :mine.sum(), :D], np.asarray(tokens)[mine])


def layer_output(tokens, logits, weights, capacity, top_k, first, here):
    send, expert, pos, keep, gate, _ = moe.route_to_capacity(
        tokens, logits, E, capacity, top_k=top_k, first_expert=first,
        experts_here=here)
    back = moe.gated_expert_ffn(
        *(w[first:first + here] for w in weights), send[..., :D])
    return moe.combine_top_k(back, expert, pos, keep, gate, first)


@pytest.mark.parametrize("capacity", [5, 2 * T])
def test_four_windows_sum_to_the_whole_layer(routed, capacity):
    tokens, logits = routed
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    weights = (jax.random.normal(keys[0], (E, D, 12)) * 0.3,
               jax.random.normal(keys[1], (E, D, 12)) * 0.3,
               jax.random.normal(keys[2], (E, 12, D)) * 0.3)
    whole = layer_output(tokens, logits, weights, capacity, 3, 0, E)
    parts = sum(layer_output(tokens, logits, weights, capacity, 3, first, 2)
                for first in range(0, E, 2))
    assert float(jnp.abs(whole).max()) > 0.1
    np.testing.assert_allclose(parts, whole, rtol=1e-5, atol=1e-6)
    # ... and the whole layer is the plain sum over each token's kept picks
    _, expert, pos, keep, gate, _ = moe.route_to_capacity(
        tokens, logits, E, capacity, top_k=3)
    plain = jnp.zeros_like(tokens)
    for k in range(3):
        e = expert[:, k]
        hidden = jax.nn.silu(jnp.einsum("td,tdh->th", tokens, weights[0][e])) \
            * jnp.einsum("td,tdh->th", tokens, weights[1][e])
        out = jnp.einsum("th,thd->td", hidden, weights[2][e])
        plain += jnp.where(keep[:, k, None], gate[:, k, None] * out, 0.0)
    np.testing.assert_allclose(whole, plain, rtol=1e-5, atol=1e-6)


def test_the_layer_is_differentiated_through_gates_and_experts(routed):
    tokens, logits = routed
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    weights = (jax.random.normal(keys[0], (E, D, 12)) * 0.3,
               jax.random.normal(keys[1], (E, D, 12)) * 0.3,
               jax.random.normal(keys[2], (E, 12, D)) * 0.3)

    def scalar(tokens, logits, weights):
        return jnp.sum(layer_output(tokens, logits, weights, 2 * T, 2, 0, E)
                       ** 2)

    grads = jax.grad(scalar, argnums=(0, 1, 2))(tokens, logits, weights)
    for leaf in jax.tree.leaves(grads):
        assert np.isfinite(leaf).all() and float(jnp.abs(leaf).max()) > 0


def test_expert_capacity_is_the_issues():
    assert moe.expert_capacity(1.25, 4096, 8, 64) == 640
    assert moe.expert_capacity(1.0, 10, 1, 3) == 4


def test_routing_stats_count_load_and_drops():
    config = dataclasses.replace(
        olmoe.OLMOE_TINY, dtype=jnp.float32, first_expert=2, experts_here=4,
        capacity_factor=1.0)
    model = olmoe.Olmoe(config)
    key = jax.random.PRNGKey(1)
    params = jax.jit(model.init)(key, jnp.zeros((1, 8), jnp.int32))["params"]
    ids = jax.random.randint(key, (3, 32), 0, config.vocab_size)
    stats = jax.jit(partial(experts.routing_stats, model))(params, ids)
    capacity = config.capacity(32)
    assert capacity == 8
    assert stats["load"].shape == (2, 4) and stats["dropped"].shape == (2,)
    # three sequences, each its own routing group of `capacity` slots
    assert int(stats["load"].max()) <= 3 * capacity
    assert (np.asarray(stats["dropped"]) > 0).all()
    # by hand for layer 0: the router sees ln_moe of the layer's stream
    _, state = jax.jit(partial(
        model.apply,
        capture_intermediates=lambda m, _: m.name == "ln_moe"))(
            {"params": params}, ids)
    n2 = state["intermediates"]["layer_0"]["ln_moe"]["__call__"][0]
    picks = lax.top_k(n2 @ params["layer_0"]["moe"]["router"], 2)[1]
    mine = (picks >= 2) & (picks < 6)
    assert int(stats["load"][0].sum() + stats["dropped"][0]) == int(mine.sum())
    np.testing.assert_allclose(
        stats["dropped_share"][0],
        stats["dropped"][0] / mine.sum(), rtol=1e-6)
    # the slots the traced step computes a sequence: 4 experts x capacity
    from traced import shapes
    assert (config.experts_held, config.top_k) == (4, 2)
    assert (3, 4, capacity, config.hidden_size) in shapes(
        partial(experts.routing_stats, model), params, ids)
