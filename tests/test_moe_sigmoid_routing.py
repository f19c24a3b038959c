"""``parallel/moe.py::route_to_capacity`` with ``scores="sigmoid"`` (the
DeepSeek-V3 family's routing): the picks are the top-k of the sigmoid
scores, a gate is its pick's score, renormalised over the picks and scaled
where asked, against a hand count; with ``selection_bias`` the picks are by
score plus bias and the gates by the score alone, and nothing is
differentiated through the bias; the windows' gates add up to the scale over
all the windows; the gradient reaches the logits through the gates; softmax
routing, and sigmoid routing without a bias, lower to the text they had; and
what is no score function is refused."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.parallel import moe

T, E, K = 6, 8, 3
SCALE = 2.446


def hand_count(logits, renormalised, scale, bias=0.0):
    """numpy, a token at a time: scores, the K largest of score + bias
    (ties to the lower index), the scores' share of their sum."""
    scores = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
    picks = np.argsort(-(scores + bias), axis=1, kind="stable")[:, :K]
    picked = np.take_along_axis(scores, picks, 1)
    if renormalised:
        picked = picked / (picked.sum(1, keepdims=True) + 1e-20)
    return picks, picked * scale


@pytest.fixture(scope="module")
def routed():
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    return (jax.random.normal(keys[0], (T, 4), jnp.float32),
            2.0 * jax.random.normal(keys[1], (T, E), jnp.float32))


@pytest.mark.parametrize("renormalised,scale", [
    (True, SCALE), (True, 1.0), (False, 1.0), (False, SCALE)])
def test_sigmoid_gates_are_the_hand_count(routed, renormalised, scale):
    tokens, logits = routed
    send, expert, pos, keep, gate, counts = moe.route_to_capacity(
        tokens, logits, E, T * K, top_k=K, scores="sigmoid",
        gates_over_picks=renormalised, gate_scale=scale)
    picks, gates = hand_count(logits, renormalised, scale)
    np.testing.assert_array_equal(expert, picks)
    np.testing.assert_allclose(gate, gates, rtol=2e-6)
    assert gate.dtype == jnp.float32 and bool(keep.all())
    assert int(counts.sum()) == T * K
    if renormalised:
        np.testing.assert_allclose(gate.sum(1), scale, rtol=1e-6)


@pytest.mark.parametrize("renormalised", [True, False])
def test_a_selection_bias_moves_the_picks_and_no_gate(routed, renormalised):
    """``noaux_tc``: the choice is by ``s + b``, the gate by ``s``."""
    tokens, logits = routed
    bias = jnp.asarray([0.9, -0.9, 0.0, 0.6, -0.3, 0.0, 0.45, -0.6])
    _, expert, _, _, gate, _ = moe.route_to_capacity(
        tokens, logits, E, T * K, top_k=K, scores="sigmoid",
        gates_over_picks=renormalised, gate_scale=SCALE,
        selection_bias=bias)
    picks, gates = hand_count(logits, renormalised, SCALE, np.asarray(bias))
    np.testing.assert_array_equal(expert, picks)
    np.testing.assert_allclose(gate, gates, rtol=2e-6)
    plain, _ = hand_count(logits, renormalised, SCALE)
    assert (picks != plain).any()  # the bias did choose otherwise
    # one number for every expert chooses as none does
    _, same, _, _, same_gate, _ = moe.route_to_capacity(
        tokens, logits, E, T * K, top_k=K, scores="sigmoid",
        gates_over_picks=renormalised, gate_scale=SCALE,
        selection_bias=jnp.full((E,), 0.25))
    np.testing.assert_array_equal(same, plain)

    def weighted(logits, bias):
        _, _, _, _, gate, _ = moe.route_to_capacity(
            tokens, logits, E, T * K, top_k=K, scores="sigmoid",
            gates_over_picks=renormalised, gate_scale=SCALE,
            selection_bias=bias)
        return jnp.sum(gate * jnp.arange(1.0, K + 1))

    to_logits, to_bias = jax.grad(weighted, (0, 1))(logits, bias)
    assert not np.asarray(to_bias).any()
    chosen = np.zeros((T, E), bool)
    np.put_along_axis(chosen, picks, True, 1)
    assert np.all(np.asarray(to_logits)[~chosen] == 0)
    assert np.all(np.abs(np.asarray(to_logits)[chosen]) > 0)


def test_a_selection_bias_is_the_sigmoid_scores_alone(routed):
    tokens, logits = routed
    with pytest.raises(ValueError, match="selection_bias is the sigmoid"):
        moe.route_to_capacity(tokens, logits, E, 4, top_k=K,
                              selection_bias=jnp.zeros((E,)))


def test_the_windows_gates_add_up_to_the_scale(routed):
    """A token's gates are normalised over all its picks wherever they
    live: two windows of four experts keep complementary pairs, and the
    kept gates add up to the scale."""
    tokens, logits = routed
    kept = 0.0
    for first in (0, 4):
        _, expert, _, keep, gate, counts = moe.route_to_capacity(
            tokens, logits, E, T * K, top_k=K, first_expert=first,
            experts_here=4, scores="sigmoid", gates_over_picks=True,
            gate_scale=SCALE)
        inside = (expert >= first) & (expert < first + 4)
        np.testing.assert_array_equal(keep, inside)
        assert int(counts.sum()) == int(inside.sum())
        kept = kept + (gate * keep).sum(1)
    np.testing.assert_allclose(kept, SCALE, rtol=1e-6)


def test_sigmoid_scores_are_taken_in_float32_from_bfloat16_logits(routed):
    tokens, logits = routed
    low = logits.astype(jnp.bfloat16)
    _, expert, _, _, gate, _ = moe.route_to_capacity(
        tokens, low, E, T * K, top_k=K, scores="sigmoid",
        gates_over_picks=True, gate_scale=SCALE)
    picks, gates = hand_count(low.astype(jnp.float32), True, SCALE)
    assert gate.dtype == jnp.float32
    np.testing.assert_array_equal(expert, picks)
    np.testing.assert_allclose(gate, gates, rtol=2e-6)


def test_the_gradient_reaches_the_logits_through_the_gates(routed):
    tokens, logits = routed

    def weighted(logits):
        _, _, _, _, gate, _ = moe.route_to_capacity(
            tokens, logits, E, T * K, top_k=K, scores="sigmoid",
            gates_over_picks=True, gate_scale=SCALE)
        return jnp.sum(gate * jnp.arange(1.0, K + 1))

    grad = jax.grad(weighted)(logits)
    picks, _ = hand_count(logits, True, SCALE)
    chosen = np.zeros((T, E), bool)
    np.put_along_axis(chosen, picks, True, 1)
    assert bool(jnp.isfinite(grad).all())
    assert np.all(np.asarray(grad)[~chosen] == 0)
    assert np.all(np.abs(np.asarray(grad)[chosen]) > 0)


@pytest.mark.parametrize("scores,scale", [("tanh", 1.0), ("softmax", 2.0)])
def test_what_is_no_score_function_is_refused(routed, scores, scale):
    tokens, logits = routed
    with pytest.raises(ValueError, match="'softmax' \\(unscaled\\) or"):
        moe.route_to_capacity(tokens, logits, E, 4, top_k=K, scores=scores,
                              gate_scale=scale)


# sha256 of jit(route_to_capacity).lower(...).as_text() at commit c659eac,
# before the score function was an argument
PARENTS_TEXT = {
    False: "45e27d9c30394f8d5e1c3edca759ad4232e77994a36135b0a96fccd3126d52fb",
    True: "ad59f18d7da24136dcc4432f67c6c585a15594fbf487479de9c1ab773c12f3cb",
}


# the same with scores="sigmoid", gate_scale=2.5 at commit 7ec0510, before
# the selection bias was an argument
PARENTS_SIGMOID_TEXT = {
    False: "15e6839f137106508717ab06f1b4cc4a81d74b0b153a11915e0cda68e6e2a832",
    True: "2ac2f72141e0044d249f263db2b0234ec36fdc46b42b2253bc86eeb4807a3d2c",
}


@pytest.mark.parametrize("over_picks", [False, True])
@pytest.mark.parametrize("scores", ["softmax", "sigmoid"])
def test_routing_without_a_bias_lowers_to_the_text_it_had(scores, over_picks):
    t = jnp.zeros((32, 8), jnp.bfloat16)
    l = jnp.zeros((32, 8), jnp.float32)  # noqa: E741
    how = {"softmax": {}, "sigmoid": dict(scores="sigmoid", gate_scale=2.5)}
    f = lambda t, l: moe.route_to_capacity(  # noqa: E731,E741
        t, l, 8, 10, top_k=2, first_expert=2, experts_here=4,
        gates_over_picks=over_picks, **how[scores])
    text = jax.jit(f).lower(t, l).as_text()
    want = {"softmax": PARENTS_TEXT, "sigmoid": PARENTS_SIGMOID_TEXT}[scores]
    assert hashlib.sha256(text.encode()).hexdigest() == want[over_picks]
