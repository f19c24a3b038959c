"""Two-matrix experts (Nemotron-H's ``relu(.)^2`` feed-forwards without a
gate): ``parallel/moe.py::plain_expert_ffn`` against a loop over the
experts, an empty slot that stays zero, ``SparseExperts(gated=False)``'s
tree of two stacked leaves beside the gated three and its lowered scope,
``take_expert_window`` and ``routing_stats`` on such a tree, and **the share
test of the model-configs guide, section 4**:
the sixteen windows' routed parts plus the shared expert once add up to the
uncut layer's."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import experts, nemotron_h
from horovod_tpu.models.parts import PlainMLP, relu2
from horovod_tpu.parallel import moe


def weights(seed=0, count=4, hidden=24, width=20):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (count, hidden, width)) * 0.2,
            jax.random.normal(keys[1], (count, width, hidden)) * 0.2,
            jax.random.normal(keys[2], (count, 6, hidden)))


@pytest.mark.parametrize("activation", [relu2, jax.nn.relu, jax.nn.silu])
def test_plain_experts_are_a_loop_over_the_experts(activation):
    w_up, w_down, x = weights()
    got = moe.plain_expert_ffn(w_up, w_down, x, activation)
    want = jnp.stack([activation(x[e] @ w_up[e]) @ w_down[e]
                      for e in range(4)])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_relu2_is_the_square_of_relu_and_keeps_zero():
    x = jnp.asarray([-2.0, -0.0, 0.0, 0.5, 3.0])
    np.testing.assert_array_equal(relu2(x), [0.0, 0.0, 0.0, 0.25, 9.0])
    np.testing.assert_array_equal(jax.grad(lambda t: relu2(t).sum())(x),
                                  [0.0, 0.0, 0.0, 1.0, 6.0])


def test_an_empty_slot_stays_zero():
    w_up, w_down, x = weights()
    x = x.at[:, 3:].set(0.0)  # the last slots of every expert are empty
    out = moe.plain_expert_ffn(w_up, w_down, x, relu2)
    np.testing.assert_array_equal(out[:, 3:], 0.0)
    assert float(jnp.abs(out[:, :3]).max()) > 0


def test_the_tree_has_two_stacked_leaves_where_no_expert_is_gated():
    cfg = nemotron_h.NEMOTRON_H_TINY
    module = experts.SparseExperts(
        cfg, activation=relu2, gates_over_picks=True, scores="sigmoid",
        gate_scale=2.5, width=cfg.moe_intermediate_size, gated=False)
    x = jnp.zeros((2, 16, cfg.hidden_size))
    shapes = jax.eval_shape(module.init, jax.random.PRNGKey(0), x)["params"]
    assert {name: leaf.shape for name, leaf in shapes.items()} == {
        "router": (48, 8), "experts_up": (8, 48, 24),
        "experts_down": (8, 24, 48)}
    gated = experts.SparseExperts(cfg, width=cfg.moe_intermediate_size)
    shapes = jax.eval_shape(gated.init, jax.random.PRNGKey(0), x)["params"]
    assert sorted(shapes) == ["experts_down", "experts_gate", "experts_up",
                              "router"]


def test_the_experts_scope_is_around_the_two_products():
    cfg = nemotron_h.NEMOTRON_H_TINY
    module = experts.SparseExperts(
        cfg, activation=relu2, gates_over_picks=True, scores="sigmoid",
        gate_scale=2.5, width=cfg.moe_intermediate_size, gated=False)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.hidden_size))
    params = module.init(jax.random.PRNGKey(0), x)
    text = jax.jit(jax.grad(
        lambda p: module.apply(p, x).astype(jnp.float32).sum())).lower(
            params).as_text(debug_info=True)
    for way in ("jvp(SparseExperts)", "transpose(jvp(SparseExperts))"):
        for spec in ("ecd,edh->ech", "ech,ehd->ecd"):
            assert f"{way}/vmap(hvd.moe.experts)/{spec}/dot_general" in text


def layer_parts(cfg, params, x):
    """``x + routed + shared`` of an ``E`` layer, and ``x + shared``."""
    layer = nemotron_h.MixerLayer(cfg, nemotron_h.EXPERTS)
    whole = layer.apply({"params": params}, x)
    normed = nemotron_h.RMSNorm(cfg.rms_norm_eps).apply(
        {"params": params["ln"]}, x)
    shared = PlainMLP(cfg, cfg.moe_shared_expert_intermediate_size).apply(
        {"params": params["shared"]}, normed)
    return whole, x + shared


def test_sixteen_windows_and_one_shared_expert_add_up_to_the_uncut_layer():
    """Guide section 4's share test at the deployment's ratio: 16 chips
    hold a sixteenth of the experts each (here 32 experts, two a window).
    Every window's layer is ``x + routed_w + shared``; the sum of the routed
    parts and the shared expert counted once is the uncut layer's, whose
    router, capacity rule and gates know nothing of windows."""
    cfg = dataclasses.replace(
        nemotron_h.NEMOTRON_H_TINY, num_experts=32, top_k=6,
        capacity_factor=1.0, dtype=jnp.float32, remat=False)
    key = jax.random.PRNGKey(11)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 32, 48))
    params = nemotron_h.MixerLayer(cfg, nemotron_h.EXPERTS).init(
        key, x)["params"]
    with jax.default_matmul_precision("highest"):
        uncut, base = layer_parts(cfg, params, x)

        @jax.jit
        def window(first):  # one program: the window's place is traced
            share = dataclasses.replace(cfg, first_expert=first,
                                        experts_here=2)
            mine = dict(params, moe={
                name: jax.lax.dynamic_slice_in_dim(leaf, first, 2)
                if name.startswith("experts_") else leaf
                for name, leaf in params["moe"].items()})
            return layer_parts(share, mine, x)[0]

        routed = sum(window(2 * w) - base for w in range(16))
    scale = float(jnp.abs(uncut - base).max())
    assert scale > 1e-3  # the routed experts do something
    np.testing.assert_allclose(base + routed, uncut, rtol=0,
                               atol=2e-4 * scale)
    # some pair was dropped at capacity 1.0, alike on both sides
    normed = nemotron_h.RMSNorm(cfg.rms_norm_eps).apply(
        {"params": params["ln"]}, x)
    scores = jax.nn.sigmoid(normed[0] @ params["moe"]["router"])
    load = np.bincount(np.asarray(jax.lax.top_k(scores, 6)[1]).ravel(),
                       minlength=32)
    assert load.max() > cfg.capacity(32)


def test_take_expert_window_cuts_two_leaves_a_layer():
    cfg = nemotron_h.NEMOTRON_H_TINY
    model = nemotron_h.NemotronH(cfg)
    whole = jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))["params"]
    share = dataclasses.replace(cfg, first_expert=2, experts_here=4)
    window = experts.take_expert_window(whole, share)
    assert window["layer_0"] is whole["layer_0"]  # a Mamba layer: no experts
    for name in ("experts_up", "experts_down"):
        np.testing.assert_array_equal(window["layer_1"]["moe"][name],
                                      whole["layer_1"]["moe"][name][2:6])
    assert "experts_gate" not in window["layer_1"]["moe"]
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 256)
    stats = jax.jit(lambda p, i: experts.routing_stats(
        nemotron_h.NemotronH(share), p, i))(window, ids)
    assert stats["load"].shape == (2, 4)  # two E layers, four experts held
