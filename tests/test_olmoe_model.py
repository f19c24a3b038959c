"""``models/olmoe.py`` against the plain reference the benchmark keeps
(``benchmark/reference/olmoe.py``): on seeded weights at a toy size the two
are one function, loss and every leaf's gradient, with the experts whole
or as a window, dropping or not, and with the multi-tile causal flash
kernels (interpreted, two tiles) or dense attention; in bfloat16 they agree
inside the measured configuration's own bands. And the model is the
published one: its sizes, its tree, its scopes in a factory step."""

import dataclasses
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import olmoe, parts

BENCHMARK_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


@pytest.fixture(scope="module")
def bench():
    """The benchmark's files, found by path as ``run.py`` finds them."""
    sys.path.insert(0, BENCHMARK_DIR)
    try:
        import cells
        import checks
    finally:
        sys.path.remove(BENCHMARK_DIR)
    return cells, checks


def toy(bench, **changes):
    cells, _ = bench
    config = cells.load_json(cells.HERE, "configs", "rehearsal-olmoe.json")
    training = dict(config["training"], **changes.pop("training", {}))
    return dict(config, training=training, **changes)


def both_sides(bench, config, rows=2, seq=32, seed=5):
    """``(loss, gradients)`` of the product and of the reference on the
    same seeded weights and tokens."""
    cells, _ = bench
    code = cells.load_code(cells.HERE, "configs", "olmoe.py")
    reference = cells.load_code(cells.HERE, "reference", "olmoe.py")
    job = {"seq_len": seq}
    key = jax.random.PRNGKey(seed)
    params = jax.jit(partial(code.init_params, config, job))(key)
    tokens = code.make_batch(config, job, jax.random.fold_in(key, 1), rows)
    product = jax.jit(jax.value_and_grad(code.loss_fn(config, job)))(
        params, tokens)
    with jax.default_matmul_precision("highest"):
        plain = jax.jit(jax.value_and_grad(
            partial(reference.loss, config)))(params, tokens)
    return product, plain, params


CASES = {
    "window_dropping_flash_two_tiles": {},
    "window_dropping_dense": {"training": {"attention": "dense"}},
    "all_experts_flash_two_tiles": {"first_expert": 0, "experts_here": 8},
    "all_experts_no_drops_dense": {
        "first_expert": 0, "experts_here": 8, "capacity_factor": 8.0,
        "training": {"attention": "dense"}},
    "last_window_top_3": {"first_expert": 6, "experts_here": 2,
                          "num_experts_per_tok": 3},
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_float32_product_is_the_reference(bench, case):
    (loss, grads), (ref_loss, ref_grads), _ = both_sides(
        bench, toy(bench, **CASES[case]))
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    for (path, got), want in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree.leaves(ref_grads)):
        scale = float(jnp.abs(want).max())
        assert scale > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            got, want, rtol=0, atol=1e-5 * scale,
            err_msg=jax.tree_util.keystr(path))


def test_bfloat16_product_is_inside_the_configurations_bands(bench):
    """The bands the chip holds the published widths to (checks (b) and
    (c)), here on a toy in bfloat16: the gradients' as they stand; the
    loss's times sqrt(4096 / 128), because a loss is a mean over tokens
    and the toy's rounding averages over 128 of them where the cell's
    averages over 4,096. No drops and twice the toy's width, so that a
    pick that rounding flips moves one small gate and no queue."""
    cells, checks = bench
    tolerance = cells.load_json(
        cells.HERE, "configs", "olmoe-1b-7b.json")["correct"]
    (loss, grads), (ref_loss, ref_grads), params = both_sides(
        bench, toy(bench, capacity_factor=8.0, hidden_size=128,
                   intermediate_size=64,
                   training={"compute_dtype": "bfloat16"}), rows=4)
    off = abs(float(loss) - float(ref_loss)) / float(ref_loss)
    assert off <= tolerance["loss_rel"] * (4096 / 128) ** 0.5, off
    names = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_leaves_with_path(params)]
    ok, seen = checks.norms_agree(
        checks.leaf_norms(grads), checks.leaf_norms(ref_grads), names,
        tolerance)
    assert ok, seen


def test_a_zeroed_expert_leaf_is_outside_the_bands(bench):
    cells, checks = bench
    tolerance = cells.load_json(
        cells.HERE, "configs", "olmoe-1b-7b.json")["correct"]
    (_, grads), (_, ref_grads), params = both_sides(bench, toy(bench))
    grads["layer_1"]["moe"]["experts_up"] *= 0.0
    names = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_leaves_with_path(params)]
    ok, seen = checks.norms_agree(
        checks.leaf_norms(grads), checks.leaf_norms(ref_grads), names,
        tolerance)
    assert not ok and "experts_up" in seen


def test_the_published_sizes_and_the_tree():
    model = olmoe.Olmoe(dataclasses.replace(
        olmoe.OLMOE_1B_7B, num_layers=4, experts_here=16))
    params = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32))["params"]
    leaves = jax.tree.leaves(params)
    assert len(leaves) == 51
    assert sum(leaf.size for leaf in leaves) == 676_366_336
    layer = params["layer_0"]
    assert layer["moe"]["experts_gate"].shape == (16, 2048, 1024)
    assert layer["moe"]["experts_down"].shape == (16, 1024, 2048)
    assert layer["moe"]["router"].shape == (2048, 64)
    assert layer["attention"]["q_norm"]["scale"].shape == (2048,)
    assert params["lm_head"].shape == (2048, 50304)
    assert all(leaf.dtype == jnp.float32 for leaf in leaves)
    config = model.config
    assert (config.head_dim, config.experts_held, config.capacity(4096)) == (
        128, 16, 640)
    whole = olmoe.OLMOE_1B_7B
    assert (whole.experts_held, whole.num_layers, whole.top_k) == (64, 16, 8)


def test_rope_rotates_pairs_and_keeps_position_zero():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 8))
    y = parts.rope(x, 10000.0)
    np.testing.assert_allclose(y[:, 0], x[:, 0], rtol=1e-6)
    np.testing.assert_allclose(  # a rotation: norms of each pair are kept
        y[..., :4] ** 2 + y[..., 4:] ** 2, x[..., :4] ** 2 + x[..., 4:] ** 2,
        rtol=1e-5)
    # scores depend on the distance only
    q, k = x[:, :, :1], x[:, :, 1:]
    shifted = parts.rope(jnp.pad(x, ((0, 0), (3, 0), (0, 0), (0, 0))),
                         10000.0)[:, 3:]
    np.testing.assert_allclose(
        jnp.einsum("bqhd,bkhd->bqk", y[:, :, :1], y[:, :, 1:]),
        jnp.einsum("bqhd,bkhd->bqk", shifted[:, :, :1], shifted[:, :, 1:]),
        rtol=1e-4, atol=1e-5)
    del q, k


def test_the_model_is_causal():
    config = dataclasses.replace(olmoe.OLMOE_TINY, dtype=jnp.float32,
                                 capacity_factor=8.0)
    model = olmoe.Olmoe(config)
    key = jax.random.PRNGKey(4)
    params = jax.jit(model.init)(key, jnp.zeros((1, 8), jnp.int32))["params"]
    ids = jax.random.randint(key, (1, 24), 0, config.vocab_size)
    apply = jax.jit(model.apply)
    logits, _, _ = apply({"params": params}, ids)
    changed, _, _ = apply(
        {"params": params}, ids.at[0, 16].set((ids[0, 16] + 1) % 512))
    np.testing.assert_allclose(changed[0, :16], logits[0, :16], atol=1e-5)
    assert float(jnp.abs(changed[0, 16:] - logits[0, 16:]).max()) > 1e-3


def test_a_factory_step_names_the_moe_phases():
    import optax

    import horovod_tpu as hvd
    from horovod_tpu import profiler

    config = dataclasses.replace(olmoe.OLMOE_TINY, dtype=jnp.float32)
    model = olmoe.Olmoe(config)
    key = jax.random.PRNGKey(0)
    params = jax.jit(model.init)(key, jnp.zeros((1, 8), jnp.int32))["params"]
    optimizer = hvd.DistributedOptimizer(optax.adamw(1e-4))
    step = hvd.data_parallel.make_train_step(
        partial(olmoe.causal_lm_loss, model), optimizer)
    tokens = hvd.data_parallel.shard_batch(jax.random.randint(
        key, (hvd.size(), 17), 0, config.vocab_size))
    params = hvd.data_parallel.replicate(params)
    opt_state = hvd.data_parallel.replicate(optimizer.init(params))
    text = step.lower(params, opt_state, tokens).compile().as_text()
    phases = {profiler.phase_of(scope)
              for scope in profiler.instruction_scopes(text).values()}
    assert {"hvd.moe.route", "hvd.moe.dispatch", "hvd.moe.experts",
            "hvd.moe.combine", "hvd.optimizer"} <= phases
    # backward operations carry the scope too
    assert any("transpose(" in scope and "hvd.moe.experts" in scope
               for scope in profiler.instruction_scopes(text).values())
