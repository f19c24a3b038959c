"""``models/granite.py`` against the plain reference the benchmark keeps
(``benchmark/reference/granite.py``: the token-by-token recurrence, softmax
attention with a mask): on seeded weights at a toy size the two are one
function, logits, loss and every leaf's gradient, with the grouped
multi-tile flash kernels (interpreted, two tiles) or dense attention, with
and without recomputation, and with the scan's Pallas kernels (interpreted,
at widths that fill their tiles), where a hand-made fault still leaves the
reference. Each of the four multipliers matters, on both
sides alike; the head is the embedding; and the model is the published one:
its sizes, its 772,160,448 parameters, its tree, its scopes in a factory
step."""

import dataclasses
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import granite, mamba2
from horovod_tpu.ops import ssd

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
    config = cells.load_json(cells.HERE, "configs", "rehearsal-granite.json")
    training = dict(config["training"], **changes.pop("training", {}))
    return dict(config, training=training, **changes)


def both_sides(bench, config, rows=2, seq=32, seed=5, double=False):
    """``(loss, gradients)`` of the product and of the reference on the
    same seeded weights and tokens; with ``double`` the reference computes
    in float64 from the same float32 weights."""
    cells, _ = bench
    code = cells.load_code(cells.HERE, "configs", "granite.py")
    reference = cells.load_code(cells.HERE, "reference", "granite.py")
    job = {"seq_len": seq}
    key = jax.random.PRNGKey(seed)
    params = jax.jit(partial(code.init_params, config, job))(key)
    tokens = code.make_batch(config, job, jax.random.fold_in(key, 1), rows)
    product = jax.jit(jax.value_and_grad(code.loss_fn(config, job)))(
        params, tokens)
    with jax.default_matmul_precision("highest"), jax.enable_x64(double):
        weights = jax.tree.map(jnp.float64, params) if double else params
        plain = jax.jit(jax.value_and_grad(
            partial(reference.loss, config)))(weights, tokens)
    return product, plain, params


CASES = {
    "flash_two_tiles_recomputed": {},
    "dense_kept": {"training": {"attention": "dense", "remat": False}},
    "dense_three_chunks": {"seq": 48, "training": {"attention": "dense"}},
    "two_groups_of_b_and_c": {
        "mamba_n_groups": 2, "training": {"attention": "dense"}},
    "attention_first_and_one_chunk": {
        "layer_types": ["attention", "mamba", "mamba", "mamba"],
        "mamba_chunk_size": 32, "training": {"attention": "dense"}},
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_float32_product_is_the_reference(bench, case):
    changes = dict(CASES[case])
    seq = changes.pop("seq", 32)
    (loss, grads), (ref_loss, ref_grads), _ = both_sides(
        bench, toy(bench, **changes), seq=seq, double=True)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    for (path, got), want in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree.leaves(ref_grads)):
        want = np.asarray(want)
        scale = float(np.abs(want).max())
        assert scale > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            got, want, rtol=0, atol=1e-4 * scale + 5e-6,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("fault", ["none", "steps_halved", "b_for_c"])
def test_through_the_scans_kernels_the_product_is_the_reference(
        bench, fault, monkeypatch):
    """Widths that fill the scan kernels' tiles (``ops.ssd._heads_a_step``:
    sixteen heads of 16 on one group, two head blocks a grid step's; a
    state and a chunk of 128; 256 tokens, so a state crosses) with
    ``ssd_scan_kernel`` in the plain form's place, interpreted, under the
    layers' recomputation: unbroken the product is the reference, loss and
    every leaf's gradient; with the scan's steps halved or ``B`` read for
    ``C`` some leaf's gradient is a hundredth and more of itself away."""
    kernel, real_scan, calls = ssd.ssd_scan_kernel, mamba2.ssd_scan, []

    def interpreted(*args):
        calls.append(args[0].shape)
        return kernel(*args, True)

    monkeypatch.setattr(ssd, "ssd_scan_kernel", interpreted)
    if fault == "steps_halved":
        monkeypatch.setattr(
            mamba2, "ssd_scan", lambda x, dt, *rest, chunk: real_scan(
                x, 0.5 * dt, *rest, chunk=chunk))
    elif fault == "b_for_c":
        monkeypatch.setattr(
            mamba2, "ssd_scan", lambda x, dt, a, b, c, d, chunk: real_scan(
                x, dt, a, b, b, d, chunk=chunk))
    config = toy(bench, mamba_n_heads=16, mamba_d_head=16, mamba_d_state=128,
                 mamba_chunk_size=128, mamba_expand=4,
                 layer_types=["mamba", "attention"], num_hidden_layers=2,
                 training={"attention": "dense"})
    (loss, grads), (ref_loss, ref_grads), _ = both_sides(
        bench, config, seq=256)
    assert (2, 256, 16, 16) in calls
    off = {jax.tree_util.keystr(path): float(
        np.abs(got - want).max() / np.abs(np.asarray(want)).max())
        for (path, got), want in zip(
            jax.tree_util.tree_leaves_with_path(grads),
            jax.tree.leaves(ref_grads))}
    if fault == "none":
        assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
        assert max(off.values()) < 2e-4, off
    else:  # the loss hardly tells at a toy's multipliers: the mixer's leaves
        assert max(off.values()) > 1e-2, off


def test_the_logits_are_the_references(bench):
    cells, _ = bench
    config = toy(bench)
    code = cells.load_code(cells.HERE, "configs", "granite.py")
    reference = cells.load_code(cells.HERE, "reference", "granite.py")
    key = jax.random.PRNGKey(3)
    params = jax.jit(partial(code.init_params, config, {}))(key)
    ids = code.make_batch(config, {"seq_len": 31}, key, 2)
    logits = jax.jit(code.model(config).apply)({"params": params}, ids)
    assert logits.shape == (2, 32, 256) and logits.dtype == jnp.float32
    with jax.default_matmul_precision("highest"):
        want = jax.jit(partial(reference.logits, config))(params, ids)
    np.testing.assert_allclose(logits, want, rtol=0,
                               atol=1e-5 * float(jnp.abs(want).max()))


MULTIPLIERS = {"embedding_multiplier": 6, "residual_multiplier": 0.44,
               "attention_multiplier": 4.0, "logits_scaling": 4}


@pytest.mark.parametrize("name", sorted(MULTIPLIERS))
def test_each_multiplier_matters_and_on_both_sides_alike(bench, name):
    """Another value of one multiplier moves the loss, and moves the
    product's and the reference's to the same place: neither side ignores
    it, and neither has it in another spot."""
    base = toy(bench, training={"attention": "dense"})
    (loss, _), (ref_loss, _), _ = both_sides(bench, base)
    changed = dict(base, **{name: MULTIPLIERS[name]})
    (moved, _), (ref_moved, _), _ = both_sides(bench, changed)
    assert abs(float(moved) - float(loss)) > 1e-4 * float(loss)
    assert float(moved) == pytest.approx(float(ref_moved), rel=1e-5)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)


def test_the_query_scale_is_the_multiplier_over_the_kernels_own():
    cfg = granite.GRANITE_4_0_H_MICRO
    assert cfg.head_dim == 64 and cfg.query_scale == 2.0 ** -3
    # a power of two: scaling the bfloat16 queries by it rounds nothing
    q = jax.random.normal(jax.random.PRNGKey(0), (512,)).astype(jnp.bfloat16)
    scaled = (q * cfg.query_scale).astype(jnp.bfloat16)
    np.testing.assert_array_equal(
        scaled.astype(jnp.float32) / cfg.query_scale, q.astype(jnp.float32))


def test_the_head_is_the_embedding(bench):
    """``tie_word_embeddings``: one leaf, whose gradient holds the
    lookups' part and the head's part, as the reference's does."""
    config = toy(bench, training={"attention": "dense"})
    (_, grads), (_, ref_grads), params = both_sides(bench, config)
    assert "lm_head" not in params and params["embedding"].shape == (256, 64)
    got, want = np.asarray(grads["embedding"]), np.asarray(
        ref_grads["embedding"])
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-4 * float(np.abs(want).max()))
    # rows no token of the batch reads still get the head's gradient
    cells, _ = bench
    code = cells.load_code(cells.HERE, "configs", "granite.py")
    tokens = code.make_batch(config, {"seq_len": 32},
                             jax.random.fold_in(jax.random.PRNGKey(5), 1), 2)
    unread = np.setdiff1d(np.arange(256), np.asarray(tokens[:, :-1]))
    assert len(unread) > 100
    assert np.abs(got[unread]).max() > 0


def test_recomputation_changes_nothing(bench):
    kept = toy(bench, training={"attention": "dense", "remat": False})
    again = toy(bench, training={"attention": "dense", "remat": True})
    (loss, grads), _, params = both_sides(bench, kept)
    (loss2, grads2), _, params2 = both_sides(bench, again)
    assert jax.tree.structure(params) == jax.tree.structure(params2)
    assert float(loss) == pytest.approx(float(loss2), rel=1e-6)
    for got, want in zip(jax.tree.leaves(grads2), jax.tree.leaves(grads)):
        np.testing.assert_allclose(
            got, want, rtol=0, atol=1e-5 * float(jnp.abs(want).max()) + 1e-8)


def test_bfloat16_product_is_near_the_reference(bench):
    _, checks = bench
    (loss, grads), (ref_loss, ref_grads), _ = both_sides(
        bench, toy(bench, hidden_size=128, shared_intermediate_size=192,
                   mamba_n_heads=16,
                   training={"compute_dtype": "bfloat16",
                             "attention": "dense"}), rows=4)
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) < 5e-3
    got, want = (np.asarray(checks.leaf_norms(g)) for g in (grads, ref_grads))
    off = np.abs(got - want) / want
    assert np.median(off) < 0.03, np.median(off)


def test_a_zeroed_leaf_of_the_mixer_is_outside_the_bands(bench):
    cells, checks = bench
    tolerance = cells.load_json(
        cells.HERE, "configs", "granite-4.0-h-micro.json")["correct"]
    (_, grads), (_, ref_grads), params = both_sides(bench, toy(bench))
    names = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_leaves_with_path(params)]
    ok, seen = checks.norms_agree(
        checks.leaf_norms(grads), checks.leaf_norms(ref_grads), names,
        tolerance)
    assert ok, seen
    grads["layer_2"]["mamba"]["conv_bias"] *= 0.0
    ok, seen = checks.norms_agree(
        checks.leaf_norms(grads), checks.leaf_norms(ref_grads), names,
        tolerance)
    assert not ok and "conv_bias" in seen


def test_the_published_sizes_and_the_tree():
    model = granite.Granite(dataclasses.replace(
        granite.GRANITE_4_0_H_MICRO, num_layers=10, vocab_size=12544))
    params = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 256), jnp.int32))["params"]
    leaves = jax.tree.leaves(params)
    size = lambda tree: sum(x.size for x in jax.tree.leaves(tree))  # noqa: E731
    assert size(params) == 772_160_448 and len(leaves) == 118
    assert all(leaf.dtype == jnp.float32 for leaf in leaves)
    assert model.config.kinds == granite.PERIOD == (
        ("mamba",) * 5 + ("attention",) + ("mamba",) * 4)
    mixer = params["layer_0"]["mamba"]
    assert mixer["in_proj"]["kernel"].shape == (2048, 4096 + 4352 + 64)
    assert mixer["conv"].shape == (4352, 4)
    assert mixer["conv_bias"].shape == (4352,)
    assert mixer["A_log"].shape == mixer["dt_bias"].shape == (64,)
    assert mixer["D"].shape == (64,)
    assert mixer["norm"]["scale"].shape == (4096,)
    assert mixer["out_proj"]["kernel"].shape == (4096, 2048)
    assert size(mixer) == 25_847_232
    assert size(params["layer_0"]) == 76_182_976
    full = params["layer_5"]["attention"]
    assert full["query"]["kernel"].shape == (2048, 2048)
    assert full["key"]["kernel"].shape == (2048, 512)
    assert full["value"]["kernel"].shape == (2048, 512)
    assert full["out"]["kernel"].shape == (2048, 2048)
    assert size(params["layer_5"]) == 60_821_504
    assert params["layer_5"]["mlp"]["input"]["kernel"].shape == (2048, 16384)
    assert params["layer_5"]["mlp"]["output"]["kernel"].shape == (8192, 2048)
    assert params["embedding"].shape == (12544, 2048)
    whole = granite.GRANITE_4_0_H_MICRO
    assert (whole.num_layers, whole.head_dim, whole.mamba_inner) == (
        40, 64, 4096)
    assert whole.kinds.count("attention") == 4
    assert [i for i, kind in enumerate(whole.kinds)
            if kind == "attention"] == [5, 15, 25, 35]
    # the whole model, as the catalog counts it: "3B"
    assert 36 * 76_182_976 + 4 * 60_821_504 + 100352 * 2048 + 2048 == (
        pytest.approx(3.19e9, rel=2e-3))


@pytest.mark.parametrize("wrong", [
    {"num_key_value_heads": 3}, {"mamba_n_heads": 7},
    {"mamba_n_groups": 3}, {"layer_types": ("mamba",)},
    {"layer_types": ("mamba", "full_attention", "mamba", "mamba")},
    {"num_attention_heads": 5}])
def test_a_configuration_the_model_does_not_have_is_refused(wrong):
    with pytest.raises(ValueError):
        dataclasses.replace(granite.GRANITE_TINY, **wrong)


def test_the_model_is_causal():
    config = dataclasses.replace(granite.GRANITE_TINY, dtype=jnp.float32)
    model = granite.Granite(config)
    key = jax.random.PRNGKey(4)
    params = jax.jit(model.init)(key, jnp.zeros((1, 8), jnp.int32))["params"]
    ids = jax.random.randint(key, (1, 32), 0, config.vocab_size)
    apply = jax.jit(model.apply)
    logits = apply({"params": params}, ids)
    changed = apply(
        {"params": params}, ids.at[0, 20].set((ids[0, 20] + 1) % 256))
    np.testing.assert_allclose(changed[0, :20], logits[0, :20], atol=1e-5)
    assert float(jnp.abs(changed[0, 20:] - logits[0, 20:]).max()) > 1e-3


def test_the_loss_is_the_old_expression_and_the_models_own():
    """``models/loss.py``'s rule against the ``log_softmax`` and the pick
    that Olmo Hybrid's ``causal_lm_loss`` is, which Granite imported until
    PR 39 and no longer does."""
    from horovod_tpu.models import olmo_hybrid

    config = dataclasses.replace(granite.GRANITE_TINY, dtype=jnp.float32)
    model = granite.Granite(config)
    key = jax.random.PRNGKey(4)
    params = jax.jit(model.init)(key, jnp.zeros((1, 8), jnp.int32))["params"]
    tokens = jax.random.randint(key, (2, 33), 0, config.vocab_size)
    old = olmo_hybrid.causal_lm_loss(model, params, tokens)
    new = granite.causal_lm_loss(model, params, tokens)
    assert abs(float(new) - float(old)) <= 1e-6 * abs(float(old))
    assert granite.causal_lm_loss is not olmo_hybrid.causal_lm_loss
    assert granite.causal_lm_loss.__module__ == granite.__name__


def test_a_factory_step_names_the_state_space_phases_and_the_blocks():
    import optax

    import horovod_tpu as hvd
    from horovod_tpu import attribution, profiler
    from traced import loop_trips

    config = dataclasses.replace(granite.GRANITE_TINY, dtype=jnp.float32)
    model = granite.Granite(config)
    key = jax.random.PRNGKey(0)
    params = jax.jit(model.init)(key, jnp.zeros((1, 8), jnp.int32))["params"]
    optimizer = hvd.DistributedOptimizer(optax.adamw(1e-4))
    step = hvd.data_parallel.make_train_step(
        partial(granite.causal_lm_loss, model), optimizer)
    tokens = hvd.data_parallel.shard_batch(jax.random.randint(
        key, (hvd.size(), 33), 0, config.vocab_size))
    params = hvd.data_parallel.replicate(params)
    opt_state = hvd.data_parallel.replicate(optimizer.init(params))
    text = step.lower(params, opt_state, tokens).compile().as_text()
    scopes = list(profiler.instruction_scopes(text).values())
    phases = {profiler.phase_of(scope) for scope in scopes}
    names = ("hvd.ssm.conv", "hvd.ssm.scan", "hvd.ssm.gate")
    assert {*names, "hvd.optimizer"} <= phases
    assert set(names) <= set(attribution.PHASE_SCOPE_NAMES)
    for name in names:
        # the backward pass's operations and the recomputed forward's
        assert any("transpose(" in scope and name in scope
                   for scope in scopes), name
        assert any(attribution.SCOPE_RECOMPUTE in scope and name in scope
                   for scope in scopes), name
    owners = {profiler.owner_of(scope) for scope in scopes}
    assert {"hvd.block.embed", "hvd.block.norm", "hvd.block.attn_proj",
            "hvd.block.ffn", "hvd.block.head"} <= owners
    # a mixer's projections are the attention block's, as Olmo Hybrid's
    assert any(profiler.owner_of(scope) == "hvd.block.attn_proj"
               and "in_proj" in scope for scope in scopes)
    # the scan's loops, forward and backward, take a trip a chunk of 8
    trips = loop_trips(text, "hvd.ssm.scan")
    assert trips and set(trips) == {32 // 8}
