"""``models/olmo_hybrid.py`` against the plain reference the benchmark keeps
(``benchmark/reference/olmo_hybrid.py``: the token-by-token recurrence): on
seeded weights at a toy size the two are one function, loss and every
leaf's gradient, with the heads whole or as a window and with the
multi-tile causal flash kernels (interpreted, two tiles) or dense
attention. The windows' shares add up to the uncut layer. And the model is
the published one: its sizes, its tree, its scopes in a factory step."""

import dataclasses
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import olmo_hybrid

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
    config = cells.load_json(
        cells.HERE, "configs", "rehearsal-olmo-hybrid.json")
    training = dict(config["training"], **changes.pop("training", {}))
    return dict(config, training=training, **changes)


def both_sides(bench, config, rows=2, seq=32, seed=5, double=False):
    """``(loss, gradients)`` of the product and of the reference on the
    same seeded weights and tokens; with ``double`` the reference computes
    in float64 from the same float32 weights."""
    cells, _ = bench
    code = cells.load_code(cells.HERE, "configs", "olmo_hybrid.py")
    reference = cells.load_code(cells.HERE, "reference", "olmo_hybrid.py")
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
    "window_flash_two_tiles": {},
    "window_dense": {"training": {"attention": "dense"}},
    "all_heads_flash_two_tiles": {"first_head": 0, "heads_here": 4},
    "all_heads_dense_three_chunks": {
        "first_head": 0, "heads_here": 4, "seq": 48,
        "training": {"attention": "dense"}},
    "first_head_alone_no_negative_eigenvalues": {
        "first_head": 0, "heads_here": 1, "linear_allow_neg_eigval": False,
        "training": {"attention": "dense"}},
    "the_other_reading_of_rope_theta": {
        "rope_parameters": {"rope_theta": 500000.0},
        "training": {"attention": "dense"}},
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
        # the floor: float32's own noise on a gradient that is the small
        # remainder of cancelling terms (a head's A_log of 1.4e-4: the
        # reference in float32 and in float64 differ by 9e-7 there too).
        # The reference is in float64 since PR 31: in float32 it is itself
        # up to 0.45e-4 of a leaf's largest from that, erring as the
        # product's solve did while that was forward substitution too, and
        # left 1e-4 no room for the product's own error. Against float64
        # the worst leaf of the six cases reads 0.59e-4 (0.29e-4 with
        # substitution, 0.72e-4 with a step of refinement on the inverse:
        # what float32 leaves of a gradient through three of these layers).
        np.testing.assert_allclose(
            got, want, rtol=0, atol=1e-4 * scale + 5e-6,
            err_msg=jax.tree_util.keystr(path))


def test_bfloat16_product_is_near_the_reference(bench):
    """In bfloat16 the toy stays within loose bands only: three delta-rule
    layers pass a bfloat16-sized rounding on amplified (the float32
    reference alone, its weights rounded to bfloat16, moves this toy's
    early layers' norms by percents), and 128 tokens average little away.
    The published widths' own bands are the chip's to hold
    (``configs/olmo-hybrid-7b.json``: median 1e-2 over 4,096 tokens)."""
    _, checks = bench
    (loss, grads), (ref_loss, ref_grads), _ = both_sides(
        bench, toy(bench, hidden_size=128, intermediate_size=192,
                   training={"compute_dtype": "bfloat16",
                             "attention": "dense"}), rows=4)
    assert abs(float(loss) - float(ref_loss)) / float(ref_loss) < 5e-3
    got, want = (np.asarray(checks.leaf_norms(g)) for g in (grads, ref_grads))
    off = np.abs(got - want) / want
    assert np.median(off) < 0.08, np.median(off)
    # the full-attention layer, last, is past the amplification
    names = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_leaves_with_path(grads)]
    late = [o for name, o in zip(names, off)
            if "layer_3" in name and "kernel" in name]
    assert max(late) < 0.02, max(late)


def test_a_zeroed_leaf_of_the_mixer_is_outside_the_bands(bench):
    cells, checks = bench
    tolerance = cells.load_json(
        cells.HERE, "configs", "olmo-hybrid-7b.json")["correct"]
    (_, grads), (_, ref_grads), params = both_sides(bench, toy(bench))
    names = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_leaves_with_path(params)]
    ok, seen = checks.norms_agree(
        checks.leaf_norms(grads), checks.leaf_norms(ref_grads), names,
        tolerance)
    assert ok, seen
    grads["layer_1"]["linear_attention"]["A_log"] *= 0.0
    ok, seen = checks.norms_agree(
        checks.leaf_norms(grads), checks.leaf_norms(ref_grads), names,
        tolerance)
    assert not ok and "A_log" in seen


class TestTheSharesAddUp:
    """Head windows 0-1 and 2-3 of the tiny model, each a model of its
    own on its slice of the whole model's parameters, against the uncut
    plain reference's layer output."""

    @pytest.fixture(scope="class")
    def whole(self, bench):
        cells, _ = bench
        config = dataclasses.replace(
            olmo_hybrid.OLMO_HYBRID_TINY, dtype=jnp.float32)
        key = jax.random.PRNGKey(11)
        params = jax.jit(olmo_hybrid.OlmoHybrid(config).init)(
            key, jnp.zeros((1, 16), jnp.int32))["params"]
        # not the initial ones everywhere: scales that differ by channel
        params = jax.tree.map(
            lambda p: p * (1 + 0.3 * jax.random.normal(key, p.shape)),
            params)
        x = jax.random.normal(jax.random.fold_in(key, 1), (2, 32, 64))
        reference = cells.load_code(cells.HERE, "reference",
                                    "olmo_hybrid.py")
        as_json = {
            "heads_here": 4, "linear_key_head_dim": 8,
            "linear_allow_neg_eigval": True, "rms_norm_eps": 1e-6,
            "rope_parameters": {"rope_theta": None}}
        return config, params, x, reference, as_json

    def shares(self, whole, layer, module, name):
        config, params, x, _, _ = whole
        parts = []
        for first in (0, 2):
            share = dataclasses.replace(config, first_head=first,
                                        heads_here=2)
            cut = olmo_hybrid.take_head_window(params, config, share)
            parts.append(module(share).apply(
                {"params": cut[layer][name]}, x))
        return parts

    def test_linear_attention(self, whole):
        _, params, x, reference, as_json = whole
        low, high = self.shares(whole, "layer_1", olmo_hybrid.GatedDeltaNet,
                                "linear_attention")
        with jax.default_matmul_precision("highest"):
            want = reference.linear_attention(
                as_json, x, params["layer_1"]["linear_attention"])
        assert float(jnp.abs(low).max()) > 1e-3
        np.testing.assert_allclose(low + high, want, rtol=0,
                                   atol=1e-5 * float(jnp.abs(want).max()))

    def test_full_attention_with_the_window_wise_statistic(self, whole):
        """The QK-norm's mean square is taken over the heads a window
        holds (the model's stated departure), so the shares add up to the
        uncut layer only once the uncut side normalises window by window
        too: the reference's block, one window of the projections at a
        time. With the whole-row statistic they do not."""
        config, params, x, reference, as_json = whole
        low, high = self.shares(whole, "layer_3", olmo_hybrid.FullAttention,
                                "attention")
        p = params["layer_3"]["attention"]
        with jax.default_matmul_precision("highest"):
            uncut = reference.full_attention(as_json, x, p)
            windowed = sum(
                reference.full_attention(
                    dict(as_json, heads_here=2), x,
                    olmo_hybrid.take_head_window(
                        params, config, dataclasses.replace(
                            config, first_head=first, heads_here=2)
                    )["layer_3"]["attention"])
                for first in (0, 2))
        scale = float(jnp.abs(uncut).max())
        np.testing.assert_allclose(low + high, windowed, rtol=0,
                                   atol=1e-5 * scale)
        assert float(jnp.abs(windowed - uncut).max()) > 1e-3 * scale


def test_the_published_sizes_and_the_tree():
    model = olmo_hybrid.OlmoHybrid(dataclasses.replace(
        olmo_hybrid.OLMO_HYBRID_7B, num_layers=4, heads_here=15,
        vocab_size=12544))
    params = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 64), jnp.int32))["params"]
    leaves = jax.tree.leaves(params)
    assert len(leaves) == 68
    assert sum(leaf.size for leaf in leaves) == 766_241_946
    mixer = params["layer_0"]["linear_attention"]
    assert mixer["query"]["kernel"].shape == (3840, 15 * 96)
    assert mixer["value"]["kernel"].shape == (3840, 15 * 192)
    assert mixer["gate"]["kernel"].shape == (3840, 15 * 192)
    assert mixer["out"]["kernel"].shape == (15 * 192, 3840)
    assert mixer["value_conv"].shape == (15 * 192, 4)
    assert mixer["A_log"].shape == mixer["dt_bias"].shape == (15,)
    assert mixer["o_norm"]["scale"].shape == (192,)
    full = params["layer_3"]["attention"]
    assert full["query"]["kernel"].shape == (3840, 15 * 128)
    assert full["q_norm"]["scale"].shape == (15 * 128,)
    assert params["layer_3"]["mlp"]["up"]["kernel"].shape == (3840, 11008)
    assert params["lm_head"].shape == (3840, 12544)
    assert all(leaf.dtype == jnp.float32 for leaf in leaves)
    whole = olmo_hybrid.OLMO_HYBRID_7B
    assert (whole.window, whole.num_layers, whole.head_dim) == (30, 32, 128)
    assert whole.kinds.count("full_attention") == 8
    assert whole.kinds[:4] == olmo_hybrid.PERIOD
    # a whole layer of either kind, as the catalog counts them
    uncut = jax.eval_shape(
        olmo_hybrid.OlmoHybrid(dataclasses.replace(
            whole, num_layers=4)).init,
        jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32))["params"]
    size = lambda tree: sum(x.size for x in jax.tree.leaves(tree))  # noqa: E731
    assert round(size(uncut["layer_0"]) / 1e6, 1) == 215.6
    assert round(size(uncut["layer_3"]) / 1e6, 1) == 185.8


@pytest.mark.parametrize("wrong", [
    {"num_key_value_heads": 2}, {"linear_num_value_heads": 8},
    {"layer_types": ("linear_attention",)}, {"heads_here": 5},
    {"first_head": 3, "heads_here": 2}])
def test_a_configuration_the_model_does_not_have_is_refused(wrong):
    with pytest.raises(ValueError):
        dataclasses.replace(olmo_hybrid.OLMO_HYBRID_TINY, **wrong)


def test_the_model_is_causal():
    config = dataclasses.replace(olmo_hybrid.OLMO_HYBRID_TINY,
                                 dtype=jnp.float32)
    model = olmo_hybrid.OlmoHybrid(config)
    key = jax.random.PRNGKey(4)
    params = jax.jit(model.init)(key, jnp.zeros((1, 16), jnp.int32))["params"]
    ids = jax.random.randint(key, (1, 32), 0, config.vocab_size)
    apply = jax.jit(model.apply)
    logits = apply({"params": params}, ids)
    changed = apply(
        {"params": params}, ids.at[0, 20].set((ids[0, 20] + 1) % 512))
    np.testing.assert_allclose(changed[0, :20], logits[0, :20], atol=1e-5)
    assert float(jnp.abs(changed[0, 20:] - logits[0, 20:]).max()) > 1e-3


def test_a_factory_step_names_the_linear_attention_phases():
    import optax

    import horovod_tpu as hvd
    from horovod_tpu import profiler
    from traced import loop_trips

    config = dataclasses.replace(olmo_hybrid.OLMO_HYBRID_TINY,
                                 dtype=jnp.float32, heads_here=2)
    model = olmo_hybrid.OlmoHybrid(config)
    key = jax.random.PRNGKey(0)
    params = jax.jit(model.init)(key, jnp.zeros((1, 16), jnp.int32))["params"]
    optimizer = hvd.DistributedOptimizer(optax.adamw(1e-4))
    step = hvd.data_parallel.make_train_step(
        partial(olmo_hybrid.causal_lm_loss, model), optimizer)
    tokens = hvd.data_parallel.shard_batch(jax.random.randint(
        key, (hvd.size(), 33), 0, config.vocab_size))
    params = hvd.data_parallel.replicate(params)
    opt_state = hvd.data_parallel.replicate(optimizer.init(params))
    text = step.lower(params, opt_state, tokens).compile().as_text()
    scopes = profiler.instruction_scopes(text).values()
    phases = {profiler.phase_of(scope) for scope in scopes}
    assert {"hvd.linattn.conv", "hvd.linattn.scan", "hvd.linattn.gate",
            "hvd.optimizer"} <= phases
    # backward operations carry the scopes too
    for name in ("hvd.linattn.conv", "hvd.linattn.scan", "hvd.linattn.gate"):
        assert any("transpose(" in scope and name in scope
                   for scope in scopes), name
    # the rule's loops, forward and backward, take a trip a chunk of 16
    trips = loop_trips(text, "hvd.linattn.scan")
    assert trips and set(trips) == {32 // 16}
