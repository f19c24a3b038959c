"""``models/nemotron_h.py`` against the plain reference the benchmark keeps
(``benchmark/reference/nemotron_h.py``: the token-by-token recurrence with
groups of ``B`` and ``C``, a norm a group, dense masked attention with
sixteen query heads a key head, sigmoid routing through the experts held one
after another): on seeded weights at a toy size the two are one function,
loss and every leaf's gradient, with the grouped flash kernels (interpreted,
several tiles) or dense attention, with and without recomputation, in
another window of the experts, with another pattern. **Nine faults made by
hand in the product each leave the reference** (what the chip's limits see of
them at seed weights is in the configuration's file), the scan's two also
where its Pallas kernels run (interpreted, at widths that fill their tiles:
they stay on the reference unbroken). And the model is the
published one: its pattern, its 666,962,944 parameters at the cell's cut, its
scopes in a lowered step."""

import dataclasses
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import experts, mamba2, nemotron_h
from horovod_tpu.ops import ssd

BENCHMARK_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


@pytest.fixture(scope="module")
def bench():
    """The benchmark's files, found by path as ``run.py`` finds them."""
    sys.path.insert(0, BENCHMARK_DIR)
    try:
        import cells
    finally:
        sys.path.remove(BENCHMARK_DIR)
    return cells


def toy(cells, **changes):
    config = cells.load_json(cells.HERE, "configs",
                             "rehearsal-nemotron-h.json")
    training = dict(config["training"], **changes.pop("training", {}))
    if "hybrid_override_pattern" in changes:
        changes.setdefault("num_hidden_layers",
                           len(changes["hybrid_override_pattern"]))
    return dict(config, training=training, **changes)


def both_sides(cells, config, rows=2, seq=32, seed=5, weights=None):
    """``(loss, gradients)`` of the product and of the reference on the
    same seeded weights (passed through ``weights`` where given) and
    tokens."""
    code = cells.load_code(cells.HERE, "configs", "nemotron_h.py")
    reference = cells.load_code(cells.HERE, "reference", "nemotron_h.py")
    job = {"seq_len": seq}
    key = jax.random.PRNGKey(seed)
    params = jax.jit(partial(code.init_params, config, job))(key)
    if weights is not None:
        params = weights(params)
    tokens = code.make_batch(config, job, jax.random.fold_in(key, 1), rows)
    with jax.default_matmul_precision("highest"):
        product = jax.jit(jax.value_and_grad(code.loss_fn(config, job)))(
            params, tokens)
        plain = jax.jit(jax.value_and_grad(
            partial(reference.loss, config)))(params, tokens)
    return product, plain


def assert_same_gradients(grads, ref_grads):
    got = jax.tree_util.tree_leaves_with_path(grads)
    assert len(got) == len(jax.tree.leaves(ref_grads))
    for (path, leaf), want in zip(got, jax.tree.leaves(ref_grads)):
        want = np.asarray(want)
        scale = float(np.abs(want).max())
        assert scale > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            leaf, want, rtol=0, atol=2e-4 * scale + 5e-7,
            err_msg=jax.tree_util.keystr(path))


CASES = {
    "flash_two_tiles_recomputed": {},
    "dense_kept": {"training": {"attention": "dense", "remat": False}},
    "attention_first_one_chunk": {
        "hybrid_override_pattern": "*MEM", "chunk_size": 32,
        "training": {"attention": "dense"}},
    "all_experts_three_groups": {
        "first_expert": 0, "experts_here": 8, "mamba_num_heads": 12,
        "n_groups": 3, "hybrid_override_pattern": "MEE",
        "training": {"attention": "dense"}},
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_float32_product_is_the_reference(bench, case):
    (loss, grads), (ref_loss, ref_grads) = both_sides(
        bench, toy(bench, **CASES[case]))
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    assert_same_gradients(grads, ref_grads)


def test_recomputed_or_kept_the_same_tree_loss_and_gradients(bench):
    code = bench.load_code(bench.HERE, "configs", "nemotron_h.py")
    key = jax.random.PRNGKey(3)
    results = []
    for remat in (True, False):
        config = toy(bench, training={"attention": "dense", "remat": remat})
        params = jax.jit(partial(code.init_params, config, {}))(key)
        tokens = code.make_batch(config, {"seq_len": 32}, key, 2)
        results.append((params, jax.jit(jax.value_and_grad(
            code.loss_fn(config, {})))(params, tokens)))
    (kept_tree, (kept_loss, kept)), (tree, (loss, grads)) = results
    assert jax.tree.structure(kept_tree) == jax.tree.structure(tree)
    for a, b in zip(jax.tree.leaves(kept_tree), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(a, b)
    assert float(loss) == pytest.approx(float(kept_loss), rel=1e-6)
    assert_same_gradients(grads, kept)


def sharper(params):
    """Larger query and key weights in the ``*`` layer: attention that is
    not uniform, so that what turns queries and keys shows."""
    mixer = dict(params["layer_3"]["attention"])
    for name in ("query", "key"):
        mixer[name] = {"kernel": 6.0 * mixer[name]["kernel"]}
    return dict(params, layer_3=dict(params["layer_3"], attention=mixer))


def rounded(x):
    """Three mantissa bits, bfloat16's exponent: the precision below the
    one the configuration states (``lax.reduce_precision`` is not removed
    as a cast and back is)."""
    return jax.lax.reduce_precision(x, 8, 3)


FAULTS = ("group_0_for_every_head", "norm_over_all_channels",
          "relu_for_relu2", "a_gate_on_the_experts", "scale_left_out",
          "gates_not_renormalised", "shared_expert_left_out",
          "scan_at_three_mantissa_bits", "rope_in_the_attention_layer")


@pytest.mark.parametrize("fault", FAULTS)
def test_each_hand_made_fault_leaves_the_reference(bench, fault,
                                                   monkeypatch):
    """The nine faults the configuration's file lists, each made in the
    product by setting an attribute of ``models.nemotron_h`` or
    ``models.mamba2`` (as the chip's probes do): the loss leaves the
    reference's by far more than float32 rounding. On the chip, at seed
    weights and published widths, some of them read inside a seed's
    rounding (RoPE in an untrained ``*`` layer; 2.5 left out, by the loss):
    the configuration's file says which limit sees which."""
    real_scan, real_norm = mamba2.ssd_scan, mamba2.RMSNorm
    real_experts, real_mlp = nemotron_h.SparseExperts, nemotron_h.PlainMLP
    real_attention = nemotron_h.dense_window_attention
    weights = None
    if fault == "group_0_for_every_head":
        def scan(x, dt, a, b, c, d, chunk):
            first = lambda t: jnp.repeat(t[:, :, :1], t.shape[2], 2)
            return real_scan(x, dt, a, first(b), first(c), d, chunk=chunk)
        monkeypatch.setattr(mamba2, "ssd_scan", scan)
    elif fault == "scan_at_three_mantissa_bits":
        def scan(x, dt, a, b, c, d, chunk):
            return real_scan(rounded(x), dt, a, rounded(b), rounded(c), d,
                             chunk=chunk)
        monkeypatch.setattr(mamba2, "ssd_scan", scan)
    elif fault == "norm_over_all_channels":
        monkeypatch.setattr(
            mamba2, "RMSNorm", lambda eps, groups, name: real_norm(
                eps, name=name))
    elif fault == "relu_for_relu2":
        monkeypatch.setattr(nemotron_h, "relu2", jax.nn.relu)
    elif fault == "shared_expert_left_out":
        class Nothing(real_mlp):
            def __call__(self, x):
                return 0.0 * real_mlp.__call__(self, x)

        monkeypatch.setattr(nemotron_h, "PlainMLP", Nothing)
    elif fault == "rope_in_the_attention_layer":
        from horovod_tpu.models import parts

        monkeypatch.setattr(
            nemotron_h, "dense_window_attention",
            lambda q, k, v, dtype: real_attention(
                parts.rope(q, 10000.0), parts.rope(k, 10000.0), v, dtype))
        weights = sharper
    else:
        change = {
            "a_gate_on_the_experts": dict(gated=True,
                                          activation=jax.nn.silu),
            "scale_left_out": dict(gate_scale=1.0),
            "gates_not_renormalised": dict(gates_over_picks=False)}[fault]
        monkeypatch.setattr(
            nemotron_h, "SparseExperts",
            lambda cfg, **kw: real_experts(cfg, **{**kw, **change}))
    config = toy(bench, training={"attention": "dense"})
    (loss, _), (ref_loss, _) = both_sides(bench, config, weights=weights)
    assert abs(float(loss) - float(ref_loss)) > 3e-5 * float(ref_loss)


# Widths that fill the scan kernels' tiles (``ops.ssd._heads_a_step``):
# eight heads a group, a state and a chunk of 128; one M layer of two chunks
KERNEL_WIDTHS = {
    "hybrid_override_pattern": "ME", "mamba_num_heads": 16, "n_groups": 2,
    "mamba_head_dim": 16, "ssm_state_size": 128, "chunk_size": 128,
    "training": {"attention": "dense"}}


@pytest.mark.parametrize("fault", [
    "none", "group_0_for_every_head", "scan_at_three_mantissa_bits"])
def test_the_scans_faults_leave_the_reference_through_its_kernels(
        bench, fault, monkeypatch):
    """The scan's two faults once more with ``ssd_scan_kernel`` in the
    plain form's place (interpreted): unbroken the product is the
    reference, loss and every leaf's gradient; with group 0's ``B`` and
    ``C`` for every head, or ``x``, ``B``, ``C`` at three mantissa bits,
    it is not."""
    kernel, real_scan, calls = ssd.ssd_scan_kernel, mamba2.ssd_scan, []

    def interpreted(*args):
        calls.append(args[0].shape)
        return kernel(*args, True)

    monkeypatch.setattr(ssd, "ssd_scan_kernel", interpreted)
    if fault == "group_0_for_every_head":
        def scan(x, dt, a, b, c, d, chunk):
            first = lambda t: jnp.repeat(t[:, :, :1], t.shape[2], 2)
            return real_scan(x, dt, a, first(b), first(c), d, chunk=chunk)
        monkeypatch.setattr(mamba2, "ssd_scan", scan)
    elif fault == "scan_at_three_mantissa_bits":
        def scan(x, dt, a, b, c, d, chunk):
            return real_scan(rounded(x), dt, a, rounded(b), rounded(c), d,
                             chunk=chunk)
        monkeypatch.setattr(mamba2, "ssd_scan", scan)
    (loss, grads), (ref_loss, ref_grads) = both_sides(
        bench, toy(bench, **KERNEL_WIDTHS), seq=256)
    assert (2, 256, 16, 16) in calls
    if fault == "none":
        assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
        assert_same_gradients(grads, ref_grads)
    else:
        assert abs(float(loss) - float(ref_loss)) > 3e-5 * float(ref_loss)


def test_sharper_weights_alone_stay_on_the_reference(bench):
    """The ninth fault's control: with the larger query and key weights and
    no rotation the product is the reference still."""
    config = toy(bench, training={"attention": "dense"})
    (loss, grads), (ref_loss, ref_grads) = both_sides(bench, config,
                                                      weights=sharper)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    assert_same_gradients(grads, ref_grads)


def test_the_pattern_names_every_layer():
    with pytest.raises(ValueError, match="must name 5 layers"):
        dataclasses.replace(nemotron_h.NEMOTRON_H_TINY,
                            hybrid_override_pattern="MEM*")
    with pytest.raises(ValueError, match="must name 5 layers"):
        dataclasses.replace(nemotron_h.NEMOTRON_H_TINY,
                            hybrid_override_pattern="MEM-E")
    with pytest.raises(ValueError, match="do not share"):
        dataclasses.replace(nemotron_h.NEMOTRON_H_TINY, n_groups=3)
    published = nemotron_h.NEMOTRON_3_NANO_30B_A3B
    assert len(published.kinds) == 52
    assert [published.kinds.count(kind) for kind in "ME*"] == [23, 23, 6]
    assert published.hybrid_override_pattern.startswith("MEMEM*EME" + "M")
    assert published.hybrid_override_pattern[:35] == "MEMEM*E" * 5
    assert published.capacity(8192) == 480


def test_parameters_at_the_published_sizes():
    """From the config's keys: an ``M`` layer 38,744,896, the ``*`` layer
    23,399,040, an ``E`` layer with 8 experts 100,125,312; the cell's cut
    666,962,944 in 68 leaves."""
    cut = dataclasses.replace(
        nemotron_h.NEMOTRON_3_NANO_30B_A3B, num_layers=9,
        hybrid_override_pattern="MEMEM*EME", vocab_size=16384,
        experts_here=8)
    shapes = jax.eval_shape(
        lambda key: nemotron_h.NemotronH(cut).init(
            key, jnp.zeros((1, 128), jnp.int32))["params"],
        jax.random.PRNGKey(0))

    def count(tree):
        return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))

    assert count(shapes["layer_0"]) == 38744896
    assert count(shapes["layer_1"]) == 100125312
    assert count(shapes["layer_5"]) == 23399040
    mamba = shapes["layer_0"]["mamba"]
    assert mamba["in_proj"]["kernel"].shape == (2688, 4096 + 6144 + 64)
    assert mamba["conv"].shape == (6144, 4)
    assert mamba["norm"]["scale"].shape == (4096,)
    assert mamba["out_proj"]["kernel"].shape == (4096, 2688)
    moe = shapes["layer_1"]["moe"]
    assert sorted(moe) == ["experts_down", "experts_up", "router"]
    assert moe["router"].shape == (2688, 128)
    assert moe["experts_up"].shape == (8, 2688, 1856)
    assert shapes["layer_1"]["shared"]["up"]["kernel"].shape == (2688, 3712)
    attention = shapes["layer_5"]["attention"]
    assert attention["query"]["kernel"].shape == (2688, 32 * 128)
    assert attention["key"]["kernel"].shape == (2688, 2 * 128)
    assert attention["out"]["kernel"].shape == (32 * 128, 2688)
    assert set(shapes["layer_0"]) == {"ln", "mamba"}
    assert set(shapes["layer_1"]) == {"ln", "moe", "shared"}
    assert set(shapes["layer_5"]) == {"ln", "attention"}
    assert count(shapes) == 666962944
    assert len(jax.tree.leaves(shapes)) == 68


def test_the_scopes_are_in_a_lowered_step_and_the_group_in_its_grid(bench):
    from traced import pallas_grids

    config = toy(bench)
    code = bench.load_code(bench.HERE, "configs", "nemotron_h.py")
    params = jax.eval_shape(partial(code.init_params, config, {}),
                            jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 33), jnp.int32)
    traced = jax.jit(jax.grad(code.loss_fn(config, {}))).trace(
        params, tokens)
    text = traced.lower().as_text(debug_info=True)
    for scope in ("hvd.ssm.conv", "hvd.ssm.scan", "hvd.ssm.gate",
                  "hvd.attn.fwd", "hvd.attn.bwd", "hvd.moe.shared",
                  "hvd.moe.route", "hvd.moe.dispatch", "hvd.moe.experts",
                  "hvd.moe.combine", "hvd.block.ffn", "hvd.block.attn_proj",
                  "hvd.block.norm", "hvd.block.embed", "hvd.block.head"):
        assert scope in text, scope
    # the experts and the shared one inside the E layer's block scope
    assert "hvd.block.ffn/moe/" in text
    assert "hvd.block.ffn/hvd.moe.shared/shared" in text
    # 8 heads on 2: the dk/dv grid's axis over a key/value head's four
    assert [grid[2] for grid in pallas_grids(traced.jaxpr)
            if len(grid) == 4] == [4]


def test_granites_mixer_is_one_group_of_each(bench):
    """The same body at Granite's sizes: one group's ``B`` and ``C``
    beside the heads' 64 channels in what the convolution mixes, and the
    leaves under the names they had."""
    from horovod_tpu.models import granite

    cfg = granite.GRANITE_TINY
    shapes = jax.eval_shape(granite.Granite(cfg).init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    mixer = shapes["layer_0"]["mamba"]
    assert cfg.mamba_n_groups == 1
    assert {name: leaf.shape for name, leaf in mixer.items()
            if name not in ("in_proj", "out_proj", "norm")} == {
        "conv": (64 + 2 * 16, 4), "conv_bias": (64 + 2 * 16,),
        "A_log": (8,), "dt_bias": (8,), "D": (8,)}
    assert mixer["in_proj"]["kernel"].shape == (32, 64 + 64 + 2 * 16 + 8)
    assert mixer["norm"]["scale"].shape == (64,)


def test_routing_stats_read_the_expert_layers_alone(bench):
    config = toy(bench, training={"attention": "dense"})
    code = bench.load_code(bench.HERE, "configs", "nemotron_h.py")
    model = code.model(config)
    key = jax.random.PRNGKey(2)
    params = jax.jit(partial(code.init_params, config, {}))(key)
    ids = code.make_batch(config, {"seq_len": 32}, key, 2)[:, :-1]
    stats = jax.jit(partial(experts.routing_stats, model))(params, ids)
    assert stats["load"].shape == (2, 4)      # two E layers of five, 4 held
    assert 0 < int(stats["load"].sum()) <= 2 * 2 * 32 * 2
