"""``models/joyai_flash.py`` against the plain reference the benchmark keeps
(``benchmark/reference/joyai_flash.py``: the source's de-interleave and
``rotate_half``, dense masked attention, the experts held one after another,
the prediction module on the main model's embedding and head): on seeded
weights at a toy size the two are one function (both outputs' logits, the
loss and every leaf's gradient, the embedding's and the head's with both
terms' contributions) with the two-width flash kernels (interpreted, several
tiles) or dense attention, with and without recomputation, with and without
a selection bias. The sixteen-fold cut adds up to the uncut layer. **Twelve
faults made by hand in the product each leave the reference** (what the
chip's limits see of them at seed weights is in the configuration's file).
And the model is the published one: its 680,439,808 parameters at the cell's
cut, its scopes and gauges in a lowered step."""

import dataclasses
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import experts, joyai_flash, latent

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
                             "rehearsal-joyai-flash.json")
    training = dict(config["training"], **changes.pop("training", {}))
    return dict(config, training=training, **changes)


def files(cells):
    return (cells.load_code(cells.HERE, "configs", "joyai_flash.py"),
            cells.load_code(cells.HERE, "reference", "joyai_flash.py"))


def seeded(cells, config, rows=2, seq=32, seed=5, weights=None):
    """``(params, tokens [rows, seq + 2])`` from the seed, as the harness
    makes them."""
    code, _ = files(cells)
    key = jax.random.PRNGKey(seed)
    params = jax.jit(partial(code.init_params, config, {}))(key)
    if weights is not None:
        params = weights(params)
    return params, code.make_batch(config, {"seq_len": seq},
                                   jax.random.fold_in(key, 1), rows)


def both_sides(cells, config, bias=None, **how):
    """``(loss, gradients)`` of the product and of the reference on the
    same seeded weights and tokens."""
    code, reference = files(cells)
    params, tokens = seeded(cells, config, **how)
    model = code.model(config)
    if bias is not None:
        model = model.clone(selection_bias=bias)
    with jax.default_matmul_precision("highest"):
        product = jax.jit(jax.value_and_grad(
            partial(joyai_flash.mtp_lm_loss, model)))(params, tokens)
        plain = jax.jit(jax.value_and_grad(partial(
            reference.loss, config, selection_bias=bias)))(params, tokens)
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


def some_bias(config, seed=11):
    """A bias large enough to change picks: sigmoid scores lie in (0, 1)."""
    layers = (config["num_hidden_layers"] - config["first_k_dense_replace"]
              + config["num_nextn_predict_layers"])
    return 0.5 * jax.random.normal(
        jax.random.PRNGKey(seed), (layers, config["n_routed_experts"]),
        jnp.float32)


CASES = {
    "flash_two_tiles_recomputed": {},
    "dense_kept": {"training": {"attention": "dense", "remat": False}},
    "all_experts_two_dense_layers": {
        "first_expert": 0, "experts_here": 8, "first_k_dense_replace": 2,
        "num_hidden_layers": 4, "training": {"attention": "dense"}},
    "no_prediction_module": {
        "num_nextn_predict_layers": 0, "training": {"attention": "dense"}},
}


@pytest.mark.parametrize("biased", [False, True])
@pytest.mark.parametrize("case", sorted(CASES))
def test_float32_product_is_the_reference(bench, case, biased):
    config = toy(bench, **CASES[case])
    bias = some_bias(config) if biased else None
    (loss, grads), (ref_loss, ref_grads) = both_sides(bench, config, bias)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    assert_same_gradients(grads, ref_grads)


def test_both_outputs_logits_are_the_references(bench):
    config = toy(bench, training={"attention": "dense"})
    code, reference = files(bench)
    params, tokens = seeded(bench, config)
    ids, following = tokens[:, :-2], tokens[:, 1:-1]
    with jax.default_matmul_precision("highest"):
        logits, ahead = jax.jit(code.model(config).apply)(
            {"params": params}, ids, following)
        want, want_ahead = jax.jit(partial(reference.logits_of, config))(
            params, ids, following)
    assert logits.shape == ahead.shape == (2, 32, 256)
    assert logits.dtype == ahead.dtype == jnp.float32
    np.testing.assert_allclose(logits, want, atol=2e-5)
    np.testing.assert_allclose(ahead, want_ahead, atol=2e-5)
    # and they are two outputs: the module is no copy of the main pass
    assert float(jnp.abs(logits - ahead).max()) > 0.1


def test_the_bias_moves_picks_and_never_a_gate(bench):
    """With a bias the loss is another (picks changed), the gradient does
    not reach the bias, and a bias that is the same for every expert
    changes nothing: it is added for the choice alone."""
    config = toy(bench, training={"attention": "dense"})
    code, _ = files(bench)
    params, tokens = seeded(bench, config)
    bias = some_bias(config)

    def loss(bias):
        return joyai_flash.mtp_lm_loss(
            code.model(config).clone(selection_bias=bias), params, tokens)

    plain = float(jax.jit(loss)(jnp.zeros_like(bias)))
    assert float(jax.jit(loss)(jnp.full_like(bias, 0.25))) == plain
    assert abs(float(jax.jit(loss)(bias)) - plain) > 1e-4 * plain
    assert not np.asarray(jax.jit(jax.grad(loss))(bias)).any()


def test_the_sixteen_fold_cut_adds_up_to_the_uncut_layer(bench):
    """``W`` windows that partition the experts, the shared expert and the
    attention counted once, add up to the reference's layer with every
    expert (here four windows of two; the cell's cut is sixteen of
    sixteen), in an expert layer of the stack and in the module's."""
    _, reference = files(bench)
    whole = toy(bench, first_expert=0, experts_here=8, capacity_factor=8.0,
                training={"attention": "dense", "remat": False})
    cfg = dataclasses.replace(
        files(bench)[0].model_config(whole), dtype=jnp.float32)
    params, _ = seeded(bench, whole)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, 64), jnp.float32)
    bias = some_bias(whole)[0]
    with jax.default_matmul_precision("highest"):
        for name in ("layer_1", "mtp_layer"):
            want = reference.layer(whole, False, x, params[name], bias)
            share = dataclasses.replace(cfg, experts_here=2, first_expert=0)
            layer = joyai_flash.DecoderLayer(share, False, None, bias)
            alone = layer.apply({"params": experts.take_expert_window(
                params, share)[name]}, x)
            # what every chip computes alike: all of it but the routed sum
            none = dataclasses.replace(cfg, experts_here=2, first_expert=0,
                                       capacity_factor=0.0)
            common = joyai_flash.DecoderLayer(none, False, None, bias).apply(
                {"params": experts.take_expert_window(params, none)[name]},
                x)
            total = common
            for first in range(0, 8, 2):
                share = dataclasses.replace(cfg, experts_here=2,
                                            first_expert=first)
                out = joyai_flash.DecoderLayer(
                    share, False, None, bias).apply(
                        {"params": experts.take_expert_window(
                            params, share)[name]}, x)
                total = total + (out - common)
            np.testing.assert_allclose(total, want, atol=2e-5)
            assert float(jnp.abs(alone - want).max()) > 1e-3


def test_recomputed_or_kept_the_same_tree_loss_and_gradients(bench):
    code, _ = files(bench)
    results = []
    for remat in (True, False):
        config = toy(bench, training={"attention": "dense", "remat": remat})
        params, tokens = seeded(bench, config, seed=3)
        results.append((params, jax.jit(jax.value_and_grad(
            code.loss_fn(config, {})))(params, tokens)))
    (kept_tree, (kept_loss, kept)), (tree, (loss, grads)) = results
    assert jax.tree.structure(kept_tree) == jax.tree.structure(tree)
    for a, b in zip(jax.tree.leaves(kept_tree), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(a, b)
    assert float(loss) == pytest.approx(float(kept_loss), rel=1e-6)
    assert_same_gradients(grads, kept)


def sharper(params):
    """Larger query and key weights in every layer: attention that is not
    uniform, so that what turns queries and keys shows."""
    out = dict(params)
    for name, layer in params.items():
        if not isinstance(layer, dict) or "attention" not in layer:
            continue
        mixer = dict(layer["attention"])
        for leaf in ("q_b", "kv_a", "kv_b"):
            mixer[leaf] = {"kernel": 4.0 * mixer[leaf]["kernel"]}
        out[name] = dict(layer, attention=mixer)
    return out


def half_split_pairs(lanes):
    """Lane ``i`` pairs with ``i + lanes / 2``: ``rotate_half`` without the
    source's de-interleave."""
    lane = np.arange(lanes)
    return ((lane + lanes // 2) % lanes,
            np.where(lane < lanes // 2, -1.0, 1.0), lane % (lanes // 2))


ROPE_FAULTS = ("turn_left_out", "all_lanes_turned", "half_split_pairs",
               "position_t_plus_1_for_the_keys")
FAULTS = ROPE_FAULTS + (
    "q_norm_left_out", "score_scale_128", "scale_left_out",
    "gates_not_renormalised", "shared_expert_left_out",
    "module_labels_t_plus_1", "module_embedding_of_t", "lambda_left_out",
    "a_head_of_its_own_for_the_module")


def faulty_loss(fault, config, code, monkeypatch):
    """``(params, tokens) -> loss`` of the product with ``fault`` made in
    it, by setting an attribute of ``models.joyai_flash`` or
    ``models.latent`` (as the chip's probes do) or by calling the model
    another way."""
    real_tables, real_experts = latent.rotary_split_tables, \
        joyai_flash.SparseExperts
    real_mlp, real_norm = joyai_flash.GatedMLP, latent.RMSNorm
    real_attention = latent.dense_causal_attention
    loss = None
    if fault == "turn_left_out":
        def tables(kept, lanes, theta, seq):
            cos, sin, swap = real_tables(kept, lanes, theta, seq)
            return jnp.ones_like(cos), jnp.zeros_like(sin), swap
        monkeypatch.setattr(latent, "rotary_split_tables", tables)
    elif fault == "all_lanes_turned":
        monkeypatch.setattr(
            latent, "rotary_split_tables",
            lambda kept, lanes, theta, seq: real_tables(
                0, kept + lanes, theta, seq))
    elif fault == "half_split_pairs":
        monkeypatch.setattr(latent, "interleaved_pairs", half_split_pairs)
    elif fault == "position_t_plus_1_for_the_keys":
        real_turn = latent.turn

        def turn(x, cos, sin, swap, dtype):
            if x.ndim == 3:  # the shared key: one position on
                cos, sin = (jnp.roll(t, -1, 0) for t in (cos, sin))
            return real_turn(x, cos, sin, swap, dtype)
        monkeypatch.setattr(latent, "turn", turn)
    elif fault == "q_norm_left_out":
        monkeypatch.setattr(
            latent, "RMSNorm", lambda eps, name: real_norm(eps, name=name)
            if name != "q_norm" else lambda x: x.astype(jnp.float32))
    elif fault == "score_scale_128":
        monkeypatch.setattr(
            latent, "dense_causal_attention",
            lambda q, k, v, dtype: real_attention(
                q * (192 / 128) ** 0.5, k, v, dtype))
    elif fault == "shared_expert_left_out":
        class Nothing(real_mlp):
            def __call__(self, x):
                out = real_mlp.__call__(self, x)
                return out if self.name == "mlp" else 0.0 * out
        monkeypatch.setattr(joyai_flash, "GatedMLP", Nothing)
    elif fault in ("scale_left_out", "gates_not_renormalised"):
        change = {"scale_left_out": dict(gate_scale=1.0),
                  "gates_not_renormalised": dict(gates_over_picks=False)}[
                      fault]
        monkeypatch.setattr(
            joyai_flash, "SparseExperts",
            lambda cfg, **kw: real_experts(cfg, **{**kw, **change}))
    elif fault == "lambda_left_out":
        config = dict(config, mtp_loss_weight=1.0)
    elif fault == "a_head_of_its_own_for_the_module":
        def loss(params, tokens):
            """The module scored by another head leaf (a copy moved a
            little), as a model that did not share it would."""
            model = code.model(config)
            other = dict(params, lm_head=params["lm_head"][:, ::-1])
            logits, _ = model.apply({"params": params}, tokens[:, :-2],
                                    tokens[:, 1:-1])
            _, ahead = model.apply({"params": other}, tokens[:, :-2],
                                   tokens[:, 1:-1])
            return joyai_flash.token_cross_entropy(
                logits, tokens[:, 1:-1]) + 0.3 * \
                joyai_flash.token_cross_entropy(ahead, tokens[:, 2:])
    else:
        def loss(params, tokens):
            model = code.model(config)
            following, labels = {
                "module_labels_t_plus_1": (tokens[:, 1:-1], tokens[:, 1:-1]),
                "module_embedding_of_t": (tokens[:, :-2], tokens[:, 2:])}[
                    fault]
            logits, ahead = model.apply({"params": params}, tokens[:, :-2],
                                        following)
            return joyai_flash.token_cross_entropy(
                logits, tokens[:, 1:-1]) + 0.3 * \
                joyai_flash.token_cross_entropy(ahead, labels)
    return loss or code.loss_fn(config, {})


@pytest.mark.parametrize("fault", FAULTS)
def test_each_hand_made_fault_leaves_the_reference(bench, fault, monkeypatch):
    """The faults the configuration's file lists, each made in the product:
    the loss leaves the reference's by far more than float32 rounding. The
    rotary ones are read with larger query and key weights (an untrained
    softmax is near uniform and sees no turn; ``test_sharper_weights_alone``
    is their control). On the chip, at seed weights and published widths,
    some of them read inside a seed's rounding: the configuration's file
    says which limit sees which."""
    code, reference = files(bench)
    config = toy(bench, training={"attention": "dense"})
    weights = sharper if fault in ROPE_FAULTS + ("score_scale_128",) else None
    params, tokens = seeded(bench, config, weights=weights)
    with jax.default_matmul_precision("highest"):
        loss = float(jax.jit(faulty_loss(fault, config, code, monkeypatch))(
            params, tokens))
        ref_loss = float(jax.jit(partial(reference.loss, config))(
            params, tokens))
    assert abs(loss - ref_loss) > 3e-5 * ref_loss


def test_sharper_weights_alone_stay_on_the_reference(bench):
    """The rotary faults' control: with the larger weights and the turn as
    it is the product is the reference still, through the kernels too."""
    for attention in ("dense", "flash_interpret"):
        config = toy(bench, training={"attention": attention})
        (loss, grads), (ref_loss, ref_grads) = both_sides(
            bench, config, weights=sharper)
        assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
        assert_same_gradients(grads, ref_grads)


def test_the_turn_is_the_sources_up_to_one_permutation_of_the_lanes(bench):
    """``latent.turn`` on a head against the reference's de-interleave and
    ``rotate_half``: the same numbers, the even lanes' first and then the
    odd ones'; the kept lanes untouched; position ``t`` is not ``t + 1``;
    and in bfloat16 the swap on the MXU moves no bit."""
    _, reference = files(bench)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 3, 24), jnp.float32)
    cos, sin, swap = latent.rotary_split_tables(16, 8, 10000.0, 16)
    got = latent.turn(x, cos, sin, swap, jnp.float32)
    np.testing.assert_array_equal(got[..., :16], x[..., :16])
    want = reference.rope_interleaved(x[..., 16:], 10000.0)
    lanes = np.concatenate([np.arange(0, 8, 2), np.arange(1, 8, 2)])
    np.testing.assert_allclose(got[..., 16:][..., lanes], want, atol=1e-6)
    np.testing.assert_array_equal(got[:, 0], x[:, 0])  # position 0: no turn
    assert float(jnp.abs(got[:, 1:, :, 16:] - got[:, :-1, :, 16:]).max()) > 0
    shifted = latent.turn(x[:, 1:], cos[:-1], sin[:-1], swap, jnp.float32)
    assert float(jnp.abs(shifted - got[:, 1:])[..., 16:].max()) > 1e-2
    low = x.astype(jnp.bfloat16)
    swapped = latent.turn(low, jnp.zeros_like(cos), jnp.ones_like(sin), swap,
                          jnp.bfloat16)  # the swap alone
    partner = 16 + (np.arange(8) ^ 1)
    np.testing.assert_array_equal(swapped[..., 16:], low[..., partner])
    assert not np.asarray(swapped[..., :16]).any()


def test_the_config_refuses_what_has_no_path():
    with pytest.raises(ValueError, match="must be 0 or 1"):
        dataclasses.replace(joyai_flash.JOYAI_FLASH_TINY,
                            num_nextn_predict_layers=2)
    model = joyai_flash.JoyAIFlash(joyai_flash.JOYAI_FLASH_TINY)
    ids = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="without next_ids"):
        model.init(jax.random.PRNGKey(0), ids)
    alone = dataclasses.replace(joyai_flash.JOYAI_FLASH_TINY,
                                num_nextn_predict_layers=0)
    tree = jax.eval_shape(joyai_flash.JoyAIFlash(alone).init,
                          jax.random.PRNGKey(0), ids)["params"]
    assert not [name for name in tree if name.startswith("mtp_")]
    published = joyai_flash.JOYAI_LLM_FLASH
    assert published.capacity(8192) == 320
    assert published.expert_layers == 40


def test_parameters_at_the_published_sizes():
    """From the config's keys: latent attention 26,347,520 a layer, the
    dense layer 70,391,808, an expert layer with 16 experts 107,091,968, the
    module 115,486,720; the cell's cut 680,439,808 in 99 leaves."""
    cut = dataclasses.replace(joyai_flash.JOYAI_LLM_FLASH, num_layers=5,
                              vocab_size=16160, experts_here=16)
    ids = jnp.zeros((1, 128), jnp.int32)
    shapes = jax.eval_shape(
        lambda key: joyai_flash.JoyAIFlash(cut).init(key, ids, ids)["params"],
        jax.random.PRNGKey(0))

    def count(tree):
        return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))

    attention = shapes["layer_1"]["attention"]
    assert count(attention) == 26347520
    assert {name: leaf["kernel"].shape for name, leaf in attention.items()
            if "kernel" in leaf} == {
        "q_a": (2048, 1536), "q_b": (1536, 32 * 192), "kv_a": (2048, 576),
        "kv_b": (512, 32 * 256), "out": (4096, 2048)}
    assert attention["q_norm"]["scale"].shape == (1536,)
    assert attention["kv_norm"]["scale"].shape == (512,)
    assert count(shapes["layer_0"]) == 70391808
    assert shapes["layer_0"]["mlp"]["gate"]["kernel"].shape == (2048, 7168)
    assert count(shapes["layer_4"]) == 107091968
    moe = shapes["layer_4"]["moe"]
    assert moe["router"].shape == (2048, 256)
    assert moe["experts_up"].shape == (16, 2048, 768)
    assert shapes["layer_4"]["shared"]["up"]["kernel"].shape == (2048, 768)
    module = {name: leaf for name, leaf in shapes.items()
              if name.startswith("mtp_")}
    assert sorted(module) == ["mtp_embed_norm", "mtp_hidden_norm",
                              "mtp_layer", "mtp_norm", "mtp_proj"]
    assert count(module) == 115486720
    assert module["mtp_proj"]["kernel"].shape == (4096, 2048)
    assert jax.tree.structure(module["mtp_layer"]) == jax.tree.structure(
        shapes["layer_4"])
    # one embedding and one head, whoever reads them
    assert shapes["token_embeddings"]["embedding"].shape == (16160, 2048)
    assert shapes["lm_head"].shape == (2048, 16160)
    assert count(shapes) == 680439808
    assert len(jax.tree.leaves(shapes)) == 99


def test_the_scopes_are_in_a_lowered_step_and_the_gauges_set(bench):
    from horovod_tpu import metrics

    config = toy(bench)
    code, _ = files(bench)
    params = jax.eval_shape(partial(code.init_params, config, {}),
                            jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 34), jnp.int32)
    text = jax.jit(jax.grad(code.loss_fn(config, {}))).lower(
        params, tokens).as_text(debug_info=True)
    for scope in ("hvd.mla.rope", "hvd.mtp", "hvd.attn.mla", "hvd.attn.fwd",
                  "hvd.attn.bwd", "hvd.moe.shared", "hvd.moe.route",
                  "hvd.moe.dispatch", "hvd.moe.experts", "hvd.moe.combine",
                  "hvd.block.ffn", "hvd.block.attn_proj", "hvd.block.norm",
                  "hvd.block.embed", "hvd.block.head"):
        assert scope in text, scope
    # the rotary split inside the layer's attention block; the module's
    # parts inside hvd.mtp, each under the owner it has in the main stack
    assert "hvd.block.attn_proj/attention/hvd.mla.rope" in text
    for inside in ("hvd.mtp/hvd.block.embed/mtp_proj",
                   "hvd.mtp/mtp_layer/hvd.block.attn_proj/attention/"
                   "hvd.mla.rope", "hvd.mtp/hvd.block.head/mtp_norm",
                   "hvd.mtp/hvd.block.head"):
        assert inside in text, inside
    assert "hvd.mtp/layer_" not in text
    assert metrics.MLA_ROPE_LANES_LAST.labels(kind="rotated").get() == 8
    assert metrics.MLA_ROPE_LANES_LAST.labels(kind="kept").get() == 16
    assert metrics.MTP_DEPTH_LAST.labels().get() == 1


def test_routing_stats_read_the_stacks_expert_layers(bench):
    config = toy(bench, training={"attention": "dense"})
    code, _ = files(bench)
    params, tokens = seeded(bench, config)
    stats = jax.jit(partial(experts.routing_stats, code.model(config)))(
        params, tokens[:, :-2], tokens[:, 1:-1])
    assert stats["load"].shape == (2, 4)   # two expert layers of three
    assert 0 < int(stats["load"].sum()) <= 2 * 2 * 32 * 2
