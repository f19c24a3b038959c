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
from traced import bound

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


def test_the_scopes_are_in_a_lowered_step_and_the_widths_its_configs(bench):
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
    # 8 lanes turned beside 16 kept: kv_a writes the one shared key's 8
    # beside the latent, q_b every head's 16 + 8; and one prediction module
    built = code.model_config(config)
    attention = params["layer_0"]["attention"]
    assert (built.qk_rope_head_dim, built.qk_nope_head_dim) == (8, 16)
    assert attention["kv_a"]["kernel"].shape[-1] == built.kv_lora_rank + 8
    assert attention["q_b"]["kernel"].shape[-1] == (
        built.num_attention_heads * (16 + 8))
    assert built.num_nextn_predict_layers == 1
    assert [name for name in params if name.startswith("mtp_layer")] == [
        "mtp_layer"]


def test_routing_stats_read_the_stacks_expert_layers(bench):
    config = toy(bench, training={"attention": "dense"})
    code, _ = files(bench)
    params, tokens = seeded(bench, config)
    stats = jax.jit(partial(experts.routing_stats, code.model(config)))(
        params, tokens[:, :-2], tokens[:, 1:-1])
    assert stats["load"].shape == (2, 4)   # two expert layers of three
    assert 0 < int(stats["load"].sum()) <= 2 * 2 * 32 * 2


# --- the one pass to the kernels (PR 55, ops/rotary_split.py)

NOPE, ROPE, V_DIM, TILE = 128, 64, 128, 32


def projected(heads, seq=2 * TILE, batch=2, seed=0):
    """``(q [B, S, H * 192], up [B, S, H * 256], shared [B, S, 64])`` in
    bfloat16, as ``q_b``, ``kv_b`` and ``kv_a`` write them, and one
    cotangent for each of ``q``, ``k``, ``v`` head-major, as the flash
    backward kernels write theirs."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    shapes = [(batch, seq, heads * (NOPE + ROPE)),
              (batch, seq, heads * (NOPE + V_DIM)), (batch, seq, ROPE)] + [
        (batch, heads, seq, lanes) for lanes in (NOPE + ROPE, NOPE + ROPE,
                                                 V_DIM)]
    drawn = [jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)
             for key, shape in zip(keys, shapes)]
    return drawn[:3], drawn[3:]


def the_parents_way(heads, seq, sum_in_float32=False):
    """``(q, up, shared) -> (q, k, v)`` head-major as the parent of PR 55
    made them: ``latent.turn`` on whole heads, the shared key broadcast and
    joined, the adapter's three transposes. ``sum_in_float32``: the shared
    key's cotangent summed over the heads in float32 and rounded once (the
    kernel's accumulator), where the broadcast's own transpose is a
    bfloat16 reduction (which XLA's CPU rounds after every add)."""
    cos, sin, swap = latent.rotary_split_tables(NOPE, ROPE, 10000.0, seq)
    dtype = jnp.bfloat16

    @jax.custom_vjp
    def every_head(turned):
        return jnp.broadcast_to(
            turned[:, :, None], turned.shape[:2] + (heads, ROPE))

    every_head.defvjp(
        lambda turned: (every_head(turned), None),
        lambda _, bar: (bar.astype(jnp.float32).sum(2).astype(bar.dtype),))

    def way(q, up, shared):
        rows = q.shape[:2]
        q = latent.turn(q.reshape(rows + (heads, -1)), cos, sin, swap, dtype)
        up = up.reshape(rows + (heads, -1))
        turned = latent.turn(shared, cos[:, NOPE:], sin[:, NOPE:],
                             swap[NOPE:, NOPE:], dtype)
        spread = every_head(turned) if sum_in_float32 else jnp.broadcast_to(
            turned[:, :, None], rows + (heads, ROPE))
        k = jnp.concatenate([up[..., :NOPE], spread], -1)
        return tuple(x.transpose(0, 2, 1, 3)
                     for x in (q, k, up[..., NOPE:]))

    return way, (cos[:, NOPE:], sin[:, NOPE:])


def bits(x):
    return np.asarray(x.astype(jnp.float32))


@pytest.mark.parametrize("heads", [2, 4])
@pytest.mark.parametrize("run", ["forward", "backward"])
@pytest.mark.parametrize("operand", ["q", "k"])
def test_the_one_pass_is_the_parents_turn_reshape_and_transposes(
        operand, run, heads):
    """``ops/rotary_split.py``'s kernels (interpreted, two tiles of tokens)
    against ``latent.turn`` + reshape + the adapter's transposes at JoyAI
    Flash's widths, **bit for bit**: ``q`` forward and ``dq`` backward; ``k``
    and ``v`` forward and ``d kv_b``, ``d k_r`` backward. The turn's
    cotangent keeps the roundings of ``turn``'s own transpose. ``d k_r``
    sums the heads in a float32 accumulator: with two heads that is the
    parent's sum bit for bit, with four it is the parent's with the sum in
    float32 bit for bit and the parent's bfloat16 sum within its
    rounding."""
    from horovod_tpu.ops import rotary_split

    (q, up, shared), bars = projected(heads)
    seq = q.shape[1]
    parent, tables = the_parents_way(heads, seq, sum_in_float32=heads > 2)

    def one_pass(q, up, shared):
        return rotary_split.head_major_operands(
            q, up, shared, *tables, heads, NOPE, TILE, interpret=True)

    want, want_pull = jax.vjp(parent, q, up, shared)
    got, got_pull = jax.vjp(one_pass, q, up, shared)
    names = {"q": ("q",), "k": ("k", "v")}[operand]
    if run == "forward":
        for name in names:
            at = "qkv".index(name)
            assert got[at].shape == want[at].shape, name
            np.testing.assert_array_equal(bits(got[at]), bits(want[at]), name)
            turned = got[at][..., NOPE:] if name != "v" else None
            if turned is not None:  # and something was turned
                flat = (q if name == "q" else jnp.broadcast_to(
                    shared[:, :, None], shared.shape[:2] + (heads, ROPE)))
                flat = flat.reshape(q.shape[:2] + (heads, -1))[
                    ..., -ROPE:].transpose(0, 2, 1, 3)
                assert float(jnp.abs(turned[:, :, 1:].astype(jnp.float32)
                                     - flat[:, :, 1:].astype(jnp.float32)
                                     ).max()) > 0.1
        return
    want_bars, got_bars = want_pull(tuple(bars)), got_pull(tuple(bars))
    for at in {"q": (0,), "k": (1, 2)}[operand]:
        name = ("dq", "d kv_b", "d k_r")[at]
        assert got_bars[at].dtype == jnp.bfloat16
        np.testing.assert_array_equal(bits(got_bars[at]), bits(want_bars[at]),
                                      name)
    if operand == "k" and heads > 2:
        rounded = jax.vjp(the_parents_way(heads, seq)[0], q, up, shared)[1](
            tuple(bars))[2]
        np.testing.assert_allclose(bits(got_bars[2]), bits(rounded),
                                   rtol=0, atol=heads * 2.0 ** -8 * 4)
        assert not np.array_equal(bits(got_bars[2]), bits(rounded))


def test_any_other_platform_lowers_the_plain_form_of_the_same_function():
    """A program lowered for the CPU holds the plain form of each of the
    four passes (``kernel_parts.where_lowered``): the kernels'
    function to a bfloat16 rounding (XLA's CPU contracts ``x cos + p sin``
    its own way), and no Pallas call."""
    from horovod_tpu.ops import rotary_split

    heads = 4
    (q, up, shared), bars = projected(heads)
    _, tables = the_parents_way(heads, q.shape[1])

    def one_pass(interpret, q, up, shared):
        return rotary_split.head_major_operands(
            q, up, shared, *tables, heads, NOPE, TILE, interpret=interpret)

    kernels, kernels_pull = jax.vjp(partial(one_pass, True), q, up, shared)
    plain, plain_pull = jax.vjp(partial(one_pass, False), q, up, shared)
    for got, want in zip(plain + plain_pull(tuple(bars)),
                         kernels + kernels_pull(tuple(bars))):
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_allclose(bits(got), bits(want), rtol=2.0 ** -7,
                                   atol=1e-6)
    lowered = jax.jit(jax.grad(lambda *xs: sum(
        x.astype(jnp.float32).sum() for x in one_pass(False, *xs)),
        (0, 1, 2))).lower(q, up, shared).as_text()
    assert "hvd.mla.rope" in jax.jit(partial(one_pass, False)).lower(
        q, up, shared).as_text(debug_info=True)
    assert "pallas" not in lowered and "custom_call" not in lowered


def latent_layer(heads, rope_theta=10000.0, attention=True, seq=2 * TILE):
    """A latent layer at JoyAI Flash's head widths, its kernels interpreted
    in tiles of ``TILE``, and an input for it."""
    cfg = dataclasses.replace(
        joyai_flash.JOYAI_FLASH_TINY, num_attention_heads=heads,
        qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE, v_head_dim=V_DIM)
    attention_fn = partial(joyai_flash.flash_attention_fn, interpret=True,
                           block=TILE) if attention else None
    layer = latent.LatentAttention(
        cfg, attention_fn, q_lora_rank=cfg.q_lora_rank,
        rope_theta=rope_theta)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, seq, cfg.hidden_size),
                          jnp.float32).astype(cfg.dtype)
    return layer, x


@pytest.mark.parametrize("shapes,path", [
    ("joyai_llm_flash", "one_pass"), ("two_heads", "one_pass"),
    ("three_heads", "plain"), ("dense_attention", "plain"),
    ("an_odd_tile", "plain"), ("the_toy", "plain")])
def test_the_trace_says_which_way_a_layer_went(shapes, path):
    """The one pass's primitives are in the trace or they are not: the
    published shapes (and any whose heads pair up into whole lane tiles,
    with an adapter that takes head-major operands) take the one pass;
    three heads, dense attention
    (it reads ``[B, S, H, D]``), tokens that fill no sublane tile and the
    toy's 16 + 8 lanes take ``latent.turn``."""
    if shapes == "joyai_llm_flash":
        cfg = joyai_flash.JOYAI_LLM_FLASH
        layer = latent.LatentAttention(
            cfg, partial(joyai_flash.flash_attention_fn, interpret=True),
            q_lora_rank=cfg.q_lora_rank, rope_theta=cfg.rope_theta)
        x = jax.ShapeDtypeStruct((1, 8192, cfg.hidden_size), cfg.dtype)
    elif shapes == "the_toy":
        cfg = joyai_flash.JOYAI_FLASH_TINY
        layer = latent.LatentAttention(
            cfg, partial(joyai_flash.flash_attention_fn, interpret=True,
                         block=16), q_lora_rank=cfg.q_lora_rank,
            rope_theta=cfg.rope_theta)
        x = jax.ShapeDtypeStruct((1, 32, cfg.hidden_size), cfg.dtype)
    else:
        layer, x = latent_layer(
            3 if shapes == "three_heads" else 2,
            attention=shapes != "dense_attention",
            seq=40 if shapes == "an_odd_tile" else 2 * TILE)
        if shapes == "an_odd_tile":  # 40 tokens: tiles of 8, half a bf16 tile
            layer = layer.clone(attention_fn=partial(
                joyai_flash.flash_attention_fn, interpret=True, block=8))
    passes = [name for name, _ in bound(
        layer.init, jax.random.PRNGKey(0), x, prefix="hvd_mla_rope")]
    assert passes == {"one_pass": ["hvd_mla_rope_queries",
                                   "hvd_mla_rope_keys"], "plain": []}[path]


def under_the_scope(jaxpr, scope="hvd.mla.rope", inside=False):
    """Every equation of ``jaxpr`` and of the jaxprs in its parameters
    whose name stack holds ``scope``."""
    from jax.extend import core

    for eqn in jaxpr.eqns:
        here = inside or scope in str(eqn.source_info.name_stack)
        if here:
            yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                if isinstance(sub, core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, core.Jaxpr):
                    yield from under_the_scope(sub, scope, here)


@pytest.mark.parametrize("heads", [2, 3])
def test_the_one_pass_holds_no_float32_copy_of_the_queries(heads):
    """The jaxpr of a layer's gradient: with two heads (the one pass)
    what lies under ``hvd.mla.rope`` is the four passes' primitives and the
    tables, and no float32 array as large as ``q``; with three (the plain
    turn) the scope holds ``q`` in float32, forward and backward: the
    test's own control."""
    layer, x = latent_layer(heads)
    params = jax.jit(layer.init)(jax.random.PRNGKey(0), x)

    def loss(p, x):
        return layer.apply(p, x).astype(jnp.float32).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss))(params, x).jaxpr
    eqns = list(under_the_scope(jaxpr))
    size = x.shape[0] * x.shape[1] * heads * (NOPE + ROPE)
    wide = [eqn.primitive.name for eqn in eqns for out in eqn.outvars
            if out.aval.dtype == jnp.float32 and out.aval.size >= size]
    passes = sorted({eqn.primitive.name for eqn in eqns
                     if eqn.primitive.name.startswith("hvd_mla_rope")})
    if heads == 2:
        assert not wide
        assert passes == ["hvd_mla_rope_keys", "hvd_mla_rope_keys_backward",
                          "hvd_mla_rope_queries",
                          "hvd_mla_rope_queries_backward"]
    else:
        assert not passes
        assert "convert_element_type" in wide and "mul" in wide


def test_a_layer_through_the_one_pass_is_the_layer_through_the_turn():
    """The whole layer, loss and every leaf's gradient: the one pass (the
    plain form of its primitives, on this platform) against the same layer
    sent down ``latent.turn`` (by an adapter that does not say
    ``head_major``), on the same weights."""
    layer, x = latent_layer(4)
    params = jax.jit(layer.init)(jax.random.PRNGKey(0), x)
    turned = layer.clone(attention_fn=lambda *a, **k: layer.attention_fn(
        *a, **k))

    def loss(layer, p, x):
        return (layer.apply(p, x).astype(jnp.float32) ** 2).sum()

    def passes(layer):
        return [name for name, _ in bound(partial(loss, layer), params, x,
                                          prefix="hvd_mla_rope")]

    got = jax.jit(jax.value_and_grad(partial(loss, layer)))(params, x)
    assert passes(layer) == ["hvd_mla_rope_queries", "hvd_mla_rope_keys"]
    want = jax.jit(jax.value_and_grad(partial(loss, turned)))(params, x)
    assert not passes(turned)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=2e-3)
    for (path, leaf), ref in zip(
            jax.tree_util.tree_leaves_with_path(got[1]),
            jax.tree.leaves(want[1])):
        scale = float(jnp.abs(ref).max())
        np.testing.assert_allclose(
            leaf, ref, rtol=0, atol=2e-2 * scale,
            err_msg=jax.tree_util.keystr(path))
