"""``models/kimi_linear.py`` against the plain reference the benchmark keeps
(``benchmark/reference/kimi_linear.py``: the token-by-token recurrence with a
decay a key channel, dense masked attention at two widths, sigmoid routing
on every position): on seeded weights at a toy size the two are one
function, loss and every leaf's gradient, with the two-width flash kernels
(interpreted, several tiles) or dense attention, with and without
recomputation, with the latent layer first, in another window of the
experts. **The share test**: the windows' routed parts, with the shared
expert counted once, add up to the uncut reference's layer. And the model
is the published one: its lists, its 602,433,408 parameters at the cell's
cut, its scopes in a lowered step."""

import dataclasses
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import experts, kimi_linear

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
                             "rehearsal-kimi-linear.json")
    training = dict(config["training"], **changes.pop("training", {}))
    linear = dict(config["linear_attn_config"],
                  **changes.pop("linear_attn_config", {}))
    return dict(config, training=training, linear_attn_config=linear,
                **changes)


def both_sides(cells, config, rows=2, seq=32, seed=5, weights=None):
    """``(loss, gradients)`` of the product and of the reference on the
    same seeded weights (passed through ``weights`` where given) and
    tokens."""
    code = cells.load_code(cells.HERE, "configs", "kimi_linear.py")
    reference = cells.load_code(cells.HERE, "reference", "kimi_linear.py")
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


def two_layers(cells):
    """A dense KDA layer and a KDA layer with experts: what the routing's
    tests need, at half the toy's compile time."""
    return toy(cells, num_hidden_layers=2,
               linear_attn_config={"kda_layers": [1, 2],
                                   "full_attn_layers": []},
               training={"attention": "dense"})


CASES = {
    "flash_two_tiles_recomputed": {},
    "dense_kept": {"training": {"attention": "dense", "remat": False}},
    "latent_layer_first_one_chunk": {
        "linear_attn_config": {"kda_layers": [2, 3, 4],
                               "full_attn_layers": [1]},
        "training": {"attention": "dense", "chunk": 32, "sub_chunk": 8}},
    "all_experts_two_dense_layers": {
        "first_expert": 0, "experts_here": 8, "first_k_dense_replace": 2,
        "training": {"attention": "dense"}},
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_float32_product_is_the_reference(bench, case):
    (loss, grads), (ref_loss, ref_grads) = both_sides(
        bench, toy(bench, **CASES[case]))
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    got = jax.tree_util.tree_leaves_with_path(grads)
    assert len(got) == len(jax.tree.leaves(ref_grads))
    for (path, leaf), want in zip(got, jax.tree.leaves(ref_grads)):
        want = np.asarray(want)
        scale = float(np.abs(want).max())
        assert scale > 0, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            leaf, want, rtol=0, atol=2e-4 * scale + 5e-7,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("fault", [
    "scale", "renormalisation", "softmax_scores", "shared_expert"])
def test_each_piece_of_the_routing_matters(bench, fault, monkeypatch):
    """A product with 2.446 left out, the gates not renormalised, softmax
    scores for sigmoid or the shared expert left out is another function
    than the reference: its loss leaves the reference's by far more than
    float32 rounding. (On the chip, at seed weights, some of these read
    inside a seed's rounding: the configuration's file says which.)"""
    real = kimi_linear.SparseExperts

    def other(cfg, **kw):
        if fault == "scale":
            kw["gate_scale"] = 1.0
        elif fault == "renormalisation":
            kw["gates_over_picks"] = False
        elif fault == "softmax_scores":
            kw.update(scores="softmax", gate_scale=1.0)
        return real(cfg, **kw)

    if fault == "shared_expert":
        mlp = kimi_linear.GatedMLP

        class Nothing(mlp):
            def __call__(self, x):
                return 0.0 * mlp.__call__(self, x)

        monkeypatch.setattr(
            kimi_linear, "GatedMLP",
            lambda cfg, width, name: (Nothing if name == "shared" else mlp)(
                cfg, width, name=name))
    else:
        monkeypatch.setattr(kimi_linear, "SparseExperts", other)
    (loss, _), (ref_loss, _) = both_sides(bench, two_layers(bench))
    assert abs(float(loss) - float(ref_loss)) > 3e-5 * float(ref_loss)


@pytest.mark.parametrize("fault", ["rope_on_the_rotary_lanes",
                                   "scale_of_the_nope_width"])
def test_the_latent_layers_scale_and_missing_rotation_matter(
        bench, fault, monkeypatch):
    """``mla_use_nope``: nothing turns ``k_r`` and the queries' last lanes,
    and the scores are scaled by the whole key width. On the chip at seed
    weights a product that applies RoPE there reads ``correct: true`` (an
    untrained latent layer's softmax is near uniform: the configuration's
    file says so), so this holds it: at the toy size with larger queries
    and keys the product leaves the reference."""
    from horovod_tpu.models import latent, parts

    config = toy(bench, num_hidden_layers=2,
                 linear_attn_config={"kda_layers": [1],
                                     "full_attn_layers": [2]},
                 training={"attention": "dense"})
    nope = config["qk_nope_head_dim"]
    real = latent.dense_causal_attention  # the module's body lives there

    def other(q, k, v, dtype):
        if fault == "rope_on_the_rotary_lanes":
            q, k = (jnp.concatenate(
                [x[..., :nope], parts.rope(x[..., nope:], 10000.0)], -1)
                for x in (q, k))
        else:
            q = q * (q.shape[-1] / nope) ** 0.5
        return real(q, k, v, dtype)

    def sharper(params):
        """Larger query and key weights: attention that is not uniform."""
        mixer = dict(params["layer_1"]["attention"])
        for name in ("query", "kv_a", "kv_b"):
            mixer[name] = {"kernel": 4.0 * mixer[name]["kernel"]}
        return dict(params, layer_1=dict(params["layer_1"], attention=mixer))

    (loss, _), (ref_loss, _) = both_sides(bench, config, weights=sharper)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    monkeypatch.setattr(latent, "dense_causal_attention", other)
    (loss, _), (ref_loss, _) = both_sides(bench, config, weights=sharper)
    assert abs(float(loss) - float(ref_loss)) > 3e-5 * float(ref_loss)


def test_the_windows_and_one_shared_expert_add_up_to_the_uncut_layer(bench):
    """Guide section 4's share test, at the deployment's ratio: 32 chips
    hold one thirty-second of the experts each (here 32 experts, one a
    window). Every window's layer is ``x' + routed_w + shared``; the sum of
    the routed parts and the shared expert counted once is the uncut
    reference's layer, whose router, capacity rule and gates know nothing
    of windows."""
    cells = bench
    reference = cells.load_code(cells.HERE, "reference", "kimi_linear.py")
    config = toy(cells, num_experts=32, num_experts_per_token=4,
                 experts_here=32, first_expert=0, capacity_factor=1.0,
                 training={"attention": "dense", "remat": False})
    code = cells.load_code(cells.HERE, "configs", "kimi_linear.py")
    whole = code.model_config(config)
    key = jax.random.PRNGKey(11)
    params = jax.jit(partial(code.init_params, config, {}))(key)["layer_1"]
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 32, 64))

    with jax.default_matmul_precision("highest"):
        uncut = reference.layer(config, 1, x, params)
        eps = config["rms_norm_eps"]
        mixed = x + reference.kimi_delta_attention(
            config, reference.rms_norm(x, params["ln_mixer"], eps),
            params["kda"])
        base = mixed + reference.gated_mlp(
            reference.rms_norm(mixed, params["ln_ffn"], eps),
            params["shared"])

        @jax.jit
        def window(first, x):  # one program: the window's place is traced
            share = dataclasses.replace(whole, first_expert=first,
                                        experts_here=1)
            mine = dict(params, moe={
                name: jax.lax.dynamic_slice_in_dim(leaf, first, 1)
                if name.startswith("experts_") else leaf
                for name, leaf in params["moe"].items()})
            return kimi_linear.DecoderLayer(
                share, kimi_linear.KDA, False).apply({"params": mine}, x)

        routed = sum(window(first, x) - base for first in range(32))
    scale = float(jnp.abs(uncut - base).max())
    assert scale > 1e-3  # the routed experts do something
    np.testing.assert_allclose(base + routed, uncut, rtol=0,
                               atol=2e-4 * scale)
    # some pair was dropped at capacity 1.0, alike on both sides
    capacity = whole.capacity(32)
    scores = jax.nn.sigmoid(reference.rms_norm(
        mixed, params["ln_ffn"], eps)[0] @ params["moe"]["router"])
    load = np.bincount(np.asarray(jax.lax.top_k(scores, 4)[1]).ravel(),
                       minlength=32)
    assert load.max() > capacity


def test_the_lists_name_each_layer_once():
    with pytest.raises(ValueError, match="each of the 4 layers once"):
        dataclasses.replace(kimi_linear.KIMI_LINEAR_TINY,
                            kda_layers=(1, 2), full_attn_layers=(4,))
    published = kimi_linear.KIMI_LINEAR_48B_A3B
    assert published.kinds.count(kimi_linear.KDA) == 20
    assert published.kinds.count(kimi_linear.MLA) == 7
    assert [i + 1 for i, kind in enumerate(published.kinds)
            if kind == kimi_linear.MLA] == [4, 8, 12, 16, 20, 24, 27]
    assert published.qk_head_dim == 192 and published.v_head_dim == 128


def test_parameters_at_the_published_sizes():
    """From the config's keys: a KDA mixer 39,514,272, an MLA mixer
    29,114,880, the dense feed-forward 63,700,992, a router 589,824, an
    expert and the shared one 7,077,888 each; the cell's cut 602,433,408;
    the whole model 49.12 B."""
    cut = dataclasses.replace(
        kimi_linear.KIMI_LINEAR_48B_A3B, num_layers=5,
        kda_layers=(1, 2, 3, 5), full_attn_layers=(4,), vocab_size=20480,
        experts_here=8)
    shapes = jax.eval_shape(
        lambda key: kimi_linear.KimiLinear(cut).init(
            key, jnp.zeros((1, 64), jnp.int32))["params"],
        jax.random.PRNGKey(0))

    def count(tree):
        return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))

    assert count(shapes["layer_0"]["kda"]) == 39514272
    assert count(shapes["layer_3"]["attention"]) == 29114880
    assert count(shapes["layer_0"]["mlp"]) == 63700992
    assert count(shapes["layer_1"]["shared"]) == 7077888
    assert shapes["layer_1"]["moe"]["router"].shape == (2304, 256)
    assert shapes["layer_1"]["moe"]["experts_gate"].shape == (8, 2304, 1024)
    assert shapes["layer_3"]["attention"]["query"]["kernel"].shape == (
        2304, 32 * 192)
    assert shapes["layer_3"]["attention"]["kv_b"]["kernel"].shape == (
        512, 32 * 256)
    assert "mlp" not in shapes["layer_1"] and "moe" not in shapes["layer_0"]
    assert count(shapes) == 602433408
    assert len(jax.tree.leaves(shapes)) == 109
    layer = 47186592 + 256 * 7077888          # a KDA expert layer, whole
    whole = (103219872 + 19 * layer
             + 7 * (layer - 39514272 + 29114880)
             + 2 * 163840 * 2304 + 2304)
    assert whole == pytest.approx(49.12e9, rel=1e-3)


def test_the_scopes_are_in_a_lowered_step_and_the_widths_in_its_trace(
        bench):
    from traced import equations, pallas_calls

    config = toy(bench)
    code = bench.load_code(bench.HERE, "configs", "kimi_linear.py")
    params = jax.eval_shape(partial(code.init_params, config, {}),
                            jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 33), jnp.int32)
    traced = jax.jit(jax.grad(code.loss_fn(config, {}))).trace(
        params, tokens)
    text = traced.lower().as_text(debug_info=True)
    for scope in ("hvd.linattn.conv", "hvd.linattn.scan", "hvd.linattn.gate",
                  "hvd.attn.mla/hvd.attn.fwd", "hvd.attn.mla/hvd.attn.bwd",
                  "hvd.moe.shared", "hvd.moe.route", "hvd.moe.experts",
                  "hvd.block.ffn", "hvd.block.attn_proj", "hvd.block.norm",
                  "hvd.block.embed", "hvd.block.head"):
        assert scope in text, scope
    # a decay a key channel: gamma is the triangle's product over the four
    # heads' 16 lanes side by side (the scalar rule's is a sum a head)
    assert (2, 2, 16, 4 * 16) in [
        eqn.invars[1].aval.shape for eqn in equations(traced.jaxpr.jaxpr)
        if eqn.primitive.name == "dot_general"
        and eqn.invars[0].aval.shape == (2, 2, 16, 16)]
    # the latent layer's kernels read keys of 16 + 8 lanes, values of 16
    assert {(shapes[1][-1], shapes[2][-1])
            for _, shapes in pallas_calls(traced.jaxpr)} == {(24, 16)}


def test_routing_stats_skip_the_dense_layer(bench):
    config = two_layers(bench)
    code = bench.load_code(bench.HERE, "configs", "kimi_linear.py")
    model = code.model(config)
    key = jax.random.PRNGKey(2)
    params = jax.jit(partial(code.init_params, config, {}))(key)
    ids = code.make_batch(config, {"seq_len": 32}, key, 2)[:, :-1]
    stats = jax.jit(partial(experts.routing_stats, model))(params, ids)
    assert stats["load"].shape == (1, 4)      # one expert layer of four
    assert 0 < int(stats["load"].sum()) <= 2 * 32 * 2
    whole = jax.jit(partial(code.init_params, dict(
        config, first_expert=0, experts_here=8), {}))(key)
    window = experts.take_expert_window(whole, model.config)
    assert window["layer_0"] is whole["layer_0"]  # dense: nothing to cut
    np.testing.assert_array_equal(
        window["layer_1"]["moe"]["experts_up"],
        whole["layer_1"]["moe"]["experts_up"][2:6])


# sha256 of jit(grad(sum(LatentAttention(KIMI_LINEAR_TINY).apply))).lower(...)
# .as_text() on a [2, 32, 64] bfloat16 input at commit 7ec0510, when the
# module lived in models/kimi_linear.py
PARENTS_LATENT_TEXT = (
    "7d55ad7f2cf88ec0857b5b19fa0088b29721149ef6e78bc5db7b2ade58e1925d")


def test_the_latent_layer_lowers_to_the_text_it_had():
    """``models/latent.py`` called as Kimi Linear calls it (no low-rank
    queries, no rotary split): every leaf under its name, and the program
    the parent's."""
    import hashlib

    from horovod_tpu.models import latent

    layer = latent.LatentAttention(kimi_linear.KIMI_LINEAR_TINY)
    x = jnp.zeros((2, 32, 64), jnp.bfloat16)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)
    assert sorted(params["params"]) == ["kv_a", "kv_b", "kv_norm", "out",
                                        "query"]

    def loss(p, x):  # the name is in the text
        return layer.apply(p, x).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss)).lower(params, x).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENTS_LATENT_TEXT


# sha256 of str(make_jaxpr(grad(loss))) of the layer below at commit 831a554,
# before models/latent.py had a second way to the kernels (PR 55)
PARENTS_LATENT_JAXPR = (
    "387dcd5d3ca496d5319b6bde8f6e36189e27497b73c8c41d8c73108aa165e644")


def test_a_layer_with_nothing_turned_traces_to_the_jaxpr_it_had():
    """Kimi Linear's latent layer has no positions (``rope_theta`` None),
    so it takes neither the turn nor the one pass (``ops/rotary_split.py``)
    even at widths whose heads pair up into whole lane tiles and with the
    adapter that takes head-major operands: its branch is untouched, the
    reshapes where they were and the adapter's three transposes after
    them."""
    import hashlib

    from horovod_tpu.models import latent, parts
    from horovod_tpu.ops import rotary_split

    cfg = dataclasses.replace(
        kimi_linear.KIMI_LINEAR_TINY, hidden_size=64, num_attention_heads=2,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        kv_lora_rank=32)
    layer = latent.LatentAttention(cfg, partial(
        parts.head_major_flash_attention, interpret=True, block=16))
    x = jnp.zeros((1, 32, 64), jnp.bfloat16)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)

    def loss(p, x):
        return layer.apply(p, x).astype(jnp.float32).sum()

    text = str(jax.make_jaxpr(jax.grad(loss))(params, x))
    assert "hvd_mla_rope" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == PARENTS_LATENT_JAXPR
    # (shapes the one pass would have taken, had anything been turned)
    assert rotary_split.tokens_a_step(
        jax.ShapeDtypeStruct((1, 32, 2 * 192), jnp.bfloat16), 2, 128, 64, 128)


# --- the norms a head, where the heads' lanes lie (PR 53)

HEADS, WIDTH = 4, 16


def by_head_norms(dtype, seed=0):
    """``x [2, 24, heads * width]`` in ``dtype``, a scale ``[width]`` and a
    cotangent."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = (3 * jax.random.normal(keys[0], (2, 24, HEADS * WIDTH))).astype(dtype)
    scale = 1 + 0.1 * jax.random.normal(keys[1], (WIDTH,))
    return x, scale, jax.random.normal(keys[2], x.shape)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("norm", ["l2norm", "rms_norm"])
def test_a_heads_norm_where_the_lanes_lie_is_the_norm_a_head(norm, dtype):
    """``l2norm_of_heads`` and ``HeadsRMSNorm`` on ``[B, S, H * d]`` are
    ``parts.l2norm`` and ``parts.RMSNorm`` on ``[B, S, H, d]``, values and
    gradients (``x``'s and the scale's), to float32's rounding: the sums a
    head are float32 products at full precision (another order of the same
    sum), the factors come back to the lanes exactly; the scale is the same
    leaf ``[d]``."""
    from horovod_tpu.models import parts

    x, scale, bar = by_head_norms(jnp.dtype(dtype))
    shape = x.shape[:2] + (HEADS, WIDTH)

    def flat(x, scale):
        if norm == "l2norm":
            return kimi_linear.l2norm_of_heads(x, HEADS)
        return kimi_linear.HeadsRMSNorm(1e-5, HEADS).apply(
            {"params": {"scale": scale}}, x)

    def a_head(x, scale):
        if norm == "l2norm":
            return parts.l2norm(x.reshape(shape)).reshape(x.shape)
        return parts.RMSNorm(1e-5).apply(
            {"params": {"scale": scale}}, x.reshape(shape)).reshape(x.shape)

    got, got_vjp = jax.vjp(flat, x, scale)
    want, want_vjp = jax.vjp(a_head, x, scale)
    assert got.dtype == want.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-6 * float(jnp.abs(want).max()))
    for name, a, b in zip(("x", "scale"), got_vjp(bar), want_vjp(bar)):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        room = 2e-6 if dtype == "float32" or name == "scale" else 2e-2
        np.testing.assert_allclose(
            a, b, rtol=0, atol=room * float(jnp.abs(b).max()) + 1e-9,
            err_msg=name)


def test_the_heads_norm_keeps_the_trees_leaf_and_a_policy_keeps_nothing():
    """The scale is ``parts.RMSNorm``'s leaf (``scale [d]``, ones), so the
    cell's parameters and their count stand; and both products have the
    batch as a batch dimension, so the decoders' recomputation policy,
    which keeps every product without one, keeps neither the sums nor the
    factors spread over the lanes (134 MB a norm at the cell's shape)."""
    from jax._src.ad_checkpoint import saved_residuals

    from horovod_tpu.models import parts

    x, _, _ = by_head_norms(jnp.float32)
    norm = kimi_linear.HeadsRMSNorm(1e-5, HEADS)
    tree = jax.eval_shape(norm.init, jax.random.PRNGKey(0), x)["params"]
    assert jax.tree.map(lambda leaf: (leaf.shape, leaf.dtype), tree) == {
        "scale": ((WIDTH,), jnp.float32)}
    kept = saved_residuals(jax.checkpoint(
        lambda x: kimi_linear.l2norm_of_heads(x, HEADS),
        policy=parts.save_kernels_and_projections), x)
    assert all(source.startswith(("from the argument", "from a constant"))
               for _, source in kept), kept
