"""``models/lfm2.py`` against the plain reference the benchmark keeps
(``benchmark/reference/lfm2.py``: ``Conv1d``'s padded sum cut to the
sequence, ``rotate_half``, dense masked attention with the keys repeated a
group, the experts held one after another, 1e-6 beside the picked scores'
sum): on seeded weights at a toy size the two are one function (logits, the
loss and every leaf's gradient, two rows) with the grouped flash kernels
(interpreted, several tiles) or dense attention, with and without
recomputation, with and without a selection bias. The eight-fold cut adds up
to the uncut layer. The mixer alone is ``Conv1d``'s definition. **Fourteen
faults made by hand in the product each leave the reference**, the epsilon's
in a case of its own (what the chip's limits see of them at seed weights is
in the configuration's file). And the model is the published one: its
469,284,992 parameters at the cell's cut, its scope and gauge in a lowered
step."""

import dataclasses
import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import experts, lfm2
from horovod_tpu.parallel import moe

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
    config = cells.load_json(cells.HERE, "configs", "rehearsal-lfm2.json")
    training = dict(config["training"], **changes.pop("training", {}))
    return dict(config, training=training, **changes)


def files(cells):
    return (cells.load_code(cells.HERE, "configs", "lfm2.py"),
            cells.load_code(cells.HERE, "reference", "lfm2.py"))


def seeded(cells, config, rows=2, seq=32, seed=5, weights=None):
    """``(params, tokens [rows, seq + 1])`` from the seed, as the harness
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
            partial(lfm2.causal_lm_loss, model)))(params, tokens)
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
    layers = config["num_hidden_layers"] - config["num_dense_layers"]
    return 0.5 * jax.random.normal(
        jax.random.PRNGKey(seed), (layers, config["num_experts"]),
        jnp.float32)


CASES = {
    "flash_two_tiles_recomputed": {},
    "dense_kept": {"training": {"attention": "dense", "remat": False}},
    "all_experts_two_dense_layers_the_sources_period": {
        "first_expert": 0, "experts_here": 8, "num_dense_layers": 2,
        "num_hidden_layers": 6,
        "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                        "conv"],
        "training": {"attention": "dense"}},
}


@pytest.mark.parametrize("case,biased", [
    ("flash_two_tiles_recomputed", True), ("dense_kept", False),
    ("all_experts_two_dense_layers_the_sources_period", True)])
def test_float32_product_is_the_reference(bench, case, biased):
    config = toy(bench, **CASES[case])
    bias = some_bias(config) if biased else None
    (loss, grads), (ref_loss, ref_grads) = both_sides(bench, config, bias)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    assert_same_gradients(grads, ref_grads)


def test_the_logits_of_two_rows_are_the_references(bench):
    config = toy(bench, training={"attention": "dense"})
    code, reference = files(bench)
    params, tokens = seeded(bench, config)
    with jax.default_matmul_precision("highest"):
        logits = jax.jit(code.model(config).apply)(
            {"params": params}, tokens[:, :-1])
        want = jax.jit(partial(reference.logits_of, config))(
            params, tokens[:, :-1])
        # a row at a time, as the cell's reference_block_rows has it
        alone = jax.jit(partial(reference.logits_of, config))(
            params, tokens[1:, :-1])
    assert logits.shape == (2, 32, 256) and logits.dtype == jnp.float32
    np.testing.assert_allclose(logits, want, atol=2e-5)
    np.testing.assert_allclose(logits[1:], alone, atol=2e-5)
    assert float(jnp.abs(logits[0] - logits[1]).max()) > 0.1


def test_the_bias_moves_picks_and_never_a_gate(bench):
    """With a bias the loss is another (picks changed), the gradient does
    not reach the bias, and a bias that is the same for every expert
    changes nothing: it is added for the choice alone. A config without
    ``use_expert_bias`` takes none."""
    config = toy(bench, training={"attention": "dense"})
    code, _ = files(bench)
    params, tokens = seeded(bench, config)
    bias = some_bias(config)

    def loss(bias):
        return lfm2.causal_lm_loss(
            code.model(config).clone(selection_bias=bias), params, tokens)

    plain = float(jax.jit(loss)(jnp.zeros_like(bias)))
    assert float(jax.jit(loss)(jnp.full_like(bias, 0.25))) == plain
    assert abs(float(jax.jit(loss)(bias)) - plain) > 1e-4 * plain
    assert not np.asarray(jax.jit(jax.grad(loss))(bias)).any()
    without = lfm2.Lfm2(dataclasses.replace(
        lfm2.LFM2_TINY, use_expert_bias=False), selection_bias=bias)
    with pytest.raises(ValueError, match="use_expert_bias"):
        without.init(jax.random.PRNGKey(0), tokens[:, :-1])


def test_the_eight_fold_cut_adds_up_to_the_uncut_layer(bench):
    """``W`` windows that partition the experts, the mixer counted once, add
    up to the reference's layer with every expert (here four windows of
    two; the cell's cut is eight of eight); the dense layer is every
    window's alike."""
    code, reference = files(bench)
    whole = toy(bench, first_expert=0, experts_here=8, capacity_factor=8.0,
                training={"attention": "dense", "remat": False})
    cfg = dataclasses.replace(code.model_config(whole), dtype=jnp.float32)
    params, _ = seeded(bench, whole)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, 64), jnp.float32)
    bias = some_bias(whole)[0]

    def share_of(name, kind, **window):
        share = dataclasses.replace(cfg, experts_here=2, **window)
        return lfm2.DecoderLayer(share, kind, False, None, bias).apply(
            {"params": experts.take_expert_window(params, share)[name]}, x)

    with jax.default_matmul_precision("highest"):
        for name, kind in (("layer_2", "conv"),):
            want = reference.layer(whole, kind, False, x, params[name], bias)
            # what every chip computes alike: all of it but the routed sum
            common = share_of(name, kind, first_expert=0,
                              capacity_factor=0.0)
            total = common
            for first in range(0, 8, 2):
                total = total + (
                    share_of(name, kind, first_expert=first) - common)
            np.testing.assert_allclose(total, want, atol=2e-5)
            alone = share_of(name, kind, first_expert=0)
            assert float(jnp.abs(alone - want).max()) > 1e-3
        dense = [lfm2.DecoderLayer(
            dataclasses.replace(cfg, experts_here=2, first_expert=first),
            "conv", True).apply({"params": params["layer_0"]}, x)
            for first in (0, 6)]
        np.testing.assert_array_equal(*dense)
        np.testing.assert_allclose(dense[0], reference.layer(
            whole, "conv", True, x, params["layer_0"], None), atol=2e-5)


class TestTheMixerAlone:
    """``ShortConv`` against ``torch.nn.Conv1d(groups=d, kernel_size=3,
    padding=2)`` cut to the sequence, written out by hand."""

    CFG = dataclasses.replace(lfm2.LFM2_TINY, dtype=jnp.float32)

    @pytest.fixture(scope="class")
    def mixer(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 64), jnp.float32)
        params = lfm2.ShortConv(self.CFG).init(jax.random.PRNGKey(1), x)
        return params, x

    def apply(self, params, x):
        with jax.default_matmul_precision("highest"):
            return lfm2.ShortConv(self.CFG).apply(params, x)

    def test_it_is_the_definition_token_by_token(self, mixer):
        params, x = mixer
        p = jax.tree.map(np.asarray, params["params"])
        w_in, w_out, taps = (p["in_proj"]["kernel"], p["out_proj"]["kernel"],
                             p["conv"])
        assert (w_in.shape, w_out.shape, taps.shape) == (
            (64, 192), (64, 64), (64, 3))
        bcx = np.asarray(x, np.float64) @ w_in
        gate_in, gate_out, inner = (
            bcx[..., :64], bcx[..., 64:128], bcx[..., 128:])
        u = gate_in * inner
        want = np.zeros_like(u)
        for t in range(12):
            for i in range(3):       # w[:, 2] weighs the token itself
                if t - 2 + i >= 0:   # zeros left of the sequence
                    want[:, t] += taps[:, i] * u[:, t - 2 + i]
        np.testing.assert_allclose(
            self.apply(params, x), (gate_out * want) @ w_out, atol=1e-5)

    def test_an_output_does_not_move_when_a_later_token_does(self, mixer):
        params, x = mixer
        out = self.apply(params, x)
        moved = self.apply(params, x.at[:, 7].add(1.0))
        np.testing.assert_array_equal(out[:, :7], moved[:, :7])
        # and tokens 7, 8 and 9 do (three taps), token 10 does not
        for t in (7, 8, 9):
            assert float(jnp.abs(out[:, t] - moved[:, t]).max()) > 1e-3
        np.testing.assert_array_equal(out[:, 10:], moved[:, 10:])

    def test_the_first_two_positions_see_the_zeros(self, mixer):
        params, x = mixer
        short = self.apply(params, x[:, :1])
        np.testing.assert_allclose(short, self.apply(params, x)[:, :1],
                                   atol=1e-6)
        p = params["params"]
        bcx = x[:, 0] @ p["in_proj"]["kernel"]
        alone = (bcx[:, 64:128] * p["conv"][:, 2] * bcx[:, :64]
                 * bcx[:, 128:]) @ p["out_proj"]["kernel"]
        np.testing.assert_allclose(short[:, 0], alone, atol=1e-5)

    def test_the_three_parts_are_lane_slices_of_one_array(self, mixer):
        """No ``[B, S, 3, d]`` view of the projection in the mixer's own
        text: a reshape of the lanes is another tiling on a TPU."""
        params, x = mixer
        text = jax.jit(lfm2.ShortConv(self.CFG).apply).lower(
            params, x).as_text()
        assert "x3x64x" not in text and "x64x3x" not in text
        assert text.count("stablehlo.slice") >= 3


def sharper(params):
    """Larger query and key weights in the attention layer: attention that
    is not uniform, so that what turns or scales queries and keys shows."""
    out = dict(params)
    for name, layer in params.items():
        if not isinstance(layer, dict) or "attention" not in layer:
            continue
        mixer = dict(layer["attention"])
        for leaf in ("query", "key"):
            mixer[leaf] = {"kernel": 4.0 * mixer[leaf]["kernel"]}
        mixer["q_norm"] = {"scale": 3.0 * mixer["q_norm"]["scale"]}
        out[name] = dict(layer, attention=mixer)
    return out


ATTENTION_FAULTS = (
    "qk_norm_left_out", "one_scale_a_head", "rope_in_interleaved_pairs",
    "rope_left_out", "score_scale_128", "the_wrong_key_head")
FAULTS = ("taps_in_reverse_order", "conv_reads_t_plus_1",
          "the_two_gates_exchanged", "silu_after_the_convolution",
          ) + ATTENTION_FAULTS + (
    "softmax_scores_for_sigmoid", "gates_not_renormalised",
    "an_untied_head")


def faulty_loss(fault, config, code, monkeypatch):
    """``(params, tokens) -> loss`` of the product with ``fault`` made in
    it, by setting an attribute of ``models.lfm2`` (as the chip's probes
    do) or by handing the model other weights."""
    real_conv, real_rope, real_norm = lfm2.short_conv, lfm2.rope, lfm2.RMSNorm
    real_experts, real_attention = (lfm2.SparseExperts,
                                    lfm2.dense_window_attention)
    real_logits = lfm2.tied_logits
    loss = code.loss_fn(config, {})

    def per_head_norm(change):
        """``q_norm`` and ``k_norm`` through ``change(norm)``."""
        monkeypatch.setattr(
            lfm2, "RMSNorm", lambda eps, name: real_norm(eps, name=name)
            if name not in ("q_norm", "k_norm")
            else change(real_norm(eps, name=name)))

    if fault == "taps_in_reverse_order":
        monkeypatch.setattr(lfm2, "short_conv",
                            lambda x, w: real_conv(x, w[:, ::-1]))
    elif fault == "conv_reads_t_plus_1":
        monkeypatch.setattr(
            lfm2, "short_conv", lambda x, w: real_conv(
                jnp.pad(x[:, 1:], ((0, 0), (0, 1), (0, 0))), w))
    elif fault == "the_two_gates_exchanged":
        def exchanged(params, tokens):
            """``C`` where ``B`` is: the projection's first two thirds
            exchanged (the third, ``x``, could change places with ``B``
            and nothing would show: their product commutes)."""
            params = dict(params)
            for name, layer in params.items():
                if isinstance(layer, dict) and "conv" in layer:
                    b, c, x = jnp.split(
                        layer["conv"]["in_proj"]["kernel"], 3, axis=1)
                    params[name] = dict(layer, conv=dict(
                        layer["conv"], in_proj={
                            "kernel": jnp.concatenate([c, b, x], 1)}))
            return loss(params, tokens)
        return exchanged
    elif fault == "silu_after_the_convolution":
        monkeypatch.setattr(lfm2, "short_conv",
                            lambda x, w: jax.nn.silu(real_conv(x, w)))
    elif fault == "qk_norm_left_out":
        per_head_norm(lambda norm: lambda x: x.astype(jnp.float32))
    elif fault == "one_scale_a_head":
        # the shared scale times a factor a head, as a model with
        # [heads, 64] scales would have after a step
        per_head_norm(lambda norm: lambda x: norm(x) * (
            1.0 + 0.1 * jnp.arange(x.shape[2], dtype=jnp.float32))[:, None])
    elif fault == "rope_in_interleaved_pairs":
        def interleaved(x, theta):
            lanes = np.arange(x.shape[-1])
            order = np.concatenate([lanes[0::2], lanes[1::2]])
            return real_rope(x[..., order], theta)[..., np.argsort(order)]
        monkeypatch.setattr(lfm2, "rope", interleaved)
    elif fault == "rope_left_out":
        monkeypatch.setattr(lfm2, "rope", lambda x, theta: x)
    elif fault == "score_scale_128":
        monkeypatch.setattr(
            lfm2, "dense_window_attention",
            lambda q, k, v, dtype: real_attention(
                q * 2 ** -0.5, k, v, dtype))  # (2 D)^-1/2 for D^-1/2
    elif fault == "the_wrong_key_head":
        def attend(q, k, v, dtype):
            """Query head ``j`` reads key head ``j % kv`` for ``j //
            group``."""
            heads, kv = q.shape[2], k.shape[2]
            order = np.array([j for m in range(kv) for j in range(heads)
                              if j % kv == m])
            return real_attention(q[:, :, order], k, v, dtype)[
                :, :, np.argsort(order)]
        monkeypatch.setattr(lfm2, "dense_window_attention", attend)
    elif fault == "an_untied_head":
        # the head's gradient goes to a leaf of its own and not to the
        # embedding's: the loss is the same number, the leaf's gradient not
        monkeypatch.setattr(
            lfm2, "tied_logits", lambda cfg, x, embedding: real_logits(
                cfg, x, jax.lax.stop_gradient(embedding)))
    elif not fault.startswith("none"):
        change = {
            "softmax_scores_for_sigmoid": dict(scores="softmax",
                                               selection_bias=None),
            "gates_not_renormalised": dict(gates_over_picks=False)}[fault]
        monkeypatch.setattr(
            lfm2, "SparseExperts",
            lambda cfg, **kw: real_experts(cfg, **{**kw, **change}))
    return loss


TWO_LAYERS = {"num_hidden_layers": 2,
              "layer_types": ["conv", "full_attention"],
              "training": {"attention": "dense"}}


@pytest.fixture(scope="module")
def references(bench):
    """``{sharper: (params, tokens, loss, gradients)}`` of the reference on
    the faults' two layers (a conv mixer under a dense feed-forward, the
    attention under experts), computed once."""
    _, reference = files(bench)
    config = toy(bench, **TWO_LAYERS)
    found = {}
    for weights in (None, sharper):
        params, tokens = seeded(bench, config, weights=weights)
        with jax.default_matmul_precision("highest"):
            found[weights is sharper] = (params, tokens) + jax.jit(
                jax.value_and_grad(partial(reference.loss, config)))(
                    params, tokens)
    return found


@pytest.mark.parametrize("fault", FAULTS + ("none", "none_sharper"))
def test_each_hand_made_fault_leaves_the_reference(bench, references, fault,
                                                   monkeypatch):
    """The faults the configuration's file lists, each made in the product:
    the loss leaves the reference's by far more than float32 rounding (the
    untied head's does not and cannot: there it is the embedding's
    gradient). The attention's are read with larger query and key weights
    (an untrained softmax is near uniform and sees little); without a fault
    the product is the reference with either weights. The fourteenth, 1e-20
    for 1e-6, is parts in ten million of a gate and has a case of its own
    below. On the chip, at seed weights and published widths, some of them
    read inside a seed's rounding: the configuration's file says which limit
    sees which."""
    code, _ = files(bench)
    config = toy(bench, **TWO_LAYERS)
    params, tokens, ref_loss, ref_grads = references[
        fault in ATTENTION_FAULTS + ("none_sharper",)]
    faulty = faulty_loss(fault, config, code, monkeypatch)
    with jax.default_matmul_precision("highest"):
        if fault.startswith("none") or fault == "an_untied_head":
            loss, grads = jax.jit(jax.value_and_grad(faulty))(params, tokens)
        else:  # the loss alone tells it: half the compile
            loss = jax.jit(faulty)(params, tokens)
    off = abs(float(loss) - float(ref_loss)) / float(ref_loss)
    if fault.startswith("none"):
        assert off < 1e-5
        assert_same_gradients(grads, ref_grads)
    elif fault == "an_untied_head":
        assert off < 1e-5
        got, want = grads["embedding"], ref_grads["embedding"]
        assert float(jnp.linalg.norm(got - want)) > 0.1 * float(
            jnp.linalg.norm(want))
    else:
        assert off > 3e-5, (fault, off)


def test_the_epsilon_beside_the_picked_sum_is_the_sources():
    """1e-6 and not 1e-20: with scores near zero the two differ, and the
    layer's are the source's; the default is what every other mixture
    here has."""
    tokens = jnp.ones((4, 8), jnp.float32)
    logits = jnp.full((4, 8), -14.0).at[:, :2].set(-13.0)  # s ~ 2e-6, 8e-7
    gates = {eps: moe.route_to_capacity(
        tokens, logits, 8, 4, top_k=2, scores="sigmoid",
        gates_over_picks=True, **({} if eps is None else {"gate_eps": eps}))[4]
        for eps in (None, 1e-20, 1e-6)}
    np.testing.assert_array_equal(gates[None], gates[1e-20])
    np.testing.assert_allclose(gates[1e-20].sum(-1), 1.0, rtol=1e-6)
    score = float(jax.nn.sigmoid(-13.0))
    np.testing.assert_allclose(
        gates[1e-6], score / (2 * score + 1e-6), rtol=1e-5)
    assert float(gates[1e-6].sum(-1)[0]) < 0.9
    assert lfm2.GATE_EPS == 1e-6


def test_the_config_is_the_sources_pattern_and_refuses_what_is_not():
    published = lfm2.LFM2_24B_A2B
    kinds = published.kinds
    assert len(kinds) == 40 and kinds.count("full_attention") == 10
    assert [i for i, kind in enumerate(kinds) if kind == "full_attention"] \
        == list(range(2, 40, 4))
    assert (published.head_dim, published.top_k, published.expert_layers) \
        == (64, 4, 38)
    assert published.capacity(8192) == 640
    with pytest.raises(ValueError, match="layer_types must name"):
        dataclasses.replace(lfm2.LFM2_TINY, layer_types=("conv", "mamba"))
    with pytest.raises(ValueError, match="cannot share"):
        dataclasses.replace(lfm2.LFM2_TINY, num_key_value_heads=3)


def test_parameters_at_the_published_sizes():
    """From the config's keys: a conv mixer 16,783,360, the attention mixer
    10,485,888, an expert 9,437,184, the dense feed-forward 72,351,744; the
    cell's cut 469,284,992; the whole model 23.84 B."""
    cut = dataclasses.replace(
        lfm2.LFM2_24B_A2B, num_layers=5, num_dense_layers=1,
        layer_types=("conv", "full_attention", "conv", "conv", "conv"),
        vocab_size=8192, experts_here=8)
    ids = jnp.zeros((1, 128), jnp.int32)
    shapes = jax.eval_shape(
        lambda key: lfm2.Lfm2(cut).init(key, ids)["params"],
        jax.random.PRNGKey(0))

    def count(tree):
        return sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))

    conv = shapes["layer_0"]["conv"]
    assert count(conv) == 16783360
    assert conv["in_proj"]["kernel"].shape == (2048, 6144)
    assert conv["conv"].shape == (2048, 3)
    attention = shapes["layer_1"]["attention"]
    assert count(attention) == 10485888
    assert attention["key"]["kernel"].shape == (2048, 512)
    assert attention["q_norm"]["scale"].shape == (64,)
    assert attention["k_norm"]["scale"].shape == (64,)
    assert count(shapes["layer_0"]["mlp"]) == 72351744
    moe_leaves = shapes["layer_4"]["moe"]
    assert moe_leaves["router"].shape == (2048, 64)
    assert moe_leaves["experts_up"].shape == (8, 2048, 1536)
    assert count(moe_leaves) == 131072 + 8 * 9437184
    assert shapes["embedding"].shape == (8192, 2048)
    assert "lm_head" not in shapes
    assert count(shapes) == 469284992
    whole = (2 * (16783360 + 72351744) + 28 * 16783360 + 10 * 10485888
             + 38 * (131072 + 64 * 9437184) + 40 * 2 * 2048
             + 65536 * 2048 + 2048)
    assert round(whole / 1e9, 2) == 23.84


def test_the_scope_is_in_a_lowered_step_and_the_widths_in_its_trace(bench):
    from traced import pallas_grids, shapes

    config = toy(bench)
    code, _ = files(bench)
    params = jax.eval_shape(partial(code.init_params, config, {}),
                            jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 33), jnp.int32)
    traced = jax.jit(jax.grad(code.loss_fn(config, {}))).trace(
        params, tokens)
    text = traced.lower().as_text(debug_info=True)
    for scope in ("hvd.shortconv.mix", "hvd.attn.fwd", "hvd.attn.bwd",
                  "hvd.moe.route", "hvd.moe.dispatch", "hvd.moe.experts",
                  "hvd.moe.combine", "hvd.block.ffn", "hvd.block.attn_proj",
                  "hvd.block.norm", "hvd.block.embed", "hvd.block.head"):
        assert scope in text, scope
    # the gates and the taps inside the layer's mixer block, the two
    # projections outside the phase
    assert "hvd.block.attn_proj/conv/hvd.shortconv.mix" in text
    assert "hvd.shortconv.mix/in_proj" not in text
    assert "hvd.shortconv.mix/out_proj" not in text
    assert "layer_1/hvd.block.attn_proj/attention/hvd.shortconv" not in text
    # three taps over the hidden size's 64 channels
    assert params["layer_0"]["conv"]["conv"].shape == (64, 3)
    # four query heads a key/value head: the dk/dv grid's axis of its own
    assert [grid[2] for grid in pallas_grids(traced.jaxpr)
            if len(grid) == 4] == [4]
    # 4 experts here x the 10 slots ``capacity`` plans a sequence of 32 (3
    # the 8 tokens ``init_params`` traces: the 12 slots the gauge once held)
    built = code.model_config(config)
    assert (built.experts_held, built.top_k) == (4, 2)
    assert (built.capacity(32), built.capacity(8)) == (10, 3)
    assert (2, 4, 10, 64) in shapes(traced.jaxpr)


def test_routing_stats_read_the_expert_layers(bench):
    config = toy(bench, training={"attention": "dense"})
    code, _ = files(bench)
    params, tokens = seeded(bench, config)
    stats = jax.jit(partial(experts.routing_stats, code.model(config)))(
        params, tokens[:, :-1])
    assert stats["load"].shape == (4, 4)   # four expert layers of five
    assert 0 < int(stats["load"].sum()) <= 4 * 2 * 32 * 2


@pytest.mark.parametrize("over_picks", [False, True])
def test_the_default_epsilon_lowers_to_the_text_it_had(over_picks):
    """The argument's default is the 1e-20 that was written in: the same
    text (``tests/test_moe_sigmoid_routing.py`` pins its hash at the
    parents), and another epsilon another text only where the gates are
    renormalised."""
    tokens = jax.ShapeDtypeStruct((16, 8), jnp.float32)
    logits = jax.ShapeDtypeStruct((16, 8), jnp.float32)
    how = dict(top_k=2, first_expert=2, experts_here=4, scores="sigmoid",
               gate_scale=2.5, gates_over_picks=over_picks)

    def text(**eps):
        return jax.jit(lambda t, x: moe.route_to_capacity(
            t, x, 8, 5, **how, **eps)).lower(tokens, logits).as_text()

    assert text() == text(gate_eps=1e-20)
    assert (text(gate_eps=1e-6) != text()) == over_picks
