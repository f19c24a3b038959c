"""``models/smallthinker.py`` against the plain float32 reference
(``benchmark/reference/smallthinker.py``) on seeded weights at a toy size:
loss and gradients with the dense fallback and with the interpreted flash
kernels, with and without recomputation (bit for bit the same), the share
test (four windows of the experts add up to the uncut reference layer), and
the two model choices ``parallel/moe.py`` takes (activation, gates over the
picks) beside what OLMoE's call computes."""

import dataclasses
import importlib.util
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import experts, parts
from horovod_tpu.models import smallthinker as st
from horovod_tpu.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_smallthinker",
        os.path.join(ROOT, "benchmark", "reference", "smallthinker.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = load_reference()
TINY = dataclasses.replace(st.SMALLTHINKER_TINY, dtype=jnp.float32)
SEQ = 64


def reference_config(cfg: st.SmallThinkerConfig) -> dict:
    """The keys the reference reads, as a configuration file has them."""
    return {
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim,
        "sliding_window_size": cfg.window,
        "sliding_window_layout": [int(flag) for flag in cfg.windowed],
        "rope_layout": [int(flag) for flag in cfg.rotary],
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.rms_norm_eps,
        "moe_num_primary_experts": cfg.num_experts,
        "moe_num_active_primary_experts": cfg.top_k,
        "first_expert": cfg.first_expert,
        "experts_here": cfg.experts_held,
        "capacity_factor": cfg.capacity_factor,
    }


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(0), (2, SEQ + 1), 0,
                              TINY.vocab_size)


@pytest.fixture(scope="module")
def params(tokens):
    return st.SmallThinker(TINY).init(jax.random.PRNGKey(1),
                                      tokens[:, :-1])["params"]


def count_primitive(jaxpr, name: str) -> int:
    """Equations of primitive ``name`` in ``jaxpr`` and everything it calls
    (a printed jaxpr shows a shared sub-jaxpr once)."""
    found = 0
    for eqn in jaxpr.eqns:
        found += eqn.primitive.name == name
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else (
                    value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    found += count_primitive(inner, name)
    return found


ATTENTION = {
    "dense": None,
    "flash": partial(st.flash_attention_fn, interpret=True, block=16),
}


def loss_and_grads(cfg, attention, params, tokens):
    model = st.SmallThinker(cfg, attention_fn=ATTENTION[attention])
    return jax.jit(jax.value_and_grad(
        partial(st.causal_lm_loss, model)))(params, tokens)


class TestAgainstTheReference:
    @pytest.mark.parametrize("remat", [True, False],
                             ids=["remat", "no-remat"])
    @pytest.mark.parametrize("attention", sorted(ATTENTION))
    def test_loss_and_every_gradient(self, attention, remat, params,
                                     tokens):
        cfg = dataclasses.replace(TINY, remat=remat)
        loss, grads = loss_and_grads(cfg, attention, params, tokens)
        want_loss, want = jax.jit(jax.value_and_grad(partial(
            reference.loss, reference_config(cfg))))(params, tokens)
        np.testing.assert_allclose(loss, want_loss, rtol=2e-6)
        flat, _ = jax.tree_util.tree_flatten_with_path(grads)
        assert len(flat) == 43
        for (path, got), ref in zip(flat, jax.tree.leaves(want)):
            np.testing.assert_allclose(
                got, ref, rtol=2e-4, atol=2e-6,
                err_msg=jax.tree_util.keystr(path))

    @pytest.mark.parametrize("attention", sorted(ATTENTION))
    def test_remat_changes_no_bit(self, attention, params, tokens):
        with_remat = loss_and_grads(TINY, attention, params, tokens)
        without = loss_and_grads(dataclasses.replace(TINY, remat=False),
                                 attention, params, tokens)
        for a, b in zip(jax.tree.leaves(with_remat),
                        jax.tree.leaves(without)):
            np.testing.assert_array_equal(a, b)

    def test_remat_and_no_remat_share_one_parameter_tree(self, tokens):
        trees = [jax.eval_shape(
            st.SmallThinker(dataclasses.replace(TINY, remat=remat)).init,
            jax.random.PRNGKey(1), tokens[:, :-1])["params"]
            for remat in (True, False)]
        assert jax.tree.structure(trees[0]) == jax.tree.structure(trees[1])
        assert jax.tree.leaves(trees[0]) == jax.tree.leaves(trees[1])

    def test_the_recomputed_layer_keeps_the_kernels_results(self, params,
                                                            tokens):
        """One forward kernel a layer in the whole differentiated program:
        the backward pass does not run it again."""
        def count(remat):
            model = st.SmallThinker(dataclasses.replace(TINY, remat=remat),
                                    attention_fn=ATTENTION["flash"])
            jaxpr = jax.make_jaxpr(jax.grad(partial(
                st.causal_lm_loss, model)))(params, tokens)
            return count_primitive(jaxpr.jaxpr, "pallas_call")

        assert count(True) == count(False) == 3 * TINY.num_layers

    def test_a_model_choice_shows(self, params, tokens):
        """The comparison above can fail: each of these is a different
        function."""
        base, _ = loss_and_grads(TINY, "dense", params, tokens)
        for change in (dict(window=23), dict(rope_layout=(1, 1, 1, 1)),
                       dict(sliding_window_layout=(1, 1, 1, 1)),
                       dict(top_k=2)):
            other, _ = loss_and_grads(dataclasses.replace(TINY, **change),
                                      "dense", params, tokens)
            assert abs(float(other) - float(base)) > 1e-5, change


class TestTheLossIsTheOldExpression:
    def test_on_the_models_own_logits(self, params, tokens):
        """``models/loss.py``'s rule against the ``log_softmax`` and the
        pick that ``causal_lm_loss`` was until PR 39."""
        model = st.SmallThinker(TINY)
        logits = model.apply({"params": params}, tokens[:, :-1])
        logp = jax.nn.log_softmax(logits, axis=-1)
        old = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1).mean()
        new = st.causal_lm_loss(model, params, tokens)
        assert abs(float(new) - float(old)) <= 1e-6 * abs(float(old))


class TestTheShareOfTheExperts:
    def layer(self, cfg, params, x, index=1):
        module = st.DecoderLayer(cfg, cfg.windowed[index],
                                 cfg.rotary[index])
        return module.apply({"params": params[f"layer_{index}"]}, x)

    def test_four_shares_add_up_to_the_uncut_reference_layer(self, params):
        """Attention (what every chip computes alike) counted once: the sum
        of the shares' outputs less three residuals-after-attention is the
        whole layer of the reference."""
        x = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, 56))
        whole_cfg = reference_config(TINY)
        p = params["layer_1"]
        want = reference.layer(whole_cfg, x, p, 1)
        after_attention = x + reference.attention(
            whole_cfg, reference.rms_norm(x, p["ln_attn"], 1e-6),
            p["attention"], 1)
        total = jnp.zeros_like(x)
        for first in range(0, 8, 2):
            share = dataclasses.replace(TINY, first_expert=first,
                                        experts_here=2)
            cut = experts.take_expert_window(params, share)
            assert cut["layer_1"]["moe"]["experts_up"].shape[0] == 2
            total = total + self.layer(share, cut, x)
        np.testing.assert_allclose(total - 3 * after_attention, want,
                                   rtol=1e-4, atol=1e-5)
        # and the product's own uncut layer is the reference's
        np.testing.assert_allclose(self.layer(TINY, params, x), want,
                                   rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("first", [0, 2, 6])
    def test_a_share_is_the_reference_given_the_same_share(self, first,
                                                           params, tokens):
        share = dataclasses.replace(TINY, first_expert=first,
                                    experts_here=2)
        cut = experts.take_expert_window(params, share)
        loss, grads = loss_and_grads(share, "dense", cut, tokens)
        want_loss, want = jax.jit(jax.value_and_grad(partial(
            reference.loss, reference_config(share))))(cut, tokens)
        np.testing.assert_allclose(loss, want_loss, rtol=2e-6)
        for got, ref in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
            np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-6)

    def test_capacity_drops_what_the_reference_drops(self, params, tokens):
        tight = dataclasses.replace(TINY, capacity_factor=0.5)
        loss, _ = loss_and_grads(tight, "dense", params, tokens)
        want = reference.loss(reference_config(tight), params, tokens)
        np.testing.assert_allclose(loss, want, rtol=2e-6)
        stats = jax.jit(partial(
            experts.routing_stats, st.SmallThinker(tight)))(
            params, tokens[:, :-1])
        assert stats["load"].shape == (4, 8)
        assert int(stats["dropped"].sum()) > 0
        roomy = jax.jit(partial(
            experts.routing_stats, st.SmallThinker(TINY)))(
            params, tokens[:, :-1])
        assert int(roomy["dropped"].sum()) == 0
        assert int(roomy["load"].sum()) == 4 * 2 * SEQ * 3


class TestRopeWhereTheProjectionWrote:
    """``rope_tokens_major``: ``rope`` of ``[B, S, heads * D]``, a head at
    a time on the lanes that hold it (PR 40), the half turn a product with
    a permutation matrix."""

    HEADS, DIM = 3, 16

    def x(self, dtype):
        return jax.random.normal(jax.random.PRNGKey(8),
                                 (2, 40, self.HEADS * self.DIM),
                                 jnp.float32).astype(dtype)

    def apart(self, x):
        return x.reshape(x.shape[:2] + (self.HEADS, self.DIM))

    @pytest.mark.parametrize("positions", [None, "[S]", "[B, S]"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["float32", "bfloat16"])
    def test_it_is_rope_of_the_heads_apart(self, dtype, positions):
        x = self.x(dtype)
        if positions is not None:
            per_row = positions == "[B, S]"
            positions = jnp.arange(40)[::-1] * 3
            if per_row:
                positions = jnp.stack([positions, positions[::-1]])
        got = parts.rope_tokens_major(x, self.HEADS, 1e4, jnp.float32,
                                   positions)
        want = parts.rope(self.apart(x), 1e4, positions).reshape(x.shape)
        assert got.dtype == want.dtype == jnp.float32
        # the same products and sums; which of them the compiler contracts
        # to one rounding is its own choice
        np.testing.assert_allclose(got, want, rtol=0, atol=5e-7)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                             ids=["float32", "bfloat16"])
    def test_and_so_is_its_gradient(self, dtype):
        x = self.x(dtype)
        weight = jax.random.normal(jax.random.PRNGKey(9), x.shape)

        def here(x):
            return (parts.rope_tokens_major(x, self.HEADS, 1e4, dtype).astype(
                jnp.float32) * weight).sum()

        def there(x):
            return (parts.rope(self.apart(x), 1e4).astype(dtype).reshape(
                x.shape).astype(jnp.float32) * weight).sum()

        got, want = jax.grad(here)(x), jax.grad(there)(x)
        assert got.dtype == want.dtype == dtype
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=0, atol=1e-6 if dtype == jnp.float32 else 2 ** -7)

    def test_the_rounding_is_a_heads_own(self):
        """``dtype`` is applied inside the function a head goes through:
        no operation of the traced program holds all heads in float32."""
        x = self.x(jnp.bfloat16)

        def pulled_back(x, cotangent):
            out, vjp = jax.vjp(lambda x: parts.rope_tokens_major(
                x, self.HEADS, 1e4, jnp.bfloat16), x)
            return out, vjp(cotangent)

        jaxpr = jax.make_jaxpr(pulled_back)(x, x)
        whole = [str(var.aval) for eqn in jaxpr.jaxpr.eqns
                 for var in eqn.outvars
                 if getattr(var.aval, "shape", ()) == x.shape
                 and var.aval.dtype == jnp.float32]
        assert not whole, whole


class TestConfig:
    def test_the_published_model(self):
        cfg = st.SMALLTHINKER_21B_A3B
        assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads,
                cfg.num_kv_heads, cfg.head_dim) == (52, 2560, 28, 4, 128)
        assert cfg.windowed == cfg.rotary == (False, True, True, True) * 13
        assert cfg.capacity(16384) == 1920 and cfg.experts_held == 64

    def test_layouts_must_cover_the_layers(self):
        with pytest.raises(ValueError, match="3 entries for 4 layers"):
            dataclasses.replace(TINY, rope_layout=(0, 1, 1))

    def test_heads_must_share_evenly(self):
        with pytest.raises(ValueError, match="cannot share"):
            dataclasses.replace(TINY, num_kv_heads=4)

    def test_a_traced_step_computes_the_plans_slots(self, params, tokens):
        """8 experts x the 64 slots ``capacity`` plans a sequence: the
        dispatch buffer of the trace."""
        from traced import shapes

        rows, seq = tokens.shape[0], tokens.shape[1] - 1
        assert (TINY.experts_held, TINY.capacity(seq), TINY.top_k) == (
            8, 64, 3)
        assert (rows, 8, 64, TINY.hidden_size) in shapes(
            partial(st.causal_lm_loss, st.SmallThinker(TINY)), params,
            tokens)


class TestWhatTheModelAsksOfMoe:
    """``route_to_capacity`` and ``gated_expert_ffn`` take the model's
    choices; without them they compute what they always did."""

    def logits(self):
        return jax.random.normal(jax.random.PRNGKey(3), (16, 8))

    def test_gates_over_the_picks_add_up_to_one(self):
        tokens = jnp.ones((16, 4))
        *_, gate, _ = moe.route_to_capacity(
            tokens, self.logits(), 8, 16, top_k=3, first_expert=2,
            experts_here=2, gates_over_picks=True)
        np.testing.assert_allclose(gate.sum(-1), 1.0, rtol=1e-6)
        picked, _ = jax.lax.top_k(self.logits(), 3)
        np.testing.assert_allclose(gate, jax.nn.softmax(picked, -1),
                                   rtol=1e-6)

    def test_the_default_gates_are_the_softmax_over_all_experts(self):
        tokens = jnp.ones((16, 4))
        _, expert, _, _, gate, _ = moe.route_to_capacity(
            tokens, self.logits(), 8, 16, top_k=3)
        want = jnp.take_along_axis(jax.nn.softmax(self.logits(), -1),
                                   expert, 1)
        np.testing.assert_array_equal(gate, want)
        assert float(gate.sum(-1).max()) < 1.0

    @pytest.mark.parametrize("name", ["silu", "relu", "gelu"])
    def test_the_activation_is_the_callers(self, name):
        keys = jax.random.split(jax.random.PRNGKey(4), 4)
        w_gate, w_up = (jax.random.normal(k, (2, 4, 6)) for k in keys[:2])
        w_down = jax.random.normal(keys[2], (2, 6, 4))
        x = jax.random.normal(keys[3], (2, 5, 4))
        activation = getattr(jax.nn, name)
        got = moe.gated_expert_ffn(w_gate, w_up, w_down, x,
                                   activation=activation)
        want = jnp.einsum(
            "ech,ehd->ecd",
            activation(jnp.einsum("ecd,edh->ech", x, w_gate))
            * jnp.einsum("ecd,edh->ech", x, w_up), w_down)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        if name == "silu":
            np.testing.assert_array_equal(
                got, moe.gated_expert_ffn(w_gate, w_up, w_down, x))
