"""``models/sdar.py`` against the plain float32 reference
(``benchmark/reference/sdar.py``) on seeded weights at a toy size: the
block-diffusion loss and every gradient with the dense fallback and with
the interpreted flash kernels, with and without recomputation (the same
arithmetic), the share test (the windows of the experts add up to the uncut
reference layer), what makes a noisy batch, and ``rope`` with position
ids."""

import dataclasses
import importlib.util
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import experts, parts, sdar, smallthinker

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_sdar",
        os.path.join(ROOT, "benchmark", "reference", "sdar.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = load_reference()
TINY = dataclasses.replace(sdar.SDAR_TINY, dtype=jnp.float32)
SEQ = 64  # clean tokens a row: 128 stream positions, 16 blocks of 4


def reference_config(cfg: sdar.SdarConfig) -> dict:
    """The keys the reference reads, as a configuration file has them."""
    return {
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "head_dim": cfg.head_dim,
        "block_length": cfg.block_length,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.rms_norm_eps,
        "num_experts": cfg.num_experts,
        "num_experts_per_tok": cfg.top_k,
        "first_expert": cfg.first_expert,
        "experts_here": cfg.experts_held,
        "capacity_factor": cfg.capacity_factor,
    }


@pytest.fixture(scope="module")
def batch():
    clean = jax.random.randint(jax.random.PRNGKey(0), (2, SEQ), 0,
                               TINY.mask_id)
    return sdar.noisy_batch(jax.random.PRNGKey(7), clean, TINY.block_length,
                            TINY.mask_id)


@pytest.fixture(scope="module")
def params(batch):
    return sdar.Sdar(TINY).init(jax.random.PRNGKey(1), batch["noisy"],
                                batch["clean"])["params"]


ATTENTION = {
    "dense": None,
    "flash": partial(sdar.flash_attention_fn, interpret=True, block=16),
}


def loss_and_grads(cfg, attention, params, batch):
    model = sdar.Sdar(cfg, attention_fn=ATTENTION[attention])
    return jax.jit(jax.value_and_grad(
        partial(sdar.block_diffusion_loss, model)))(params, batch)


def reference_loss_and_grads(cfg, params, batch):
    return jax.jit(jax.value_and_grad(partial(
        reference.loss, reference_config(cfg))))(params, batch)


class TestAgainstTheReference:
    @pytest.mark.parametrize("remat", [True, False],
                             ids=["remat", "no-remat"])
    @pytest.mark.parametrize("attention", sorted(ATTENTION))
    def test_loss_and_every_gradient(self, attention, remat, params, batch):
        cfg = dataclasses.replace(TINY, remat=remat)
        loss, grads = loss_and_grads(cfg, attention, params, batch)
        want_loss, want = reference_loss_and_grads(cfg, params, batch)
        np.testing.assert_allclose(loss, want_loss, rtol=2e-6)
        flat, _ = jax.tree_util.tree_flatten_with_path(grads)
        assert len(flat) == 3 + 12 * TINY.num_layers
        for (path, got), ref in zip(flat, jax.tree.leaves(want)):
            np.testing.assert_allclose(
                got, ref, rtol=3e-4, atol=3e-6,
                err_msg=jax.tree_util.keystr(path))

    @pytest.mark.parametrize("attention", sorted(ATTENTION))
    def test_remat_changes_no_arithmetic(self, attention, params, batch):
        """Bit for bit with the dense fallback. With the kernels the
        recomputed forward holds the own block's sums of four terms, which
        XLA's CPU fusion may contract in another order than the first
        forward: the same arithmetic to float32 round-off."""
        with_remat = loss_and_grads(TINY, attention, params, batch)
        without = loss_and_grads(dataclasses.replace(TINY, remat=False),
                                 attention, params, batch)
        for a, b in zip(jax.tree.leaves(with_remat),
                        jax.tree.leaves(without)):
            if attention == "dense":
                np.testing.assert_array_equal(a, b)
            else:
                np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-7)

    def test_the_recomputed_layer_keeps_the_kernels_results(self, params,
                                                            batch):
        """Two forward kernels a layer (the clean stream's and the noisy
        stream's clean past) in the whole differentiated program, each
        with its dq and dkv kernel: the backward pass runs no forward
        kernel again."""
        from test_smallthinker import count_primitive

        def count(remat):
            model = sdar.Sdar(dataclasses.replace(TINY, remat=remat),
                              attention_fn=ATTENTION["flash"])
            jaxpr = jax.make_jaxpr(jax.grad(partial(
                sdar.block_diffusion_loss, model)))(params, batch)
            return count_primitive(jaxpr.jaxpr, "pallas_call")

        assert count(True) == count(False) == 6 * TINY.num_layers

    def test_the_policy_is_the_one_smallthinker_shares(self, params, batch):
        """``models/parts.py``'s: what the ``jax.checkpoint`` equations of
        either model's forward pass carry."""
        def policies(model, variables, *inputs):
            jaxpr = jax.make_jaxpr(model.apply)(variables, *inputs)
            return {eqn.params["policy"] for eqn in jaxpr.eqns
                    if "policy" in eqn.params}

        small = smallthinker.SmallThinker(smallthinker.SMALLTHINKER_TINY)
        ids = jnp.zeros((1, 32), jnp.int32)
        assert policies(
            sdar.Sdar(TINY), {"params": params}, batch["noisy"],
            batch["clean"]) == policies(
            small, jax.eval_shape(small.init, jax.random.PRNGKey(0), ids),
            ids) == {parts.save_kernels_and_projections}

    @pytest.mark.parametrize("change", [
        dict(block_length=8), dict(block_length=2), dict(top_k=3),
        dict(rope_theta=1e4)], ids=str)
    def test_a_model_choice_shows(self, change, params, batch):
        """The comparison above can fail: each of these is a different
        function."""
        base, _ = loss_and_grads(TINY, "dense", params, batch)
        other, _ = loss_and_grads(dataclasses.replace(TINY, **change),
                                  "dense", params, batch)
        assert abs(float(other) - float(base)) > 1e-5

    def test_the_head_reads_the_noisy_half_only(self, params, batch):
        logits = sdar.Sdar(TINY).apply({"params": params}, batch["noisy"],
                                       batch["clean"])
        assert logits.shape == (2, SEQ, TINY.vocab_size)
        assert logits.dtype == jnp.float32


class TestTheMaskAsTheModelSeesIt:
    """Information flows where the three predicates let it and nowhere
    else, through the whole model (with room for every routed pair: a
    full expert would let one position's pick push another's out)."""

    def logits(self, params, noisy, clean):
        roomy = dataclasses.replace(TINY, capacity_factor=8.0)
        return sdar.Sdar(roomy, attention_fn=ATTENTION["flash"]).apply(
            {"params": params}, noisy, clean)

    def test_a_noisy_token_moves_its_own_block_alone(self, params, batch):
        noisy = batch["noisy"]
        other = noisy.at[:, 21].set((noisy[:, 21] + 1) % TINY.mask_id)
        moved = np.abs(np.asarray(
            self.logits(params, noisy, batch["clean"])
            - self.logits(params, other, batch["clean"]))).max(-1)
        block = np.zeros(SEQ, bool)
        block[20:24] = True
        assert (moved[:, block] > 1e-6).all()
        assert (moved[:, ~block] == 0).all()

    def test_a_clean_token_moves_the_later_blocks_alone(self, params,
                                                        batch):
        clean = batch["clean"]
        other = clean.at[:, 21].set((clean[:, 21] + 1) % TINY.mask_id)
        moved = np.abs(np.asarray(
            self.logits(params, batch["noisy"], clean)
            - self.logits(params, batch["noisy"], other))).max(-1)
        assert (moved[:, :24] == 0).all()  # its own block and before
        assert (moved[:, 24:] > 1e-7).all()

    def test_the_dense_mask_leaves_s_times_s_plus_b_pairs(self):
        for block_length, seq in ((4, 64), (8, 64), (2, 16), (4, 8192 // 64)):
            seen = np.asarray(sdar.visible(block_length, seq))
            assert seen.sum() == seq * (seq + block_length)
            assert not seen[seq:, :seq].any()  # no clean query, noisy key
            assert seen[:seq, :seq].sum() == seq * block_length
        np.testing.assert_array_equal(
            np.asarray(sdar.visible(4, 16)),
            np.asarray(reference.seen(
                4, *(jnp.concatenate([jnp.arange(16)] * 2),
                     jnp.arange(32) < 16) * 2)))


class TestTheShareOfTheExperts:
    def layer(self, cfg, params, x, index=1):
        positions = jnp.concatenate([jnp.arange(SEQ)] * 2)
        return sdar.DecoderLayer(cfg).apply(
            {"params": params[f"layer_{index}"]}, x, positions)

    def test_eight_shares_add_up_to_the_uncut_reference_layer(self, params):
        """Attention (what every chip computes alike) counted once: the sum
        of the eight shares' outputs less seven residuals-after-attention
        is the whole layer of the reference."""
        x = jax.random.normal(jax.random.PRNGKey(5),
                              (2, 2 * SEQ, TINY.hidden_size))
        whole_cfg = reference_config(TINY)
        positions = jnp.concatenate([jnp.arange(SEQ)] * 2)
        noisy = jnp.arange(2 * SEQ) < SEQ
        p = params["layer_1"]
        want = reference.layer(whole_cfg, x, p, positions, noisy)
        after_attention = x + reference.attention(
            whole_cfg, reference.rms_norm(x, p["ln_attn"], 1e-6),
            p["attention"], positions, noisy)
        total = jnp.zeros_like(x)
        for first in range(8):
            share = dataclasses.replace(TINY, first_expert=first,
                                        experts_here=1)
            cut = experts.take_expert_window(params, share)
            assert cut["layer_1"]["moe"]["experts_up"].shape[0] == 1
            assert cut["layer_1"]["moe"]["router"].shape == (64, 8)
            total = total + self.layer(share, cut, x)
        np.testing.assert_allclose(total - 7 * after_attention, want,
                                   rtol=1e-4, atol=1e-5)
        # and the product's own uncut layer is the reference's
        np.testing.assert_allclose(self.layer(TINY, params, x), want,
                                   rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("first", [0, 2, 6])
    def test_a_share_is_the_reference_given_the_same_share(self, first,
                                                           params, batch):
        share = dataclasses.replace(TINY, first_expert=first,
                                    experts_here=2)
        cut = experts.take_expert_window(params, share)
        loss, grads = loss_and_grads(share, "dense", cut, batch)
        want_loss, want = reference_loss_and_grads(share, cut, batch)
        np.testing.assert_allclose(loss, want_loss, rtol=2e-6)
        for got, ref in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
            np.testing.assert_allclose(got, ref, rtol=3e-4, atol=3e-6)

    def test_capacity_drops_what_the_reference_drops(self, params, batch):
        tight = dataclasses.replace(TINY, capacity_factor=0.5)
        loss, _ = loss_and_grads(tight, "dense", params, batch)
        want = reference.loss(reference_config(tight), params, batch)
        np.testing.assert_allclose(loss, want, rtol=2e-6)
        stats = jax.jit(partial(experts.routing_stats, sdar.Sdar(tight)))(
            params, batch["noisy"], batch["clean"])
        assert stats["load"].shape == (2, 8)
        assert int(stats["dropped"].sum()) > 0
        # the model's own factor leaves room for every pair of these rows
        roomy = jax.jit(partial(experts.routing_stats, sdar.Sdar(
            dataclasses.replace(TINY, capacity_factor=8.0))))(
                params, batch["noisy"], batch["clean"])
        assert int(roomy["dropped"].sum()) == 0
        # both halves of the stream are routed: 2 rows x 2S positions x 2
        assert int(roomy["load"].sum()) == 2 * 2 * 2 * SEQ * 2


class TestTheNoisyBatch:
    def test_what_a_batch_holds(self, batch):
        clean, noisy, weight = batch["clean"], batch["noisy"], batch["weight"]
        masked = np.asarray(noisy == TINY.mask_id)
        assert (np.asarray(clean) != TINY.mask_id).all()
        np.testing.assert_array_equal(np.asarray(noisy)[~masked],
                                      np.asarray(clean)[~masked])
        weight = np.asarray(weight)
        assert (weight[~masked] == 0).all()
        assert (weight[masked] >= 1.0).all() and (
            weight[masked] <= TINY.block_length).all()
        # one noise level a block: its masked positions share a weight
        blocks = weight.reshape(2, SEQ // 4, 4)
        for row in blocks.reshape(-1, 4):
            assert len(set(row[row > 0])) <= 1

    def test_the_share_masked_is_the_schedules_and_the_weights_say_it(self):
        clean = jax.random.randint(jax.random.PRNGKey(2), (4, 4096), 0, 255)
        made = jax.jit(partial(sdar.noisy_batch, block_length=4,
                               mask_id=255))(jax.random.PRNGKey(3), clean)
        share = float((made["noisy"] == 255).mean())
        assert share == pytest.approx(0.625, abs=0.02)  # (1/4 + 1) / 2
        # a masked position, and no other, is scored
        assert float((made["weight"] > 0).mean()) == share
        # E[m / t] = 1: the weights average one over all positions
        assert float(made["weight"].mean()) == pytest.approx(1.0, abs=0.03)

    def test_no_block_is_under_the_least_noise(self):
        """``1 / block_length``, no option: blocks of one position are
        all mask, at weight one."""
        clean = jnp.zeros((2, 1024), jnp.int32)
        made = sdar.noisy_batch(jax.random.PRNGKey(4), clean, 1, 9)
        assert (np.asarray(made["noisy"]) == 9).all()
        assert (np.asarray(made["weight"]) == 1.0).all()

    def test_whole_blocks_only(self):
        with pytest.raises(ValueError, match="no whole blocks"):
            sdar.noisy_batch(jax.random.PRNGKey(0),
                             jnp.zeros((1, 10), jnp.int32), 4, 9)

    def test_the_weight_is_part_of_the_loss(self, params, batch):
        base, _ = loss_and_grads(TINY, "dense", params, batch)
        flat = dict(batch, weight=(batch["weight"] > 0).astype(jnp.float32))
        other, _ = loss_and_grads(TINY, "dense", params, flat)
        assert abs(float(other) - float(base)) > 1e-3


class TestTheLossIsTheOldExpression:
    def test_on_the_models_own_logits(self, params, batch):
        """``models/loss.py``'s rule against the ``log_softmax``, the pick
        and the weights that ``block_diffusion_loss`` was until PR 39."""
        model = sdar.Sdar(TINY)
        logits = model.apply({"params": params}, batch["noisy"],
                             batch["clean"])
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, batch["clean"][..., None], -1)
        old = -(batch["weight"] * picked[..., 0]).mean()
        new = sdar.block_diffusion_loss(model, params, batch)
        assert abs(float(new) - float(old)) <= 1e-6 * abs(float(old))


class TestRopeWithPositionIds:
    def x(self):
        return jax.random.normal(jax.random.PRNGKey(6), (2, 32, 3, 16))

    @pytest.mark.parametrize("shape", ["[S]", "[B, S]"])
    def test_arange_gives_the_bits_of_no_positions(self, shape):
        positions = jnp.arange(32)
        if shape == "[B, S]":
            positions = jnp.tile(positions, (2, 1))
        np.testing.assert_array_equal(
            parts.rope(self.x(), 1e6), parts.rope(self.x(), 1e6, positions))

    def test_a_caller_without_them_lowers_to_the_same_text(self):
        """``None`` is ``arange`` before anything is traced: the older
        decoders' steps hold the instructions they held."""
        def old(x, theta):
            half = x.shape[-1] // 2
            inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
            angle = jnp.arange(x.shape[1],
                               dtype=jnp.float32)[:, None] * inv_freq
            cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
            x1, x2 = x[..., :half], x[..., half:]
            return jnp.concatenate(
                [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

        assert str(jax.make_jaxpr(partial(parts.rope, theta=1e4))(
            self.x())) == str(jax.make_jaxpr(partial(old, theta=1e4))(
                self.x()))

    def test_repeated_positions_rotate_both_halves_alike(self):
        x = self.x()
        both = jnp.concatenate([x, x], axis=1)
        positions = jnp.concatenate([jnp.arange(32)] * 2)
        out = parts.rope(both, 1e6, positions)
        np.testing.assert_array_equal(out[:, :32], out[:, 32:])
        np.testing.assert_array_equal(out[:, :32], parts.rope(x, 1e6))


class TestTheStreamsAreCutBeforeTheProjections:
    """``TwoStreamAttention`` projects, norms and rotates each stream apart
    and hands its ``attention_fn`` the two of them (PR 40): q, k, v and
    the context never exist at both streams' length."""

    def test_what_the_adapter_is_handed_and_returns(self, params, batch):
        seen = []

        def watched(noisy, clean, dtype, block_length):
            seen.append([tuple(x.shape for x in stream)
                         for stream in (noisy, clean)])
            outs = sdar.flash_attention_fn(
                noisy, clean, dtype, block_length, interpret=True, block=16)
            assert [out.shape for out in outs] == [noisy[0].shape] * 2
            return outs

        cfg = dataclasses.replace(TINY, remat=False)
        sdar.block_diffusion_loss(sdar.Sdar(cfg, attention_fn=watched),
                                  params, batch)
        rows, seq = batch["clean"].shape
        stream = ((rows, seq, cfg.num_heads, cfg.head_dim),) + (
            (rows, seq, cfg.num_kv_heads, cfg.head_dim),) * 2
        assert seen == [[stream, stream]] * cfg.num_layers

    def test_the_dense_fallback_takes_and_returns_the_streams_too(self):
        keys = jax.random.split(jax.random.PRNGKey(14), 6)
        noisy, clean = (tuple(
            jax.random.normal(key, (2, 16, heads, 8))
            for key, heads in zip(three, (4, 2, 2)))
            for three in (keys[:3], keys[3:]))
        dense = sdar.dense_block_diffusion_attention(noisy, clean,
                                                     jnp.float32, 4)
        flash = sdar.flash_attention_fn(noisy, clean, jnp.float32, 4,
                                        interpret=True, block=8)
        for a, b in zip(dense, flash):
            assert a.shape == b.shape == noisy[0].shape
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


class TestConfig:
    def test_the_published_model(self):
        cfg = sdar.SDAR_30B_A3B
        assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads,
                cfg.num_kv_heads, cfg.head_dim, cfg.intermediate_size,
                cfg.num_experts, cfg.top_k, cfg.vocab_size) == (
                    48, 2048, 32, 4, 128, 768, 128, 8, 151936)
        assert (cfg.block_length, cfg.rope_theta, cfg.rms_norm_eps) == (
            4, 1e6, 1e-6)
        # a row of 8,192 clean tokens is 16,384 routed positions
        assert cfg.capacity(2 * 8192) == 1280 and cfg.experts_held == 128
        assert cfg.mask_id == 151935

    def test_heads_must_share_evenly(self):
        with pytest.raises(ValueError, match="cannot share"):
            dataclasses.replace(TINY, num_kv_heads=3)

    def test_the_qk_norm_is_a_heads(self, params):
        attention = params["layer_0"]["attention"]
        assert attention["q_norm"]["scale"].shape == (TINY.head_dim,)
        assert attention["k_norm"]["scale"].shape == (TINY.head_dim,)
        assert attention["key"]["kernel"].shape == (
            TINY.hidden_size, TINY.num_kv_heads * TINY.head_dim)

    def test_a_traced_step_computes_the_plans_slots(self, params, batch):
        """A routing group is a row of both streams: 8 experts x the 64
        slots ``capacity`` plans, the dispatch buffer of the trace."""
        from traced import shapes

        assert (TINY.experts_held, TINY.capacity(2 * SEQ), TINY.top_k) == (
            8, 64, 2)
        assert (2, 8, 64, TINY.hidden_size) in shapes(
            partial(sdar.block_diffusion_loss, sdar.Sdar(TINY)), params,
            batch)

    def test_the_package_exports_the_model(self):
        from horovod_tpu import models

        assert models.Sdar is sdar.Sdar
        assert models.SDAR_30B_A3B is sdar.SDAR_30B_A3B
        assert models.block_diffusion_loss is sdar.block_diffusion_loss
