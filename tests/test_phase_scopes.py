"""The compiled step's phase scopes: every builder's step carries
``hvd.wire`` (where there is a wire), ``hvd.optimizer`` and, around the
flash kernels, ``hvd.attn.fwd`` / ``hvd.attn.bwd`` in its metadata, and
they are metadata only: the optimized HLO is the same program with the
scope helper patched to nothing."""

from __future__ import annotations

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu import attribution, profiler


@pytest.fixture()
def world():
    """``form(n)`` re-forms the world on the first ``n`` devices; the
    suite's eight-device world is put back afterwards."""
    import horovod_tpu as hvd

    def form(n):
        hvd.shutdown()
        hvd.init(devices=jax.devices()[:n])
        assert hvd.size() == n
        return hvd

    yield form
    hvd.shutdown()
    hvd.init()


def loss_fn(params, batch):
    x, y = batch
    hidden = jnp.tanh(x @ params["w1"] + params["b1"])
    return jnp.mean((hidden @ params["w2"] - y) ** 2)


PARAMS = {"w1": np.ones((4, 8), np.float32), "b1": np.zeros((8,), np.float32),
          "w2": np.ones((8, 1), np.float32)}


def build(hvd, mode, overlapped=False):
    """``(step, arguments)`` of the toy model through the factory in
    ``mode``, bf16 on the wire."""
    dp = hvd.data_parallel
    optimizer = hvd.DistributedOptimizer(
        optax.adamw(1e-3), compression=hvd.Compression.bf16, sync_mode=mode)
    factory = (dp.make_overlapped_train_step if overlapped
               else dp.make_train_step)
    step = factory(loss_fn, optimizer)
    opt_state = optimizer.init(PARAMS)
    if mode == "allreduce":
        opt_state, params = dp.replicate(opt_state), dp.replicate(PARAMS)
    elif mode == "sharded":
        opt_state, params = hvd.shard_state(opt_state), dp.replicate(PARAMS)
    else:
        opt_state = hvd.shard_state(opt_state)
        params = hvd.shard_state(hvd.shard_params(PARAMS))
    rows = 2 * hvd.size()
    batch = dp.shard_batch((np.ones((rows, 4), np.float32),
                            np.zeros((rows, 1), np.float32)))
    return step, (params, opt_state, batch)


def compiled_text(step, arguments) -> str:
    return step.lower(*arguments).compile().as_text()


TABLES = re.compile(r"^(FileNames|FunctionNames|FileLocations|StackFrames)$"
                    r"|^\d+ ")


def without_metadata(text: str) -> str:
    """The program without what names it: no ``metadata={...}``, none of
    the debug tables at the head of the text."""
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    return "\n".join(line for line in text.splitlines()
                     if not TABLES.match(line))


def scope_paths(text: str) -> set:
    """The ``hvd.`` components of every instruction's name stack, joined."""
    return {"/".join(part for part in scope.split("/")
                     if part.startswith(attribution.SCOPE_PREFIX))
            for scope in profiler.instruction_scopes(text).values()}


@pytest.mark.parametrize("size", [1, 4])
@pytest.mark.parametrize("mode", ["allreduce", "sharded", "fsdp"])
def test_scopes_are_there_and_are_metadata_only(world, monkeypatch, mode,
                                                size):
    hvd = world(size)
    text = compiled_text(*build(hvd, mode))
    paths = scope_paths(text)
    assert "hvd.optimizer" in paths
    wired = any(path.startswith("hvd.wire") or "/hvd.wire" in path
                for path in paths)
    # One member: the allreduce wire is short-circuited away; the sharded
    # modes keep their (one-member) collectives.
    assert wired == (size > 1 or mode != "allreduce"), paths
    if size > 1 and mode == "allreduce":
        assert any(re.fullmatch(r"hvd\.wire/hvd\.allreduce\.bucket0\.\d+B",
                                path) for path in paths), paths
        assert "hvd.wire/hvd.wire.unpack" in paths
    monkeypatch.setattr(profiler, "annotate_collective",
                        lambda name: contextlib.nullcontext())
    bare = compiled_text(*build(hvd, mode))
    assert "hvd." not in bare
    assert without_metadata(bare) == without_metadata(text)


def test_the_overlapped_steps_wire_is_found_under_transpose(world):
    hvd = world(4)
    scopes = profiler.instruction_scopes(
        compiled_text(*build(hvd, "allreduce", overlapped=True)))
    wire = [scope for scope in scopes.values()
            if profiler.phase_of(scope) == "hvd.wire"]
    assert wire and any("transpose(" in scope for scope in wire)
    assert any(profiler.phase_of(scope) == "hvd.optimizer"
               for scope in scopes.values())


def test_a_bare_optimizer_is_all_update(world):
    hvd = world(4)
    dp = hvd.data_parallel
    optimizer = optax.sgd(0.1)
    step = dp.make_train_step(loss_fn, optimizer)
    arguments = (dp.replicate(PARAMS), dp.replicate(optimizer.init(PARAMS)),
                 dp.shard_batch((np.ones((8, 4), np.float32),
                                 np.zeros((8, 1), np.float32))))
    assert scope_paths(compiled_text(step, arguments)) >= {"hvd.optimizer"}


def test_attention_kernels_are_told_apart_by_scope(monkeypatch):
    from horovod_tpu.ops.attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v, interpret=True).astype(
            jnp.float32).sum()

    q = jnp.ones((1, 2, 128, 64), jnp.float32)
    lowered = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q)
    text = lowered.compile().as_text()
    phases = {profiler.phase_of(scope)
              for scope in profiler.instruction_scopes(text).values()}
    assert {"hvd.attn.fwd", "hvd.attn.bwd"} <= phases
    # The kernel's one name sits under the scope that tells which it is.
    located = lowered.as_text(debug_info=True)
    assert "hvd.attn.fwd/flash_attention" in located
    assert "hvd.attn.bwd/flash_attention" in located
    monkeypatch.setattr(profiler, "annotate_collective",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    bare = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q).compile().as_text()
    jax.clear_caches()
    assert "hvd.attn" not in bare
    assert without_metadata(bare) == without_metadata(text)


def test_a_text_without_a_phase_scope_is_refused():
    text = ('  %psum.1 = f32[8]{0} all-reduce(%x), metadata={op_name='
            '"jit(spmd_step)/shard_map/hvd.allreduce.bucket0.32B/psum"}\n')
    with pytest.raises(ValueError, match="no phase scope") as refused:
        profiler.instruction_scopes(text)
    assert "persistent compilation cache" in str(refused.value)
    scoped = text.replace("shard_map/", "shard_map/hvd.wire/")
    assert profiler.instruction_scopes(scoped) == {
        "psum.1": "jit(spmd_step)/shard_map/hvd.wire/"
                  "hvd.allreduce.bucket0.32B/psum"}


@pytest.mark.parametrize("scope, phase", [
    ("jit(spmd_step)/shard_map/hvd.wire/hvd.wire.unpack/slice", "hvd.wire"),
    ("jit(s)/transpose(jvp(f))/hvd.overlap.segment0/hvd.wire/psum",
     "hvd.wire"),
    ("jit(s)/hvd.optimizer/mul", "hvd.optimizer"),
    ("jit(s)/jvp(Bert)/attention/jit(flash_attention)/hvd.attn.fwd/"
     "flash_attention/pallas_call", "hvd.attn.fwd"),
    ("jit(s)/jvp(Bert)/layer_0/dot_general", None),
    ("jit(s)/hvd.optimizer_like/mul", None),
    # a transformation wraps the outermost name of what it transforms
    ("jit(s)/jvp(Olmoe)/layer_0/moe/vmap(hvd.moe.route)/dot_general",
     "hvd.moe.route"),
    ("jit(s)/transpose(jvp(hvd.moe.experts))/dot_general", "hvd.moe.experts"),
    ("jit(s)/transpose(jvp(Olmoe))/layer_0/moe/vmap(hvd.moe.combine)/"
     "scatter-add", "hvd.moe.combine"),
    ("jit(s)/vmap(hvd.moe.routes)/add", None),
])
def test_phase_of_takes_the_innermost_phase_scope(scope, phase):
    assert profiler.phase_of(scope) == phase
