"""Compiled for a TPU v5e that is not there (the installed libtpu compiles
for a described topology): what only the chip's compiler decides about the
attention kernels, checked at no chip time.

XLA names a custom call's instruction after the innermost component of its
name stack, a device trace names the event after the instruction, and the
benchmark finds the kernels by that name (``attn_kernel_ms.json``'s
``kernel_names``). So the name is part of the yardstick: a scope or a
``name=`` put around a ``pallas_call`` must not change it, and which kernel
is a forward and which a backward one is told by the scope one level up.

The topology is described inside a fixture, never at import (one process
at a time may load the TPU's library; see the on-chip-measurement guide),
and this is the only test file that does so.
"""

from __future__ import annotations

import json
import math
import os
import re

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topology = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:1x1",
            chips_per_host_bounds=(1, 1, 1))
    except Exception as e:  # noqa: BLE001 — no libtpu here, or it is taken
        pytest.skip(f"no v5e:1x1 topology can be described here: {e}")
    return SingleDeviceSharding(topology.devices[0])


def kernel_instructions(text: str) -> list:
    """``(instruction name, op_name)`` of every Mosaic custom call."""
    found = []
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = re.match(r"\s*(?:ROOT )?%?(\S+) = ", line).group(1)
        found.append((name, re.search(r'op_name="([^"]*)"', line).group(1)))
    return found


def kernel_bodies(lowered_text: str) -> list:
    """The Mosaic module of every ``tpu_custom_call`` of a lowered
    (StableHLO) text, as MLIR text: what the kernel and its index maps
    were traced to, before the chip's compiler has it."""
    import base64

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    bodies = []
    for config in re.findall(r'backend_config = "((?:[^"\\]|\\.)*)"',
                             lowered_text):
        body = json.loads(config.replace("\\22", '"'))[
            "custom_call_config"]["body"]
        context = mlir.make_ir_context()
        # serialised in the ``stable_mosaic`` dialect, which nothing registers
        context.allow_unregistered_dialects = True
        with context:
            module = ir.Module.parse(base64.b64decode(body))
            bodies.append(module.operation.get_asm(enable_debug_info=False))
    return bodies


def kernel_grids(bodies: list) -> list:
    """Each Mosaic body's grid, as its text has it: ``"32, 16, 16"``."""
    return [re.search(r"iteration_bounds = array<i64: ([^>]*)>", body)
            .group(1) for body in bodies]


def kernel_operands(lowered_text: str) -> list:
    """The type of the first operand of every ``tpu_custom_call`` of a
    lowered (StableHLO) text, in the text's order (layers of one shape
    share their text): the layout a kernel was fed in (``1x2048x512xbf16``
    tokens-major, ``4x2048x128xbf16`` head-major)."""
    return re.findall(
        r"stablehlo\.custom_call @tpu_custom_call\(.*\} : "
        r"\(tensor<([^>]*)>", lowered_text)


CLAMP = re.compile(r"arith\.(minsi|maxsi)")


@pytest.mark.parametrize("seq, kernels", [(512, 2), (1024, 3)])
def test_the_flash_kernels_keep_the_name_the_benchmark_finds_them_by(
        one_chip, seq, kernels):
    import jax
    import jax.numpy as jnp

    from horovod_tpu import profiler
    from horovod_tpu.ops.attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    shape = jax.ShapeDtypeStruct((2, 4, seq, 64), jnp.bfloat16,
                                 sharding=one_chip)
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        shape, shape, shape).compile().as_text()
    found = kernel_instructions(text)
    # One tile: a forward and a fused backward kernel; more: dq and dk/dv.
    assert len(found) == kernels
    with open(os.path.join(REPO_ROOT, "benchmark", "layer_metrics",
                           "attn_kernel_ms.json")) as f:
        wanted = re.compile(json.load(f)["kernel_names"])
    assert all(wanted.search(name) for name, _ in found), found
    phases = [profiler.phase_of(scope) for _, scope in found]
    assert phases.count("hvd.attn.fwd") == 1
    assert phases.count("hvd.attn.bwd") == kernels - 1


def test_berts_s128_kernels_take_a_group_of_slices_a_grid_step(one_chip):
    """96 rows of 128 in 16 heads of 64: 1,536 single-tile slices, which
    the forward and the fused backward kernel take ``G`` a grid step. The
    grid is ``BH // G`` with ``G > 1`` as ``_group_size`` plans, the blocks
    hold ``G`` slices, the body is the ungrouped one's two and five products
    (batched, no loop: its size does not grow with ``G``), and the
    compiled step still holds one kernel of each under the name the
    benchmark finds them by."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu import profiler
    from horovod_tpu.ops import attention
    from horovod_tpu.ops.attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    bh = 96 * 16
    shape = jax.ShapeDtypeStruct((96, 16, 128, 64), jnp.bfloat16,
                                 sharding=one_chip)
    lowered = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        shape, shape, shape)
    groups = [attention._group_size(bh, 128, 128, 64, 2, **counts)
              for counts in (attention._FWD_SLICE, attention._BWD_SLICE)]
    bodies = kernel_bodies(lowered.as_text())
    assert len(bodies) == 2
    for group, body, products in zip(groups, bodies, (2, 5)):
        assert group > 1 and bh % group == 0
        assert f"iteration_bounds = array<i64: {bh // group}>" in body
        assert f"window_bounds = array<i64: {group}, 128, 64>" in body
        assert f"window_bounds = array<i64: {group}, 1, 128>" in body
        assert "scf.for" not in body
        assert body.count("tpu.matmul") == products
    found = kernel_instructions(lowered.compile().as_text())
    assert len(found) == 2
    with open(os.path.join(REPO_ROOT, "benchmark", "layer_metrics",
                           "attn_kernel_ms.json")) as f:
        wanted = re.compile(json.load(f)["kernel_names"])
    assert all(wanted.search(name) for name, _ in found), found
    phases = [profiler.phase_of(scope) for _, scope in found]
    assert sorted(phases) == ["hvd.attn.bwd", "hvd.attn.fwd"]


def test_olmoes_causal_kernels_compile_at_its_widths(one_chip):
    """One sequence of 4,096 in 16 heads of 128, causal: a grid of 16 x 8
    x 8 tiles of 512 through the multi-tile forward, dq and dkv kernels,
    found by the name the OLMoE cell's readers look for."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu import profiler
    from horovod_tpu.models import olmoe

    def loss(q, k, v):
        out = olmoe.flash_attention_fn(q, k, v, jnp.bfloat16)
        return out.astype(jnp.float32).sum()

    shape = jax.ShapeDtypeStruct((1, 4096, 16, 128), jnp.bfloat16,
                                 sharding=one_chip)
    lowered = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        shape, shape, shape)
    # Each of the three stops (or starts) its inner block index at the
    # last (first) tile the mask leaves anything of, and steps only on
    # such a tile: a third branch beside the first / last inner step's.
    bodies = kernel_bodies(lowered.as_text())
    assert len(bodies) == 3
    assert all(CLAMP.search(body) for body in bodies)
    assert all(body.count("scf.if") == 3 for body in bodies)
    text = lowered.compile().as_text()
    found = kernel_instructions(text)
    assert len(found) == 3
    with open(os.path.join(REPO_ROOT, "benchmark", "layer_metrics",
                           "causal_attn_kernel_ms.json")) as f:
        wanted = re.compile(json.load(f)["kernel_names"])
    assert all(wanted.search(name) for name, _ in found), found
    phases = [profiler.phase_of(scope) for _, scope in found]
    assert sorted(phases) == ["hvd.attn.bwd", "hvd.attn.bwd", "hvd.attn.fwd"]


@pytest.mark.parametrize("window, fwd_grid, dkv_grid", [
    pytest.param(4096, "28, 32, 9", "4, 32, 7, 9", id="window-layer"),
    pytest.param(None, "28, 32, 32", "4, 32, 7, 32", id="full-layer"),
])
def test_smallthinkers_kernels_compile_on_the_grid_they_should(
        one_chip, window, fwd_grid, dkv_grid):
    """One sequence of 16,384 in 28 query heads on 4 key/value heads of
    128, tiles of 512. Under the window of 4,096 the innermost grid
    dimension is the band's 9 tiles (PR 33), in the forward, the dq and
    the dk/dv kernel, each stepping on from its row's (column's) first
    block and stopping its index at the last; the full causal layer keeps
    the whole 32. Three kernels either way, under the name the cell's
    readers find them by, a windowed call's under ``hvd.attn.window``."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu import attribution, profiler
    from horovod_tpu.models import smallthinker
    from horovod_tpu.ops import attention

    def loss(q, k, v):
        out = smallthinker.flash_attention_fn(q, k, v, jnp.bfloat16,
                                              window=window)
        return out.astype(jnp.float32).sum()

    def shaped(heads):
        return jax.ShapeDtypeStruct((1, 16384, heads, 128), jnp.bfloat16,
                                    sharding=one_chip)

    lowered = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        shaped(28), shaped(4), shaped(4))
    # the plan of 32 x 32 tiles of 512: the pairs a slice computes, and the
    # K blocks a q block its grids walk (x 32 q blocks: 288 or 1,024 steps)
    computed, band_kb, band_qb = attention._tile_plan(
        True, 32, 32, 512, 512, 0, 0, window)
    assert (computed, 32 * 32 - computed, 32 * band_kb) == (
        (252, 772, 288) if window else (528, 496, 1024))
    bodies = kernel_bodies(lowered.as_text())
    assert kernel_grids(bodies) == [fwd_grid, fwd_grid, dkv_grid]
    assert fwd_grid.endswith(f", {band_kb}")
    assert dkv_grid.endswith(f", {band_qb}")
    assert all(CLAMP.search(body) for body in bodies)
    assert all(body.count("scf.if") == 3 for body in bodies)
    found = kernel_instructions(lowered.compile().as_text())
    assert len(found) == 3
    with open(os.path.join(REPO_ROOT, "benchmark", "layer_metrics",
                           "window_attn_kernel_ms.json")) as f:
        wanted = re.compile(json.load(f)["kernel_names"])
    assert all(wanted.search(name) for name, _ in found), found
    assert sorted(profiler.phase_of(scope) for _, scope in found) == [
        "hvd.attn.bwd", "hvd.attn.bwd", "hvd.attn.fwd"]
    assert all((attribution.SCOPE_ATTN_WINDOW in scope) == bool(window)
               for _, scope in found)


def test_a_call_that_is_not_causal_holds_no_clamp_and_no_tile_branch(
        one_chip):
    """The skipping exists only under ``causal``: the multi-tile kernels
    of a bidirectional call keep plain ``(bh, j, 0)`` index maps and their
    two ``pl.when`` (first and last inner step), and still compile."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    shape = jax.ShapeDtypeStruct((1, 16, 4096, 128), jnp.bfloat16,
                                 sharding=one_chip)
    lowered = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        shape, shape, shape)
    bodies = kernel_bodies(lowered.as_text())
    assert len(bodies) == 3
    assert not any(CLAMP.search(body) for body in bodies)
    assert all(body.count("scf.if") == 2 for body in bodies)
    assert len(kernel_instructions(lowered.compile().as_text())) == 3


def test_olmo_hybrids_delta_rule_compiles_at_its_widths(one_chip):
    """One sequence of 4,096 in 15 heads of 96 / 192, chunks of 64: the
    chunk-parallel gated delta rule, forward and backward, as the v5e's
    compiler takes it. The scan stays one loop over the 64 chunks each way
    with the float32 state as its carry, the solve is no
    ``triangular_solve`` but multiply-adds and three products at the
    highest precision (PR 31), and every operation of it carries the
    scope the Olmo Hybrid cell's readers sum. Arguments + temporaries
    were 742,576,128 B with XLA's substitution (PR 30) and may not pass
    that by 1% (680,411,136 now): a rule that kept every level of the
    inverse for the backward pass would."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu import profiler
    from horovod_tpu.ops import linear_attention

    def loss(q, k, v, g, beta):
        out = linear_attention.gated_delta_rule(q, k, v, g, beta, chunk=64)
        return out.astype(jnp.float32).sum()

    def shaped(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    narrow, wide = shaped(1, 4096, 15, 96), shaped(1, 4096, 15, 192)
    gate = shaped(1, 4096, 15, dtype=jnp.float32)
    compiled = jax.jit(jax.grad(loss, argnums=range(5))).lower(
        narrow, narrow, wide, gate, gate).compile()
    text = compiled.as_text()
    assert "triangular_solve" not in text and "triangular-solve" not in text
    planned = compiled.memory_analysis()
    assert (planned.argument_size_in_bytes + planned.temp_size_in_bytes
            <= 1.01 * 742_576_128)
    scopes = profiler.instruction_scopes(text)
    # (the loss's own cast and sum are the only operations outside it)
    assert {profiler.phase_of(scope) for scope in scopes.values()} == {
        "hvd.linattn.scan", None}
    # two loops, the scan forward and the scan backward, each carrying
    # the 64 chunks' operands; the solve has none
    loops = [line for line in text.splitlines() if " while(" in line]
    assert len(loops) == 2, len(loops)
    assert all("[64,1,15," in line and "f32[1,15,96,192]" in line
               for line in loops)
    assert "operand_precision={highest,highest}" in text
    assert "custom_call_target=\"tpu_custom_call\"" not in text


def test_kimis_pair_kernels_compile_at_its_widths(one_chip):
    """One sequence of 8,192 in 32 heads of 128, chunks of 64 in sub-blocks
    of 16: Kimi Delta Attention's rule, forward and backward, as the v5e's
    compiler takes it. The program lowered for the TPU forms the pair terms
    in two Pallas kernels (``pair_terms_kernel`` and its backward) named
    ``kda_pair_terms`` (not ``flash_attention``, by which the benchmark finds
    the attention kernels) under the scope the cell's readers sum, four
    chunks of eight heads a grid step (the planning functions, and the
    grids of the lowered kernels, whose first operands are tokens-major).
    What
    the plain form wrote to HBM is gone: no array of ``k_right``'s shape
    (``[..., 4, 64, 128]``, four times ``k``), no ``sub x sub x d`` cube.
    **And no array is head-major** (PR 53): the four kernels take ``q``,
    ``k``, ``v``, ``gamma`` and give their cotangents as ``[1, 8192,
    4096]``, so nothing of the shape ``[1, 32, 128, 64, 128]`` that
    ``chunks()`` made (and ``dgamma`` came back through) is in the text.
    Arguments + temporaries are 1,748,331,008 B and may not pass that by
    1% (2,358,731,264 with head-major operands; the plain form's program:
    2,687,887,872). The same call lowered for the CPU holds no custom call
    and runs."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu import profiler
    from horovod_tpu.ops import linear_attention

    def loss(q, k, v, g, beta):
        out = linear_attention.kimi_delta_rule(q, k, v, g, beta, chunk=64,
                                               sub=16)
        return out.astype(jnp.float32).sum()

    def shaped(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    grads = jax.jit(jax.grad(loss, argnums=range(5)))
    wide = shaped(1, 8192, 32, 128)
    on_tpu = grads.lower(
        wide, wide, wide, shaped(1, 8192, 32, 128, dtype=jnp.float32),
        shaped(1, 8192, 32, dtype=jnp.float32))
    assert linear_attention._chunks_a_step(8192 // 64) == 4
    assert linear_attention._scan_heads_a_step(wide, wide, 64) == 8
    # 128 chunks in steps of four, 32 heads in steps of eight; the chunk
    # loop a chunk a step
    assert sorted(kernel_grids(kernel_bodies(on_tpu.as_text()))) == [
        "1, 128, 4"] * 2 + ["1, 32, 4"] * 2
    assert set(kernel_operands(on_tpu.as_text())) <= {
        "1x128x64x4096xbf16", "1x8192x4096xbf16"}
    compiled = on_tpu.compile()
    text = compiled.as_text()
    kernels = kernel_instructions(text)
    # since PR 51 the solve and the chunk loop are two kernels of their own
    # beside them (``chunk_scan_kernel``, ``kda_chunk_scan``), under the
    # same scope: no ``while`` is left in the rule
    for kernel in (linear_attention.PAIR_KERNEL_NAME,
                   linear_attention.SCAN_KERNEL_NAME):
        assert len([name for name, _ in kernels
                    if name.startswith(kernel + ".")]) == 2, kernels
    assert len(kernels) == 4 and all(
        "flash_attention" not in name for name, _ in kernels), kernels
    assert " while(" not in text
    scopes = profiler.instruction_scopes(text)
    assert {profiler.phase_of(scopes[name]) for name, _ in kernels} == {
        "hvd.linattn.scan"}
    assert not re.search(r"\[[\d,]*4,64,128\]", text)
    assert not re.search(r"\[[\d,]*16,16,128\]", text)
    assert not re.search(r"\[(1,)?32,128,64,128\]", text)
    planned = compiled.memory_analysis()
    assert (planned.argument_size_in_bytes + planned.temp_size_in_bytes
            <= 1.01 * 1_748_331_008)

    # the CPU's program of the same call: the plain form, and it runs
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    q, k, v = (jax.random.normal(key, (1, 128, 2, 128)) for key in keys[:3])
    q, k, v = (x.astype(jnp.bfloat16) for x in (
        q / 128, k / jnp.linalg.norm(k, axis=-1, keepdims=True), v))
    g = -jax.random.uniform(keys[3], (1, 128, 2, 128))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (1, 128, 2)))
    lowered = grads.lower(q, k, v, g, beta)
    assert "tpu_custom_call" not in lowered.as_text()
    assert re.search(r"4x64x128x", lowered.as_text())  # k_right is there
    assert all(bool(jnp.isfinite(x.astype(jnp.float32)).all())
               for x in lowered.compile()(q, k, v, g, beta))


def test_a_recomputed_layer_forms_kimis_pair_terms_again(one_chip):
    """Two layers of the rule (2,048 tokens in 8 heads of 128) each under
    the decoders' ``jax.checkpoint`` policy, which keeps what a
    ``pallas_call`` returned: it sees the pair terms' primitive and not the
    call the TPU's lowering makes of it, so the two ``[C, C]`` results
    (134 MB a layer at the cell's shape, in a step 1 GiB under the chip's
    memory) are kept by nobody. A layer and pass holds one forward or one
    backward ``kda_pair_terms`` call: forward, recomputed and backward,
    three a layer, where keeping the results would leave two."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import parts
    from horovod_tpu.ops import linear_attention

    def layer(x, w):
        wide = jnp.dot(x, w).reshape(x.shape[:2] + (4, 8, 128))
        q, k, v, g = (wide[:, :, n] for n in range(4))
        beta = jax.nn.sigmoid(g[..., 0].astype(jnp.float32))
        out = linear_attention.kimi_delta_rule(
            q, k, v, -jax.nn.softplus(g.astype(jnp.float32)), beta,
            chunk=64, sub=16)
        return x + out.reshape(x.shape[:2] + (-1,))[..., :x.shape[-1]]

    def loss(x, first, second):
        recomputed = jax.checkpoint(
            layer, policy=parts.save_kernels_and_projections)
        out = recomputed(recomputed(x, first), second)
        return (out.astype(jnp.float32) ** 2).sum()

    def shaped(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)

    text = jax.jit(jax.grad(loss, argnums=(1, 2))).lower(
        shaped(1, 2048, 1024), shaped(1024, 4096),
        shaped(1024, 4096)).compile().as_text()
    kernels = kernel_instructions(text)
    # the chunk loop's kernels (PR 51) beside the pair terms', alike
    for kernel in (linear_attention.PAIR_KERNEL_NAME,
                   linear_attention.SCAN_KERNEL_NAME):
        passes = [("recomputed" if "rematted_computation" in op_name else
                   "backward" if "transpose(" in op_name else "forward")
                  for name, op_name in kernels
                  if name.startswith(kernel + ".")]
        assert sorted(passes) == sorted(
            ["forward", "recomputed", "backward"] * 2), kernels
    assert len(kernels) == 12, kernels


def test_kimis_delta_mixer_moves_nothing_as_large_as_its_queries(one_chip):
    """One Kimi Delta Attention mixer at the cell's widths (8,192 tokens of
    2,304 into 32 heads of 128), value and every gradient, as the v5e's
    compiler takes it: from the projections to the output's gate no
    ``copy``, ``transpose`` or ``reshape`` instruction moves an array as
    large as the queries (33.5 M elements). The four kernels read and write
    ``[1, 8192, 4096]`` (PR 53), and the norms a head in front of them and
    behind them (``l2norm_of_heads``, ``HeadsRMSNorm``) sum a head's lanes
    and spread the factor back over them as products: a reduction over
    part of the lanes made the compiler lay ``[S, H * d]`` out a head a row
    and back, a dozen such passes a layer (16 ms of the cell's step)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import kimi_linear

    mixer = kimi_linear.KimiDeltaAttention(kimi_linear.KIMI_LINEAR_48B_A3B)
    x = jax.ShapeDtypeStruct((1, 8192, 2304), jnp.bfloat16,
                             sharding=one_chip)
    params = jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                          sharding=one_chip),
        jax.eval_shape(mixer.init, jax.random.PRNGKey(0), x)["params"])

    def loss(params, x):
        out = mixer.apply({"params": params}, x)
        return (out.astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    assert len(kernel_instructions(text)) == 4
    moved = []
    for line in text.splitlines():
        at = re.match(r"\s*(?:ROOT )?%?(\S+) = \w+\[([\d,]*)\]\S* "
                      r"(copy|transpose|reshape)\(", line)
        if at and math.prod(int(n) for n in at.group(2).split(",")
                            if n) >= 8192 * 32 * 128:
            moved.append((at.group(1), at.group(2)))
    assert not moved, moved


@pytest.mark.parametrize("path", ["one_pass", "plain"])
def test_joyais_latent_layer_reaches_the_kernels_in_one_pass(one_chip, path):
    """One latent layer at JoyAI Flash's published widths (8,192 tokens, 32
    heads of 128 + 64 lanes, values of 128), value and every gradient, as
    the v5e's compiler takes it. **The one pass** (PR 55): beside the three
    ``flash_attention`` calls under ``hvd.attn.mla`` four calls named
    ``mla_rope_heads`` under ``hvd.mla.rope`` (queries and keys with values,
    forward and backward), and between the projections and the kernels no
    ``copy``, ``transpose``, ``reshape`` or ``convert`` of an array as large
    as the queries (50.3 M elements). **The plain turn** (the same layer
    with an adapter that does not say ``head_major``: the test's control,
    and what the parent compiled to): the float32 ``convert`` of ``q`` and
    the copies that re-tile it."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import joyai_flash, latent, parts
    from horovod_tpu.ops import rotary_split

    cfg = joyai_flash.JOYAI_LLM_FLASH
    attend = joyai_flash.flash_attention_fn
    if path == "plain":
        def attend(q, k, v, dtype):
            return joyai_flash.flash_attention_fn(q, k, v, dtype)
    layer = latent.LatentAttention(cfg, attend, q_lora_rank=cfg.q_lora_rank,
                                   rope_theta=cfg.rope_theta)
    x = jax.ShapeDtypeStruct((1, 8192, cfg.hidden_size), cfg.dtype,
                             sharding=one_chip)
    params = jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                          sharding=one_chip),
        jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)["params"])

    def loss(params, x):
        out = layer.apply({"params": params}, x)
        return (out.astype(jnp.float32) ** 2).sum()

    # the two things the layer observes: the shapes fill the tiles either
    # way, the adapter says ``head_major`` or does not
    queries = jax.ShapeDtypeStruct((1, 8192, 32 * 192), cfg.dtype)
    assert rotary_split.tokens_a_step(queries, 32, 128, 64, 128) == 512
    assert parts.takes_head_major(attend) == (path == "one_pass")
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    kernels = kernel_instructions(text)
    flash = [scope for name, scope in kernels
             if re.fullmatch(r"flash_attention(\.\d+)?", name)]
    turned = [scope for name, scope in kernels
              if re.fullmatch(r"mla_rope_heads(\.\d+)?", name)]
    assert len(flash) == 3 and all("hvd.attn.mla" in s for s in flash)
    assert len(flash) + len(turned) == len(kernels)
    moved = []
    for line in text.splitlines():
        at = re.match(r"\s*(?:ROOT )?%?(\S+) = (\w+)\[([\d,]*)\]\S* "
                      r"(copy|transpose|reshape|convert)\(", line)
        if at and math.prod(int(n) for n in at.group(3).split(",")
                            if n) >= 8192 * 32 * 192:
            moved.append((at.group(4), at.group(2), at.group(3)))
    if path == "one_pass":
        assert len(turned) == 4
        assert all("hvd.mla.rope" in s and "hvd.attn" not in s
                   for s in turned)
        assert sum("transpose(" in s for s in turned) == 2  # the backward
        assert not moved, moved
    else:
        assert not turned
        assert [dims for kind, dtype, dims in moved
                if kind == "convert" and dtype == "f32"]
        assert sum(kind == "copy" for kind, _, _ in moved) >= 2


def test_kimi_linears_step_holds_the_chunk_loops_kernels_three_a_layer(
        one_chip):
    """The Kimi Linear cell's train step as ``benchmark/aot.py`` builds it,
    lowered (not compiled) for the described ``v5e:1x1``: every layer is
    recomputed, so each of the four Kimi Delta Attention layers holds the
    chunk loop's kernels three times (forward without residuals,
    recomputed with them, backward) beside the pair terms' three and the
    latent attention layer's three flash kernels: 3 + 12 + 12 custom
    calls, and the chunk loop's twelve walk the 128 chunks of a sequence
    eight of the 32 heads a grid step."""
    import sys

    import horovod_tpu as hvd
    from horovod_tpu.ops import linear_attention

    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    try:
        import lowered_sha
    finally:
        sys.path.remove(os.path.join(REPO_ROOT, "tools"))
    try:
        text = lowered_sha.lowered_text("kimi-linear-48b-a3b_s8192_e8_dp1")
    finally:
        hvd.shutdown()
        hvd.init()
    names = re.findall(r'kernel_name = "([^"]*)"', text)
    assert sorted(set(names)) == sorted([
        "flash_attention", linear_attention.PAIR_KERNEL_NAME,
        linear_attention.SCAN_KERNEL_NAME]), set(names)
    assert {name: names.count(name) for name in set(names)} == {
        "flash_attention": 3, linear_attention.PAIR_KERNEL_NAME: 12,
        linear_attention.SCAN_KERNEL_NAME: 12}
    assert kernel_grids(kernel_bodies(text)).count("1, 128, 4") == 12


@pytest.mark.parametrize("rows, seq, groups", [(24, 512, (2, 1)),
                                               (96, 128, (16, 8))])
def test_a_bert_layer_hands_the_kernels_what_its_projections_wrote(
        one_chip, rows, seq, groups):
    """A BERT-Large layer at the benchmark's two shapes, forward and
    backward: the projections write ``bf16[rows, seq, 1024]`` and the two
    flash kernels take it in blocks of 128 lanes, two heads, of ``G`` rows
    (``_heads_per_block`` 2, ``_group_size`` G), their bodies two and five
    products a head. Nothing of an activation's size is copied or
    transposed anywhere in the compiled layer: not q, k, v, the context or
    their gradients under ``hvd.block.attn_proj`` (8 copies a layer of
    ``bf16[rows, 16, seq, 64]`` until PR 35), nor anything else."""
    import math

    import jax
    import jax.numpy as jnp

    from horovod_tpu import profiler
    from horovod_tpu.models import bert
    from horovod_tpu.ops import attention

    cfg = bert.BERT_LARGE
    layer = bert.TransformerLayer(cfg, bert.flash_attention_fn)
    x = jax.ShapeDtypeStruct((rows, seq, cfg.hidden_size), jnp.bfloat16,
                             sharding=one_chip)
    bias = jax.ShapeDtypeStruct((rows, 1, 1, seq), jnp.float32,
                                sharding=one_chip)
    params = jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                          sharding=one_chip),
        jax.eval_shape(lambda: layer.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, cfg.hidden_size)),
            jnp.zeros((1, 1, 1, 8)), True)))

    def loss(params, x, bias):
        return layer.apply(params, x, bias, True).astype(jnp.float32).sum()

    lowered = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, x, bias)
    assert attention._heads_per_block(64, 16) == 2
    assert tuple(
        attention._group_size(rows, seq, seq, 128, 2, heads=2, **counts)
        for counts in (attention._FWD_SLICE,
                       attention._BWD_FROM_OUT_SLICE)) == groups
    bodies = kernel_bodies(lowered.as_text())
    assert len(bodies) == 2
    for group, body, products in zip(groups, bodies, (2, 5)):
        assert f"iteration_bounds = array<i64: {rows // group}, 8>" in body
        assert f"window_bounds = array<i64: {group}, {seq}, 128>" in body
        assert f"window_bounds = array<i64: {group}, 1, 2, {seq}>" in body
        assert "scf.for" not in body
        assert body.count("tpu.matmul") == 2 * products

    text = lowered.compile().as_text()
    found = kernel_instructions(text)
    assert sorted(profiler.phase_of(scope) for _, scope in found) == [
        "hvd.attn.bwd", "hvd.attn.fwd"]
    assert all("hvd.block.attn_proj" in scope for _, scope in found)
    activation = rows * seq * cfg.hidden_size
    moved = [
        (name, owner.opcode, owner.shape)
        for name, owner in profiler.instruction_owners(text).items()
        if owner.opcode in ("copy", "transpose")
        and math.prod(int(n) for n in re.match(
            r"\w+\[([\d,]*)\]", owner.shape).group(1).split(",")
            if n) >= activation]
    assert not moved, moved


def test_sdars_two_streams_compile_on_two_causal_grids(one_chip):
    """One row of 8,192 clean tokens beside its noisy twin, 32 query heads
    on 4 key/value heads of 128, blocks of 4, tiles of 512: two calls of
    the multi-tile kernels over the clean keys, each on the whole 16 x 16
    grid of an S x S causal call (never the 32 x 32 of the doubled stream),
    six kernels under the name the cell's readers find them by and under
    ``hvd.attn.blockdiff``; the block mask is an integer remainder in the
    kernel, which the chip's compiler takes; and no array of the compiled
    program holds two dimensions of the stream's length."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu import attribution, profiler
    from horovod_tpu.models import sdar
    from horovod_tpu.ops import attention

    def loss(q, k, v):
        streams = ([x[:, rows] for x in (q, k, v)]
                   for rows in (slice(None, 8192), slice(8192, None)))
        out = jnp.concatenate(sdar.flash_attention_fn(
            *streams, jnp.bfloat16, 4), axis=1)
        return out.astype(jnp.float32).sum()

    def shaped(heads):
        return jax.ShapeDtypeStruct((1, 16384, heads, 128), jnp.bfloat16,
                                    sharding=one_chip)

    lowered = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        shaped(32), shaped(4), shaped(4))
    # the two calls' plans together, out of the doubled stream's 32 x 32
    # tiles: 136 + 136 computed, 752 skipped, 2 x 16 x 16 grid steps
    plans = [attention._tile_plan(True, 16, 16, 512, 512, 0, 0,
                                  behind=behind) for behind in (0, 4)]
    computed = sum(pairs for pairs, _, _ in plans)
    assert (computed, 4 * 16 * 16 - computed,
            sum(16 * band_kb for _, band_kb, _ in plans)) == (272, 752, 512)
    bodies = kernel_bodies(lowered.as_text())
    assert sorted(kernel_grids(bodies)) == (
        ["32, 16, 16"] * 4 + ["4, 16, 8, 16"] * 2)
    assert all(CLAMP.search(body) for body in bodies)
    assert all("arith.remsi" in body for body in bodies)
    compiled = lowered.compile().as_text()
    found = kernel_instructions(compiled)
    assert len(found) == 6
    with open(os.path.join(REPO_ROOT, "benchmark", "layer_metrics",
                           "blockdiff_attn_kernel_ms.json")) as f:
        wanted = re.compile(json.load(f)["kernel_names"])
    assert all(wanted.search(name) for name, _ in found), found
    assert sorted(profiler.phase_of(scope) for _, scope in found) == [
        "hvd.attn.bwd"] * 4 + ["hvd.attn.fwd"] * 2
    assert all(attribution.SCOPE_ATTN_BLOCKDIFF in scope
               for _, scope in found)
    square = [shape for shape in re.findall(r"\[([0-9,]+)\]", compiled)
              if sum(int(d) >= 8192 for d in shape.split(",")) >= 2]
    assert not square, sorted(set(square))


def moved_activations(text: str, elements: int) -> list:
    """``copy`` and ``transpose`` instructions of a compiled program whose
    result has ``elements`` elements, under a layer's ``attention``
    module: a query-sized array re-laid out between a projection and a
    kernel."""
    import math

    found = []
    for line in text.splitlines():
        at = re.match(r"\s*(?:ROOT )?%?(\S+) = \w+\[([\d,]*)\]\S* "
                      r"(copy|transpose)\(", line)
        scope = re.search(r'op_name="([^"]*)"', line)
        if (at and scope and "/attention/" in scope.group(1)
                and math.prod(int(n) for n in at.group(2).split(",")
                              if n) == elements):
            found.append((at.group(1), scope.group(1)))
    return found


def test_a_toy_smallthinker_step_moves_no_query_sized_array(one_chip):
    """Four layers (full + NoPE, then three windowed with RoPE) of 4 query
    heads of 128 on 2, one sequence of 2,048 in tiles of 512. **The
    windowed layers'** projections reach their nine kernels as they lie,
    RoPE turns a head at a time on the lanes that hold it, and the compiled
    step holds no ``copy`` or ``transpose`` of a ``[1, 2048, 4 * 128]``
    array under those layers' attention (the parent's held the context's,
    forward and recomputed, and dq's, a layer). **The layer of full
    attention** is fed head-major, as the parent's (its kernels run twice
    as long, and longer still tokens-major: PERF.md, PR 40). Granite's
    narrower heads take the same adapter to the transposing call."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import granite, smallthinker

    cfg = smallthinker.SmallThinkerConfig(
        vocab_size=512, hidden_size=256, num_layers=4, num_heads=4,
        num_kv_heads=2, head_dim=128, intermediate_size=128, num_experts=4,
        top_k=2, capacity_factor=2.0, window=1024)
    model = smallthinker.SmallThinker(
        cfg, attention_fn=smallthinker.flash_attention_fn)
    params = jax.eval_shape(
        lambda: smallthinker.SmallThinker(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])

    def placed(tree):
        return jax.tree.map(lambda leaf: jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=one_chip), tree)

    tokens = jax.ShapeDtypeStruct((1, 2049), jnp.int32, sharding=one_chip)
    lowered = jax.jit(jax.value_and_grad(
        lambda params, tokens: smallthinker.causal_lm_loss(
            model, params, tokens))).lower(placed(params), tokens)
    # the layer of full attention's kernels are fed head-major, the
    # windowed layers' as the projections wrote them
    assert set(kernel_operands(lowered.as_text())) == {
        "1x2048x512xbf16", "4x2048x128xbf16"}
    text = lowered.compile().as_text()
    assert len(kernel_instructions(text)) == 3 * cfg.num_layers
    moved = moved_activations(text, 2048 * 4 * 128)
    assert moved and all("/layer_0/" in scope for _, scope in moved), moved

    def attend(q, k, v):
        return granite.flash_attention_fn(q, k, v, jnp.bfloat16).astype(
            jnp.float32).sum()

    def shaped(heads):
        return jax.ShapeDtypeStruct((1, 2048, heads, 64), jnp.bfloat16,
                                    sharding=one_chip)

    narrow = jax.jit(jax.grad(attend, argnums=(0, 1, 2))).lower(
        shaped(8), shaped(2), shaped(2))
    assert kernel_operands(narrow.as_text()) == ["8x2048x64xbf16"] * 3


def test_a_toy_sdar_step_never_holds_both_streams_heads_in_one_array(
        one_chip):
    """Two layers of 4 query heads of 128 on 2 over a noisy and a clean
    stream of 1,024 each, blocks of 4, tiles of 512. The streams are cut
    before the projections (PR 40), where a row is the hidden size wide,
    and the kernels and the merge take a stream each: six kernels a layer,
    fed head-major (their tiles are contiguous: fed tokens-major they took
    8.7% longer in the cell, PERF.md), and no array of the compiled step
    holds the doubled stream's 2,048 rows at the heads' width (the
    parent's held q, k, v and the context so, and cut and joined them
    around the kernels)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.models import sdar

    cfg = sdar.SdarConfig(
        vocab_size=384, hidden_size=256, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=128, intermediate_size=96, num_experts=4,
        top_k=2, capacity_factor=2.0, block_length=4)
    model = sdar.Sdar(cfg, attention_fn=sdar.flash_attention_fn)
    ids = jnp.zeros((1, 8), jnp.int32)
    params = jax.eval_shape(lambda: sdar.Sdar(cfg).init(
        jax.random.PRNGKey(0), ids, ids)["params"])

    def placed(tree):
        return jax.tree.map(lambda leaf: jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=one_chip), tree)

    def row(dtype):
        return jax.ShapeDtypeStruct((1, 1024), dtype, sharding=one_chip)

    batch = dict(clean=row(jnp.int32), noisy=row(jnp.int32),
                 weight=row(jnp.float32))
    lowered = jax.jit(jax.value_and_grad(
        lambda params, batch: sdar.block_diffusion_loss(
            model, params, batch))).lower(placed(params), batch)
    assert set(kernel_operands(lowered.as_text())) == {"4x1024x128xbf16"}
    text = lowered.compile().as_text()
    assert len(kernel_instructions(text)) == 6 * cfg.num_layers
    doubled = sorted({
        shape for shape in re.findall(r"\w+\[([0-9,]+)\]", text)
        if "2048" in shape.split(",")
        and ({"4", "128"} <= set(shape.split(","))
             or "512" in shape.split(","))})
    assert not doubled, doubled


def test_granites_layers_compile_at_their_widths(one_chip):
    """One sequence of 4,096. The attention layer: 32 query heads on 8
    key/value heads of 64 through the multi-tile causal kernels (every
    other multi-tile cell is D = 128, and BERT's D = 64 is single-tile):
    grids of 32 x 8 x 8 and, for dkv, 8 x 8 x 4 x 8, three kernels under
    the name the readers find them by, the queries scaled by 2^-3 outside
    them. The Mamba-2 scan: 64 heads of 64 with a state of 128 in 16
    chunks of 256, forward and backward, as the v5e's compiler takes it:
    two Pallas kernels named ``ssd_chunk_scan`` (not ``flash_attention``),
    eight heads a grid step (``ssd._heads_a_step``, and the lowered
    kernels' grid of 16 chunks x 8 steps), no loop over the chunks and no ``reduce-window``, every operation under
    the scope the cell's readers sum, and arguments + temporaries under
    0.25 GiB (the plain form's decay matrices alone are 268 MB in float32;
    the kernels' residual, the states that enter the chunks, is 33.5 MB)."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu import profiler
    from horovod_tpu.models import granite
    from horovod_tpu.ops import attention, ssd

    def shaped(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def attend(q, k, v):
        out = granite.flash_attention_fn(
            q * granite.GRANITE_4_0_H_MICRO.query_scale, k, v, jnp.bfloat16)
        return out.astype(jnp.float32).sum()

    lowered = jax.jit(jax.value_and_grad(attend, argnums=(0, 1, 2))).lower(
        shaped(1, 4096, 32, 64), shaped(1, 4096, 8, 64),
        shaped(1, 4096, 8, 64))
    # four query heads read a key/value head: the dk/dv grid's third axis
    assert attention._tiled_shapes(
        shaped(32, 4096, 64), shaped(8, 4096, 64), None)[-1] == 4
    bodies = kernel_bodies(lowered.as_text())
    assert sorted(kernel_grids(bodies)) == [
        "32, 8, 8", "32, 8, 8", "8, 8, 4, 8"]
    found = kernel_instructions(lowered.compile().as_text())
    assert len(found) == 3
    with open(os.path.join(REPO_ROOT, "benchmark", "layer_metrics",
                           "causal_attn_kernel_ms.json")) as f:
        wanted = re.compile(json.load(f)["kernel_names"])
    assert all(wanted.search(name) for name, _ in found), found
    assert sorted(profiler.phase_of(scope) for _, scope in found) == [
        "hvd.attn.bwd", "hvd.attn.bwd", "hvd.attn.fwd"]

    def scan(x, dt, a, b, c, d):
        return ssd.ssd_scan(x, dt, a, b, c, d, chunk=256).astype(
            jnp.float32).sum()

    heads = shaped(64, dtype=jnp.float32)
    x, group = shaped(1, 4096, 64, 64), shaped(1, 4096, 1, 128)
    scanned = jax.jit(jax.grad(scan, argnums=range(6))).lower(
        x, shaped(1, 4096, 64, dtype=jnp.float32), heads, group, group,
        heads)
    assert ssd._heads_a_step(x, group, 256) == 8
    assert kernel_grids(kernel_bodies(scanned.as_text())) == ["1, 16, 8"] * 2
    compiled = scanned.compile()
    text = compiled.as_text()
    kernels = kernel_instructions(text)
    assert len(kernels) == 2 and all(
        name.startswith(ssd.SCAN_KERNEL_NAME + ".")
        and "flash_attention" not in name for name, _ in kernels), kernels
    planned = compiled.memory_analysis()
    assert (planned.argument_size_in_bytes + planned.temp_size_in_bytes
            <= 0.25 * 2 ** 30)
    scopes = profiler.instruction_scopes(text)
    # (the loss's own cast and sum are the only operations outside it)
    assert {profiler.phase_of(scope) for scope in scopes.values()} == {
        "hvd.ssm.scan", None}
    assert " while(" not in text and "reduce-window" not in text


@pytest.mark.parametrize("cell", ["granite", "nemotron_h"])
def test_a_recomputed_mixer_holds_the_scans_kernels_three_a_layer(one_chip,
                                                                  cell):
    """Each cell's Mamba-2 mixer at its published widths (Granite: 4,096
    tokens, one group, chunk 256; Nemotron-H: 8,192 tokens, eight groups,
    chunk 128) under the decoders' ``jax.checkpoint`` policy, as the v5e's
    compiler takes it: the policy keeps what a ``pallas_call`` returned
    and sees the scan's primitive instead, so a layer holds the kernel
    three times, forward (no states written), recomputed (with the states
    that enter the chunks, the backward kernel's residual) and backward,
    all named ``ssd_chunk_scan`` under the scope the cells' readers sum,
    eight heads a grid step; and nothing under that scope is a
    ``reduce-window`` (the running sum is a product) or a loop."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from horovod_tpu import profiler
    from horovod_tpu.models import granite, nemotron_h, parts
    from horovod_tpu.ops import ssd

    module, cfg, seq, chunk = {
        "granite": (granite.Mamba2Mixer, granite.GRANITE_4_0_H_MICRO, 4096,
                    256),
        "nemotron_h": (nemotron_h.Mamba2Mixer,
                       nemotron_h.NEMOTRON_3_NANO_30B_A3B, 8192, 128)}[cell]
    mixer = nn.remat(module, policy=parts.save_kernels_and_projections)(cfg)
    x = jax.ShapeDtypeStruct((1, seq, cfg.hidden_size), jnp.bfloat16,
                             sharding=one_chip)
    params = jax.tree.map(
        lambda leaf: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                          sharding=one_chip),
        jax.eval_shape(mixer.init, jax.random.PRNGKey(0), x))

    def loss(params, x):
        return mixer.apply(params, x).astype(jnp.float32).sum()

    lowered = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        params, x)
    # both cells' 64 heads in steps of eight
    assert kernel_grids(kernel_bodies(lowered.as_text())) == [
        f"1, {seq // chunk}, 8"] * 3
    text = lowered.compile().as_text()
    kernels = kernel_instructions(text)
    assert all(name.startswith(ssd.SCAN_KERNEL_NAME + ".")
               for name, _ in kernels), kernels
    passes = [("recomputed" if "rematted_computation" in op_name else
               "backward" if "transpose(" in op_name else "forward")
              for _, op_name in kernels]
    assert sorted(passes) == ["backward", "forward", "recomputed"], kernels
    scopes = profiler.instruction_scopes(text)
    assert {profiler.phase_of(scopes[name]) for name, _ in kernels} == {
        "hvd.ssm.scan"}
    states = f"f32[1,{seq // chunk},128,4096]"
    written = [line for line in text.splitlines()
               if "tpu_custom_call" in line and f"{states}{{" in
               line.split(" custom-call(")[0]]
    assert len(written) == 1 and "rematted_computation" in written[0]
    under = [line for line in text.splitlines()
             if "hvd.ssm.scan" in line]
    assert under and not any(
        " reduce-window(" in line or " while(" in line for line in under)


@pytest.fixture(scope="module")
def four_chips():
    """The devices of a described ``v5e:2x2`` host."""
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topology = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2",
            chips_per_host_bounds=(2, 2, 1))
    except Exception as e:  # noqa: BLE001 — no libtpu here, or it is taken
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topology.devices


def test_an_fsdp_step_with_large_leaves_compiles_without_a_loop(
        four_chips, monkeypatch):
    """``make_train_step`` under ``fsdp`` on four chips, two feed-forward
    layers at BERT-Large's widths (four matrices over
    ``PACK_CUTOFF_BYTES`` on the bf16 wire, four biases under it). Packed
    whole, as every bucket was until PR 37, a ``(4, R)`` block and the flat
    vector of the collective are tiled differently, and the chip's compiler
    writes the copy between them as a ``while`` over the rows, once a
    direction; with the matrices gathered and scattered as themselves there
    is none."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.ops import fusion

    layers, width, inner = 2, 1024, 4096

    def init():
        return {f"layer{i}": {
            "w1": jnp.zeros((width, inner)), "b1": jnp.zeros((inner,)),
            "w2": jnp.zeros((inner, width)), "b2": jnp.zeros((width,)),
        } for i in range(layers)}

    def loss_fn(params, batch):
        h = batch.astype(jnp.bfloat16)
        for i in range(layers):
            p = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                             params[f"layer{i}"])
            h = h + jnp.tanh(h @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]
        return jnp.mean(h.astype(jnp.float32) ** 2)

    def whiles():
        optimizer = hvd.DistributedOptimizer(
            optax.adamw(1e-4), compression=hvd.Compression.bf16,
            sync_mode="fsdp")
        step = hvd.data_parallel.make_train_step(loss_fn, optimizer)
        sharded = NamedSharding(hvd.global_mesh(),
                                P(hvd.global_axis_name()))

        def placed(tree):
            return jax.tree.map(lambda leaf: jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=sharded), tree)

        params = jax.eval_shape(init)
        text = step.lower(
            placed(jax.eval_shape(hvd.shard_params, params)),
            placed(jax.eval_shape(optimizer.init, params)),
            placed(jax.ShapeDtypeStruct((4 * 256, width), jnp.float32)),
        ).compile().as_text()
        assert " all-gather(" in text
        return len(re.findall(r" while\(", text))

    hvd.shutdown()
    try:
        hvd.init(devices=four_chips)
        assert whiles() == 0
        monkeypatch.setattr(fusion, "PACK_CUTOFF_BYTES", 1 << 40)
        assert whiles() >= 1
    finally:
        hvd.shutdown()
        hvd.init()
