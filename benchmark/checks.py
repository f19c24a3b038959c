"""What decides ``correct``: the plain reference's loss and per-leaf gradient
norms, the product's own, the comparison between them, and what the
compiled step must hold. Plain JAX; nothing of ``horovod_tpu``."""

from __future__ import annotations

import re
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def leaf_norms(tree):
    """The L2 norm of every leaf, in float32, as one vector in the tree's
    own leaf order. Zero padding changes no norm, so a leaf kept in
    another layout (sharded rows) compares with its plain self."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
                      for leaf in jax.tree.leaves(tree)])


def reference_program(loss, devices, block_rows: int):
    """``(params, batch) -> (loss, per-leaf gradient norms)`` of the plain
    float32 ``loss`` over the whole batch, as one program: the batch is cut
    into blocks of ``block_rows``, each device of ``devices`` takes its
    share of the blocks one after another, and losses and gradients are
    averaged over all blocks. Blocks are equal, so that is the batch's
    mean. A block is what the reference sees at once (one chip's batch
    where batch statistics matter)."""
    mesh = Mesh(np.array(devices), ("blocks",))

    def local(params, blocks):
        def one_block(carry, block):
            value, grads = jax.value_and_grad(loss)(params, block)
            return (carry[0] + value,
                    jax.tree.map(jnp.add, carry[1], grads)), None

        zero = (jnp.zeros((), jnp.float32),
                jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params))
        (value, grads), _ = jax.lax.scan(one_block, zero, blocks)
        count = jax.lax.psum(jax.tree.leaves(blocks)[0].shape[0], "blocks")
        value, grads = jax.lax.psum((value, grads), "blocks")
        return value / count, leaf_norms(grads) / count

    spmd = jax.shard_map(local, mesh=mesh, in_specs=(P(), P("blocks")),
                         out_specs=(P(), P()), check_vma=False)

    def program(params, batch):
        blocks = jax.tree.map(
            lambda x: x.reshape((-1, block_rows) + x.shape[1:]), batch)
        with jax.default_matmul_precision("highest"):
            return spmd(params, blocks)

    return jax.jit(program, in_shardings=(
        NamedSharding(mesh, P()), NamedSharding(mesh, P())))


def replica_checksums(tree, mesh, axis_name: str):
    """``[devices, leaves]`` checksums of each device's own copy of a
    replicated tree, computed where the copies are: the sum of every
    leaf's bits as unsigned integers. Rows that differ are replicas that
    differ."""
    unsigned = {4: jnp.uint32, 2: jnp.uint16, 1: jnp.uint8}

    def local(tree):
        return jnp.stack([
            jnp.sum(jax.lax.bitcast_convert_type(
                leaf, unsigned[leaf.dtype.itemsize]), dtype=jnp.uint32)
            for leaf in jax.tree.leaves(tree)])[None]

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=P(), out_specs=P(axis_name),
        check_vma=False))(tree)


def norms_agree(product, reference, names: list,
                tolerance: dict) -> tuple[bool, str]:
    """Every leaf's gradient norm against the reference's: the median
    relative difference within ``gradient_norm_rel_median``, the largest
    within ``gradient_norm_rel_worst``. The median is the tight one and
    tells a precision that was lowered; a single leaf's difference is held
    looser, because a gradient that is the small remainder of large
    cancelling terms carries the compute type's rounding of those terms,
    and tells a leaf that was dropped or scaled. Some leaves have a
    gradient that is zero by the mathematics or nearly so (a key
    projection's bias, which softmax cancels; query and key kernels under
    near-uniform attention; BatchNorm's running averages) and hold only
    rounding, so a leaf is measured against its reference norm or
    ``gradient_norm_floor_share`` of the median leaf's, whichever is
    larger."""
    product, reference = np.asarray(product), np.asarray(reference)
    scale = np.maximum(reference, tolerance["gradient_norm_floor_share"]
                       * np.median(reference))
    off = np.abs(product - reference) / scale
    middle, most, largest = np.quantile(off, [0.5, 0.9, 1.0])
    ok = bool(np.all(np.isfinite(product))
              and middle <= tolerance["gradient_norm_rel_median"]
              and largest <= tolerance["gradient_norm_rel_worst"])
    worst = ", ".join(
        f"{names[i]} {product[i]:.4e} against {reference[i]:.4e}"
        for i in np.argsort(-off)[:3])
    return ok, (
        f"relative difference of a leaf's gradient norm over {len(off)} "
        f"leaves (median reference norm {np.median(reference):.3e}): median "
        f"{middle:.3e} (allowed {tolerance['gradient_norm_rel_median']:.1e}), "
        f"nine in ten under {most:.3e}, largest {largest:.3e} (allowed "
        f"{tolerance['gradient_norm_rel_worst']:.1e}); the worst: {worst}")


def loss_in_record(loss: float, seed: int, record: dict,
                   rel: float) -> tuple[bool, str]:
    """The loss after the warm-up steps against what this job's file
    records. Weights and batches follow the seed, so a recorded seed is
    held to its own record within ``rel``. An unrecorded one is held to
    the band the recorded ones span, widened by its own width on either
    side and by ``rel``: k recorded seeds leave a new one outside their
    bare range two times in k + 1 with nothing wrong."""
    if str(seed) in record:
        want = record[str(seed)]
        ok = abs(loss - want) <= rel * abs(want)
        return ok, (f"{loss:.6f} against {want:.6f} recorded for seed "
                    f"{seed} (allowed {rel:.2e} relative)")
    if not record:
        return True, f"{loss:.6f}; nothing recorded for this job yet"
    low, high = min(record.values()), max(record.values())
    width = high - low
    low, high = (low - width) * (1 - rel), (high + width) * (1 + rel)
    return low <= loss <= high, (
        f"{loss:.6f} for unrecorded seed {seed} against the band "
        f"[{low:.6f}, {high:.6f}] of {len(record)} recorded seeds")


_COLLECTIVE = re.compile(
    r"= (.*?) (all-reduce|reduce-scatter|all-gather)(?:-start)?\(")


def collective_counts(hlo: str) -> dict:
    """Collective instructions in a compiled step's HLO text, by opcode
    (async pairs counted once, by their ``-start``), and how many of them
    carry bf16: the compressed gradient wire. Copied from
    ``chip_smoke.py``."""
    counts = {"all-reduce": 0, "reduce-scatter": 0, "all-gather": 0,
              "bf16": 0}
    for result_type, op in _COLLECTIVE.findall(hlo):
        counts[op] += 1
        counts["bf16"] += "bf16[" in result_type
    return counts


def pallas_call_count(hlo: str) -> int:
    return hlo.count('custom_call_target="tpu_custom_call"')
