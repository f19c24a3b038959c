"""Ahead-of-time compile of a cell at full size, for a TPU v5e that is not
there: do this in the sandbox before a chip call.

    JAX_PLATFORMS=cpu python benchmark/aot.py <cell> [<cell> ...]

For each cell it compiles the product's train step (``run.build_step``: the
very step ``run.py`` runs) and the plain reference's program for a described
``v5e:1x1`` or ``v5e:2x2``, and prints what the compiler plans: Pallas
custom calls, collectives, and ``memory_analysis()`` per chip. The
installed libtpu refuses here what it would refuse on the chip (a kernel's
tiling, too much fast memory, a program that does not fit). Nothing runs:
no result, no time and no HBM figure of a chip comes from this. 30-90 s a
cell on the sandbox's CPU.
"""

from __future__ import annotations

import os
import sys
import time
from functools import partial

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import cells  # noqa: E402

sys.path.insert(0, cells.ROOT)

GIB = 2.0 ** 30
BOUNDS = {1: (1, 1, 1), 4: (2, 2, 1)}


def planned(compiled) -> str:
    mem = compiled.memory_analysis()
    return (f"arguments {mem.argument_size_in_bytes / GIB:.2f} GiB + "
            f"temporaries {mem.temp_size_in_bytes / GIB:.2f} GiB + outputs "
            f"not aliased to arguments "
            f"{(mem.output_size_in_bytes - mem.alias_size_in_bytes) / GIB:.2f}"
            f" GiB per chip")


def compile_cell(name: str) -> None:
    import jax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    import checks
    import horovod_tpu as hvd
    import run

    cell = cells.resolve(name)
    bounds = BOUNDS[cell.chips]
    topology = topologies.get_topology_desc(
        platform="tpu", topology_name=f"v5e:{bounds[0]}x{bounds[1]}",
        chips_per_host_bounds=bounds)
    hvd.shutdown()
    hvd.init(devices=topology.devices)
    mesh, axis = hvd.global_mesh(), hvd.global_axis_name()
    mode = cell.job["sync_mode"]
    print(f"== {name}: {cell.chips} x "
          f"{topology.devices[0].device_kind!r}, sync_mode={mode}")

    def placed(tree, spec):
        sharding = NamedSharding(mesh, spec)
        return jax.tree.map(lambda leaf: jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=sharding), tree)

    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(
        partial(cell.code.init_params, cell.config, cell.job), key)
    batch = jax.eval_shape(partial(
        cell.code.make_batch, cell.config, cell.job, rows=cell.rows), key)

    t0 = time.perf_counter()
    reference = checks.reference_program(
        partial(cell.reference.loss, cell.config), topology.devices,
        cell.job["reference_block_rows"])
    compiled = reference.lower(placed(params, P()),
                               placed(batch, P())).compile()
    print(f"reference: compiled in {time.perf_counter() - t0:.0f} s; "
          f"{planned(compiled)}")

    optimizer, step = run.build_step(cell)
    opt_state = placed(jax.eval_shape(optimizer.init, params),
                       P() if mode == "allreduce" else P(axis))
    if mode == "fsdp":
        params = placed(jax.eval_shape(hvd.shard_params, params), P(axis))
    else:
        params = placed(params, P())
    t0 = time.perf_counter()
    compiled = step.lower(params, opt_state, placed(batch, P(axis))).compile()
    hlo = compiled.as_text()
    print(f"step: compiled in {time.perf_counter() - t0:.0f} s (on this "
          f"sandbox's CPU); {checks.pallas_call_count(hlo)} Pallas custom "
          f"calls (at least {cell.code.min_pallas_calls(cell.config)} "
          f"wanted), collectives {checks.collective_counts(hlo)}; "
          f"{planned(compiled)}")


if __name__ == "__main__":
    if not sys.argv[1:]:
        raise SystemExit(__doc__)
    for cell_name in sys.argv[1:]:
        compile_cell(cell_name)
