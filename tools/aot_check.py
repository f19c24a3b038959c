"""Ahead-of-time compile check: chip_smoke's train step, compiled for a
TPU v5e that is not there.

    JAX_PLATFORMS=cpu python tools/aot_check.py [CHIPS] [SYNC_MODE]

The installed libtpu compiles for a described topology with no device
present, Mosaic kernels included (an oversized block is refused with
``RESOURCE_EXHAUSTED ... vmem``). So whether the step compiles, how many
Pallas custom calls and collectives it holds and how much HBM the compiler
plans for can be read in the sandbox, at no chip time, before a run is
sent. Nothing is executed: runtime start-up, real collectives, real HBM
and every time are the chip's to show. CHIPS is 1 or 4 (one v5e host),
SYNC_MODE ``allreduce`` | ``sharded`` | ``fsdp``.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(chips: int = 4, sync_mode: str = "allreduce") -> None:
    import jax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    import chip_smoke
    import horovod_tpu as hvd

    bounds = {1: (1, 1, 1), 4: (2, 2, 1)}[chips]
    topology = topologies.get_topology_desc(
        platform="tpu", topology_name=f"v5e:{bounds[0]}x{bounds[1]}",
        chips_per_host_bounds=bounds)
    hvd.init(devices=topology.devices)
    mesh, axis = hvd.global_mesh(), hvd.global_axis_name()
    print(f"target: {chips} x {topology.devices[0].device_kind!r}, "
          f"sync_mode={sync_mode}")

    cfg, opt, step = chip_smoke.build_step(sync_mode)

    def placed(tree, spec):
        sharding = NamedSharding(mesh, spec)
        return jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                           sharding=sharding), tree)

    params = jax.eval_shape(
        lambda key: chip_smoke.init_params(cfg, key), jax.random.PRNGKey(0))
    opt_state = placed(jax.eval_shape(opt.init, params),
                       P() if sync_mode == "allreduce" else P(axis))
    if sync_mode == "fsdp":
        params = placed(jax.eval_shape(hvd.shard_params, params), P(axis))
    else:
        params = placed(params, P())
    rows = chip_smoke.PER_CHIP_BATCH * chips
    batch = placed(tuple(
        jax.ShapeDtypeStruct((rows, width), "int32")
        for width in (chip_smoke.SEQ_LEN,) +
        (chip_smoke.MASKED_POSITIONS,) * 3), P(axis))

    t0 = time.perf_counter()
    lowered = step.lower(params, opt_state, batch)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    hlo = compiled.as_text()
    print(f"lowered in {t1 - t0:.0f} s, compiled in {t2 - t1:.0f} s "
          "(on this sandbox's CPU)")
    print(f"hlo: {chip_smoke.pallas_call_count(hlo)} Pallas custom calls "
          f"(a forward and a backward kernel in each of {cfg.num_layers} "
          f"layers = {2 * cfg.num_layers}), collectives "
          f"{chip_smoke.collective_counts(hlo)}")
    mem = compiled.memory_analysis()
    gib = 2 ** 30
    print(f"hbm planned per chip: arguments "
          f"{mem.argument_size_in_bytes / gib:.2f} GiB + temporaries "
          f"{mem.temp_size_in_bytes / gib:.2f} GiB + outputs not aliased to "
          f"arguments "
          f"{(mem.output_size_in_bytes - mem.alias_size_in_bytes) / gib:.2f}"
          f" GiB")


if __name__ == "__main__":
    args = sys.argv[1:]
    main(int(args[0]) if args else 4,
         args[1] if len(args) > 1 else "allreduce")
