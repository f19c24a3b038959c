"""sha256 of a cell's lowered train step, to show that a refactor left the
program alone before any chip is asked (PRs 38, 40 and 42 each needed it).

    JAX_PLATFORMS=cpu python tools/lowered_sha.py <cell or toy job> [...]

A cell of ``BENCHMARK.json`` is lowered (not compiled) for the described
``v5e:1x1`` / ``v5e:2x2`` as ``benchmark/aot.py`` builds it; a toy
(``rehearsal-*``, a file of ``benchmark/jobs/``) on the CPU with interpreted
kernels, as the pin in ``tests/benchmark/test_benchmark_smallthinker.py``
does: for a toy ``text`` below is that pin's hash. Four-chip toys want
``XLA_FLAGS=--xla_force_host_platform_device_count=4``. Printed a cell:

* ``text``: the lowered text with every Mosaic body (base64 MLIR bytecode in
  a ``tpu_custom_call``'s ``backend_config``) printed **without** source
  locations: equal on two trees iff the programs are.
* ``positions``: the bodies printed **with** their locations, the checkout's
  root cut off and every frame in a file under ``horovod_tpu/models/``
  (file, line, column and function name) made anonymous: equal iff nothing
  moved but model code. ``-`` where the text holds no body.

Run it from each tree (copy this file into the other one) **with the same
names in the same order**: the hashes do not depend on where the checkout
lies, but a function traced once a process keeps its first caller's frames,
so ``positions`` depends on what was lowered before. Nothing runs and no
time comes of it.
"""

from __future__ import annotations

import base64
import hashlib
import os
import re
import sys
from functools import partial

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]

BOUNDS = {1: (1, 1, 1), 4: (2, 2, 1)}
BODY = re.compile(r'(\\22body\\22: \\22)([A-Za-z0-9+/=]+)(\\22)')
MODEL_FRAME = r'loc\("horovod_tpu/models/\w+\.py":[^)]*\)'


def sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()


def without_positions(text: str):
    """``(text, positions)`` hashes of a lowered step's ``text``."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    context = mlir.make_ir_context()
    context.allow_unregistered_dialects = True
    located = []

    def plain(match):
        with context:
            body = ir.Module.parse(base64.b64decode(match.group(2)))
            bare = body.operation.get_asm(enable_debug_info=False)
            full = body.operation.get_asm(enable_debug_info=True)
        full = full.replace(ROOT + os.sep, "")
        moved = set(re.findall(rf"^(#loc\d+) = {MODEL_FRAME}", full, re.M))
        full = re.sub(MODEL_FRAME, 'loc("models")', full)
        located.append(re.sub(
            r'loc\("[\w.<>]+"\((#loc\d+)\)\)',
            lambda frame: (f'loc("frame"({frame.group(1)}))'
                           if frame.group(1) in moved else frame.group(0)),
            full))
        return match.group(1) + sha(bare) + match.group(3)

    text = BODY.sub(plain, text)
    return sha(text), sha("".join(located)) if located else "-"


def resolve(name: str):
    """``(cell, devices)``: a listed cell on its described topology, a toy
    job on the CPU's devices."""
    import cells
    import jax

    if not name.startswith("rehearsal-"):
        from jax.experimental import topologies

        cell = cells.resolve(name)
        bounds = BOUNDS[cell.chips]
        return cell, topologies.get_topology_desc(
            platform="tpu", topology_name=f"v5e:{bounds[0]}x{bounds[1]}",
            chips_per_host_bounds=bounds).devices
    listed = {entry["traffic"]: entry for entry in cells.load_json(
        cells.HERE, "rehearsal.json")["workloads"]}
    entry = listed.get(name, {"config": name.rsplit("_", 1)[0], "chips": 1})
    config = cells.load_json(cells.HERE, "configs", entry["config"] + ".json")
    cell = cells.Cell(
        name=name, chips=entry["chips"], measured=False, config=config,
        job=cells.load_json(cells.HERE, "jobs", name + ".json"),
        code=cells.load_code(cells.HERE, "configs", config["code"]),
        reference=cells.load_code(cells.HERE, "reference",
                                  config["reference"]))
    return cell, jax.devices()[:cell.chips]


def lowered_text(name: str) -> str:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    import run

    cell, devices = resolve(name)
    hvd.shutdown()
    hvd.init(devices=devices)
    mesh, axis = hvd.global_mesh(), hvd.global_axis_name()
    mode = cell.job["sync_mode"]

    def placed(tree, spec):
        sharding = NamedSharding(mesh, spec)
        return jax.tree.map(lambda leaf: jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=sharding), tree)

    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(
        partial(cell.code.init_params, cell.config, cell.job), key)
    batch = jax.eval_shape(partial(
        cell.code.make_batch, cell.config, cell.job, rows=cell.rows), key)
    optimizer, step = run.build_step(cell)
    opt_state = placed(jax.eval_shape(optimizer.init, params),
                       P() if mode == "allreduce" else P(axis))
    if mode == "fsdp":
        params = placed(jax.eval_shape(hvd.shard_params, params), P(axis))
    else:
        params = placed(params, P())
    return step.lower(params, opt_state, placed(batch, P(axis))).as_text()


if __name__ == "__main__":
    if not sys.argv[1:]:
        raise SystemExit(__doc__)
    for cell_name in sys.argv[1:]:
        text, positions = without_positions(lowered_text(cell_name))
        print(f"{cell_name} text {text} positions {positions}", flush=True)
