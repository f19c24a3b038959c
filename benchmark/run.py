"""One cell of the benchmark, in one process.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell's configuration goes through the product the way a user's job
does: ``hvd.init`` -> ``hvd.DistributedOptimizer`` ->
``hvd.data_parallel.make_train_step`` on the cell's chips, with weights and
four batches made on the device from ``--seed``. Set-up (everything up to
the end of warm-up, the checks that decide ``correct`` included) is timed
as ``setup_s``; then ``--trace 0`` measures groups of steps for
``--seconds`` and prints the cell's end-to-end metrics, and ``--trace 1``
records a device trace of a few groups and prints its per-layer metrics.
The last line of standard output is the result, one JSON object.

A cell of ``BENCHMARK.json`` runs on a TPU or not at all: anywhere else
the exit code is non-zero and no result is printed. Only the toy cells of
``rehearsal.json`` run elsewhere, and their result names the platform.
One process, no children, no ``HOROVOD_*`` variable set. A step that
raises ends the run with its traceback and no result: its arguments were
donated, so there is nothing to go on with.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up counts from here, imports included

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from functools import partial  # noqa: E402

import cells  # noqa: E402

sys.path.insert(0, cells.ROOT)  # horovod_tpu, from this checkout

GROUP = 5  # steps dispatched back to back, then one sync
BATCHES = 4  # seeded batches, cycled
GIB = 2.0 ** 30


def say(text: str) -> None:
    print(text, flush=True)


def build_step(cell: cells.Cell):
    """The cell's optimizer and train step from the product's own factory:
    ``(optimizer, step)``. ``aot.py`` compiles this very step."""
    import horovod_tpu as hvd

    optimizer = hvd.DistributedOptimizer(
        cell.code.inner_optimizer(cell.config),
        compression=getattr(hvd.Compression, cell.job["compression"]),
        sync_mode=cell.job["sync_mode"])
    step = hvd.data_parallel.make_train_step(
        cell.code.loss_fn(cell.config, cell.job), optimizer)
    return optimizer, step


def place_state(cell: cells.Cell, optimizer, params):
    """Parameters and optimizer state where the cell's sync mode keeps
    them: ``(params, opt_state)``."""
    import horovod_tpu as hvd

    mode = cell.job["sync_mode"]
    opt_state = optimizer.init(params)
    if mode == "allreduce":
        opt_state = hvd.data_parallel.replicate(opt_state)
    else:
        opt_state = hvd.shard_state(opt_state)
    if mode == "fsdp":
        params = hvd.shard_state(hvd.shard_params(params))
    else:
        params = hvd.data_parallel.replicate(params)
    return params, opt_state


class Loop:
    """The training loop: its state, and what it records of every group."""

    def __init__(self, step, params, opt_state, batches):
        self.step, self.batches = step, batches
        self.params, self.opt_state = params, opt_state
        self.steps = 0
        self.start_records()

    def start_records(self) -> None:
        self.losses = []  # one device scalar a step, read after the window
        self.group_s = []  # a step's mean seconds, one entry a group
        self.call_s = []  # seconds for step(...) to return, no sync
        self.first_dispatch = self.last_completion = None

    def group(self, steps: int = GROUP) -> None:
        """``steps`` steps dispatched back to back, closed by one sync on
        everything the last of them returns."""
        import jax
        from jax.profiler import TraceAnnotation

        start = time.perf_counter()
        if self.first_dispatch is None:
            self.first_dispatch = start
        for _ in range(steps):
            batch = self.batches[self.steps % len(self.batches)]
            called = time.perf_counter()
            with TraceAnnotation("bench.step_call"):
                self.params, self.opt_state, loss = self.step(
                    self.params, self.opt_state, batch)
            self.call_s.append(time.perf_counter() - called)
            self.losses.append(loss)
            self.steps += 1
        with TraceAnnotation("bench.sync"):
            jax.block_until_ready((self.params, self.opt_state, loss))
        self.last_completion = time.perf_counter()
        self.group_s.append((self.last_completion - start) / steps)


STALL = 3.0  # a group this many times the median group is a stall


def steady(group_s: list) -> list:
    """The groups that no stall hit. On a shared host a process now and
    then stands still for seconds (three of twelve runs alike lost 0.2 to
    2.5 s of a 10 s window; PERF.md, PR 22): that is the machine's, and a
    mean that held it would hide every other difference. What a hook of
    the program costs every so many steps stays in: it does not triple a
    group."""
    limit = STALL * statistics.median(group_s)
    return [seconds for seconds in group_s if seconds <= limit]


def quartiles(values: list) -> tuple:
    if len(values) < 2:
        return values[0], values[0], values[0]
    return tuple(statistics.quantiles(values, n=4, method="inclusive"))


def memory_rows(devices) -> list:
    """``memory_stats()`` of every device of the cell; empty where the
    backend keeps none (the CPU of a rehearsal)."""
    return [row for row in (d.memory_stats() for d in devices) if row]


def taken_bytes(row: dict) -> int:
    """Buffers plus the loaded programs' temporaries. This runtime counts
    the two apart, and ``bytes_limit`` less both is the largest free block
    (PERF.md, PR 21)."""
    return row["bytes_in_use"] + row.get("bytes_reserved", 0)


def out_dir(cell: cells.Cell) -> str:
    """Where a run leaves what is too long for its output: a fixed place
    inside the checkout, which ``.gitignore`` lists."""
    path = os.path.join(cells.ROOT, ".benchmark_out", cell.name)
    os.makedirs(path, exist_ok=True)
    return path


def gate(cell: cells.Cell):
    """The cell's devices, or no run at all."""
    import jax

    backend, found = jax.default_backend(), jax.devices()
    if cell.measured and (backend != "tpu" or len(found) < cell.chips):
        raise SystemExit(
            f"benchmark: cell {cell.name!r} needs {cell.chips} TPU chip(s); "
            f"JAX found backend={backend!r} with {len(found)} x "
            f"{found[0].device_kind!r}. Not running on anything else.")
    if len(found) < cell.chips:
        raise SystemExit(
            f"benchmark: rehearsal cell {cell.name!r} needs {cell.chips} "
            f"devices; JAX found {len(found)}")
    return found[:cell.chips]


def set_up(cell: cells.Cell, seed: int, devices, compile_events: dict):
    """Weights, batches, the reference's answers, the product's step, its
    first step and the warm-up, with every check that needs no window:
    ``(loop, checked, hlo)`` where ``checked`` maps a check's name to
    ``(ok, what was seen)`` and ``hlo`` is the compiled step's text."""
    import jax
    import numpy as np

    import checks
    import horovod_tpu as hvd

    hvd.init(devices=devices)
    mesh, axis = hvd.global_mesh(), hvd.global_axis_name()
    rows = cell.rows
    tolerance = cell.config["correct"]
    checked = {}

    # -- weights and batches: on the device, from the seed, one call each ---
    t0 = time.perf_counter()
    key = jax.random.PRNGKey(seed)
    with jax.default_device(devices[0]):
        params = jax.jit(partial(
            cell.code.init_params, cell.config, cell.job))(key)
        make_batch = jax.jit(partial(
            cell.code.make_batch, cell.config, cell.job, rows=rows))
        plain_batches = [make_batch(jax.random.fold_in(key, 1 + i))
                         for i in range(BATCHES)]
    jax.block_until_ready((params, plain_batches))
    n_params = sum(int(leaf.size) for leaf in jax.tree.leaves(params))
    names = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_leaves_with_path(params)]
    say(f"weights and {BATCHES} batches: {n_params / 1e6:.1f} M parameters "
        f"in {len(jax.tree.leaves(params))} leaves, {rows} rows a step, "
        f"{time.perf_counter() - t0:.2f} s")

    # -- the plain reference first, gone before the step is loaded ----------
    t0 = time.perf_counter()
    reference = checks.reference_program(
        partial(cell.reference.loss, cell.config), devices,
        cell.job["reference_block_rows"])
    ref_loss, ref_norms = reference(params, plain_batches[0])
    ref_loss, ref_norms = float(ref_loss), np.asarray(ref_norms)
    del reference
    jax.clear_caches()  # unloads its executable, and with it the temporaries
    say(f"reference: float32 loss {ref_loss:.6f} and {len(ref_norms)} "
        f"gradient norms on batch 0, in blocks of "
        f"{cell.job['reference_block_rows']} rows, "
        f"{time.perf_counter() - t0:.2f} s (its share of setup_s)")

    # -- the product's step ---------------------------------------------------
    t0 = time.perf_counter()
    optimizer, step = build_step(cell)
    params, opt_state = place_state(cell, optimizer, params)
    batches = [hvd.data_parallel.shard_batch(b) for b in plain_batches]
    del plain_batches
    loop = Loop(step, params, opt_state, batches)
    del params, opt_state
    placed_s = time.perf_counter() - t0
    before = dict(compile_events)
    loop.group(1)
    loaded = {k: compile_events[k] - before[k] for k in before}
    say(f"state placed in {placed_s:.2f} s; first step "
        f"{loop.group_s[0]:.2f} s, of which backend compile or cache load "
        f"{loaded['backend_compile_s']:.2f} s (persistent cache "
        f"hits={loaded['hits']} misses={loaded['misses']})")

    # (b) the first step's loss: the seed weights on batch 0
    first_loss = float(loop.losses[0])
    off = abs(first_loss - ref_loss) / abs(ref_loss)
    checked["loss_vs_reference"] = (
        off <= tolerance["loss_rel"],
        f"product {first_loss:.6f}, reference {ref_loss:.6f}, relative "
        f"difference {off:.3e}, allowed {tolerance['loss_rel']:.3e}")
    # (c) the gradient the optimizer was handed in that step, leaf by leaf:
    # backward kernels, compression and fused buckets are all in it
    product_norms = jax.jit(lambda state: checks.leaf_norms(
        cell.code.first_gradient(state)))(loop.opt_state)
    product_norms = np.asarray(product_norms)
    checked["gradient_norms_vs_reference"] = checks.norms_agree(
        product_norms, ref_norms, names, tolerance)
    # Kept beside the trace: which leaves a failed check is about.
    with open(os.path.join(out_dir(cell), "gradient_norms.json"), "w") as f:
        json.dump({"leaves": names, "product": product_norms.tolist(),
                   "reference": ref_norms.tolist()}, f)

    # -- warm-up: one group, whose last loss the job's file records ----------
    loop.group()
    warm_loss = float(loop.losses[-1])
    say(f"warm-up: {loop.steps} steps, losses "
        + " ".join(f"{float(x):.6f}" for x in loop.losses))
    # (d)
    checked["loss_after_warmup"] = checks.loss_in_record(
        warm_loss, seed, cell.job["loss_after_warmup"],
        tolerance["loss_record_rel"])
    say(f"loss_after_warmup record: \"{seed}\": {warm_loss!r}")
    # (e) replicas that drifted apart would still each train
    if cell.chips > 1 and cell.job["sync_mode"] != "fsdp":
        sums = np.asarray(checks.replica_checksums(loop.params, mesh, axis))
        checked["replicas_identical"] = (
            bool((sums == sums[0]).all()),
            f"{sums.shape[0]} replicas of {sums.shape[1]} leaves, checksums "
            f"taken on the devices")

    # -- what was compiled: the kernels and the wire -------------------------
    # jit memoises the executable of the calls above, so this compiles
    # nothing; the seconds are printed so that a second compile would show.
    t0 = time.perf_counter()
    hlo = step.lower(loop.params, loop.opt_state,
                     batches[0]).compile().as_text()
    pallas = checks.pallas_call_count(hlo)
    collectives = checks.collective_counts(hlo)
    say(f"hlo: {pallas} Pallas custom calls, collectives {collectives} "
        f"({time.perf_counter() - t0:.2f} s to fetch)")
    wanted = cell.code.min_pallas_calls(cell.config)
    checked["kernels_in_step"] = (
        pallas >= wanted, f"{pallas} Pallas custom calls, at least {wanted}")
    if cell.chips > 1:
        # The loss's pmean is an all-reduce in every mode, so the gradient
        # wire is told by its dtype; XLA's CPU backend widens a bf16
        # all-reduce to float32, so that is asked of a TPU's program only.
        bf16_wire = (cell.job["compression"] == "bf16"
                     and devices[0].platform == "tpu")
        checked["wire_in_step"] = (
            collectives["all-reduce"] > 0
            and (collectives["bf16"] > 0 or not bf16_wire),
            f"{collectives}, bf16 wire wanted: {bf16_wire}")
    return loop, checked, hlo


def measure(loop: Loop, seconds: float) -> None:
    loop.start_records()
    loop.group()
    while loop.last_completion - loop.first_dispatch < seconds:
        loop.group()


def trace(loop: Loop, cell: cells.Cell):
    """A device trace of the job's ``trace_groups`` groups, reduced:
    ``trace_reduce.Trace``. The Python tracer stays off, so that the host
    side of a step costs what it costs untraced."""
    import jax

    import trace_reduce

    out = os.path.join(out_dir(cell), "trace")
    shutil.rmtree(out, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    loop.start_records()
    jax.profiler.start_trace(out, profiler_options=options)
    try:
        for _ in range(cell.job["trace_groups"]):
            loop.group()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    say(f"trace: {path} ({os.path.getsize(path) / 2 ** 20:.1f} MiB)")
    return trace_reduce.read(path)


def listen_to_compiles() -> dict:
    """JAX's own account of compiling, kept up to date as the run goes:
    persistent-cache hits and misses and backend-compile seconds (on a hit,
    the seconds of loading the executable)."""
    import jax.monitoring

    events = {"hits": 0, "misses": 0, "backend_compile_s": 0.0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            events["misses"] += 1

    def on_duration(event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            events["backend_compile_s"] += seconds

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    return events


def end_to_end(cell: cells.Cell, loop: Loop, peak, memory: list,
               setup_s: float) -> dict:
    """The window's end-to-end metrics, with what explains them on earlier
    lines. Off a TPU there is no peak and no memory counter, and no number
    appears under their names."""
    q1, median, q3 = quartiles(loop.group_s)
    unstalled = steady(loop.group_s)
    mean_step_s = statistics.fmean(unstalled)
    units, unit_name = cell.code.units_per_step(cell.job, cell.rows)
    say(f"window: {len(loop.losses)} steps in {len(loop.group_s)} groups of "
        f"{GROUP}, {loop.last_completion - loop.first_dispatch:.3f} s from "
        f"the first dispatch to the last completion; a step's mean seconds "
        f"by group: q1 {q1 * 1e3:.3f} ms, median {median * 1e3:.3f} ms, q3 "
        f"{q3 * 1e3:.3f} ms, mean {mean_step_s * 1e3:.3f} ms over the "
        f"{len(unstalled)} groups under {STALL:g} times the median; "
        f"{units / mean_step_s:.1f} {unit_name}/s; step(...) returns in "
        f"{statistics.median(loop.call_s) * 1e3:.3f} ms (median)")
    say("groups, ms a step: "
        + " ".join(f"{seconds * 1e3:.2f}" for seconds in loop.group_s))
    metrics = {"step_ms": {"value": median * 1e3, "unit": "ms"}}
    if peak:
        flops = cell.code.flops_per_step(cell.config, cell.job, cell.rows)
        metrics["mfu"] = {
            "value": flops / (mean_step_s * cell.chips
                              * peak["bf16_flops_per_s"]),
            "unit": "share"}
    if memory:
        metrics["hbm_gib"] = {
            "value": max(map(taken_bytes, memory)) / GIB, "unit": "GiB"}
    metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    return metrics


def per_layer(cell: cells.Cell, loop: Loop, reduced, hlo: str, peak,
              memory: list, compile_events: dict) -> tuple:
    """The traced window's per-layer metrics, each from its own reader (one
    that finds nothing to read is left out), and what the result line's
    ``device`` and ``breakdown`` take from the trace: ``(metrics, device
    fields, breakdown or None)``."""
    import trace_reduce

    steps = len(loop.losses)
    run = types.SimpleNamespace(
        cell=cell, trace=reduced, steps=steps, peak=peak,
        compile=compile_events, memory=memory, call_s=loop.call_s)
    metrics = {}
    for entry, params, reader in cells.layer_metrics(cell.name):
        value = reader.read(run, params)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    busy = trace_reduce.busy_seconds(reduced)
    if not busy:  # no TPU plane in the trace: a rehearsal on the CPU
        return metrics, {}, None
    return (metrics,
            {"busy_s": statistics.fmean(busy.values()),
             "window_s": reduced.window[1] - reduced.window[0]},
            trace_reduce.breakdown(reduced, steps,
                                   trace_reduce.scopes_of(hlo)))


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args()
    cell = cells.resolve(args.workload)
    t0 = time.perf_counter()
    devices = gate(cell)
    backend_start_s = time.perf_counter() - t0

    import jax
    import numpy as np

    import horovod_tpu as hvd

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say(f"cell: {cell.name} seed={args.seed} seconds={args.seconds:g} "
        f"trace={args.trace}")
    say(f"device: platform={device['platform']} "
        f"device_kind={device['kind']!r} count={device['count']}")
    say("HOROVOD_* variables found set (this program sets none): "
        f"{sorted(k for k in os.environ if k.startswith('HOROVOD_'))}")
    # An unknown device kind is an error on a measured cell, not a default.
    peaks = cells.load_json(cells.HERE, "peaks.json")["device_kinds"]
    if cell.measured and device["kind"] not in peaks:
        raise SystemExit(
            f"benchmark: peaks.json has no device kind {device['kind']!r} "
            f"(it has {sorted(peaks)})")
    peak = peaks.get(device["kind"])

    compile_events = listen_to_compiles()
    # Every program of a run is kept, the small ones too, so that a later
    # run of the cell in this checkout compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    say(f"compile cache: {hvd.enable_compile_cache()}")

    loop, checked, hlo = set_up(cell, args.seed, devices, compile_events)
    # The seconds until JAX had reached the chip (import jax and the first
    # jax.devices(): the runtime's own start-up) are left out. They are
    # neither the program's nor the benchmark's, and between processes that
    # are alike they differ by more than all the rest (PERF.md, PR 22).
    setup_s = time.perf_counter() - T_START - backend_start_s
    say(f"set-up: {setup_s:.2f} s from process start to the end of warm-up, "
        f"without the {backend_start_s:.2f} s the runtime took to reach the "
        f"{'chips' if cell.chips > 1 else 'chip'}; backend compile or cache "
        f"load {compile_events['backend_compile_s']:.2f} s, persistent cache "
        f"hits={compile_events['hits']} misses={compile_events['misses']}")
    misses_in_setup = compile_events["misses"]

    if args.trace:
        reduced = trace(loop, cell)
    else:
        measure(loop, args.seconds)
    if compile_events["misses"] != misses_in_setup:
        # A shape that warm-up missed: the window's numbers are compile time.
        checked["nothing_compiled_in_window"] = (
            False, f"{compile_events['misses'] - misses_in_setup} programs "
            "compiled inside the window")
    # (a) every loss of the window, read only now
    losses = np.asarray([float(x) for x in loop.losses])
    failed = int((~np.isfinite(losses)).sum())
    checked["losses_finite"] = (
        failed == 0,
        f"{failed} of {len(losses)} steps returned a non-finite loss")

    memory = memory_rows(devices)  # the step's executable is still loaded
    device["memory_peak_bytes"] = max(
        (max(taken_bytes(row), row.get("peak_bytes_in_use", 0))
         for row in memory), default=0)
    result = {"correct": all(ok for ok, _ in checked.values()),
              "attempted": len(losses), "failed": failed, "device": device}
    if args.trace:
        result["metrics"], traced, breakdown = per_layer(
            cell, loop, reduced, hlo, peak, memory, compile_events)
        device.update(traced)
        if breakdown:
            result["breakdown"] = breakdown
        expected = [entry["name"] for entry, _, _ in
                    cells.layer_metrics(cell.name)]
    else:
        result["metrics"] = end_to_end(cell, loop, peak, memory, setup_s)
        expected = [entry["name"]
                    for entry in cells.benchmark()["end_to_end"]]
    for row, d in zip(memory, devices):
        say(f"hbm: device {d.id} buffers {row['bytes_in_use'] / GIB:.3f} "
            f"GiB now, {row.get('peak_bytes_in_use', 0) / GIB:.3f} at peak; "
            f"program temporaries {row.get('bytes_reserved', 0) / GIB:.3f} "
            f"GiB now, {row.get('peak_bytes_reserved', 0) / GIB:.3f} at "
            f"peak; limit {row.get('bytes_limit', 0) / GIB:.3f} GiB")
    for name, (ok, seen) in checked.items():
        say(f"check {name}: {'ok' if ok else 'FAILED'}: {seen}")
    unusable = [name for name in expected if cell.measured and not (
        name in result["metrics"]
        and math.isfinite(result["metrics"][name]["value"]))]
    if unusable:
        raise SystemExit(f"benchmark: no usable value for {unusable}")
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
