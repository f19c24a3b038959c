"""Benchmark: ResNet-50 + BERT-Large data-parallel training via horovod_tpu.

Prints one JSON line per completed section — each line is the FULL
cumulative record so far, so the LAST complete line always carries every
measurement taken before any later failure (the driver parses the last
line).

Headline metric is ResNet-50 images/sec (BASELINE config #2); the record
also carries the BERT-Large pretraining row (config #3: tokens/sec + MFU,
flash-attention kernel, masked-position MLM head) and both efficiency
numbers:

- ``vs_baseline``: DistributedOptimizer step throughput / hand-written
  raw-JAX step throughput on the same devices — what a user actually
  experiences. On one chip the framework legitimately short-circuits the
  wire machinery, so this measures the real product behavior.
- ``vs_baseline_machinery``: same ratio with
  HOROVOD_FORCE_WIRE_MACHINERY=1 — the single-rank short-circuit disabled,
  so compression casts + fusion bucketing + the (identity) collective all
  execute. This is the non-circular "what does the machinery cost" number
  VERDICT r2 asked for; on n>1 worlds the two converge.
- ``vs_baseline_machinery_sharded``: same protocol with
  sync_mode="sharded" (ZeRO-1 wire: reduce-scatter + shard-local update +
  parameter allgather), plus per-rank optimizer-state bytes for both
  modes — the memory half of the trade.
- ``vs_baseline_machinery_fsdp``: same protocol with sync_mode="fsdp"
  (ZeRO-3 wire: params resident-sharded, per-segment just-in-time
  gathers, reduce-scatter inside backprop, no trailing allgather), plus
  ``resident_bytes_per_rank`` for all three modes, the standalone
  gather-probe price (``param_gather_probe_ms`` →
  ``hvd_param_gather_seconds``) and the derived
  ``fsdp_prefetch_overlap_ratio``.

Communication health: the ``comms`` record (section 6, --smoke
included) microprobes the interconnect, fits the online α–β link cost
model (``horovod_tpu/comms_model.py``), reports fitted alpha/beta + bus
bandwidth per (op, algorithm, link_class) and the efficiency ratio,
checks the fit predicts observed per-bucket latency for all three
sync-mode wires within ``HOROVOD_COMMS_FIT_TOLERANCE``, and A/B-tests
model-guided autotune pruning against the exhaustive sweep — so the
perf trajectory tracks communication health, not just throughput.

Step-time breakdown: ``phase_span_medians_ms`` carries derived
forward_backward/collective/optimizer_update medians (phase-probe
programs differenced against the headline step — see section 4d; the
phase vocabulary is ``horovod_tpu.attribution.PHASE_SPAN_NAMES``, the
one constant set the elastic step and the attribution plane share), and
the ``attribution`` record (section 7) carries the framework-side
compute/exposed_comm/straggler_wait/overhead decomposition + MFU of the
same step, so BENCH_r*.json records where the step time goes, not just
throughput.

Failure contract: every section runs under ``_run_section`` — a section
that raises records an ``errors`` entry and the later sections still run,
so one failure costs one row, not the record. Exit code is 0 only when
the headline ResNet row was measured AND no section raised.
"""

from __future__ import annotations

import contextlib
import json
import os
import statistics
import sys
import time


@contextlib.contextmanager
def _forced_wire():
    """Machinery-forced section scope: disable the n=1 short-circuit so
    compression/bucketing/collective actually execute, restoring any
    user-set value of the flag afterwards."""
    prev = os.environ.get("HOROVOD_FORCE_WIRE_MACHINERY")
    os.environ["HOROVOD_FORCE_WIRE_MACHINERY"] = "1"
    try:
        yield
    finally:
        if prev is None:
            del os.environ["HOROVOD_FORCE_WIRE_MACHINERY"]
        else:
            os.environ["HOROVOD_FORCE_WIRE_MACHINERY"] = prev


def _run_section(section: str, fn, errors: list):
    """Run ``fn()``. A section that raises is recorded in ``errors``
    (which makes the run exit non-zero) and returns None, so the
    sections after it still get measured."""
    try:
        return fn()
    except Exception as exc:  # noqa: BLE001 — reported via errors + exit code
        msg = f"{section}: {type(exc).__name__}: {exc}"
        print(f"# bench: {msg}"[:500], file=sys.stderr)
        errors.append(msg[:300])
        return None


def _exit_code(headline, errors: list) -> int:
    """0 only when the headline row was measured and no section raised."""
    return 0 if headline is not None and not errors else 1


class _Emitter:
    """Cumulative-record printer: every call prints the FULL record as one
    JSON line (flushed), so the last complete stdout line is always the
    best snapshot."""

    def __init__(self):
        self.record = {
            "metric": "resnet50_images_per_sec",
            "value": None,
            "unit": "images/sec",
            "vs_baseline": None,
        }

    def update(self, **kv):
        self.record.update(kv)
        print(json.dumps(self.record), flush=True)


def _build_step(model, optimizer, mesh, axis_name, loss_fn, sync_grads=None,
                overlap_spec=None, sharded_spec=None, fsdp_spec=None,
                world_size=None, mesh2d_shape=None):
    """sync_grads: None when `optimizer` already syncs (DistributedOptimizer);
    for the raw baseline it is the hand-written pmean a correct hand-rolled
    DP step must do, so both sides do equivalent communication work.

    overlap_spec: a ReduceSpec (``hvd.reduce_spec_of``) switches the step
    to the overlap scheduler's wire — gradients reduce per segment INSIDE
    the backward pass — and ``optimizer`` must then be the BARE inner
    optimizer (the spec's wire already did the reduction).

    sharded_spec: a sync_mode='sharded' ReduceSpec switches the step to
    the ZeRO-1 wire — per-bucket reduce-scatter, shard-local inner
    update (opt_state arrives in the STACKED sharded layout, sharded
    over the axis), allgather of updated parameter shards.

    fsdp_spec: a sync_mode='fsdp' ReduceSpec switches the step to the
    ZeRO-3 wire — the params argument is the resident ShardedParams
    rows (sharded over the axis, ~1/n per rank at rest), each segment's
    full tensors are allgathered just in time in the forward, gradients
    reduce-scatter inside backprop at the gather boundaries, and the
    shard-local update writes back to the resident rows with no
    trailing allgather.

    mesh2d_shape: a (batch, model) pair switches the fsdp wire to the
    2-D mesh — ``mesh`` must then be the named (batch, model) mesh,
    rows ride P(("model", "batch")), the batch rides P(("batch",
    "model")) (flat rank order), and the gather takes the two-leg
    rank-factorized form (``gather_params_2d``)."""
    import jax
    import optax
    from jax.sharding import PartitionSpec as P

    def spmd_step(params, batch_stats, opt_state, batch):
        x, y = batch

        if fsdp_spec is not None:
            from horovod_tpu.parallel.param_sharding import (
                gather_params,
                gather_params_2d,
            )

            meta = params.meta
            shards = jax.tree.unflatten(
                meta.treedef, [a[0] for a in params.rows])
            local_state = jax.tree.map(lambda a: a[0], opt_state)

            def loss_of_shards(sh):
                if mesh2d_shape is not None:
                    full = gather_params_2d(
                        sh, meta, fsdp_spec,
                        int(mesh2d_shape[0]), int(mesh2d_shape[1]))
                else:
                    full = gather_params(sh, meta, fsdp_spec, axis_name,
                                         int(world_size))
                logits, updated = model.apply(
                    {"params": full, "batch_stats": batch_stats},
                    x, train=True, mutable=["batch_stats"])
                return loss_fn(logits, y), updated["batch_stats"]

            (loss, new_stats), grad_shards = jax.value_and_grad(
                loss_of_shards, has_aux=True)(shards)
            updates, new_local = fsdp_spec.inner.update(
                grad_shards, local_state, shards)
            new_shards = optax.apply_updates(shards, updates)
            new_rows = type(params)(
                [a[None] for a in jax.tree.leaves(new_shards)], meta)
            new_opt = jax.tree.map(lambda a: a[None], new_local)
            return new_rows, new_stats, new_opt, loss

        def loss_of(p):
            if overlap_spec is not None:
                from horovod_tpu.parallel.data_parallel import (
                    overlap_gradient_sync,
                )

                p = overlap_gradient_sync(
                    p, overlap_spec, axis_name=axis_name)
            logits, updated = model.apply(
                {"params": p, "batch_stats": batch_stats},
                x,
                train=True,
                mutable=["batch_stats"],
            )
            return loss_fn(logits, y), updated["batch_stats"]

        (loss, new_stats), grads = jax.value_and_grad(loss_of, has_aux=True)(
            params
        )
        if sharded_spec is not None:
            from horovod_tpu import sharded_step_update

            local_state = jax.tree.map(lambda a: a[0], opt_state)
            new_params, new_local = sharded_step_update(
                sharded_spec, grads, local_state, params,
                axis_name=axis_name)
            new_opt = jax.tree.map(lambda a: a[None], new_local)
            return new_params, new_stats, new_opt, loss
        if sync_grads is not None:
            grads = sync_grads(grads)
        updates, new_opt = optimizer.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        return new_params, new_stats, new_opt, loss

    sharded_state = sharded_spec is not None or fsdp_spec is not None
    if mesh2d_shape is not None:
        from horovod_tpu.parallel.mesh import MESH2D_AXES, MESH2D_ROW_AXES

        opt_spec = param_spec = P(MESH2D_ROW_AXES)
        batch_spec = P(MESH2D_AXES)
    else:
        opt_spec = P(axis_name) if sharded_state else P()
        param_spec = P(axis_name) if fsdp_spec is not None else P()
        batch_spec = P(axis_name)
    return jax.jit(
        jax.shard_map(
            spmd_step,
            mesh=mesh,
            in_specs=(param_spec, P(), opt_spec, batch_spec),
            out_specs=(param_spec, P(), opt_spec, P()),
            check_vma=False,
        ),
        donate_argnums=(0, 1, 2),
    )


def _tree_bytes(tree) -> int:
    """Static byte count of a pytree — reads shape/dtype only, so it
    never materializes device arrays and accepts eval_shape trees
    (ShapeDtypeStructs) for sizing a state without allocating it."""
    import jax
    import numpy as np

    return int(sum(
        int(np.prod(np.shape(l)) if np.shape(l) else 1)
        * np.dtype(l.dtype).itemsize
        for l in jax.tree.leaves(tree)))


def _peak_rss_bytes() -> int | None:
    """Host-side peak resident set size (VmHWM from /proc/self/status):
    the high-water mark of everything this process ever held in host
    RAM — on the CPU-mesh bench the analog of the device HBM peak, and
    the sanity bound the per-rank resident predictions must sit under.
    None off Linux (no procfs)."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM:"):
                    return int(line.split()[1]) * 1024  # kB -> bytes
    except OSError:
        pass
    return None


def _time_steps(step, state, batch, warmup=4, iters=20, repeats=3):
    """Median-of-repeats step time (sec) + relative spread.

    Warmup absorbs compilation; each repeat times ``iters`` steps
    back-to-back and ends in ``jax.block_until_ready`` on the last
    step's outputs. The median repeat is the headline (min/max recorded
    as spread so the number can be judged for noise).
    """
    import jax

    params, stats, opt_state = state
    for _ in range(warmup):
        params, stats, opt_state, loss = step(params, stats, opt_state, batch)
    jax.block_until_ready(loss)
    times = []
    t_section = time.perf_counter()
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            params, stats, opt_state, loss = step(
                params, stats, opt_state, batch
            )
        jax.block_until_ready((params, stats, opt_state, loss))
        times.append((time.perf_counter() - t0) / iters)
    # Timed training is productive time by definition: the goodput
    # counters in the bench record (and the /metrics scrape the premerge
    # gate takes) carry real seconds, not zeros.
    try:
        from horovod_tpu import metrics as _metrics

        _metrics.goodput().add_productive(time.perf_counter() - t_section)
    except Exception:  # noqa: BLE001 — observability only
        pass
    times.sort()
    median = statistics.median(times)
    spread = (times[-1] - times[0]) / median if median else 0.0
    return median, spread


# Analytic ResNet-50 cost: ~4.09 GMACs forward at 224x224 (8.18 GFLOPs);
# training ~= 3x forward (backward is ~2x). Used for MFU on TPU only — the
# CPU-mesh run uses 32x32 inputs where this constant doesn't apply.
RESNET50_TRAIN_FLOPS_PER_IMAGE_224 = 3 * 2 * 4.089e9


# BERT-Large analytic FLOPs/token (fwd), masked-position head:
#   layers: 2 * L * (4H^2 + 2HI); attention: 4 * L * S * H;
#   head (transform + tied logits) scaled by P/S. Train = 3x fwd.
def bert_flops_per_token(cfg, seq_len: int, num_predictions: int) -> float:
    H, I, L, V = (cfg.hidden_size, cfg.intermediate_size, cfg.num_layers,
                  cfg.vocab_size)
    layer_matmuls = 2.0 * L * (4 * H * H + 2 * H * I)
    attention = 4.0 * L * seq_len * H
    head = 2.0 * (H * H + V * H) * (num_predictions / seq_len)
    return 3.0 * (layer_matmuls + attention + head)


def bench_bert(hvd, timing):
    """BERT-Large (BASELINE config #3) MLM pretraining step: bf16, flash
    attention (Pallas), masked-position head (max_predictions_per_seq
    recipe), AdamW. Returns the metrics dict."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.attribution import peak_flops_for_kind
    from horovod_tpu.models import bert as bert_mod

    on_tpu = jax.default_backend() == "tpu"
    n = hvd.size()
    if on_tpu:
        cfg = dataclasses.replace(bert_mod.BERT_LARGE, dropout_rate=0.0)
        # batch sweep (docs/benchmarks.md): 8 -> 51.2k tok/s, 16 -> 52.0k,
        # 24 -> 55.0k (peak), 32 -> 51.9k, 48 -> 48.2k on one v5e
        per_chip, seq, preds = 24, 512, 76
        attention_fn = bert_mod.flash_attention_fn
    else:
        cfg = dataclasses.replace(bert_mod.BERT_TINY, dropout_rate=0.0)
        per_chip, seq, preds = 2, 128, 16
        attention_fn = None  # CPU: jnp oracle path
    B = per_chip * n
    model = bert_mod.Bert(cfg, attention_fn=attention_fn)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(B, seq)).astype(np.int32)
    labels = rng.randint(0, cfg.vocab_size, size=(B, seq)).astype(np.int32)
    positions = np.stack(
        [rng.choice(seq, preds, replace=False) for _ in range(B)]
    ).astype(np.int32)
    plabels = np.take_along_axis(labels, positions, axis=1)
    lmask = np.ones((B, preds), np.int32)

    variables = model.init(jax.random.PRNGKey(0), jnp.asarray(ids[:1]))
    params = variables["params"]
    opt = hvd.DistributedOptimizer(
        optax.adamw(1e-4),
        compression=hvd.Compression.bf16 if on_tpu else hvd.Compression.none,
    )
    mesh = hvd.global_mesh()
    axis = hvd.global_axis_name()
    batch = hvd.data_parallel.shard_batch(
        (ids, positions, plabels, lmask)
    )

    def spmd_step(params, opt_state, batch):
        ids, positions, plabels, lmask = batch

        def loss_of(p):
            _, logits = model.apply(
                {"params": p}, ids, train=True, masked_positions=positions
            )
            return bert_mod.mlm_loss(logits, plabels, lmask)

        loss, grads = jax.value_and_grad(loss_of)(params)
        updates, new_opt = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_opt, loss

    step = jax.jit(
        jax.shard_map(
            spmd_step,
            mesh=mesh,
            in_specs=(P(), P(), P(axis)),
            out_specs=(P(), P(), P()),
            check_vma=False,
        ),
        donate_argnums=(0, 1),
    )
    p_ = hvd.data_parallel.replicate(params)
    o_ = hvd.data_parallel.replicate(opt.init(params))

    for _ in range(timing["warmup"]):
        p_, o_, loss = step(p_, o_, batch)
    jax.block_until_ready(loss)
    times = []
    for _ in range(timing["repeats"]):
        t0 = time.perf_counter()
        for _ in range(timing["iters"]):
            p_, o_, loss = step(p_, o_, batch)
        jax.block_until_ready((p_, o_, loss))
        times.append((time.perf_counter() - t0) / timing["iters"])
    times.sort()

    t_step = statistics.median(times)
    tokens_per_sec = B * seq / t_step
    mfu = None
    if on_tpu:
        peak = peak_flops_for_kind(jax.devices()[0].device_kind)
        mfu = (tokens_per_sec *
               bert_flops_per_token(cfg, seq, preds)) / (peak * n)
    return {
        "bert_tokens_per_sec": round(tokens_per_sec, 1),
        "bert_step_time_ms": round(t_step * 1e3, 2),
        "bert_mfu": round(mfu, 4) if mfu is not None else None,
        "bert_global_batch": B,
        "bert_seq_len": seq,
    }


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.attribution import peak_flops_for_kind
    from horovod_tpu.models.lenet import cross_entropy_loss  # reuse CE
    from horovod_tpu.models.resnet import ResNet50

    hvd.enable_compile_cache()

    # --smoke: the pre-merge gate (tools/premerge.sh) — 2 timed steps per
    # section on whatever backend is present, BERT and int8 rows skipped,
    # so the full machinery (dist step, raw baseline, forced wire, overlap
    # scheduler) compiles and runs in minutes on CPU.
    smoke = "--smoke" in sys.argv[1:]

    t_start = time.perf_counter()
    emit = _Emitter()
    errors: list = []

    hvd.init()
    n = hvd.size()
    # The deadline is a LOCAL decision; in a multi-controller world a
    # rank skipping a section would desert peers mid-collective and hang
    # the bench. Single-controller (one process, one chip or several)
    # keeps the gate; multi-process worlds run every section.
    single_controller = int(
        os.environ.get("HOROVOD_NUM_PROCESSES", "1") or 1) <= 1
    # The deadline exists so a slow run still reports every row measured
    # so far; sections it skips are absent from the record.
    deadline_s = (float(os.environ.get("BENCH_DEADLINE", "900"))
                  if single_controller else float("inf"))

    def out_of_time() -> bool:
        return time.perf_counter() - t_start > deadline_s

    on_tpu = jax.default_backend() == "tpu"
    # 128/chip saturates the v5e MXU for ResNet-50 (measured: 64→24.5% MFU,
    # 128→30.3%, 256→30.3% — same throughput, double latency).
    per_chip_batch = 128 if on_tpu else 4
    image = 224 if on_tpu else 32
    global_batch = per_chip_batch * n

    model = ResNet50(
        num_classes=1000, dtype=jnp.bfloat16 if on_tpu else jnp.float32
    )
    rng = np.random.RandomState(0)
    x = rng.rand(global_batch, image, image, 3).astype(np.float32)
    y = rng.randint(0, 1000, size=(global_batch,)).astype(np.int32)

    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, image, image, 3)), train=True
    )
    params, batch_stats = variables["params"], variables["batch_stats"]

    def loss_fn(logits, labels):
        return cross_entropy_loss(logits, labels, num_classes=1000)

    mesh = hvd.global_mesh()
    axis = hvd.global_axis_name()
    batch = hvd.data_parallel.shard_batch((x, y))

    def fresh_state(opt):
        return (
            hvd.data_parallel.replicate(params),
            hvd.data_parallel.replicate(batch_stats),
            hvd.data_parallel.replicate(opt.init(params)),
        )

    # CPU-mesh runs exist to exercise the fusion machinery and produce
    # vs_baseline, not absolute speed — keep the loop short there.
    timing = (
        dict(warmup=4, iters=20, repeats=3)
        if on_tpu
        else dict(warmup=2, iters=5, repeats=2)
    )
    if smoke:
        timing = dict(warmup=1, iters=2, repeats=1)

    peak = (peak_flops_for_kind(jax.devices()[0].device_kind)
            if on_tpu else None)

    # Declare the model's analytic FLOPs to the attribution plane (MFU
    # promotion): every synced tracer step now exports hvd_mfu_ratio and
    # the phase gauges ride the metrics snapshot into the premerge
    # scrape gate. The 224x224 constant is only honest on TPU; the
    # CPU-mesh smoke leaves it unset (the gauge stays zero-materialized).
    if on_tpu and image == 224:
        hvd.set_model_flops_per_step(
            RESNET50_TRAIN_FLOPS_PER_IMAGE_224 * global_batch)

    # --- section 1 (headline): DistributedOptimizer (fused allreduce +
    # bf16 wire). Emitted immediately so a later failure cannot erase it.
    dist_opt = hvd.DistributedOptimizer(
        optax.sgd(0.1, momentum=0.9),
        compression=hvd.Compression.bf16 if on_tpu else hvd.Compression.none,
    )

    def run_dist():
        step = _build_step(model, dist_opt, mesh, axis, loss_fn)
        return _time_steps(step, fresh_state(dist_opt), batch, **timing)

    dist = _run_section("resnet_dist", run_dist, errors)
    if dist is not None:
        t_dist, spread = dist
        images_per_sec = global_batch / t_dist
        mfu = None
        if on_tpu and image == 224:
            mfu = (images_per_sec *
                   RESNET50_TRAIN_FLOPS_PER_IMAGE_224) / (peak * n)
        emit.update(
            value=round(images_per_sec, 2),
            step_time_ms=round(t_dist * 1e3, 3),
            step_time_spread=round(spread, 4),
            mfu=round(mfu, 4) if mfu is not None else None,
            global_batch=global_batch,
            n_devices=n,
            backend=jax.default_backend(),
            device_kind=getattr(jax.devices()[0], "device_kind", "unknown"),
        )

    # --- section 2: raw JAX baseline — hand-written DP step (per-leaf grad
    # pmean, no fusion/compression machinery).
    def run_raw():
        raw_opt = optax.sgd(0.1, momentum=0.9)

        def hand_pmean(grads):
            return jax.tree.map(lambda g: jax.lax.pmean(g, axis), grads)

        step = _build_step(
            model, raw_opt, mesh, axis, loss_fn, sync_grads=hand_pmean
        )
        return _time_steps(step, fresh_state(raw_opt), batch, **timing)

    raw = None
    if not out_of_time():
        raw = _run_section("resnet_raw", run_raw, errors)
        if raw is not None and dist is not None:
            emit.update(vs_baseline=round(raw[0] / dist[0], 4))

    # --- section 3: BERT-Large MLM pretraining row. Runs BEFORE the
    # machinery-forced variant: under a tight budget the BERT MFU row is
    # worth more than the second efficiency ratio.
    bert = None
    if not smoke and not out_of_time():
        bert = _run_section("bert", lambda: bench_bert(hvd, timing), errors)
        if bert is not None:
            emit.update(**bert)

    # --- section 4: machinery-forced efficiency — disable the n=1
    # short-circuit so compression/bucketing/collective actually execute.
    def run_forced():
        with _forced_wire():
            step = _build_step(model, dist_opt, mesh, axis, loss_fn)
            return _time_steps(step, fresh_state(dist_opt), batch, **timing)

    if raw is not None and not out_of_time():
        forced = _run_section("resnet_forced", run_forced, errors)
        if forced is not None:
            emit.update(vs_baseline_machinery=round(raw[0] / forced[0], 4))

    # --- section 4b: overlap scheduler, machinery-forced — the segmented
    # bucket scheduler issues each parameter segment's allreduce INSIDE
    # the backward pass (identity-forward / reduce-backward boundaries),
    # so ICI transfers pipeline against backward compute instead of
    # serializing after it. Compare vs_baseline_machinery_overlap with
    # vs_baseline_machinery: same wire, monolithic post-backward block.
    def run_overlap():
        with _forced_wire():
            from horovod_tpu import reduce_spec_of
            from horovod_tpu.ops.fusion import overlap_segments

            spec = reduce_spec_of(dist_opt)
            step = _build_step(model, spec.inner, mesh, axis, loss_fn,
                               overlap_spec=spec)
            timed = _time_steps(step, fresh_state(dist_opt), batch,
                                **timing)
            return timed, overlap_segments()

    if raw is not None and not out_of_time():
        overlap = _run_section("resnet_overlap", run_overlap, errors)
        if overlap is not None:
            (t_overlap, _), segments = overlap
            emit.update(
                vs_baseline_machinery_overlap=round(raw[0] / t_overlap, 4),
                overlap_segments=segments,
            )

    # --- section 4c: sharded sync mode (ZeRO-1 wire), machinery-forced —
    # each bucket's allreduce splits into reduce-scatter + allgather: the
    # inner update runs only on this rank's owned shard (1/n optimizer
    # compute + state memory) and the allgather moves to the UPDATED
    # PARAMETERS, off the gradient critical path. Same protocol as
    # vs_baseline_machinery so the two ratios are directly comparable;
    # the per-rank optimizer-state bytes for both modes are reported
    # alongside (the memory half of the trade).
    def run_sharded():
        with _forced_wire():
            sharded_opt = hvd.DistributedOptimizer(
                optax.sgd(0.1, momentum=0.9),
                compression=(hvd.Compression.bf16 if on_tpu
                             else hvd.Compression.none),
                sync_mode="sharded",
            )
            spec = hvd.reduce_spec_of(sharded_opt)
            step = _build_step(model, sharded_opt, mesh, axis, loss_fn,
                               sharded_spec=spec)
            stacked = sharded_opt.init(params)
            state = (
                hvd.data_parallel.replicate(params),
                hvd.data_parallel.replicate(batch_stats),
                hvd.data_parallel.shard_state(stacked),
            )
            per_rank_bytes = _tree_bytes(stacked) // max(1, n)
            return _time_steps(step, state, batch, **timing), per_rank_bytes

    sharded = None
    if raw is not None and not out_of_time():
        sharded = _run_section("resnet_sharded", run_sharded, errors)
        if sharded is not None:
            (t_sharded, _), sharded_bytes = sharded
            # eval_shape: size the monolithic state without allocating
            # it (2x model bytes for momentum/Adam states).
            mono_state_bytes = _tree_bytes(
                jax.eval_shape(dist_opt.init, params))
            emit.update(
                vs_baseline_machinery_sharded=round(raw[0] / t_sharded, 4),
                opt_state_bytes_per_rank=mono_state_bytes,
                opt_state_bytes_per_rank_sharded=sharded_bytes,
            )

    # --- section 4c2: full parameter sharding (ZeRO-3 / FSDP wire),
    # machinery-forced — params live sharded at rest (~1/n per rank) and
    # full tensors exist only transiently per segment: forward allgathers
    # each segment just in time, the backward emits the gradient
    # reduce-scatter inside backprop at the gather boundaries, and the
    # shard-local update writes back to the resident shard with NO
    # trailing allgather. Reported alongside: per-rank resident
    # param+optimizer bytes for all three modes (the memory story that
    # motivates the mode), a standalone gather-program probe (the price
    # the step must hide under compute -> hvd_param_gather_seconds), and
    # the derived prefetch-overlap ratio.
    def run_fsdp():
        import jax
        from jax.sharding import PartitionSpec as P

        from horovod_tpu import tracing
        from horovod_tpu.parallel import param_sharding

        with _forced_wire():
            fsdp_opt = hvd.DistributedOptimizer(
                optax.sgd(0.1, momentum=0.9),
                compression=(hvd.Compression.bf16 if on_tpu
                             else hvd.Compression.none),
                sync_mode="fsdp",
            )
            spec = hvd.reduce_spec_of(fsdp_opt)
            step = _build_step(model, fsdp_opt, mesh, axis, loss_fn,
                               fsdp_spec=spec, world_size=n)
            sp = hvd.shard_params(params, n)
            stacked = fsdp_opt.init(params)
            resident = {
                "params": param_sharding.resident_param_bytes(sp),
                "opt_state": _tree_bytes(stacked) // max(1, n),
            }
            state = (
                hvd.data_parallel.shard_state(sp),
                hvd.data_parallel.replicate(batch_stats),
                hvd.data_parallel.shard_state(stacked),
            )
            timed = _time_steps(step, state, batch, **timing)

            # Standalone gather probe: the full per-segment parameter
            # gather as its own program — total gather time with NOTHING
            # to hide it under. The sum over every gathered leaf defeats
            # DCE without meaningfully adding to the collective cost.
            meta = sp.meta

            def gather_only(rows):
                shards = jax.tree.unflatten(
                    meta.treedef, [a[0] for a in rows.rows])
                full = param_sharding.gather_params(
                    shards, meta, spec, axis, n)
                return sum(jnp.sum(l) for l in jax.tree.leaves(full))

            gather_prog = jax.jit(jax.shard_map(
                gather_only, mesh=mesh, in_specs=(P(axis),),
                out_specs=P(), check_vma=False))
            probe_sp = hvd.data_parallel.shard_state(hvd.shard_params(
                params, n))
            jax.block_until_ready(gather_prog(probe_sp))
            samples = []
            for _ in range(max(2, timing["repeats"])):
                t0 = time.perf_counter()
                for _ in range(timing["iters"]):
                    out = gather_prog(probe_sp)
                jax.block_until_ready(out)
                dt = (time.perf_counter() - t0) / timing["iters"]
                samples.append(dt)
                try:
                    hvd.metrics.PARAM_GATHER_SECONDS.observe(dt)
                    # Per-algorithm attribution for the comms model:
                    # the probe IS the fsdp gather half, end to end —
                    # total gathered bytes at this measured latency,
                    # classed like any world-set collective would be.
                    from horovod_tpu.ops.collective_ops import \
                        _link_class_of
                    from horovod_tpu.process_sets import \
                        global_process_set
                    hvd.comms_model.observe(
                        "allgather", "fsdp",
                        _link_class_of(global_process_set),
                        _tree_bytes(params), dt)
                except Exception:  # noqa: BLE001 — observability only
                    pass
            samples.sort()
            t_gather = statistics.median(samples)
            t_base = tracing.clock_sync().now()
            tracing.record_span("fsdp_param_gather", "collective",
                                t_base, t_gather,
                                args={"probe": "standalone"})
            return timed, resident, t_gather

    if raw is not None and not out_of_time():
        fsdp = _run_section("resnet_fsdp", run_fsdp, errors)
        if fsdp is not None:
            from horovod_tpu import tracing as _tracing

            (t_fsdp, _), fsdp_resident, t_gather = fsdp
            mono_params_bytes = _tree_bytes(params)
            mono_state_bytes = _tree_bytes(
                jax.eval_shape(dist_opt.init, params))
            resident_by_mode = {
                "monolithic": mono_params_bytes + mono_state_bytes,
                "fsdp": fsdp_resident["params"] + fsdp_resident["opt_state"],
            }
            if sharded is not None:
                resident_by_mode["sharded"] = (
                    mono_params_bytes + sharded[1])
            record = {
                "vs_baseline_machinery_fsdp": round(raw[0] / t_fsdp, 4),
                "resident_bytes_per_rank": resident_by_mode,
            }
            if sharded is not None and t_gather > 0:
                # Prefetch-overlap ratio: the standalone probe prices the
                # total gather time; the fsdp-vs-sharded step delta is
                # the EXPOSED part (both wires move the same bytes per
                # step — RS+AG — so the comparison cancels everything but
                # where the gather sits relative to compute). The hidden
                # fraction is what the just-in-time prefetch bought.
                exposed = max(t_fsdp - sharded[0][0], 0.0)
                ratio = max(0.0, min(1.0, (t_gather - exposed) / t_gather))
                try:
                    hvd.metrics.FSDP_PREFETCH_OVERLAP.set(ratio)
                except Exception:  # noqa: BLE001 — observability only
                    pass
                _tracing.record_span(
                    "fsdp_gather_exposed", "collective",
                    _tracing.clock_sync().now(), exposed,
                    args={"derived": True})
                record["fsdp_prefetch_overlap_ratio"] = round(ratio, 4)
            record["param_gather_probe_ms"] = round(t_gather * 1e3, 3)
            emit.update(**record)

    # --- section 4c3: the 2-D (batch, model) fsdp wire, machinery-forced
    # — the SAME rank-factorized resident row layout (byte parity with
    # the 1-D rows is exact by the ceil identity, so the gate asserts
    # <=), but the parameter gather splits into two legs: the bucketed
    # batch-axis gather moves ~1/model of the 1-D gather bytes
    # (hvd_param_gather_bytes{axis="batch"}) and the model-axis
    # all_gather rides the short-hop contiguous-rank links.
    def run_fsdp_2d():
        from horovod_tpu.parallel import param_sharding
        from horovod_tpu.parallel.mesh import mesh_2d

        b2, m2 = n // 2, 2
        with _forced_wire():
            fsdp_opt = hvd.DistributedOptimizer(
                optax.sgd(0.1, momentum=0.9),
                compression=(hvd.Compression.bf16 if on_tpu
                             else hvd.Compression.none),
                sync_mode="fsdp",
            )
            spec = hvd.reduce_spec_of(fsdp_opt)
            mesh2 = mesh_2d(b2, m2)
            step = _build_step(model, fsdp_opt, mesh2, None, loss_fn,
                               fsdp_spec=spec, world_size=n,
                               mesh2d_shape=(b2, m2))
            sp = hvd.shard_params(params, n)
            stacked = fsdp_opt.init(params)
            resident = {
                "params": param_sharding.resident_param_bytes(sp),
                "opt_state": _tree_bytes(stacked) // max(1, n),
            }
            state = (
                hvd.data_parallel.shard_state(sp, mesh=mesh2),
                hvd.data_parallel.replicate(batch_stats, mesh=mesh2),
                hvd.data_parallel.shard_state(stacked, mesh=mesh2),
            )
            batch2 = hvd.data_parallel.shard_batch((x, y), mesh=mesh2)
            timed = _time_steps(step, state, batch2, **timing)
            return timed, resident

    if raw is not None and n >= 4 and n % 2 == 0 and not out_of_time():
        fsdp_2d = _run_section("resnet_fsdp_2d", run_fsdp_2d, errors)
        if fsdp_2d is not None:
            (t_2d, _), resident_2d = fsdp_2d
            resident_by_mode = dict(
                emit.record.get("resident_bytes_per_rank") or {})
            resident_by_mode["fsdp_2d"] = (
                resident_2d["params"] + resident_2d["opt_state"])
            emit.update(
                vs_baseline_machinery_fsdp_2d=round(raw[0] / t_2d, 4),
                resident_bytes_per_rank=resident_by_mode,
            )

    # --- section 4c4: memory observatory — the analytic footprint model
    # (horovod_tpu/memory.predict_footprint) priced against the measured
    # resident bytes the mode lanes above reported, one row per sync
    # mode that actually ran. drift_ratio is |predicted - measured| /
    # measured — the premerge memory gate asserts the fsdp row stays
    # under 5%. host_peak_rss_bytes (VmHWM) is the host-side high-water
    # mark: on the CPU mesh every "device" buffer is host RAM, so the
    # per-rank predictions must sit comfortably under it.
    def run_memory():
        from horovod_tpu import memory as _memory

        measured = dict(emit.record.get("resident_bytes_per_rank") or {})
        lanes = {
            "monolithic": ("allreduce", None),
            "sharded": ("sharded", None),
            "fsdp": ("fsdp", None),
            "fsdp_2d": ("fsdp", (n // 2, 2)),
        }
        rows = {}
        for mode, got in measured.items():
            sync_mode, shape = lanes.get(mode, (None, None))
            if sync_mode is None:
                continue
            fp = _memory.footprint_of(dist_opt, params, world_size=n,
                                      sync_mode=sync_mode,
                                      mesh_shape=shape)
            want = int(fp["resident_total"])
            rows[mode] = {
                "predicted_resident_bytes": want,
                "measured_resident_bytes": int(got),
                "drift_ratio": (round(abs(want - got) / got, 6)
                                if got else None),
                "predicted_peak_bytes": int(fp["peak_total"]),
            }
        out = {"predicted_vs_measured": rows}
        hwm = _peak_rss_bytes()
        if hwm is not None:
            out["host_peak_rss_bytes"] = hwm
        summary = _memory.summary()
        out["resident_bytes"] = summary.get("resident") or {}
        out["watermark_bytes"] = summary.get("watermarks") or {}
        return out

    if raw is not None:
        memory_lane = _run_section("memory", run_memory, errors)
        if memory_lane is not None:
            emit.update(memory=memory_lane)

    # --- section 4d: per-phase step-time breakdown — forward_backward /
    # collective / optimizer_update medians (the attribution plane's
    # shared phase-span vocabulary, horovod_tpu/attribution.py), derived
    # by differencing phase-probe programs against the headline dist step
    # (one jitted SPMD program cannot be phase-timed from the host, so
    # the probes isolate prefixes of the step):
    #   forward_backward = t(value_and_grad)
    #   optimizer_update = t(grad + bare update, no sync) - t(value_and_grad)
    #   collective       = t(dist step) - t(no-sync step)
    # Recorded as a SYNCED step on the tracing plane — so the trace
    # snapshot and the premerge /timeline + /criticalpath lanes carry
    # the breakdown, and attribution.note_step prices the phase gauges —
    # and as phase_span_medians_ms in this record.
    def run_phases():
        import jax
        from jax.sharding import PartitionSpec as P

        from horovod_tpu import attribution, tracing

        def grad_fn(p, stats, b):
            x, y = b

            def loss_of(q):
                logits, updated = model.apply(
                    {"params": q, "batch_stats": stats}, x, train=True,
                    mutable=["batch_stats"])
                return loss_fn(logits, y), updated["batch_stats"]

            (loss, _), grads = jax.value_and_grad(
                loss_of, has_aux=True)(p)
            # Gradients ride the outputs so nothing is dead-code
            # eliminated; the caller fetches only the loss.
            return jax.lax.pmean(loss, axis), grads

        grad_prog = jax.jit(jax.shard_map(
            grad_fn, mesh=mesh, in_specs=(P(), P(), P(axis)),
            out_specs=(P(), P()), check_vma=False))

        p0 = hvd.data_parallel.replicate(params)
        s0 = hvd.data_parallel.replicate(batch_stats)

        def time_fn(fn):
            for _ in range(max(timing["warmup"], 1)):
                loss = fn()
            jax.block_until_ready(loss)
            times = []
            for _ in range(timing["repeats"]):
                t0 = time.perf_counter()
                for _ in range(timing["iters"]):
                    loss = fn()
                jax.block_until_ready(loss)
                times.append((time.perf_counter() - t0) / timing["iters"])
            return statistics.median(times)

        t_grad = time_fn(lambda: grad_prog(p0, s0, batch)[0])

        raw_opt = optax.sgd(0.1, momentum=0.9)
        nosync_step = _build_step(model, raw_opt, mesh, axis, loss_fn)
        t_nosync, _ = _time_steps(
            nosync_step, fresh_state(raw_opt), batch, **timing)
        t_full = dist[0]
        phases = {
            attribution.SPAN_FORWARD_BACKWARD: max(t_grad, 0.0),
            attribution.SPAN_OPTIMIZER_UPDATE: max(t_nosync - t_grad, 0.0),
            attribution.SPAN_COLLECTIVE: max(t_full - t_nosync, 0.0),
        }
        # One representative step on the tracer: the derived phase spans
        # laid back to back, so the shipped/archived timeline carries the
        # breakdown visually. Marked synced — the durations ARE measured
        # wall time — so attribution.note_step decomposes it into the
        # phase/exposed-comm/MFU gauges the scrape gate asserts, and the
        # shipped payload gives /criticalpath a real group to analyze.
        t_base = tracing.clock_sync().now()
        tracer = tracing.get_tracer()
        with tracer.step_scope("bench_phases") as rec:
            rec.synced = True
            cursor = t_base
            for name, dur in phases.items():
                cat = (attribution.CAT_COLLECTIVE
                       if name == attribution.SPAN_COLLECTIVE
                       else attribution.CAT_PHASE)
                tracer.record(name, cat, cursor, dur,
                              args={"derived": True})
                cursor += dur
        return {f"{k}_ms": round(v * 1e3, 3) for k, v in phases.items()}

    if dist is not None and not out_of_time():
        phase_medians = _run_section("resnet_phases", run_phases, errors)
        if phase_medians is not None:
            emit.update(phase_span_medians_ms=phase_medians)


    # --- section 5: int8 (EQuARX-style) wire, machinery-forced — the
    # quantize -> exchange -> dequant round trip demonstrably executes
    # even on one chip; the ratio shows what the int8 wire costs relative
    # to the raw step (on multi-chip meshes it buys halved ICI bytes).
    def run_int8():
        with _forced_wire():
            int8_opt = hvd.DistributedOptimizer(
                optax.sgd(0.1, momentum=0.9),
                compression=hvd.Compression.int8,
            )
            step = _build_step(model, int8_opt, mesh, axis, loss_fn)
            return _time_steps(step, fresh_state(int8_opt), batch, **timing)

    if raw is not None and not smoke and not out_of_time():
        int8 = _run_section("resnet_int8", run_int8, errors)
        if int8 is not None:
            emit.update(
                vs_baseline_machinery_int8=round(raw[0] / int8[0], 4))

    # --- section 6: comms observatory lane — microprobe the interconnect,
    # fit the online alpha-beta cost model, report fitted alpha/beta + bus
    # bandwidth per (op, algorithm, link_class) and the live efficiency
    # ratio, check the fit predicts the observed per-bucket latencies for
    # all three sync-mode wires within a documented tolerance, and A/B the
    # model-guided autotune pruning against the exhaustive sweep (the
    # pruned grid must pin the SAME winner from the same measurements
    # while dropping at least one dominated candidate). Runs in --smoke:
    # the premerge gates assert this record. See docs/observability.md
    # ("Communication cost model").
    def run_comms():
        import statistics as _stats

        from horovod_tpu import comms_model as cm
        from horovod_tpu.basics import _state as _hvd_state

        # No reset: the flat fits come only from this lane's microprobe
        # anyway (earlier sections are compiled), and the fsdp gather
        # probe's (allgather|fsdp) attribution from section 4c2 should
        # survive into the payload/snapshot.
        model_ = cm.get_model()
        topo = _hvd_state.topology
        link = (topo.set_link_class(list(range(n)))
                if topo is not None else "ici")
        probe_sizes = (4096, 65536, 1 << 20)
        probes = hvd.run_comms_microprobe(
            sizes=probe_sizes, repeats=2 if smoke else 3)
        observed = {
            op: {nb: _stats.median(samples)
                 for nb, samples in per_op.items()}
            for op, per_op in probes.items()
        }
        # Fit-quality check: for each sync mode's wire, the fitted model
        # must predict the observed per-bucket (= per-probe-payload)
        # latency within HOROVOD_COMMS_FIT_TOLERANCE relative error
        # (default 1.0 — a factor-2 band, generous because CPU-smoke
        # medians of 2 are noisy; TPU runs can tighten it).
        tolerance = float(os.environ.get(
            "HOROVOD_COMMS_FIT_TOLERANCE", "1.0"))
        # One wire table: the same per-mode collective halves the
        # autotune predictor prices (a private copy here could silently
        # drift from what predict_flush_cost actually uses).
        per_mode_residual = {}
        for mode, wire in cm._MODE_WIRE.items():
            worst = 0.0
            for nbytes in set().union(*[observed[op].keys()
                                        for op, _ in wire]):
                pred = 0.0
                obs = 0.0
                ok = True
                for op, algo in wire:
                    p = model_.predict(op, algo, link, nbytes)
                    o = observed[op].get(nbytes)
                    if p is None or o is None:
                        ok = False
                        break
                    pred += p
                    obs += o
                if ok and obs > 0:
                    worst = max(worst, abs(pred - obs) / obs)
            per_mode_residual[mode] = round(worst, 4)
        within = all(v <= tolerance for v in per_mode_residual.values())

        # Model-guided autotune A/B on the plane the model prices (the
        # host-observable collective latencies the fit came from):
        # measure the FULL candidate grid once — one eager dispatch per
        # fusion bucket the candidate's (threshold, segments) layout
        # would emit over a synthetic 24-leaf gradient wire — then
        # compare the exhaustive winner (argmin over all measurements)
        # with the model-guided winner (argmin over the KEPT candidates,
        # same measurements). Pruning must drop >=1 dominated point and
        # keep the measured winner — the A/B the premerge gate asserts.
        # The verdict is computed BEFORE the sweep, from the microprobe
        # fit alone, exactly as AutotuneStep prunes before sampling.
        import numpy as np

        leaf_sizes = [(256 * 1024, "float32")] * 24  # 6 MiB wire
        cands = [(64 * 1024, 1), (1 << 20, 1), (16 << 20, 1),
                 (16 << 20, 2)]
        verdict = cm.prune_candidates(cands, leaf_sizes, link)

        def flush_buckets(threshold, segments):
            return [b for run in cm.segment_byte_runs(leaf_sizes,
                                                      segments)
                    for b in cm.bucket_byte_sizes(run, threshold)]

        def measure_flush(threshold, segments, repeats=2):
            samples = []
            arrays = [
                np.ones((n, max(1, b // 4 // n)), np.float32)
                for b in flush_buckets(threshold, segments)]
            for a in arrays:  # warm each signature's executable
                hvd.allreduce(a, op=hvd.Sum)
            for _ in range(repeats):
                t0 = time.perf_counter()
                for a in arrays:
                    hvd.allreduce(a, op=hvd.Sum)
                samples.append(time.perf_counter() - t0)
            return _stats.median(samples)

        measured = [measure_flush(t, s) for t, s in cands]
        winner_ex = cands[int(np.argmin(measured))]
        kept = verdict["kept"]
        kept_times = [(t, c) for c, t in zip(cands, measured)
                      if c in kept]
        winner_guided = min(kept_times)[1] if kept_times else winner_ex

        fits = {k: {kk: d.get(kk) for kk in (
                    "alpha_s", "beta_s_per_byte",
                    "bandwidth_bytes_per_second", "samples", "r2")}
                for k, d in model_.payload()["fits"].items()}
        eff = model_.efficiency()
        return {
            "link_class": link,
            "fits": fits,
            "efficiency_ratio": (round(eff, 4)
                                 if eff is not None else None),
            "residual_s": round(model_.residual_s(), 6),
            "fit_tolerance": tolerance,
            "per_mode_rel_residual": per_mode_residual,
            "within_tolerance": within,
            "autotune_grid": cands,
            "autotune_measured_s": [round(t, 6) for t in measured],
            "autotune_predicted_s": [
                round(c, 6) if c is not None else None
                for c in verdict["costs"]],
            "autotune_pruned": len(verdict["pruned"]),
            "autotune_pruned_candidates": verdict["pruned"],
            "autotune_winner_exhaustive": winner_ex,
            "autotune_winner_guided": winner_guided,
        }

    if not out_of_time():
        comms = _run_section("comms", run_comms, errors)
        if comms is not None:
            emit.update(comms=comms)

    # --- section 6b: comms-planner lane (--smoke included) — the
    # per-bucket collective algorithm axis (ops/comms_planner.py) A/B'd
    # against the flat-pinned wire on two fabrics:
    #   * emulated 2-slice (HOROVOD_LINK_CLASS_MAP=0-3;4-7): the planner
    #     must select two_level for the above-crossover buckets, and the
    #     seed-priced margin (predicted planned vs predicted flat) is
    #     recorded — the CPU mesh cannot emulate a slow DCN link, so the
    #     wall-clock comparison is honest only on the uniform fabric
    #     while the schedule choice + model margin are asserted here;
    #   * uniform single-class fabric: the planner must pick flat and
    #     the planned step must stay within ~2% of the flat-pinned one
    #     (premerge gate 3 enforces both).
    def run_planner():
        import statistics as _stats

        import jax
        from jax.sharding import PartitionSpec as P

        from horovod_tpu.ops import comms_planner as cp
        from horovod_tpu.ops.fusion import fused_allreduce

        if n < 2:
            return {"skipped": "single-device world (nothing to plan)"}
        mesh_ = hvd.global_mesh()
        axis_ = hvd.global_axis_name()
        leaf_elems = 256 * 1024  # 1 MiB/leaf: above the seed crossover
        n_leaves = 4
        bucket_bytes = leaf_elems * 4
        leaves = [np.ones((n, leaf_elems), np.float32)
                  for _ in range(n_leaves)]

        def build_flush():
            def body(*vs):
                ls = [v[0] for v in vs]
                out = fused_allreduce(ls, op=hvd.Sum, axis_name=axis_,
                                      threshold_bytes=1, world_size=n)
                return tuple(o[None] for o in out)

            return jax.jit(jax.shard_map(
                body, mesh=mesh_,
                in_specs=(P(axis_),) * n_leaves,
                out_specs=(P(axis_),) * n_leaves, check_vma=False))

        @contextlib.contextmanager
        def fabric(planner=None, lmap=None):
            prev = {k: os.environ.get(k)
                    for k in ("HOROVOD_COMMS_PLANNER",
                              "HOROVOD_LINK_CLASS_MAP")}
            try:
                for k, v in (("HOROVOD_COMMS_PLANNER", planner),
                             ("HOROVOD_LINK_CLASS_MAP", lmap)):
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v
                cp.reset_for_testing()
                yield
            finally:
                for k, v in prev.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v
                cp.reset_for_testing()

        def compile_flush():
            prog = build_flush()
            jax.block_until_ready(prog(*leaves))  # compile + settle
            return prog

        def time_interleaved(progs, windows=5, iters=10):
            """Median window time per program, windows INTERLEAVED
            (A/B/A/B/...) so host-load drift during the lane hits both
            sides equally — the flat-parity gate compares two copies of
            the SAME compiled program on the uniform fabric, where
            sequential timing would gate on noise."""
            samples: list[list[float]] = [[] for _ in progs]
            for _ in range(windows):
                for prog, acc in zip(progs, samples):
                    t0 = time.perf_counter()
                    for _ in range(iters):
                        out = prog(*leaves)
                    jax.block_until_ready(out)
                    acc.append((time.perf_counter() - t0) / iters)
            return [_stats.median(sorted(acc)) for acc in samples]

        emu_map = ";".join(
            f"{i * (n // 2)}-{(i + 1) * (n // 2) - 1}" for i in range(2)
        ) if n % 2 == 0 else None
        record = {"world": n, "bucket_bytes": bucket_bytes,
                  "emulated_map": emu_map}
        with fabric():
            uniform_flat = compile_flush()
            flat_text = uniform_flat.lower(*leaves).as_text()
        with fabric(planner="auto"):
            plan = cp.plan_bucket("allreduce", bucket_bytes, n)
            record["uniform_selected_algorithm"] = (
                plan.algorithm if plan else "flat")
            uniform_planned = compile_flush()
            planned_text = uniform_planned.lower(*leaves).as_text()
        # Parity on the uniform fabric is PROVABLE, not just measurable:
        # the planner picks flat there, so the two lowerings must be
        # byte-identical — in which case wall parity holds by
        # construction and the timed comparison below is informational
        # (on a loaded CPU box identical programs time ±20% apart; the
        # premerge gate falls back to the 2% wall check only when the
        # programs actually diverge).
        record["uniform_program_identical"] = flat_text == planned_text
        t_flat, t_planned = time_interleaved([uniform_flat,
                                              uniform_planned])
        record["uniform_flat_step_s"] = round(t_flat, 6)
        record["uniform_planned_step_s"] = round(t_planned, 6)
        if emu_map is not None:
            with fabric(lmap=emu_map):
                split_flat = compile_flush()
            with fabric(planner="auto", lmap=emu_map):
                plan = cp.plan_bucket("allreduce", bucket_bytes, n)
                record["split_selected_algorithm"] = (
                    plan.algorithm if plan else "flat")
                record["split_provenance"] = (
                    plan.provenance if plan else None)
                costs = plan.costs if plan else {}
                record["split_predicted_planned_s"] = (
                    round(costs.get(plan.algorithm), 9)
                    if plan and plan.algorithm in costs else None)
                record["split_predicted_flat_s"] = (
                    round(costs["flat"], 9) if "flat" in costs else None)
                split_planned = compile_flush()
            t_flat, t_planned = time_interleaved([split_flat,
                                                  split_planned])
            record["split_flat_step_s"] = round(t_flat, 6)
            record["split_planned_step_s"] = round(t_planned, 6)
        return record

    if not out_of_time():
        planner_lane = _run_section("planner", run_planner, errors)
        if planner_lane is not None:
            emit.update(planner=planner_lane)

    # --- section 6c: expert-parallel MoE lane (--smoke included) — the
    # alltoall sync path (parallel/moe.py) A/B'd against the dense
    # data-parallel MoE baseline. Both layers run identical routing and
    # identical per-rank FFN FLOPs (E·capacity token slots through one
    # D→H→D expert each); the EP side adds the real dispatch/combine
    # exchanges and in return shards the expert weights 1/E per rank —
    # a memory win a virtual CPU mesh cannot cash in, so on the smoke
    # fabric EP ≤ DP by construction and premerge gate 3's floor guards
    # a pathologically slow wire, not parity. The dispatch-probe A/B
    # times the quantized (int8) vs fp32 wire in isolation.
    def run_moe():
        import statistics as _stats

        from horovod_tpu import attribution
        from horovod_tpu.parallel import moe as moe_mod

        if n < 2:
            return {"skipped": "single-device world (no expert set)"}
        tok_per_rank, d_model, d_ff = (16, 64, 128) if smoke \
            else (64, 128, 256)
        cap = 8
        rng = np.random.RandomState(7)
        tokens = rng.randn(n * tok_per_rank, d_model).astype(np.float32)
        gates = rng.randn(d_model, n).astype(np.float32)
        w1 = rng.randn(n, d_model, d_ff).astype(np.float32)
        w2 = rng.randn(n, d_ff, d_model).astype(np.float32)
        args = (tokens, gates, w1, w2)
        dp_step = moe_mod.make_data_parallel_moe_step(capacity=cap,
                                                      segments=2)
        ep_step = moe_mod.make_expert_parallel_moe_step(capacity=cap,
                                                        segments=2)
        ep_int8 = moe_mod.make_expert_parallel_moe_step(
            capacity=cap, segments=2, compression="int8")

        def time_interleaved(fns, probe_args, windows, iters):
            # Interleaved A/B windows, same rationale as the planner
            # lane: host-load drift hits every side equally.
            samples: list[list[float]] = [[] for _ in fns]
            for fn in fns:
                jax.block_until_ready(fn(*probe_args))  # compile
            for _ in range(windows):
                for fn, acc in zip(fns, samples):
                    t0 = time.perf_counter()
                    for _ in range(iters):
                        out = fn(*probe_args)
                    jax.block_until_ready(out)
                    acc.append((time.perf_counter() - t0) / iters)
            return [_stats.median(sorted(a)) for a in samples]

        windows, iters = (2, 2) if smoke else (5, 10)
        t_dp, t_ep, t_ep8 = time_interleaved(
            [dp_step, ep_step, ep_int8], args, windows, iters)
        toks = n * tok_per_rank
        # Analytic routed-FFN FLOPs/step (forward): every kept token
        # does x@w1 + h@w2 = 4·D·H; declared to the attribution plane
        # so the MoE step exports hvd_mfu_ratio, then restored so the
        # resnet sections' constant survives the lane.
        moe_flops = 4.0 * d_model * d_ff * toks
        prev_flops, _ = attribution.model_flops()
        hvd.set_model_flops_per_step(moe_flops)
        try:
            with hvd.tracing.get_tracer().step_scope("moe_step"):
                jax.block_until_ready(ep_step(*args))
        finally:
            hvd.set_model_flops_per_step(prev_flops)
        mfu = (moe_flops / (t_ep * peak * n)
               if peak is not None else None)
        t_probe32, t_probe8 = time_interleaved(
            [ep_step.dispatch_probe, ep_int8.dispatch_probe],
            (tokens, gates), windows, iters)
        return {
            "world": n, "tokens_per_step": toks, "capacity": cap,
            "segments": ep_step.meta["segments"],
            "algorithm": ep_step.meta["algorithm"],
            "dispatch_bytes_fp32": ep_step.meta["nbytes"],
            "dispatch_bytes_int8": ep_int8.meta["nbytes"],
            "dp_tokens_per_sec": round(toks / t_dp, 1),
            "ep_tokens_per_sec": round(toks / t_ep, 1),
            "ep_int8_tokens_per_sec": round(toks / t_ep8, 1),
            "ep_vs_dp": round(t_dp / t_ep, 4),
            "mfu": round(mfu, 6) if mfu is not None else None,
            "dispatch_probe_fp32_s": round(t_probe32, 6),
            "dispatch_probe_int8_s": round(t_probe8, 6),
            "dispatch_int8_vs_fp32": round(t_probe32 / t_probe8, 4),
        }

    if not out_of_time():
        moe_lane = _run_section("moe", run_moe, errors)
        if moe_lane is not None:
            emit.update(moe=moe_lane)

    # --- section 6c: serving lane — inference latency under concurrent
    # hot-swap (the training→serving bridge's RCU pointer flip,
    # horovod_tpu/serving.py). Pure host math, no collectives: an
    # in-process ModelServer takes a storm of installs on one thread
    # while this thread hammers reads, measuring request p50/p99 with
    # the swaps landing mid-stream, the swap-latency distribution, and
    # — the robustness headline — that not one read observed a torn
    # model (the params a request sees always match the digest the same
    # snapshot claims). Runs in --smoke: premerge gate 4 scrapes the
    # hvd_serve_* instruments this lane exercises.
    def run_serving():
        import statistics as _stats
        import threading as _threading

        from horovod_tpu import serving as _serving

        swaps_target = 30 if smoke else 100
        server = _serving.ModelServer()
        swap_ms: list = []

        def _install(k: int) -> bool:
            payload = np.full(1024, k, np.float32)
            t0 = time.perf_counter()
            ok = server.install(payload, generation=0, step=k,
                                digest=f"model-{k}")
            if ok:
                swap_ms.append((time.perf_counter() - t0) * 1e3)
            return ok

        _install(0)
        stop = _threading.Event()

        def _swapper():
            k = 1
            while not stop.is_set() and k <= swaps_target:
                _install(k)
                k += 1
                time.sleep(0.001)
            stop.set()

        torn = 0
        req_ms: list = []
        swapper = _threading.Thread(target=_swapper, daemon=True)
        swapper.start()
        while not stop.is_set():
            t0 = time.perf_counter()
            model = server.current()
            k = int(model.digest.rsplit("-", 1)[1])
            if not (model.params == k).all() or model.step != k:
                torn += 1
            req_ms.append((time.perf_counter() - t0) * 1e3)
        swapper.join(timeout=30)
        req_ms.sort()
        return {
            "swaps": len(swap_ms),
            "torn_reads": torn,
            "requests": len(req_ms),
            "request_p50_ms": round(_stats.median(req_ms), 6),
            "request_p99_ms": round(
                req_ms[min(len(req_ms) - 1,
                           int(len(req_ms) * 0.99))], 6),
            "swap_p50_ms": round(_stats.median(swap_ms), 6),
            "swap_p99_ms": round(max(swap_ms), 6),
        }

    if not out_of_time():
        serving_lane = _run_section("serving", run_serving, errors)
        if serving_lane is not None:
            emit.update(serving=serving_lane)

    # --- section 7: attribution lane — the framework-side decomposition
    # of the bench_phases step (compute / exposed_comm / straggler_wait /
    # overhead summing to the step wall time), the measured
    # overlap-hidden ratio, MFU (TPU only — the analytic constant), and
    # the alpha-beta model's predicted-vs-observed exposed-comm residual
    # (real now: section 6 just fitted the model). BENCH_r*.json thereby
    # records where the step time went through the SAME plane operators
    # scrape, not just the bench-local medians. Runs in --smoke: the
    # premerge /criticalpath gate rides the trace this lane's
    # bench_phases step shipped.
    def run_attribution():
        from horovod_tpu import attribution

        summary = attribution.summary()
        last = summary.get("last_step") or {}
        return {
            "phases_ms": {k: round(v * 1e3, 3)
                          for k, v in (last.get("phases") or {}).items()},
            "wall_ms": (round(last["wall_s"] * 1e3, 3)
                        if last.get("wall_s") is not None else None),
            "overlap_hidden_ratio": last.get("overlap_hidden_ratio"),
            "mfu": last.get("mfu"),
            "exposed_comm_predicted_s":
                summary.get("exposed_comm_predicted_s"),
            "exposed_comm_residual_s":
                summary.get("exposed_comm_residual_s"),
            "sentinel_steps": (summary.get("sentinel") or {}).get(
                "steps_observed"),
        }

    if dist is not None and not out_of_time():
        att_lane = _run_section("attribution", run_attribution, errors)
        if att_lane is not None:
            emit.update(attribution=att_lane)

    if errors:
        emit.record["errors"] = errors
    # One cache/dispatch snapshot per run: how many eager dispatches ran
    # and how the executable cache behaved while producing these numbers.
    try:
        emit.record["cache_stats"] = hvd.cache_stats()
    except Exception as exc:  # noqa: BLE001 — observability only
        print(f"# bench: cache_stats unavailable: {exc}", file=sys.stderr)
    # Goodput ledger (productive seconds accrued by the timed sections
    # above): every bench record carries where its wall time went.
    try:
        emit.record["goodput"] = hvd.metrics.goodput().summary()
    except Exception as exc:  # noqa: BLE001 — observability only
        print(f"# bench: goodput unavailable: {exc}", file=sys.stderr)
    # HOROVOD_METRICS_SNAPSHOT=/path: dump the full instrument snapshot
    # (the same families a worker piggybacks on heartbeats) so the
    # premerge metrics lane can publish THIS run's numbers to a real KV
    # server and scrape them back over /metrics. A tiny eager allreduce
    # runs first so the collective latency/byte histograms carry at
    # least one real dispatch even in all-compiled runs.
    snap_path = os.environ.get("HOROVOD_METRICS_SNAPSHOT", "")
    if snap_path:
        try:
            import json as _json

            hvd.allreduce(np.ones((n, 8), np.float32), op=hvd.Sum)
            with open(snap_path, "w") as f:
                _json.dump(hvd.metrics.snapshot(), f)
            print(f"# bench: metrics snapshot written to {snap_path}",
                  file=sys.stderr)
        except Exception as exc:  # noqa: BLE001 — observability only
            print(f"# bench: metrics snapshot failed: {exc}",
                  file=sys.stderr)
    # HOROVOD_TRACE_SNAPSHOT=/path: dump this run's trace payload (the
    # same wire format a worker ships to PUT /trace/<host>) so the
    # premerge timeline lane can publish it to a real KV server and fetch
    # the merged GET /timeline back over HTTP.
    trace_path = os.environ.get("HOROVOD_TRACE_SNAPSHOT", "")
    if trace_path:
        try:
            import json as _json

            from horovod_tpu import tracing as _tracing

            with open(trace_path, "w") as f:
                _json.dump(_tracing.get_tracer().payload(), f)
            print(f"# bench: trace snapshot written to {trace_path}",
                  file=sys.stderr)
        except Exception as exc:  # noqa: BLE001 — observability only
            print(f"# bench: trace snapshot failed: {exc}",
                  file=sys.stderr)
    # HOROVOD_COMMS_SNAPSHOT=/path: dump this run's comms-model payload
    # (the same wire format a worker piggybacks on heartbeats) so the
    # premerge gate can publish it to a live KV server as two ranks and
    # fetch the cluster-merged GET /comms back over HTTP.
    comms_path = os.environ.get("HOROVOD_COMMS_SNAPSHOT", "")
    if comms_path:
        try:
            import json as _json

            from horovod_tpu import comms_model as _comms_model

            with open(comms_path, "w") as f:
                _json.dump(_comms_model.get_model().payload(), f)
            print(f"# bench: comms snapshot written to {comms_path}",
                  file=sys.stderr)
        except Exception as exc:  # noqa: BLE001 — observability only
            print(f"# bench: comms snapshot failed: {exc}",
                  file=sys.stderr)
    # HOROVOD_MEMORY_SNAPSHOT=/path: dump this run's memory-observatory
    # payload (the same wire format a worker piggybacks on heartbeats)
    # so the premerge gate can publish it to a live KV server as two
    # ranks and fetch the cluster-merged GET /memory back over HTTP.
    memory_path = os.environ.get("HOROVOD_MEMORY_SNAPSHOT", "")
    if memory_path:
        try:
            import json as _json

            from horovod_tpu import memory as _memory

            with open(memory_path, "w") as f:
                _json.dump(_memory.get_observatory().payload(), f)
            print(f"# bench: memory snapshot written to {memory_path}",
                  file=sys.stderr)
        except Exception as exc:  # noqa: BLE001 — observability only
            print(f"# bench: memory snapshot failed: {exc}",
                  file=sys.stderr)
    emit.update(bench_wall_time_s=round(time.perf_counter() - t_start, 1))
    return _exit_code(dist, errors)


if __name__ == "__main__":
    sys.exit(main())
