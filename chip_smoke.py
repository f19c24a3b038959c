"""Chip smoke test: BERT-Large through the product's own training path.

    python chip_smoke.py

One process, no children. On a TPU it takes a few training steps of
BERT-Large at its full published width (24 x 1024, 16 heads, FFN 4096,
vocabulary 30,522; sequences of 512, 24 per chip, 76 masked positions)
through ``hvd.init`` -> ``hvd.DistributedOptimizer`` ->
``hvd.data_parallel.make_train_step`` on every chip JAX sees, checks what
came out, and prints as its last line

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Anywhere else it exits non-zero before building a model. No phase is
caught: any failure is a traceback and a non-zero exit. The times and
sizes it prints are information about this one run, not a benchmark.

``run`` takes the sync mode so ``sharded`` and ``fsdp`` can be driven
through the same code: ``python -c "import chip_smoke;
chip_smoke.run('fsdp')"``.
"""

from __future__ import annotations

import dataclasses
import importlib.metadata
import json
import re
import statistics
import time

SEQ_LEN = 512
PER_CHIP_BATCH = 24
MASKED_POSITIONS = 76
TIMED_STEPS = 5  # after the compiling one


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def build_step(sync_mode: str):
    """BERT-Large through the product factory: ``(config, optimizer,
    train step)``. Shared with ``tools/aot_check.py`` so the sandbox's
    ahead-of-time compile is of this very program."""
    import optax

    import horovod_tpu as hvd
    from horovod_tpu.models import bert

    cfg = dataclasses.replace(bert.BERT_LARGE, dropout_rate=0.0)
    model = bert.Bert(cfg, attention_fn=bert.flash_attention_fn)

    def loss_fn(params, batch):
        ids, positions, labels, label_mask = batch
        _, logits = model.apply({"params": params}, ids, train=True,
                                masked_positions=positions)
        return bert.mlm_loss(logits, labels, label_mask)

    opt = hvd.DistributedOptimizer(
        optax.adamw(1e-4), compression=hvd.Compression.bf16,
        sync_mode=sync_mode)
    return cfg, opt, hvd.data_parallel.make_train_step(loss_fn, opt)


def init_params(cfg, key):
    """Random BERT weights. They depend neither on the attention function
    nor on the input length, so they come from the plain model on a short
    input: one small compile instead of a second copy of the big one."""
    import jax.numpy as jnp

    from horovod_tpu.models import bert

    return bert.Bert(cfg).init(key, jnp.zeros((1, 8), jnp.int32))["params"]


_COLLECTIVE = re.compile(
    r"= (.*?) (all-reduce|reduce-scatter|all-gather)(?:-start)?\(")


def collective_counts(hlo: str) -> dict:
    """Collective instructions in a compiled step's HLO text, by opcode
    (async pairs counted once, by their ``-start``), and how many of them
    carry bf16 — the compressed gradient wire."""
    counts = {"all-reduce": 0, "reduce-scatter": 0, "all-gather": 0,
              "bf16": 0}
    for result_type, op in _COLLECTIVE.findall(hlo):
        counts[op] += 1
        counts["bf16"] += "bf16[" in result_type
    return counts


def pallas_call_count(hlo: str) -> int:
    return hlo.count('custom_call_target="tpu_custom_call"')


def run(sync_mode: str = "allreduce") -> dict:
    """Train BERT-Large, uncut, for ``1 + TIMED_STEPS`` steps on one fixed
    batch and check the run."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU; JAX found backend={backend!r} "
            f"({len(jax.devices())} x {jax.devices()[0].device_kind!r}). "
            "Not running on anything else.")

    import jaxlib
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.attribution import peak_flops_for_kind
    from horovod_tpu.memory import footprint_of, local_device_memory_stats
    from horovod_tpu.topology import Topology

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"device: platform={device['platform']} "
          f"device_kind={device['kind']!r} count={device['count']}")
    print(f"versions: jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
          f"libtpu {importlib.metadata.version('libtpu')}")
    # An unknown device kind is an error here, not a missing number.
    print(f"peak: {peak_flops_for_kind(device['kind']) / 1e12:.0f} "
          "TFLOP/s bf16 per chip (attribution.CHIP_PEAK_FLOPS)")

    # From here on the program keeps JAX's account of compiling
    # (hvd.cache_stats()["compile"]), the step's first call by itself.
    print(f"compile cache: {hvd.enable_compile_cache()}")

    hvd.init()
    n = hvd.size()
    _require(n == jax.device_count(),
             f"hvd.size()={n} but jax.device_count()={jax.device_count()}")
    rank_order = hvd.global_mesh().devices.flatten().tolist()
    topology = Topology(rank_order)
    _require(topology.devices == rank_order,
             "the global mesh is not in Topology's rank order")
    print(topology.describe())

    cfg, opt, step = build_step(sync_mode)

    # -- synthetic batch and random weights from fixed seeds ----------------
    global_batch = PER_CHIP_BATCH * n
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (global_batch, SEQ_LEN))
    positions = np.stack([rng.choice(SEQ_LEN, MASKED_POSITIONS, replace=False)
                          for _ in range(global_batch)])
    labels = rng.randint(0, cfg.vocab_size, (global_batch, MASKED_POSITIONS))
    batch = hvd.data_parallel.shard_batch((
        ids.astype(np.int32), positions.astype(np.int32),
        labels.astype(np.int32),
        np.ones((global_batch, MASKED_POSITIONS), np.int32)))

    params = jax.jit(lambda key: init_params(cfg, key))(
        jax.random.PRNGKey(0))
    n_params = sum(int(l.size) for l in jax.tree.leaves(params))
    predicted = footprint_of(opt, params, world_size=n)
    opt_state = opt.init(params)
    if sync_mode == "allreduce":
        opt_state = hvd.data_parallel.replicate(opt_state)
    else:
        opt_state = hvd.shard_state(opt_state)
    if sync_mode == "fsdp":
        params = hvd.shard_state(hvd.shard_params(params))
    else:
        params = hvd.data_parallel.replicate(params)
    print(f"model: BERT-Large {cfg.num_layers} x {cfg.hidden_size}, "
          f"{n_params / 1e6:.1f} M parameters, sync_mode={sync_mode}, "
          f"global batch {global_batch} x {SEQ_LEN}")

    # -- one compiling step, then timed steps on the same batch -------------
    def one_step(params, opt_state):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, batch)
        jax.block_until_ready((params, opt_state, loss))
        seconds = time.perf_counter() - t0
        return params, opt_state, float(loss), seconds

    params, opt_state, loss, first_step_s = one_step(params, opt_state)
    compile_events = hvd.cache_stats()["compile"]["steps"]["train_step"][
        "first_call"]
    losses, step_s = [loss], []
    for _ in range(TIMED_STEPS):
        params, opt_state, loss, seconds = one_step(params, opt_state)
        losses.append(loss)
        step_s.append(seconds)
    print("losses: " + " ".join(f"{x:.6f}" for x in losses))
    _require(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    _require(losses[-1] < losses[0],
             f"loss did not fall: {losses[0]} -> {losses[-1]}")

    # -- what was compiled: the kernels and the wire -------------------------
    # jit memoises the executable of the call above, so this compiles
    # nothing; the seconds are printed so a second compile would show.
    t0 = time.perf_counter()
    hlo = step.lower(params, opt_state, batch).compile().as_text()
    hlo_s = time.perf_counter() - t0
    pallas_calls = pallas_call_count(hlo)
    collectives = collective_counts(hlo)
    print(f"hlo: {pallas_calls} Pallas custom calls, collectives "
          f"{collectives} ({hlo_s:.1f} s to fetch)")
    _require(pallas_calls >= 2 * cfg.num_layers,
             f"{pallas_calls} Pallas custom calls in the step, expected a "
             f"forward and a backward kernel in each of {cfg.num_layers} "
             "layers: a kernel gave way to something else")
    if n > 1:
        # The loss's pmean is an all-reduce in every mode, so the gradient
        # wire is told by its dtype.
        _require(collectives["all-reduce"] > 0 and collectives["bf16"] > 0,
                 "the multi-chip step holds no all-reduce, or no collective "
                 "carries the bf16 gradient wire")

    # -- every chip took part -------------------------------------------------
    def devices_holding(tree):
        return set.intersection(*(
            {s.device for s in leaf.addressable_shards}
            for leaf in jax.tree.leaves(tree)))

    _require(devices_holding(batch) == set(devices),
             "some device holds no shard of the batch")
    _require(all(s.data.shape[0] == PER_CHIP_BATCH
                 for leaf in batch for s in leaf.addressable_shards),
             f"a batch shard is not {PER_CHIP_BATCH} sequences")
    _require(devices_holding(params) == set(devices),
             "some device holds no parameters")
    if n > 1 and sync_mode != "fsdp":
        # fsdp keeps no replicas: each device holds its own rows.
        for leaf in jax.tree.leaves(params):
            first, *rest = (np.asarray(s.data).view(np.uint32)
                            for s in leaf.addressable_shards)
            _require(all(np.array_equal(first, other) for other in rest),
                     "parameter replicas differ between devices")
        print(f"replicas: {n} bit-identical copies of every parameter")
    memory = local_device_memory_stats()
    gib = 2 ** 30
    for stats in memory:
        # Buffers and a loaded program's temporaries are counted apart
        # (the step's executable is still loaded here); the two peaks need
        # not coincide, so their sum is an upper bound.
        print(f"hbm: device {stats['id']} buffers "
              f"{stats.get('bytes_in_use', 0) / gib:.2f} GiB now, "
              f"{stats.get('peak_bytes_in_use', 0) / gib:.2f} GiB at peak; "
              f"program temporaries "
              f"{stats.get('bytes_reserved', 0) / gib:.2f} GiB now, "
              f"{stats.get('peak_bytes_reserved', 0) / gib:.2f} GiB at peak; "
              f"limit {stats.get('bytes_limit', 0) / gib:.2f} GiB")
        _require(stats.get("peak_bytes_in_use", 0) > 0,
                 f"device {stats['id']} reports no memory in use")
    print(f"hbm predicted (memory.footprint_of): resident "
          f"{predicted['resident_total'] / gib:.2f} GiB per device")

    cache_hit = (compile_events["cache_hits"] > 0
                 and compile_events["cache_misses"] == 0)
    print(f"first step: {first_step_s:.1f} s, of which tracing "
          f"{compile_events['trace_s']:.1f} s, lowering "
          f"{compile_events['lower_s']:.1f} s, backend compile "
          f"{compile_events['backend_compile_s']:.1f} s; persistent cache "
          f"hits={compile_events['cache_hits']} "
          f"misses={compile_events['cache_misses']} "
          f"-> {'served from the cache' if cache_hit else 'compiled'}")
    print(f"steps: median {statistics.median(step_s) * 1e3:.1f} ms over "
          f"{TIMED_STEPS} (min {min(step_s) * 1e3:.1f}, max "
          f"{max(step_s) * 1e3:.1f}), {global_batch * SEQ_LEN} tokens each")
    return {
        "device": device, "sync_mode": sync_mode, "losses": losses,
        "first_step_s": first_step_s, "cache_hit": cache_hit,
        "backend_compile_s": compile_events["backend_compile_s"],
        "step_ms": [s * 1e3 for s in step_s], "memory": memory,
        "predicted_resident_bytes": predicted["resident_total"],
        "pallas_calls": pallas_calls, "collectives": collectives,
    }


if __name__ == "__main__":
    print(json.dumps({"ok": True, "device": run()["device"]}))
