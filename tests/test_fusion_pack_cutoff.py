"""What a bucket of ``ops.fusion.fused_allreduce`` packs: a leaf of at least
``PACK_CUTOFF_BYTES`` is reduced as itself, in its own shape, and only the
leaves under it share the bucket's flat vector. The same rule on the
``sharded`` and ``fsdp`` wires (``fused_reducescatter``,
``fused_allgather_shards``): a large leaf is scattered and gathered as
itself, the small ones share the bucket's ``(world, R)`` block. Four host
devices, through ``shard_map``; the structure is read from the traced
jaxpr."""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu import comms_model, metrics, optimizer
from horovod_tpu.compression import Compression
from horovod_tpu.ops import comms_planner, fusion, quantization
from horovod_tpu.ops.collective_ops import Average, Max, Min, Sum

N = 4
CUTOFF = fusion.PACK_CUTOFF_BYTES
# Two float32 leaves at and over the cutoff among small ones, then two
# small bfloat16 leaves (another dtype: a bucket of their own).
SHAPES = [((512, 512), np.float32), ((7,), np.float32), ((3, 5), np.float32),
          ((512, 1024), np.float32), ((33,), np.float32),
          ((9,), jnp.bfloat16), ((2, 4), jnp.bfloat16)]
NBYTES = [int(np.prod(shape)) * jnp.dtype(dtype).itemsize
          for shape, dtype in SHAPES]
LARGE = [i for i, nbytes in enumerate(NBYTES) if nbytes >= CUTOFF]
# 2.5 MiB: the 2 MiB leaf does not fit behind the 1 MiB one.
THRESHOLD = 5 * CUTOFF // 2
BUCKETS = [[0, 1, 2], [3, 4], [5, 6]]
# What each bucket packs: its small leaves, where there are two or more (in
# the second bucket, the one small leaf is reduced as itself as well).
PACKED = [[1, 2], [], [5, 6]]
PLAIN = {Sum: lax.psum, Average: lax.pmean, Min: lax.pmin, Max: lax.pmax}


def leaves(seed=0, shapes=SHAPES):
    """Stacked ``(N, *shape)`` leaves of small whole numbers: every sum,
    mean over four and scale by a power of two is exact in either dtype."""
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randint(-4, 5, size=(N,) + shape), dtype)
            for shape, dtype in shapes]


def sharded(body, axes=("w",), shape=(N,)):
    """``body(list of a rank's leaves) -> list`` over the mesh, jitted."""
    mesh = Mesh(np.array(jax.devices()[:N]).reshape(shape), axes)
    spec = P(axes if len(axes) > 1 else axes[0])

    def per_rank(*stacked):
        # squeeze, not [0]: no slice in the jaxpr but the wire's own.
        return tuple(out[None] for out in body(
            [jnp.squeeze(s, 0) for s in stacked]))

    return jax.jit(jax.shard_map(per_rank, mesh=mesh, in_specs=spec,
                                 out_specs=spec, check_vma=False))


def fused(op=Sum, axis="w", **kwargs):
    kwargs.setdefault("threshold_bytes", THRESHOLD)
    return lambda ls: fusion.fused_allreduce(ls, op, axis, **kwargs)


def equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                yield from equations(inner)


def traced(fn, *args):
    return list(equations(jax.make_jaxpr(fn)(*args).jaxpr))


def nbytes_of(var) -> int:
    return int(np.prod(var.aval.shape)) * var.aval.dtype.itemsize


def packs_a_large_leaf(eqns, least=CUTOFF) -> bool:
    """``least``: the bytes of a large leaf where it is concatenated (a
    gather concatenates shards, a world-th of a leaf each)."""
    return any(nbytes_of(operand) >= least for eqn in eqns
               if eqn.primitive.name == "concatenate"
               for operand in eqn.invars)


@pytest.mark.parametrize("scales", [(1.0, 1.0), (0.5, 4.0)],
                         ids=["unscaled", "scaled"])
@pytest.mark.parametrize("issue_reversed", [False, True],
                         ids=["in_order", "reversed"])
@pytest.mark.parametrize("op", [Sum, Average, Min, Max])
def test_a_mixed_list_reduces_to_what_a_plain_collective_a_leaf_gives(
        op, issue_reversed, scales):
    pre, post = scales

    def plain(ls):
        return [PLAIN[op](leaf * jnp.asarray(pre, leaf.dtype), "w")
                * jnp.asarray(post, leaf.dtype) for leaf in ls]

    xs = leaves()
    got = sharded(fused(op, prescale_factor=pre, postscale_factor=post,
                        issue_reversed=issue_reversed))(*xs)
    want = sharded(plain)(*xs)
    for g, w, (shape, dtype) in zip(got, want, SHAPES):
        assert g.shape == (N,) + shape and g.dtype == dtype
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


@pytest.mark.parametrize("issue_reversed", [False, True],
                         ids=["in_order", "reversed"])
def test_a_large_leaf_is_neither_concatenated_nor_cut_back(issue_reversed):
    eqns = traced(sharded(fused(Average, issue_reversed=issue_reversed)),
                  *leaves())
    concatenates = [e for e in eqns if e.primitive.name == "concatenate"]
    # One packed vector a bucket, of its small leaves alone.
    assert sorted(sorted(v.aval.shape[0] for v in e.invars)
                  for e in concatenates) == sorted(
        sorted(int(np.prod(SHAPES[i][0])) for i in packed)
        for packed in PACKED if packed)
    assert not packs_a_large_leaf(eqns)
    sliced = [nbytes_of(e.outvars[0]) for e in eqns
              if e.primitive.name in ("slice", "dynamic_slice")]
    assert len(sliced) == sum(map(len, PACKED)) and max(sliced) < CUTOFF
    # A large leaf rides the collective in its own shape, not ravelled.
    reduced = [v.aval.shape for e in eqns if e.primitive.name == "psum"
               for v in e.invars]
    assert all(SHAPES[i][0] in reduced for i in LARGE)
    assert len(reduced) == len(SHAPES) - sum(map(len, PACKED)) + sum(
        1 for packed in PACKED if packed)


def test_a_lone_small_leaf_is_reduced_as_itself():
    xs = [leaves()[1]]
    eqns = traced(sharded(fused(Sum)), *xs)
    assert {e.primitive.name for e in eqns} & {
        "concatenate", "slice", "reshape"} == set()


def plan_rhd(monkeypatch):
    monkeypatch.setenv("HOROVOD_COMMS_PLANNER", "rhd")
    comms_planner.reset_for_testing()


def planned(monkeypatch):
    plan_rhd(monkeypatch)
    return sharded(fused(Sum, world_size=N)), Sum


def two_axes(monkeypatch):
    return sharded(fused(Sum, axis=("cross", "local")),
                   axes=("cross", "local"), shape=(2, 2)), Sum


def int8(monkeypatch):
    return sharded(lambda ls: quantization.int8_fused_allreduce(
        ls, "w", N, op=Average, threshold_bytes=THRESHOLD)), None


@pytest.mark.parametrize("wire", [planned, two_axes, int8])
def test_a_schedule_over_one_vector_still_packs_whole_buckets(
        wire, monkeypatch):
    fn, exact_op = wire(monkeypatch)
    xs = leaves()[:5]  # int8 quantizes float32
    try:
        eqns = traced(fn, *xs)
        got = fn(*xs)
    finally:
        monkeypatch.undo()
        comms_planner.reset_for_testing()
    assert packs_a_large_leaf(eqns)
    if exact_op is not None:
        for g, x in zip(got, xs):
            np.testing.assert_array_equal(
                np.asarray(g[0]), np.asarray(x).sum(0))


def flush(compression, monkeypatch=None, planner=None):
    """Trace one gradient flush of ``optimizer._reduce_grads`` and return
    the gauges it left: ``(wire bytes, packed bytes)``."""
    if planner:
        monkeypatch.setenv("HOROVOD_COMMS_PLANNER", planner)
        comms_planner.reset_for_testing()

    def body(ls):
        return optimizer._reduce_grads(
            ls, Average, "w", compression, 1.0, 1.0, THRESHOLD, 0,
            world_size=N)

    jax.make_jaxpr(sharded(body))(*leaves()[:5])
    return tuple(int(gauge.labels(sync_mode="allreduce").get()) for gauge in (
        metrics.GRAD_SYNC_LAST_BYTES, metrics.GRAD_SYNC_LAST_PACKED_BYTES))


def test_the_gauge_counts_the_small_leaves_bytes():
    # float32 on the wire: leaves 0 and 3 ride alone, and so does leaf 4,
    # the one small leaf of its bucket.
    assert flush(Compression.none) == (
        sum(NBYTES[:5]), sum(NBYTES[i] for i in PACKED[0]))
    # bf16 halves every leaf: one bucket holds all five, and 512 x 512
    # falls under the cutoff and packs with the three small ones.
    assert flush(Compression.bf16) == (
        sum(NBYTES[:5]) // 2, sum(NBYTES[i] for i in (0, 1, 2, 4)) // 2)


def test_the_gauge_counts_every_byte_of_a_planned_or_int8_flush(monkeypatch):
    try:
        assert flush(Compression.none, monkeypatch, "rhd") == (
            sum(NBYTES[:5]),) * 2
    finally:
        monkeypatch.undo()
        comms_planner.reset_for_testing()
    elements = sum(NBYTES[:5]) // 4
    assert flush(Compression.int8) == (elements, elements)


@pytest.mark.parametrize("issue_reversed", [False, True],
                         ids=["in_order", "reversed"])
def test_one_scope_a_bucket_in_the_grammar_the_comms_model_parses(
        issue_reversed):
    eqns = traced(sharded(fused(Sum, issue_reversed=issue_reversed)),
                  *leaves())
    scope = re.compile(r"hvd\.(allreduce\.bucket(\d+)\.\d+B)")
    seen = []
    for eqn in eqns:
        stack = str(eqn.source_info.name_stack)
        found = scope.search(stack)
        if eqn.primitive.name == "psum":
            assert found, stack
            parsed = comms_model._BUCKET_NAME_RE.match(found.group(1))
            bucket = BUCKETS[int(found.group(2))]
            # The bucket's bytes: its large leaves and its small ones.
            assert parsed.group("op") == "allreduce"
            assert int(parsed.group("bytes")) == sum(
                NBYTES[i] for i in bucket)
            assert parsed.group("algo") is None
            seen.append(int(found.group(2)))
        elif eqn.primitive.name == "slice":
            assert "hvd.wire.unpack" in stack and not found
    order = sorted(set(seen), key=seen.index)
    assert order == ([2, 1, 0] if issue_reversed else [0, 1, 2])


# The sharded and fsdp wires: the same leaves and buckets, and behind them a
# large leaf whose size no world of four divides (a bucket of its own).
SHARD_SHAPES = SHAPES + [((513, 513), np.float32)]
SHARD_NBYTES = NBYTES + [513 * 513 * 4]
SHARD_BUCKETS = BUCKETS + [[7]]
SHARD_LARGE = LARGE + [7]
OWNED = fusion.shard_ownership(
    [np.empty(shape) for shape, _ in SHARD_SHAPES], N)


def scattered(op=Sum, **kwargs):
    kwargs.setdefault("threshold_bytes", THRESHOLD)
    return lambda ls: fusion.fused_reducescatter(ls, op, "w", N, **kwargs)


def gathered(shapes=SHARD_SHAPES, **kwargs):
    """A rank's leaves cut to its owned shards (by hand, outside the wire)
    and gathered back."""
    kwargs.setdefault("threshold_bytes", THRESHOLD)
    templates = [jax.ShapeDtypeStruct(shape, dtype)
                 for shape, dtype in shapes]

    def body(shards):
        return fusion.fused_allgather_shards(
            shards, templates, "w", N, **kwargs)

    return body


def owned_rows(full, s):
    """``(N, s)``: row ``r`` is rank r's slice of ``full`` by
    ``shard_ownership``."""
    flat = np.asarray(full, np.float32).ravel()
    return np.pad(flat, (0, N * s - flat.size)).reshape(N, s)


def shards_of(xs):
    """Stacked ``(N, s)`` shards whose gather is leaf ``xs[i][0]``."""
    return [jnp.asarray(owned_rows(x[0], s), x.dtype)
            for x, s in zip(xs, OWNED)]


def all_packed(monkeypatch):
    """The parent's form: no leaf reaches the cutoff."""
    monkeypatch.setattr(fusion, "PACK_CUTOFF_BYTES", 1 << 40)


@pytest.mark.parametrize("scales", [(1.0, 1.0), (0.5, 4.0)],
                         ids=["unscaled", "scaled"])
@pytest.mark.parametrize("issue_reversed", [False, True],
                         ids=["in_order", "reversed"])
@pytest.mark.parametrize("op", [Sum, Average])
def test_a_mixed_list_scatters_to_each_owners_slice_of_a_plain_psum(
        op, issue_reversed, scales):
    pre, post = scales

    def plain(ls):
        return [PLAIN[op](leaf * jnp.asarray(pre, leaf.dtype), "w")
                * jnp.asarray(post, leaf.dtype) for leaf in ls]

    xs = leaves(shapes=SHARD_SHAPES)
    got = sharded(scattered(op, prescale_factor=pre, postscale_factor=post,
                            issue_reversed=issue_reversed))(*xs)
    want = sharded(plain)(*xs)
    for g, w, s, (_, dtype) in zip(got, want, OWNED, SHARD_SHAPES):
        assert g.shape == (N, s) and g.dtype == dtype
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      owned_rows(w[0], s))


@pytest.mark.parametrize("issue_reversed", [False, True],
                         ids=["in_order", "reversed"])
def test_a_mixed_list_gathers_back_to_its_leaves(issue_reversed):
    xs = leaves(shapes=SHARD_SHAPES)
    got = sharded(gathered(issue_reversed=issue_reversed))(*shards_of(xs))
    for g, x, (shape, dtype) in zip(got, xs, SHARD_SHAPES):
        assert g.shape == (N,) + shape and g.dtype == dtype
        for rank in range(N):
            np.testing.assert_array_equal(np.asarray(g[rank], np.float32),
                                          np.asarray(x[0], np.float32))


@pytest.mark.parametrize("half", ["scatter", "gather"])
def test_the_values_are_the_all_packed_forms_bit_for_bit(half, monkeypatch):
    # Not whole numbers here: a sum over ranks has an order to keep.
    rng = np.random.RandomState(1)
    xs = [jnp.asarray(rng.standard_normal((N,) + shape), dtype)
          for shape, dtype in SHARD_SHAPES]
    if half == "scatter":
        fn, args, least = scattered(Average, prescale_factor=0.3), xs, CUTOFF
    else:
        fn, args, least = gathered(), [
            jnp.asarray(rng.standard_normal((N, s)), dtype)
            for s, (_, dtype) in zip(OWNED, SHARD_SHAPES)], CUTOFF // N
    got = sharded(fn)(*args)
    all_packed(monkeypatch)
    assert packs_a_large_leaf(traced(sharded(fn), *args), least)
    want = sharded(fn)(*args)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


def collective_operands(eqns, primitive):
    return [v.aval.shape for e in eqns if e.primitive.name == primitive
            for v in e.invars]


@pytest.mark.parametrize("issue_reversed", [False, True],
                         ids=["in_order", "reversed"])
@pytest.mark.parametrize("half", ["scatter", "gather"])
def test_a_large_leaf_is_neither_packed_nor_cut_out_of_a_grid(
        half, issue_reversed):
    xs = leaves(shapes=SHARD_SHAPES)
    if half == "scatter":
        eqns = traced(sharded(scattered(
            Average, issue_reversed=issue_reversed)), *xs)
        operands = collective_operands(eqns, "reduce_scatter")
        alone, least = [(N * OWNED[i],) for i in SHARD_LARGE], CUTOFF
    else:
        eqns = traced(sharded(gathered(issue_reversed=issue_reversed)),
                      *shards_of(xs))
        operands = collective_operands(eqns, "all_gather")
        alone, least = [(OWNED[i],) for i in SHARD_LARGE], CUTOFF // N
    assert not packs_a_large_leaf(eqns, least)
    # Every cut is out of the small leaves' block, but the three elements
    # of padding off the end of the leaf that does not divide: one
    # contiguous cut, no stride.
    cuts = [e for e in eqns
            if e.primitive.name in ("slice", "dynamic_slice", "gather")
            and nbytes_of(e.invars[0]) >= CUTOFF]
    if half == "scatter":
        assert not cuts
    else:
        assert [(e.primitive.name, e.invars[0].aval.shape,
                 e.outvars[0].aval.shape, e.params["strides"])
                for e in cuts] == [
            ("slice", (N * OWNED[7],), (513 * 513,), None)]
    # One collective a large leaf, of its flat view, one a packed block,
    # and one for the lone small leaf of the second bucket.
    assert all(shape in operands for shape in alone)
    assert len(operands) == len(SHARD_LARGE) + 1 + sum(
        1 for packed in PACKED if packed)


@pytest.mark.parametrize("half", ["scatter", "gather"])
def test_a_lone_small_leaf_is_scattered_and_gathered_as_itself(half):
    shapes = [SHAPES[6]]  # eight elements: nothing to pad either
    xs = leaves(shapes=shapes)
    if half == "scatter":
        eqns = traced(sharded(scattered()), *xs)
    else:
        eqns = traced(sharded(gathered(shapes)), *[x[:, 0, :2] for x in xs])
    assert {e.primitive.name for e in eqns} & {
        "concatenate", "slice", "dynamic_slice", "pad"} == set()


def planned_scatter(monkeypatch):
    plan_rhd(monkeypatch)
    return scattered(Sum), leaves(shapes=SHARD_SHAPES), CUTOFF, True


def planned_gather(monkeypatch):
    plan_rhd(monkeypatch)
    return (gathered(), shards_of(leaves(shapes=SHARD_SHAPES)), CUTOFF // N,
            True)


def int8_scatter(monkeypatch):
    return ((lambda ls: quantization.int8_fused_reducescatter(
        ls, "w", N, op=Average, threshold_bytes=THRESHOLD)), leaves()[:5],
        CUTOFF, False)


def int8_gather(monkeypatch):
    templates = [jax.ShapeDtypeStruct(shape, dtype)
                 for shape, dtype in SHAPES[:5]]
    return (lambda shards: quantization.int8_fused_allgather_shards(
        shards, templates, "w", N, threshold_bytes=THRESHOLD),
        shards_of(leaves()[:5]), CUTOFF // N, False)


@pytest.mark.parametrize(
    "wire", [planned_scatter, planned_gather, int8_scatter, int8_gather])
def test_a_planned_or_int8_bucket_of_shards_still_packs_whole(
        wire, monkeypatch):
    body, xs, least, exact = wire(monkeypatch)
    try:
        eqns = traced(sharded(body), *xs)
        got = sharded(body)(*xs)
    finally:
        monkeypatch.undo()
        comms_planner.reset_for_testing()
    assert packs_a_large_leaf(eqns, least)
    if exact:
        want = sharded(body)(*xs)  # the planner off: large leaves alone
        assert not packs_a_large_leaf(traced(sharded(body), *xs), least)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g, np.float32),
                                          np.asarray(w, np.float32))


@pytest.mark.parametrize("issue_reversed", [False, True],
                         ids=["in_order", "reversed"])
@pytest.mark.parametrize("half", ["scatter", "gather"])
def test_one_scope_a_bucket_of_shards_in_the_grammar_the_comms_model_parses(
        half, issue_reversed):
    xs = leaves(shapes=SHARD_SHAPES)
    if half == "scatter":
        eqns = traced(sharded(scattered(issue_reversed=issue_reversed)), *xs)
        op, primitive = "reducescatter", "reduce_scatter"
        on_the_wire = SHARD_NBYTES
    else:
        eqns = traced(sharded(gathered(issue_reversed=issue_reversed)),
                      *shards_of(xs))
        op, primitive = "allgather", "all_gather"
        # What the gather moves: every rank's shard, padding and all.
        on_the_wire = [N * s * jnp.dtype(dtype).itemsize
                       for s, (_, dtype) in zip(OWNED, SHARD_SHAPES)]
    scope = re.compile(r"hvd\.(%s\.bucket(\d+)\.\d+B)" % op)
    seen = []
    for eqn in eqns:
        stack = str(eqn.source_info.name_stack)
        found = scope.search(stack)
        if eqn.primitive.name == primitive:
            assert found, stack
            parsed = comms_model._BUCKET_NAME_RE.match(found.group(1))
            bucket = SHARD_BUCKETS[int(found.group(2))]
            assert parsed.group("op") == op
            assert int(parsed.group("bytes")) == sum(
                on_the_wire[i] for i in bucket)
            assert parsed.group("algo") is None
            seen.append(int(found.group(2)))
        elif eqn.primitive.name == "slice":
            assert "hvd.wire.unpack" in stack and not found
    order = sorted(set(seen), key=seen.index)
    assert order == ([3, 2, 1, 0] if issue_reversed else [0, 1, 2, 3])


def sharded_flush(compression, label, monkeypatch=None, planner=None):
    """Trace one flush of ``optimizer._reducescatter_grads`` and return the
    gauges it left under ``label``: ``(wire bytes, packed bytes)``."""
    if planner:
        monkeypatch.setenv("HOROVOD_COMMS_PLANNER", planner)
        comms_planner.reset_for_testing()

    def body(ls):
        return optimizer._reducescatter_grads(
            ls, Average, "w", compression, 1.0, 1.0, THRESHOLD, 0,
            world_size=N, flush_label=label)

    jax.make_jaxpr(sharded(body))(*leaves()[:5])
    return tuple(int(gauge.labels(sync_mode=label).get()) for gauge in (
        metrics.GRAD_SYNC_LAST_BYTES, metrics.GRAD_SYNC_LAST_PACKED_BYTES))


@pytest.mark.parametrize("label", ["fsdp", "sharded"])
def test_the_gauge_of_a_sharded_flush_counts_the_small_leaves_bytes(label):
    assert sharded_flush(Compression.none, label) == (
        sum(NBYTES[:5]), sum(NBYTES[i] for i in PACKED[0]))
    assert sharded_flush(Compression.bf16, label) == (
        sum(NBYTES[:5]) // 2, sum(NBYTES[i] for i in (0, 1, 2, 4)) // 2)


def test_the_gauge_counts_every_byte_of_a_planned_or_int8_sharded_flush(
        monkeypatch):
    try:
        assert sharded_flush(Compression.none, "fsdp", monkeypatch,
                             "rhd") == (sum(NBYTES[:5]),) * 2
    finally:
        monkeypatch.undo()
        comms_planner.reset_for_testing()
    elements = sum(NBYTES[:5]) // 4
    assert sharded_flush(Compression.int8, "fsdp") == (elements, elements)


def gather_record(compression, monkeypatch=None, planner=None):
    """Trace one fsdp gather boundary's forward and return what it added
    to the two histograms: ``(wire bytes, packed bytes)``."""
    from horovod_tpu.parallel import param_sharding

    if planner:
        monkeypatch.setenv("HOROVOD_COMMS_PLANNER", planner)
        comms_planner.reset_for_testing()
    spec = optimizer.ReduceSpec(
        inner=None, op=Average, compression=compression,
        prescale_factor=1.0, postscale_factor=1.0, process_set=None,
        num_groups=0, fusion_threshold_bytes=THRESHOLD,
        backward_passes_per_step=1, sync_mode="fsdp")
    templates = [jax.ShapeDtypeStruct(shape, dtype)
                 for shape, dtype in SHAPES[:5]]

    def body(shards):
        return param_sharding._gather_boundary(
            shards, templates, 0, spec, "w", N, None)

    def sums():
        return [sum(s["sum"] for s in h.dump()["samples"]
                    if s["labels"].get("axis") == "batch")
                for h in (metrics.PARAM_GATHER_BYTES,
                          metrics.PARAM_GATHER_PACKED_BYTES)]

    before = sums()
    jax.make_jaxpr(sharded(body))(*shards_of(leaves()[:5]))
    return tuple(int(a - b) for a, b in zip(sums(), before))


def test_a_gathers_record_counts_the_small_leaves_bytes(monkeypatch):
    assert gather_record(Compression.none) == (
        sum(NBYTES[:5]), sum(NBYTES[i] for i in PACKED[0]))
    try:
        assert gather_record(Compression.none, monkeypatch, "rhd") == (
            sum(NBYTES[:5]),) * 2
    finally:
        monkeypatch.undo()
        comms_planner.reset_for_testing()
    elements = sum(NBYTES[:5]) // 4
    assert gather_record(Compression.int8) == (elements, elements)
