"""Expert-parallel MoE dispatch on the device mesh.

The reference added the alltoall collective for MoE-style workloads but
ships no MoE layer (SURVEY.md §3.6: "only the collective primitive
exists"); this module goes one step beyond parity with the TPU-idiomatic
expert-parallel layer built on this framework's collectives: one expert
per device, top-1 routing, capacity-factor dispatch buffers (static
shapes — the GShard/Switch recipe, because XLA cannot do ragged
exchange), and ONE ``lax.all_to_all`` HLO out plus one back, riding ICI.

``examples/jax_moe_expert_parallel.py`` drives this layer end-to-end and
verifies it against a dense oracle; ``__graft_entry__.dryrun_multichip``
exercises the one-HLO dispatch on the virtual multi-chip mesh.

Beyond the demo layer, expert parallelism is a first-class sync path
(:func:`make_expert_parallel_moe_step`): experts shard one-per-rank
across a ``process_sets`` subgroup pattern (data-parallel across the
``world/E`` copies — :func:`process_sets.expert_partition`), and three
performance planes ride the dispatch/combine alltoall wire:

- **quantization** — ``HOROVOD_MOE_COMPRESSION=int8`` sends the token
  payload through the EQuARX blockwise-int8 exchange
  (``ops/quantization.int8_alltoall_rows``; the occupancy mask rides the
  f32 side channel exactly — routing never quantizes);
- **overlap** — the dispatch alltoalls software-pipeline against expert
  FFN compute (``ops/fusion.pipeline_interleave``): segment ``i+1``'s
  exchange is emitted before segment ``i``'s FFN, so XLA's
  latency-hiding scheduler runs them concurrently (jaxpr-asserted in
  tests/test_moe_parallel.py; reverse-mode AD reverses program order, so
  the combine transposes interleave with the backward for free);
- **planner** — the dispatch bucket is priced per-algorithm by the
  comms planner's ``alltoall`` vocabulary (flat vs the two_level
  ICI×DCN staged form, ``ops/comms_planner.two_level_alltoall``), with
  the ``HOROVOD_COMMS_PLANNER``-unset path bit-for-bit identical to the
  flat emission.

``faults.MOE_DISPATCH`` (``moe.dispatch``) is the canonical MoE chaos
injector on this wire; docs/perf.md "Expert parallelism" documents the
knobs and the sync-mode guard table.
"""

from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def expert_ffn(w1, w2, x):
    """The per-expert feed-forward: relu(x @ w1) @ w2."""
    return jnp.maximum(x @ w1, 0.0) @ w2


def moe_layer(tokens, gates_w, w1, w2, axis, capacity):
    """One expert-parallel MoE layer, per-device view under shard_map.

    tokens: [T, D] this device's tokens; w1/w2: THIS device's expert.
    Returns [T, D] with each token processed by its routed expert
    (dropped tokens — over capacity — pass through unchanged, the
    standard capacity-factor semantics).
    """
    n = lax.psum(1, axis)
    T, D = tokens.shape
    logits = tokens @ gates_w                      # [T, n]
    expert = jnp.argmax(logits, axis=-1)           # [T]
    gate = jax.nn.softmax(logits, axis=-1)
    gate = jnp.take_along_axis(gate, expert[:, None], axis=1)[:, 0]

    # Position of each token within its expert's send buffer; tokens past
    # `capacity` are dropped (pass through). Static shapes throughout.
    onehot = jax.nn.one_hot(expert, n, dtype=jnp.int32)        # [T, n]
    pos = jnp.cumsum(onehot, axis=0) * onehot                  # 1-based
    pos = jnp.sum(pos, axis=1) - 1                             # [T]
    keep = (pos >= 0) & (pos < capacity)

    # Scatter kept tokens into the [n, capacity, D+1] dispatch buffer —
    # the last channel carries the occupancy mask, so ONE exchange moves
    # payload and mask together.
    send = jnp.zeros((n, capacity, D + 1), tokens.dtype)
    payload = jnp.concatenate(
        [tokens, jnp.ones((T, 1), tokens.dtype)], axis=1)
    send = send.at[expert, jnp.clip(pos, 0, capacity - 1)].add(
        jnp.where(keep[:, None], payload, 0.0))

    # ONE all_to_all out: slot j of my buffer -> device j. Received:
    # [n, capacity, D+1] = every device's tokens routed to MY expert.
    recv = lax.all_to_all(send, axis, split_axis=0, concat_axis=0,
                          tiled=True).reshape(n, capacity, D + 1)
    recv_mask = recv[..., -1] > 0.5
    out = expert_ffn(w1, w2, recv[..., :D].reshape(n * capacity, D))
    out = jnp.where(recv_mask.reshape(-1)[:, None], out, 0.0)
    out = out.reshape(n, capacity, D)

    # all_to_all back: expert results return to their source devices.
    back = lax.all_to_all(out, axis, split_axis=0, concat_axis=0,
                          tiled=True).reshape(n, capacity, D)

    # Gather each token's result from (its expert's row, its position).
    result = back[expert, jnp.clip(pos, 0, capacity - 1)]
    return jnp.where(keep[:, None], gate[:, None] * result, tokens)


def make_moe_step(axis_name: str = "hvd", capacity: int = 4, mesh=None):
    """Build the jitted one-HLO-each-way MoE dispatch over the mesh.

    Takes global ``tokens [n*T, D]``, replicated ``gates_w [D, n]``, and
    expert weights stacked on the device axis (``w1 [n, D, H]``,
    ``w2 [n, H, D]``); returns the routed ``[n*T, D]`` output — the
    one-call user surface mirroring ``make_sp_attention_step``.
    """
    from .. import basics

    mesh = mesh or basics.global_mesh()
    step = jax.shard_map(
        lambda t, g, w1, w2: moe_layer(t, g, w1[0], w2[0], axis_name,
                                       capacity),
        mesh=mesh,
        in_specs=(P(axis_name), P(), P(axis_name), P(axis_name)),
        out_specs=P(axis_name),
        check_vma=False)
    return jax.jit(step)


# ---------------------------------------------------------------------------
# Expert parallelism as a first-class sync path
# ---------------------------------------------------------------------------


def route_to_capacity(tokens, logits, num_experts, capacity, top_k=1,
                      first_expert=0, experts_here=None,
                      gates_over_picks=False, scores="softmax",
                      gate_scale=1.0, selection_bias=None, gate_eps=1e-20):
    """Capacity-factor top-k routing into fixed per-expert slots — the
    jit-compatible answer to ragged dispatch (the helper the uneven-split
    ``alltoall`` rejection points at).

    ``tokens [T, D]`` + router ``logits [T, num_experts]`` →
    ``send [experts_here, capacity, D+1]`` (last channel = occupancy
    mask, so one exchange moves payload and mask together) plus the
    routing state that brings results home
    (:func:`combine_from_capacity` for top-1, :func:`combine_top_k`):
    ``expert`` (the picks, ``lax.top_k`` of the logits: ties go to the
    lower index, as ``argmax``), ``pos`` (slot within the expert's
    buffer), ``keep``, ``gate`` (softmax prob of each pick over all
    ``num_experts``, not renormalised over the picks; with
    ``gates_over_picks`` the softmax over the ``top_k`` picked logits
    alone, whichever window holds each pick, so that a token's gates add
    up to one over all the windows; with ``scores="sigmoid"`` the picks
    are the ``top_k`` of ``sigmoid(logits)`` in float32 and a gate is its
    pick's score, with ``gates_over_picks`` divided by the sum of the
    token's picked scores plus ``gate_eps`` (the source's own constant:
    1e-20 in DeepSeek-V3's, Kimi Linear's, Nemotron-H's and JoyAI Flash's
    routers, the default; 1e-6 in LFM2's), and in either case times
    ``gate_scale``: the routing of the DeepSeek-V3 family; with
    ``selection_bias``, a float32 ``[num_experts]`` vector, the picks are
    the ``top_k`` of ``sigmoid(logits) + selection_bias`` while a gate stays
    its pick's score without it, and no gradient reaches the bias: the
    family's ``noaux_tc`` choice, whose update rule is the load balancer's
    and not here), each ``[T]`` for
    ``top_k=1`` and ``[T, top_k]`` otherwise, and ``counts
    [experts_here]`` (kept pairs per expert — the
    ``hvd_moe_expert_load`` signal).

    The expert window: this caller holds the ``experts_here`` experts
    from ``first_expert`` on (default: all of them). A (token, pick)
    pair whose expert lies outside the window takes no slot here and is
    not kept (whoever holds that expert computes it); inside the window,
    pairs take slots in token order, then pick order, and a pair past
    ``capacity`` is dropped. Over windows that partition the experts the
    kept pairs partition those of the whole layer. Static shapes
    throughout; for ``top_k=1`` over all experts, identical math to
    :func:`moe_layer`'s inline routing.
    """
    from ..attribution import SCOPE_MOE_DISPATCH, SCOPE_MOE_ROUTE
    from ..profiler import annotate_collective

    T, D = tokens.shape
    if experts_here is None:
        experts_here = num_experts - first_expert
    with annotate_collective(SCOPE_MOE_ROUTE):
        if scores not in ("softmax", "sigmoid") or (
                scores == "softmax" and gate_scale != 1.0):
            raise ValueError(
                f"route_to_capacity: scores={scores!r} with gate_scale="
                f"{gate_scale}; 'softmax' (unscaled) or 'sigmoid'")
        if selection_bias is not None and scores != "sigmoid":
            raise ValueError(
                "route_to_capacity: selection_bias is the sigmoid scores'; "
                f"got scores={scores!r}")
        if scores == "sigmoid":
            score = jax.nn.sigmoid(logits.astype(jnp.float32))
            if selection_bias is None:
                picked, expert = lax.top_k(score, top_k)
            else:  # the choice by score + bias, the gate by the score
                _, expert = lax.top_k(score + lax.stop_gradient(
                    selection_bias.astype(jnp.float32)), top_k)
                picked = jnp.take_along_axis(score, expert, axis=1)
            if gates_over_picks:
                picked = picked / (picked.sum(-1, keepdims=True) + gate_eps)
            gate = picked * gate_scale
        else:
            picked, expert = lax.top_k(logits, top_k)          # [T, K]
            if gates_over_picks:
                gate = jax.nn.softmax(picked, axis=-1)
            else:
                gate = jnp.take_along_axis(
                    jax.nn.softmax(logits, axis=-1), expert, axis=1)
        # Pairs in token order, then pick order. An expert outside the
        # window has no column: its row of the one-hot is all zero.
        local = expert.reshape(T * top_k) - first_expert
        onehot = jax.nn.one_hot(local, experts_here, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) * onehot              # 1-based
        pos = jnp.sum(pos, axis=1) - 1                         # [T*K]
        keep = (pos >= 0) & (pos < capacity)
        counts = jnp.sum(onehot * keep[:, None].astype(jnp.int32), axis=0)
    with annotate_collective(SCOPE_MOE_DISPATCH):
        send = _fill_slots(tokens, local, pos, keep, experts_here, capacity,
                           top_k)
    shape = (T,) if top_k == 1 else (T, top_k)
    return (send, expert.reshape(shape), pos.reshape(shape),
            keep.reshape(shape), gate.reshape(shape), counts)


def _slot_pairs(local, pos, keep, experts_here, capacity):
    """``[experts_here · capacity]``: the index of the (token, pick) pair
    that sits in each slot, or the number of pairs for an empty one. The
    pairs are written to their slots as integers (kept pairs have slots
    of their own), so that the tokens' rows can then be *read* by slot: on
    a v5e filling OLMoE's slots so took 0.28 ms where scattering the rows
    took 2.9 (PERF.md, PR 26)."""
    slots = experts_here * capacity
    pairs = local.shape[0]
    return jnp.full((slots,), pairs, jnp.int32).at[
        jnp.where(keep, local * capacity + pos, slots)].set(
            jnp.arange(pairs, dtype=jnp.int32), mode="drop")


def _fill_slots(tokens, local, pos, keep, experts_here, capacity, top_k):
    """``send [experts_here, capacity, D+1]``: every kept pair's token in
    its slot, occupancy in the last channel, the empty slots zero."""
    T, D = tokens.shape
    payload = jnp.concatenate(
        [tokens, jnp.ones((T, 1), tokens.dtype)], axis=1)
    # row T is the empty slots' zero
    payload = jnp.concatenate(
        [payload, jnp.zeros((1, D + 1), tokens.dtype)], axis=0)
    source = _slot_pairs(local, pos, keep, experts_here, capacity) // top_k
    return payload[source].reshape(experts_here, capacity, D + 1)


def combine_top_k(back, expert, pos, keep, gate, first_expert=0):
    """The way home for ``top_k`` picks a token: ``Σ_k gate_k ·
    back[expert_k, pos_k]`` over the kept pairs, ``[T, D]``. A pair that
    was dropped, or whose expert another window holds, adds nothing: the
    caller's residual carries the token (no passthrough here, unlike
    :func:`combine_from_capacity`). Each occupied slot's row is weighted
    and added to its token's, in float32."""
    from ..attribution import SCOPE_MOE_COMBINE
    from ..profiler import annotate_collective

    experts_here, capacity, D = back.shape
    T, top_k = expert.shape
    with annotate_collective(SCOPE_MOE_COMBINE):
        pairs = _slot_pairs(
            expert.reshape(-1) - first_expert, pos.reshape(-1),
            keep.reshape(-1), experts_here, capacity)
        weight = jnp.concatenate(
            [gate.reshape(-1), jnp.zeros((1,), gate.dtype)])[pairs]
        rows = back.reshape(-1, D).astype(jnp.float32) * weight[:, None]
        # an empty slot's pair index is T · top_k: row T, cut off
        out = jnp.zeros((T + 1, D), jnp.float32).at[pairs // top_k].add(rows)
        return out[:T].astype(back.dtype)


def gated_expert_ffn(w_gate, w_up, w_down, x, activation=jax.nn.silu):
    """``experts`` gated feed-forwards at once, one batched matmul a
    projection: ``x [experts, capacity, D]``, ``w_gate`` / ``w_up``
    ``[experts, D, H]``, ``w_down [experts, H, D]`` → ``w_down ·
    (activation(w_gate · x) ⊙ (w_up · x))``, SiLU unless the model says
    otherwise (``jax.nn.relu``: a ReGLU). No bias, and every such
    activation keeps zero at zero, so an empty slot (zeros) stays zero and
    needs no mask."""
    from ..attribution import SCOPE_MOE_EXPERTS
    from ..profiler import annotate_collective

    with annotate_collective(SCOPE_MOE_EXPERTS):
        hidden = activation(jnp.einsum("ecd,edh->ech", x, w_gate)) \
            * jnp.einsum("ecd,edh->ech", x, w_up)
        return jnp.einsum("ech,ehd->ecd", hidden, w_down)


def plain_expert_ffn(w_up, w_down, x, activation):
    """``experts`` ungated feed-forwards at once, two batched matmuls:
    ``x [experts, capacity, D]``, ``w_up [experts, D, H]``, ``w_down
    [experts, H, D]`` → ``w_down · activation(w_up · x)`` (Nemotron-H's
    experts: ``activation`` is ``relu(·)²``). No bias, and the activation
    must keep zero at zero, as every one of :func:`gated_expert_ffn` does,
    so that an empty slot (zeros) stays zero and needs no mask."""
    from ..attribution import SCOPE_MOE_EXPERTS
    from ..profiler import annotate_collective

    with annotate_collective(SCOPE_MOE_EXPERTS):
        hidden = activation(jnp.einsum("ecd,edh->ech", x, w_up))
        return jnp.einsum("ech,ehd->ecd", hidden, w_down)


def expert_capacity(capacity_factor, tokens, top_k, num_experts):
    """Slots an expert gets for one routing group of ``tokens``:
    ``ceil(capacity_factor · tokens · top_k / num_experts)``."""
    return int(math.ceil(capacity_factor * tokens * top_k / num_experts))


def combine_from_capacity(back, tokens, expert, pos, keep, gate, capacity):
    """Inverse of :func:`route_to_capacity`: gather each token's expert
    result from ``back [num_experts, capacity, D]`` at (its expert, its
    slot), gate it, and give dropped tokens the passthrough residual."""
    result = back[expert, jnp.clip(pos, 0, capacity - 1)]
    return jnp.where(keep[:, None], gate[:, None] * result, tokens)


def moe_compression(override=None):
    """Resolve the MoE wire compression: ``HOROVOD_MOE_COMPRESSION``
    (or an explicit ``override``) → ``None`` (fp32, exact) | ``"int8"``
    (the EQuARX blockwise exchange). Unknown values raise — a silently
    ignored compression knob is a benchmarking lie."""
    raw = override if override is not None else os.environ.get(
        "HOROVOD_MOE_COMPRESSION", "")
    raw = str(raw).strip().lower()
    if raw in ("", "none", "0", "off"):
        return None
    if raw == "int8":
        return "int8"
    raise ValueError(
        f"HOROVOD_MOE_COMPRESSION={raw!r}: expected 'int8' or unset/"
        f"'none' (fp32)")


def replicate_expert_weights(w_experts, groups):
    """Lay ``w_experts [E, ...]`` out rank-major for the expert-sharded
    in_spec: rank ``groups[g][j]`` gets expert ``j``'s slice, so every
    dispatch group holds one full copy of the expert set. Returns
    ``[world, ...]`` ready for ``P(axis)`` sharding."""
    e = len(groups[0])
    world = sum(len(g) for g in groups)
    if w_experts.shape[0] != e:
        raise ValueError(
            f"w_experts has {w_experts.shape[0]} experts but each "
            f"dispatch group holds {e}")
    rows = [None] * world
    for grp in groups:
        for j, r in enumerate(grp):
            rows[r] = w_experts[j]
    return jnp.stack(rows, axis=0)


def _moe_exchange(axis, groups, plan):
    """The dispatch/combine wire: one callable serving both the f32 and
    the int8 exchanges (and both directions), so every payload rides the
    SAME schedule. Planner plan with a non-flat algorithm → the staged
    two_level form; otherwise the flat tiled alltoall scoped to the
    dispatch groups — which is also the planner-off emission, the
    bit-for-bit inertness contract (``bucket_plan`` returns None for
    flat plans, so a flat *choice* never reaches here either)."""
    idx_groups = [list(g) for g in groups]

    def _exchange(buf):
        if plan is not None and plan.algorithm == "two_level":
            from ..ops import comms_planner

            return comms_planner.two_level_alltoall(buf, axis,
                                                    plan.islands)
        return lax.all_to_all(buf, axis, split_axis=0, concat_axis=0,
                              tiled=True, axis_index_groups=idx_groups)

    return _exchange


def _dispatch_exchange(send, axis, exchange, compression, salt):
    """One dispatch exchange of a ``[E, c, D+1]`` buffer slice →
    ``(payload [E, c, D], mask [E, c])`` as received. Under int8 the
    payload rides the EQuARX quantized wire and the occupancy mask rides
    the f32 side channel EXACTLY (routing never quantizes)."""
    e, c, dp1 = send.shape
    d = dp1 - 1
    if compression == "int8":
        from ..ops import quantization

        deq, mask = quantization.int8_alltoall_rows(
            send[..., :d].reshape(e, c * d), axis, salt=salt,
            extra=send[..., d], a2a=exchange)
        return deq.reshape(e, c, d), mask
    recv = exchange(send).reshape(e, c, dp1)
    return recv[..., :d], recv[..., d]


def _combine_exchange(out_seg, axis, exchange, compression, salt):
    """One combine exchange of ``[E, c, D]`` expert outputs back to
    their source ranks (no mask — combine addresses every slot)."""
    e, c, d = out_seg.shape
    if compression == "int8":
        from ..ops import quantization

        deq, _ = quantization.int8_alltoall_rows(
            out_seg.reshape(e, c * d), axis, salt=salt, a2a=exchange)
        return deq.reshape(e, c, d)
    return exchange(out_seg).reshape(e, c, d)


def expert_parallel_moe_layer(tokens, gates_w, w1, w2, axis, capacity,
                              groups, *, segments=1, compression=None,
                              plan=None, salt=None):
    """One expert-parallel MoE layer, per-device view under shard_map —
    the first-class sync-path flavor of :func:`moe_layer`.

    ``tokens [T, D]`` this device's tokens; ``w1 [D, H]`` / ``w2 [H,
    D]`` THIS device's expert; ``gates_w [D, E]`` where ``E =
    len(groups[0])`` is the expert-set size (``groups`` from
    :func:`process_sets.expert_partition` — experts shard one-per-rank
    within each dispatch group, data-parallel across groups).

    The dispatch is segmented along the capacity dim and
    software-pipelined (:func:`fusion.pipeline_interleave`): segment
    ``i+1``'s dispatch alltoall is emitted before segment ``i``'s expert
    FFN, so XLA overlaps wire and compute. ``compression="int8"`` rides
    the EQuARX exchange; a planner ``plan`` (from
    ``fusion.bucket_plan("alltoall", ...)``) stages the wire two_level.
    Returns ``(out [T, D], dropped [1] int32, load [1, E] int32)``.
    """
    from ..ops import fusion

    e = len(groups[0])
    send, expert, pos, keep, gate, counts = route_to_capacity(
        tokens, tokens @ gates_w, e, capacity)
    exchange = _moe_exchange(axis, groups, plan)
    segments = max(1, int(segments))
    if capacity % segments:
        raise ValueError(
            f"segments={segments} must divide capacity={capacity}")
    cs = capacity // segments
    d = tokens.shape[1]

    def _launch(i):
        return _dispatch_exchange(send[:, i * cs:(i + 1) * cs, :], axis,
                                  exchange, compression, salt)

    def _consume(i, launched):
        x, mask = launched
        h = expert_ffn(w1, w2, x.reshape(e * cs, d))
        h = jnp.where(mask.reshape(-1)[:, None] > 0.5, h, 0.0)
        return _combine_exchange(h.reshape(e, cs, d), axis, exchange,
                                 compression, salt)

    backs = fusion.pipeline_interleave(segments, _launch, _consume)
    back = backs[0] if segments == 1 else jnp.concatenate(backs, axis=1)
    out = combine_from_capacity(back, tokens, expert, pos, keep, gate,
                                capacity)
    dropped = jnp.sum((~keep).astype(jnp.int32)).reshape(1)
    return out, dropped, counts.reshape(1, e)


def data_parallel_moe_layer(tokens, gates_w, w1_all, w2_all, capacity,
                            *, segments=1):
    """The dense data-parallel baseline: every rank holds ALL experts
    (``w1_all [E, D, H]`` / ``w2_all [E, H, D]`` replicated) and routes
    locally — zero collectives, E× the resident expert bytes. Same
    routing math and segment walk as the expert-parallel layer, so the
    two trajectories are comparable token for token."""
    e = w1_all.shape[0]
    send, expert, pos, keep, gate, counts = route_to_capacity(
        tokens, tokens @ gates_w, e, capacity)
    segments = max(1, int(segments))
    if capacity % segments:
        raise ValueError(
            f"segments={segments} must divide capacity={capacity}")
    cs = capacity // segments
    d = tokens.shape[1]
    backs = []
    for i in range(segments):
        seg = send[:, i * cs:(i + 1) * cs, :]
        h = jax.vmap(expert_ffn)(w1_all, w2_all, seg[..., :d])
        backs.append(jnp.where(seg[..., d:] > 0.5, h, 0.0))
    back = backs[0] if segments == 1 else jnp.concatenate(backs, axis=1)
    out = combine_from_capacity(back, tokens, expert, pos, keep, gate,
                                capacity)
    dropped = jnp.sum((~keep).astype(jnp.int32)).reshape(1)
    return out, dropped, counts.reshape(1, e)


def _wire_bytes(e, capacity, d, compression):
    """Per-rank dispatch-exchange bytes as priced/observed (wire view:
    post-compression). int8 ≈ 1 B/elem payload + the f32 mask and
    per-block scale side channel, approximated at 8 B/slot — a
    documented approximation, not an accounting identity."""
    if compression == "int8":
        return e * capacity * d + 8 * e * capacity
    return e * capacity * (d + 1) * 4


def make_expert_parallel_moe_step(axis_name: str = "hvd",
                                  capacity: int = 4, mesh=None,
                                  expert_set=None, segments=None,
                                  compression=None, salt=None):
    """Build the jitted expert-parallel MoE step — experts sharded
    one-per-rank across ``expert_set`` (a ProcessSet, a rank list, or
    None for the whole world; :func:`process_sets.expert_partition`
    derives the dispatch groups and the data-parallel replica sets),
    capacity-factor dispatch/combine alltoalls over the expert set.

    Takes global ``tokens [n·T, D]``, replicated ``gates_w [D, E]``,
    and expert weights stacked rank-major on the device axis (``w1 [n,
    D, H]``, ``w2 [n, H, D]`` — :func:`replicate_expert_weights` builds
    the ``E < n`` layout); returns the routed ``[n·T, D]`` output, the
    :func:`make_moe_step` surface. Per-rank resident expert bytes are
    1/E of the dense replicated baseline.

    Knobs (all inert-by-default): ``compression`` /
    ``HOROVOD_MOE_COMPRESSION`` (int8 wire), ``segments`` /
    ``HOROVOD_OVERLAP_SEGMENTS`` (dispatch↔compute pipelining, clamped
    to a divisor of ``capacity``), and the comms planner
    (``HOROVOD_COMMS_PLANNER``) which may stage the full-world dispatch
    two_level. With every knob unset the emitted program is bit-for-bit
    the flat fp32 exchange.

    The returned callable carries introspection hooks: ``.jitted`` (the
    underlying jit for ``.lower()``/jaxpr assertions), ``.meta``
    (plan/bytes/algorithm, populated at first trace),
    ``.expert_groups``/``.replica_groups``/``.num_experts``, and
    ``.dispatch_probe(tokens, gates_w)`` — a route+dispatch-only
    program timed under a ``moe.dispatch.<bytes>B.<algo>`` span that
    feeds ``hvd_alltoall_latency_seconds`` and the α-β comms model.
    ``faults.MOE_DISPATCH`` fires here (the canonical MoE chaos
    injector): drop returns the passthrough residual for the whole
    batch, corrupt flips seeded bits in the token payload pre-dispatch.
    """
    import numpy as np

    from .. import basics, comms_model, faults
    from .. import metrics as _metrics
    from .. import process_sets, tracing
    from ..ops import comms_planner, fusion

    mesh = mesh or basics.global_mesh()
    n = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    groups, replicas = process_sets.expert_partition(expert_set, n)
    e = len(groups[0])
    comp = moe_compression(compression)
    req = int(segments) if segments else fusion.overlap_segments()
    segs = max(dv for dv in range(1, min(req, capacity) + 1)
               if capacity % dv == 0)
    meta = {"plan": None, "nbytes": None, "algorithm": "flat",
            "link_class": "ici", "compression": comp, "segments": segs}

    def _plan_for(d):
        wire = _wire_bytes(e, capacity, d, comp)
        plan = fusion.bucket_plan("alltoall", wire, axis_name, e,
                                   candidates=("flat", "two_level"))
        meta.update(
            plan=plan, nbytes=int(wire),
            algorithm=(plan.algorithm if plan is not None else "flat"),
            link_class=comms_planner._worst_link_class(
                comms_planner._islands_for(e)))
        return plan, wire

    def _traced(tokens, gates_w, w1, w2):
        plan, wire = _plan_for(tokens.shape[1])
        # Trace-time observation: one sample per PROGRAM, the
        # hvd_grad_sync_* idiom — steady-state steps replay the cached
        # executable without re-observing.
        _metrics.MOE_DISPATCH_BYTES.observe(float(wire))
        fn = lambda t, g, a, b: expert_parallel_moe_layer(  # noqa: E731
            t, g, a[0], b[0], axis_name, capacity, groups,
            segments=segs, compression=comp, plan=plan, salt=salt)
        return jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P(axis_name), P(), P(axis_name), P(axis_name)),
            out_specs=(P(axis_name), P(axis_name), P(axis_name)),
            check_vma=False)(tokens, gates_w, w1, w2)

    jitted = jax.jit(_traced)

    def _probe_traced(tokens, gates_w):
        plan, _ = _plan_for(tokens.shape[1])

        def fn(t, g):
            send, *_rest = route_to_capacity(t, t @ g, e, capacity)
            payload, mask = _dispatch_exchange(
                send, axis_name, _moe_exchange(axis_name, groups, plan),
                comp, salt)
            return payload * mask[..., None]

        return jax.shard_map(
            fn, mesh=mesh, in_specs=(P(axis_name), P()),
            out_specs=P(axis_name), check_vma=False)(tokens, gates_w)

    probe_jitted = jax.jit(_probe_traced)

    def dispatch_probe(tokens, gates_w):
        """Route + dispatch only (no FFN, no combine), timed — the
        quantized-vs-fp32 wire A/B and the latency-histogram feed."""
        import time

        name = (f"moe.dispatch.{meta['nbytes'] or 0}B"
                f".{meta['algorithm']}")
        t0 = time.perf_counter()
        with tracing.span(name, "collective",
                          args={"bytes": meta["nbytes"],
                                "op": "alltoall",
                                "algorithm": meta["algorithm"],
                                "link_class": meta["link_class"]}):
            out = probe_jitted(tokens, gates_w)
            jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        _metrics.ALLTOALL_LATENCY.observe(dt,
                                          algorithm=meta["algorithm"])
        if meta["nbytes"]:
            comms_model.observe("alltoall", meta["algorithm"],
                                meta["link_class"], meta["nbytes"], dt)
        return out

    def step(tokens, gates_w, w1, w2):
        spec = (faults.active().get(faults.MOE_DISPATCH)
                if faults.armed(faults.MOE_DISPATCH) else None)
        if spec is not None and spec.mode == "corrupt":
            blob = np.ascontiguousarray(np.asarray(tokens,
                                                   dtype=np.float32))
            flipped = faults.corrupt_payload(faults.MOE_DISPATCH,
                                             blob.tobytes())
            tokens = jnp.asarray(
                np.frombuffer(flipped, np.float32).reshape(blob.shape))
        elif spec is not None and faults.fire(faults.MOE_DISPATCH):
            # Dropped dispatch: the exchange never happens, every token
            # takes the capacity-overflow passthrough residual.
            return jnp.asarray(tokens)
        out, dropped, load = jitted(tokens, gates_w, w1, w2)
        # Zero-duration start markers on both wire directions — the
        # compute_skew attribution's cross-rank lateness food.
        name = f"{meta['nbytes'] or 0}B.{meta['algorithm']}"
        tracer = tracing.get_tracer()
        tracer.record_dispatch(f"moe.dispatch.{name}", cat="collective")
        tracer.record_dispatch(f"moe.combine.{name}", cat="collective")
        dropped = np.asarray(dropped)
        if dropped.sum():
            _metrics.MOE_TOKENS_DROPPED.inc(float(dropped.sum()))
        loads = np.asarray(load).sum(axis=0)
        for j in range(e):
            _metrics.MOE_EXPERT_LOAD.set(float(loads[j]),
                                         expert=str(j))
        return out

    step.jitted = jitted
    step.dispatch_probe = dispatch_probe
    step.expert_groups = groups
    step.replica_groups = replicas
    step.num_experts = e
    step.meta = meta
    return step


def make_data_parallel_moe_step(axis_name: str = "hvd",
                                capacity: int = 4, mesh=None,
                                segments=None):
    """Build the dense data-parallel MoE baseline step: all experts
    replicated on every rank (``w1_all [E, D, H]`` / ``w2_all [E, H,
    D]`` unsharded in_specs), local routing, zero collectives — the
    loss-trajectory oracle and the resident-bytes/throughput comparator
    for :func:`make_expert_parallel_moe_step`. Same wrapper-side
    metrics (dropped tokens, expert load) so the host-cost profile is
    symmetric when the two are compared."""
    import numpy as np

    from .. import basics
    from .. import metrics as _metrics
    from ..ops import fusion

    mesh = mesh or basics.global_mesh()
    req = int(segments) if segments else fusion.overlap_segments()
    segs = max(dv for dv in range(1, min(req, capacity) + 1)
               if capacity % dv == 0)

    jitted = jax.jit(jax.shard_map(
        lambda t, g, a, b: data_parallel_moe_layer(t, g, a, b, capacity,
                                                   segments=segs),
        mesh=mesh,
        in_specs=(P(axis_name), P(), P(), P()),
        out_specs=(P(axis_name), P(axis_name), P(axis_name)),
        check_vma=False))

    def step(tokens, gates_w, w1_all, w2_all):
        out, dropped, load = jitted(tokens, gates_w, w1_all, w2_all)
        dropped = np.asarray(dropped)
        if dropped.sum():
            _metrics.MOE_TOKENS_DROPPED.inc(float(dropped.sum()))
        loads = np.asarray(load).sum(axis=0)
        for j in range(loads.shape[0]):
            _metrics.MOE_EXPERT_LOAD.set(float(loads[j]),
                                         expert=str(j))
        return out

    step.jitted = jitted
    step.num_experts = None  # derived from gates_w at call time
    return step
