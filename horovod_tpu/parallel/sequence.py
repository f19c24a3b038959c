"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

No reference counterpart — the reference is data-parallel only (SURVEY.md
§3.6: SP/CP "absent"; the `alltoall`/`allgather` primitives it ships are
exactly what a sequence-parallel scheme needs). This module is the
TPU-native long-context subsystem the north star makes first-class:

- **Ring attention** (``ring_attention``): sequence sharded over a mesh
  axis; K/V blocks rotate around the ring via ``lax.ppermute`` — on TPU
  these are neighbor transfers over ICI torus links, overlapping with each
  step's blockwise-attention compute. Memory per chip stays O(S/N); total
  sequence length scales linearly with the ring size.
- **Ulysses** (``ulysses_attention``): ``lax.all_to_all`` re-shards
  sequence↔heads so each chip runs *full-sequence* attention on H/N heads;
  cheaper collectives for moderate S, requires H divisible by the axis.

Both run inside ``shard_map`` over a 1-D sub-axis (by default the global
``'hvd'`` axis, composable with DP via process sets / mesh reshapes) and use
the same online-softmax math as ``horovod_tpu.ops.attention`` with fp32
accumulators, so either scheme matches the dense oracle to bf16 tolerance.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.attention import (
    LSE_MASKED,
    NEG_INF,
    _attend_block,
    _finalize,
    blockwise_attention_reference,
    flash_attention,
    flash_attention_lse,
)


def _local_attend(q, k, v, m, l, o, scale, causal, q_offset, k_offset):
    """Fold one K/V shard into the running (m, l, o) for all [B, H] rows.

    q: [B, H, Sq, D]; k, v: [B, H, Sk, D]; m, l: [B, H, Sq]; o fp32 like q.
    """
    mask = None
    if causal:
        Sq, Sk = q.shape[2], k.shape[2]
        qpos = q_offset + jnp.arange(Sq)
        kpos = k_offset + jnp.arange(Sk)
        mask = qpos[:, None] >= kpos[None, :]

    def per_head(qh, kh, vh, mh, lh, oh):
        return _attend_block(qh, kh, vh, mh, lh, oh, mask, scale)

    return jax.vmap(jax.vmap(per_head))(q, k, v, m, l, o)


def ring_attention(q, k, v, axis_name: str = "hvd", causal: bool = False,
                   use_flash: bool = False, interpret: bool = False):
    """Ring (context-parallel) attention inside shard_map.

    Args: q, k, v ``[B, H, S_local, D]`` — the sequence dimension is the
    shard of a global sequence ``S_local * axis_size``, shard r holding
    positions ``[r*S_local, (r+1)*S_local)``. Returns the local output
    shard ``[B, H, S_local, D]``.

    Step t computes attention of the local Q block against the K/V block
    that originated on rank ``(idx - t) % n``, while ppermute-ing K/V one
    hop forward for step t+1 — compute and ICI transfer overlap (XLA
    schedules the independent ops concurrently).

    ``use_flash=True`` runs each step through the Pallas flash kernel and
    merges the per-shard partials by logsumexp — the MXU-tiled hot path
    for long sequences (trainable: the kernel has a custom_vjp backward).
    """
    n = lax.psum(1, axis_name)  # mesh axis size: a static Python int
    idx = lax.axis_index(axis_name)
    B, H, S, D = q.shape
    scale = 1.0 / (D ** 0.5)
    perm = [(i, (i + 1) % n) for i in range(n)]

    if use_flash:
        return _ring_attention_flash(q, k, v, n, idx, perm, axis_name,
                                     causal, interpret)

    q32 = q.astype(jnp.float32)
    m = jnp.full((B, H, S), NEG_INF, jnp.float32)
    l = jnp.zeros((B, H, S), jnp.float32)
    o = jnp.zeros((B, H, S, D), jnp.float32)

    # Static unroll over the (static) ring size: rotate for the NEXT step
    # before computing, so the ICI transfer overlaps the compute — and skip
    # the rotation on the last step (its result would be discarded, but XLA
    # cannot DCE a collective).
    kt, vt = k, v
    for t in range(n):
        src = (idx - t) % n  # which rank's K/V block we currently hold
        if t < n - 1:
            k_next = lax.ppermute(kt, axis_name, perm)
            v_next = lax.ppermute(vt, axis_name, perm)
        m, l, o = _local_attend(
            q32, kt, vt, m, l, o, scale, causal,
            q_offset=idx * S, k_offset=src * S,
        )
        if t < n - 1:
            kt, vt = k_next, v_next

    out = jax.vmap(jax.vmap(_finalize))(l, o)
    return out.astype(q.dtype)


def _ring_attention_flash(q, k, v, n, idx, perm, axis_name, causal,
                          interpret):
    """Flash-kernel ring: per-step (out_t, lse_t) from the Pallas kernel,
    merged online by logsumexp.

    Causality without traced kernel offsets (Pallas mask offsets are
    static): step t==0 is the diagonal block (causal kernel, Sq==Sk);
    later steps are block-wise all-or-nothing — the K/V shard originated
    on ``src = (idx - t) % n``, entirely in the past (visible, non-causal
    kernel) or entirely in the future (contribution erased by setting its
    lse to -inf, a traced select on the merge weights).
    """
    B, H, S, D = q.shape
    m_run = jnp.full((B, H, S), NEG_INF, jnp.float32)
    l_run = jnp.zeros((B, H, S), jnp.float32)
    acc = jnp.zeros((B, H, S, D), jnp.float32)

    kt, vt = k, v
    for t in range(n):
        src = (idx - t) % n
        if t < n - 1:
            k_next = lax.ppermute(kt, axis_name, perm)
            v_next = lax.ppermute(vt, axis_name, perm)
        o_t, lse_t = flash_attention_lse(
            q, kt, vt, causal=(causal and t == 0), interpret=interpret)
        # Fully-masked-row sentinel (+BIG) means "no keys": merge as -inf.
        lse_t = jnp.where(lse_t >= LSE_MASKED * 0.5, NEG_INF, lse_t)
        if causal and t > 0:
            visible = (src < idx)  # whole-block causality, traced scalar
            lse_t = jnp.where(visible, lse_t, NEG_INF)
        # Online logsumexp merge of the partial attention.
        m_new = jnp.maximum(m_run, lse_t)
        # Clamp so untouched rows (both -inf) stay a no-op.
        corr = jnp.exp(jnp.minimum(m_run - m_new, 0.0))
        w = jnp.exp(jnp.minimum(lse_t - m_new, 0.0))
        w = jnp.where(lse_t <= NEG_INF * 0.5, 0.0, w)
        corr = jnp.where(m_run <= NEG_INF * 0.5, 0.0, corr)
        acc = acc * corr[..., None] + w[..., None] * o_t.astype(jnp.float32)
        l_run = l_run * corr + w
        m_run = m_new
        if t < n - 1:
            kt, vt = k_next, v_next

    safe = jnp.where(l_run == 0.0, 1.0, l_run)
    return (acc / safe[..., None]).astype(q.dtype)


def ulysses_attention(q, k, v, axis_name: str = "hvd", causal: bool = False,
                      use_flash: bool = False, interpret: bool = False):
    """Ulysses-style sequence parallelism inside shard_map.

    Args: q, k, v ``[B, H, S_local, D]`` with ``H`` divisible by the axis
    size. all_to_all re-shards to ``[B, H/n, S_global, D]``, runs full
    attention per head group (optionally the Pallas flash kernel), and
    re-shards back. Returns ``[B, H, S_local, D]``.
    """
    n = lax.psum(1, axis_name)
    B, H, S, D = q.shape

    def to_seq(x):  # [B, H, S/n, D] -> [B, H/n, S, D]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def to_heads(x):  # [B, H/n, S, D] -> [B, H, S/n, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qs, ks, vs = to_seq(q), to_seq(k), to_seq(v)
    if use_flash:
        out = flash_attention(qs, ks, vs, causal=causal, interpret=interpret)
    else:
        out = blockwise_attention_reference(qs, ks, vs, causal=causal)
    return to_heads(out)


def shard_sequence(tree, axis: int = 2, process_set=None):
    """Split arrays along the sequence axis into the stacked-rank layout
    expected by shard_map over the set's mesh (helper for input pipelines)."""
    from ..process_sets import global_process_set

    ps = process_set if process_set is not None else global_process_set
    n = ps.size()

    def split(x):
        if x.shape[axis] % n:
            raise ValueError(
                f"sequence length {x.shape[axis]} not divisible by "
                f"sequence-parallel size {n}"
            )
        return jnp.stack(jnp.split(x, n, axis=axis))

    return jax.tree.map(split, tree)


def make_sp_attention_step(axis_name: str = "hvd", scheme: str = "ring",
                           causal: bool = False, mesh=None,
                           interpret: bool = False):
    """Build a jitted global-sequence attention fn over the mesh.

    Takes global [B, H, S, D] arrays, shards S over the axis, runs the
    chosen scheme, returns the global output — the one-call user surface.
    ``interpret=True`` runs the ``ring-flash`` Pallas kernel in
    interpret mode (CPU tests); the default compiles it for the TPU.
    """
    from jax.sharding import PartitionSpec as P

    from .. import basics

    mesh = mesh or basics.global_mesh()
    if scheme == "ring":
        inner = functools.partial(ring_attention, axis_name=axis_name,
                                  causal=causal)
    elif scheme == "ring-flash":
        inner = functools.partial(
            ring_attention, axis_name=axis_name, causal=causal,
            use_flash=True, interpret=interpret,
        )
    elif scheme == "ulysses":
        inner = functools.partial(ulysses_attention, axis_name=axis_name,
                                  causal=causal)
    else:
        raise ValueError(
            f"unknown scheme {scheme!r}; use 'ring', 'ring-flash' or "
            "'ulysses'")

    spec = P(None, None, axis_name, None)
    sharded = jax.shard_map(
        inner, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return jax.jit(sharded)
