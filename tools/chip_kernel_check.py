"""The Pallas kernels, compiled on the chip, against their references.

    python tools/chip_kernel_check.py

``flash_attention`` forward and backward in bf16 against
``blockwise_attention_reference`` in float32 at the single-tile shapes
BERT-Large uses (S512 and S128, D64: a grid step takes a group of slices),
at 15 causal single-tile slices, the same two kernels on tokens-major
operands (``flash_attention_tokens_major``: ``[B, S, H * D]``, two heads of
D64 or one of D128 a 128-lane block), at a multi-tile causal shape (S2048
D128) and at OLMoE's (one sequence of S4096, 16 heads of D128: a grid of
16 x 8 x 8 tiles of 512); then the masks and layouts the later decoders
brought, each against a dense masked softmax in float32 (``check_masked``):
a window under the diagonal, grouped keys and values (8 query heads on 2
key/value heads), the two block masks of block-diffusion training and
``block_diffusion_attention`` over a doubled stream (SDAR's: blocks of 4, 8
query heads on one key/value head), and that call once more at the SDAR
cell's own shapes on a sample of rows (``check_sdar_rows``); and the
multi-tile kernels fed tokens-major against themselves fed head-major, bit
for bit (``check_tiles_as_they_lie``); and latent attention's call, keys of
192 lanes and values of 128, checked and timed beside 128 / 128 and 256 /
128 at the Kimi Linear cell's shape (``check_two_widths``; ``python
tools/chip_kernel_check.py two_widths`` runs that alone); and Kimi Delta
Attention's pair terms, the Pallas kernel and its backward kernel against
the plain form on the chip, checked and timed at the cell's shape
(``check_pair_terms``; ``python tools/chip_kernel_check.py pair_terms``
runs that alone); and the Mamba-2 scan's two kernels against the plain
chunk form and the float32 recurrence at Granite's and Nemotron-H's mixer
shapes, checked and timed (``check_ssd``; ``python
tools/chip_kernel_check.py ssd`` runs that alone); and Kimi Delta
Attention's solve and chunk loop, the two kernels against the plain form
and the float32 recurrence at the cell's shape, checked and timed
(``check_kda_scan``; ``python tools/chip_kernel_check.py kda_scan`` runs
that alone); and latent attention's one pass to the kernels at the JoyAI
Flash cell's shapes, the four kernels against ``latent.turn`` with XLA's
reshapes and transposes, checked and timed (``check_mla_rope``; ``python
tools/chip_kernel_check.py mla_rope`` runs that alone). Compiled, never ``interpret=True``: off a TPU this exits
non-zero.

The tolerance is the one ``tests/test_sequence_parallel.py`` uses for bf16
inputs (rtol = atol = 2e-2) with atol multiplied by the reference's
largest magnitude. That is looser than the test's where a tensor exceeds 1,
and on purpose: the kernels feed bf16 probabilities and score gradients to
the MXU, so an element's error follows the size of the terms summed into it
(the tensor's scale, up to 24 for dk at S2048) and not its own value, which
may be near zero. The test's inputs keep every tensor near 1, where the two
agree. How many elements the unscaled tolerance would refuse is printed
beside each tensor, so the difference is on record (PERF.md).
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BF16_TOL = 2e-2


def plain_form(fn):
    """``fn`` as any platform but a TPU lowers it, compiled for the chip
    all the same: every primitive of ``ops/kernel_parts.where_lowered``
    takes its plain form (its lowering for a CPU), and XLA compiles the
    program for the device that runs this. What the kernels are held to
    and timed beside, through the public entries alone. Traced and
    compiled at the first call's shapes."""
    import jax

    compiled = []

    def run(*args):
        if not compiled:
            compiled.append(jax.jit(fn).trace(*args).lower(
                lowering_platforms=("cpu",)).compile())
        return compiled[0](*args)

    return run


def _close(name, got, want) -> None:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    scale = max(1.0, float(np.abs(want).max()))
    err = np.abs(got - want)
    unscaled = int((err > BF16_TOL + BF16_TOL * np.abs(want)).sum())
    print(f"  {name}: max |err| {float(err.max()):.3e} (reference max "
          f"{scale:.3g}); outside unscaled atol: {unscaled} of {err.size}")
    np.testing.assert_allclose(got, want, rtol=BF16_TOL,
                               atol=BF16_TOL * scale, err_msg=name)


def check_flash(batch, heads, seq, dim, causal, tokens_major=False) -> None:
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.attention import (
        blockwise_attention_reference,
        flash_attention,
        flash_attention_tokens_major,
    )

    print(f"flash_attention B{batch} H{heads} S{seq} D{dim} "
          f"causal={causal} bf16{' tokens-major' if tokens_major else ''}")
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(key, (batch, heads, seq, dim), jnp.bfloat16)
               for key in keys)

    def across(x):  # [B, H, S, D] <-> [B, S, H, D]
        return x.transpose(0, 2, 1, 3)

    def loss_flash(q, k, v):
        if tokens_major:  # the transposes are the check's, not the entry's
            out = across(flash_attention_tokens_major(
                *(across(x).reshape(batch, seq, heads * dim)
                  for x in (q, k, v)), heads, causal=causal).reshape(
                      batch, seq, heads, dim))
        else:
            out = flash_attention(q, k, v, causal=causal)
        return jnp.sum(out.astype(jnp.float32) ** 2), out

    def loss_ref(q, k, v):
        out = blockwise_attention_reference(q, k, v, causal=causal)
        return jnp.sum(out ** 2), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        loss_flash, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    (_, want_out), want_grads = jax.jit(jax.value_and_grad(
        loss_ref, argnums=(0, 1, 2), has_aux=True))(
            *(x.astype(jnp.float32) for x in (q, k, v)))
    _close("out", out, want_out)
    for name, g, w in zip(("dq", "dk", "dv"), grads, want_grads):
        _close(name, g, w)


def check_masked(name, attend, mask, heads, kv_heads, seq, dim=128,
                 v_dim=None) -> None:
    """``attend(q, k, v)`` (``q [1, heads, seq, dim]``, ``k [1, kv_heads,
    seq, dim]``, ``v [1, kv_heads, seq, v_dim]`` in bf16; ``v_dim`` is
    ``dim`` unless given) and its three gradients against the float32
    softmax under the dense ``mask [seq, seq]``, the keys and values of a
    group repeated."""
    import jax
    import jax.numpy as jnp

    v_dim = v_dim or dim
    print(f"{name}: H{heads} on KV{kv_heads} S{seq} D{dim} "
          f"{'' if v_dim == dim else f'values D{v_dim} '}bf16, "
          f"{int(mask.sum())} of {mask.size} pairs a head")
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(keys[0], (1, heads, seq, dim), jnp.bfloat16)
    k = jax.random.normal(keys[1], (1, kv_heads, seq, dim), jnp.bfloat16)
    v = jax.random.normal(keys[2], (1, kv_heads, seq, v_dim), jnp.bfloat16)

    def dense(q, k, v):
        k, v = (jnp.repeat(x, heads // kv_heads, axis=1) for x in (k, v))
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / dim ** 0.5
        return jnp.einsum(
            "bhqk,bhkd->bhqd",
            jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), -1), v)

    def loss(fn, q, k, v):
        out = fn(q, k, v)
        return jnp.sum(out.astype(jnp.float32) ** 2), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        lambda *a: loss(attend, *a), argnums=(0, 1, 2), has_aux=True))(
            q, k, v)
    with jax.default_matmul_precision("highest"):
        (_, want_out), want_grads = jax.jit(jax.value_and_grad(
            lambda *a: loss(dense, *a), argnums=(0, 1, 2), has_aux=True))(
                *(x.astype(jnp.float32) for x in (q, k, v)))
    _close("out", out, want_out)
    for name, g, w in zip(("dq", "dk", "dv"), grads, want_grads):
        _close(name, g, w)


def check_masks() -> None:
    """A window, grouped keys and values, the two block masks and the
    doubled stream of block-diffusion training: several tiles of 512 each,
    so the tile plan, the clamps and the in-tile masks all run."""
    from functools import partial

    import numpy as np

    from horovod_tpu.models.sdar import visible
    from horovod_tpu.ops.attention import (block_diffusion_attention,
                                           flash_attention)

    seq = 2048
    ahead = np.arange(seq)[:, None] - np.arange(seq)[None, :]
    check_masked("window 768, grouped",
                 partial(flash_attention, causal=True, window=768),
                 (ahead >= 0) & (ahead < 768), 8, 2, seq)
    check_masked("full causal, grouped",
                 partial(flash_attention, causal=True), ahead >= 0, 8, 2,
                 seq)
    blk = np.arange(seq) // 4
    check_masked("block-causal, blocks of 4",
                 partial(flash_attention, causal=True, block_length=4),
                 blk[None, :] <= blk[:, None], 8, 1, seq)
    check_masked("doubled stream, blocks of 4",
                 partial(block_diffusion_attention, block_length=4),
                 np.asarray(visible(4, seq // 2)), 8, 1, seq)


def check_sdar_rows(heads=32, kv_heads=4, seq=8192, dim=128,
                    length=4) -> None:
    """``block_diffusion_attention`` at the SDAR cell's own shapes (one
    row, 32 query heads on 4 key/value heads of 128, 8,192 noisy and 8,192
    clean positions, blocks of 4), forward, on a sample of the query rows
    of both halves against the float32 softmax under the three predicates
    written out here: the first blocks, where four keys too many or too
    few are a third of what a query sees (the cell's ``correct`` cannot
    see them: PERF.md), the rows on either side of a tile's edge, the last
    ones and rows drawn at random. ``[rows, 2 seq]`` scores a head: the
    square is never built."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops.attention import block_diffusion_attention

    print(f"doubled stream at the cell's shapes: H{heads} on KV{kv_heads} "
          f"S{seq}+{seq} D{dim} bf16, blocks of {length}, sampled rows")
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(keys[0], (1, heads, 2 * seq, dim), jnp.bfloat16)
    k, v = (jax.random.normal(key, (1, kv_heads, 2 * seq, dim),
                              jnp.bfloat16) for key in keys[1:])
    out = jax.jit(partial(block_diffusion_attention,
                          block_length=length))(q, k, v)

    half = np.unique(np.concatenate([
        np.arange(16), np.arange(504, 520), np.arange(seq - 16, seq),
        np.random.default_rng(0).integers(0, seq, 48)]))
    rows = np.concatenate([half, seq + half])
    pos, noisy = np.arange(2 * seq) % seq, np.arange(2 * seq) < seq
    q_blk, k_blk = pos[rows, None] // length, pos[None, :] // length
    q_noisy, k_noisy = noisy[rows, None], noisy[None, :]
    seen = ((q_noisy & k_noisy & (k_blk == q_blk))
            | (q_noisy & ~k_noisy & (k_blk < q_blk))
            | (~q_noisy & ~k_noisy & (k_blk <= q_blk)))
    assert int(seen.sum()) == int(
        (pos[rows] // length * length + length).sum())

    @jax.jit
    def dense(q, k, v):
        q, k, v = (x[0].astype(jnp.float32) for x in (q, k, v))
        k, v = (jnp.repeat(x, heads // kv_heads, axis=0) for x in (k, v))
        scores = jnp.einsum("hqd,hkd->hqk", q[:, rows], k) / dim ** 0.5
        return jnp.einsum(
            "hqk,hkd->hqd",
            jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1), v)

    with jax.default_matmul_precision("highest"):
        want = dense(q, k, v)
    for name, pick in (("noisy rows", slice(0, len(half))),
                       ("clean rows", slice(len(half), None))):
        _close(name, out[0][:, rows][:, pick], want[:, pick])


def check_two_widths(heads=32, seq=8192, calls=20) -> None:
    """Latent attention's call (``models/kimi_linear.py``: the scores
    contract over 192 lanes, the values are 128 wide): against the dense
    softmax at S2048, then timed at the Kimi Linear cell's own shape, one
    sequence of 8,192 and 32 heads, forward and backward, beside the same
    call at 128 / 128 lanes (what a block of one and a half lane blocks
    costs over one) and at 256 / 128 (what padding the keys in HBM would
    cost): ``calls`` dispatched back to back, only the last result kept."""
    import time
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops.attention import flash_attention

    ahead = np.arange(2048)[:, None] - np.arange(2048)[None, :]
    check_masked("latent attention's widths, causal",
                 partial(flash_attention, causal=True), ahead >= 0, 8, 8,
                 2048, dim=192, v_dim=128)
    step = jax.jit(jax.value_and_grad(
        lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True).astype(jnp.float32)), argnums=(0, 1, 2)))
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    for dim in (192, 128, 256):
        q, k = (jax.random.normal(key, (1, heads, seq, dim), jnp.bfloat16)
                for key in keys[:2])
        v = jax.random.normal(keys[2], (1, heads, seq, 128), jnp.bfloat16)
        jax.block_until_ready(step(q, k, v))
        t0 = time.perf_counter()
        for _ in range(calls):
            out = step(q, k, v)
        jax.block_until_ready(out)
        print(f"  H{heads} S{seq} causal, keys D{dim}, values D128: "
              f"{(time.perf_counter() - t0) / calls * 1e3:.3f} ms a forward "
              f"and backward call (with the sum and its gradient)")


def check_pair_terms(heads=32, chunks=128, size=64, sub=16, width=128,
                     calls=20) -> None:
    """``ops.linear_attention.pair_terms_kernel`` (operands tokens-major,
    ``[1, 8192, 32, 128]``, as the cell's projections write them) against
    the same call's plain form (:func:`plain_form`: on the head-major view
    of the same arrays, the transposes part of what it costs),
    both compiled on the chip, at the Kimi Linear cell's shape
    in bfloat16 with a float32 ``gamma`` that falls by 0.05, 1.6 and
    20 a token: the two results (float32's rounding: the same reference
    rows and the same roundings to bfloat16; the strict upper triangle
    exactly zero) and ``dq``, ``dk``, ``dgamma`` under random cotangents
    (a bfloat16 cotangent's rounding); then each form timed, forward alone
    and forward with backward, ``calls`` dispatched back to back with only
    the last result kept."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops import linear_attention

    dtype, shape = jnp.bfloat16, (1, heads, chunks, size, width)

    def tokens_major(x):  # [B, H, N, C, d] -> [B, S, H, d]
        return jnp.moveaxis(x, 1, 3).reshape(1, chunks * size, heads, width)

    def forward(*a):
        return linear_attention.pair_terms_kernel(*a, size, sub, dtype)

    def both(q, k, gamma, bars):
        out, vjp = jax.vjp(forward, q, k, gamma)
        return out + vjp(bars)

    runs = {name: {"forward": compiled(forward),
                   "forward and backward": compiled(both)}
            for name, compiled in (("plain", plain_form),
                                   ("kernel", jax.jit))}
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    q, k = (tokens_major(jax.random.normal(key, shape).astype(dtype))
            for key in keys[:2])
    bars = tuple(jax.random.normal(key, shape[:-1] + (size,))
                 for key in keys[3:])
    names = ("inside", "a", "dq", "dk", "dgamma")
    for rate in (0.05, 1.6, 20.0):
        gamma = tokens_major(jnp.cumsum(-rate * jax.random.uniform(
            keys[2], shape, minval=0.5, maxval=1.0), -2))
        want, got = (
            [np.asarray(x, np.float32)
             for x in runs[name]["forward and backward"](q, k, gamma, bars)]
            for name in ("plain", "kernel"))
        print(f" pair terms at {rate} a token:")
        for name, a, b in zip(names, got, want):
            assert np.isfinite(a).all(), name
            scale = float(np.abs(b).max())
            print(f"  {name}: max |kernel - plain| "
                  f"{float(np.abs(a - b).max()):.3e} (plain max {scale:.3g})")
            if name == "dgamma":  # its diagonal terms cancel, to q dq's rounding
                scale += float(np.abs(got[2]).max())
            room = 1e-5 if name in ("inside", "a") else BF16_TOL
            np.testing.assert_allclose(a, b, rtol=0, atol=room * scale,
                                       err_msg=name)
            if name in ("inside", "a"):
                assert (np.triu(a, 1) == 0).all(), name
    for name, by_what in runs.items():
        for what, fn in by_what.items():
            args = (q, k, gamma) + ((bars,) if what != "forward" else ())
            jax.block_until_ready(fn(*args))
            t0 = time.perf_counter()
            for _ in range(calls):
                out = fn(*args)
            jax.block_until_ready(out)
            print(f"  {name}, {what}: "
                  f"{(time.perf_counter() - t0) / calls * 1e3:.3f} ms a call "
                  f"at [1, {chunks * size}, {heads}, {width}]")


def check_kda_scan(heads=32, chunks=128, size=64, sub=16, width=128,
                   calls=20) -> None:
    """Kimi Delta Attention's solve and chunk loop at the Kimi Linear
    cell's shape (one sequence of 8,192 = 128 chunks of 64, 32 heads of
    128, bfloat16 with float32 decays): the rule as the TPU's program
    holds it (``ops.linear_attention.chunk_scan_kernel``'s two kernels
    behind the pair kernels) and the rule's plain form
    (:func:`plain_form`), both against the float32 token-by-token
    recurrence, ``o`` and the five gradients under a random cotangent, at
    the initial draw's decays and at 1.6 a token; then
    ``chunk_scan_kernel`` and its plain form timed alone on the same
    operands, forward alone and forward with backward, ``calls`` dispatched back to back with only the
    last result kept. The VLIW bundles a grid step come from the sandbox's
    compile (``--xla_jf_dump_to``: PERF.md), not from here."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from horovod_tpu.ops import kernel_parts, linear_attention

    f32, dtype = jnp.float32, jnp.bfloat16
    seq = chunks * size

    def recurrence(q, k, v, g, beta):
        """Token by token in float32; 128 tokens at a time under
        ``jax.checkpoint``, so that its gradient keeps a state every 128
        tokens and not all 8,192 of them (17 GB)."""
        def one_token(state, xs):
            q, k, v, g, beta = xs
            state = jnp.exp(g)[..., None] * state
            seen = jnp.einsum("bhkv,bhk->bhv", state, k)
            state = state + jnp.einsum(
                "bhk,bhv->bhkv", beta[..., None] * k, v - seen)
            return state, jnp.einsum("bhkv,bhk->bhv", state, q)

        @jax.checkpoint
        def some_tokens(state, xs):
            return lax.scan(one_token, state, xs)

        start = jnp.zeros((1, heads, width, width), f32)
        out = lax.scan(some_tokens, start, jax.tree.map(
            lambda t: jnp.moveaxis(t.astype(f32), 1, 0).reshape(
                (-1, 128) + t.shape[:1] + t.shape[2:]),
            (q, k, v, g, beta)))[1]
        return jnp.moveaxis(out.reshape((-1,) + out.shape[2:]), 0, 1)

    def rule(*t):
        return linear_attention.kimi_delta_rule(*t, chunk=size, sub=sub)

    def both(form, compiled=jax.jit):
        def run(*t):
            out, vjp = jax.vjp(form, *t[:-1])
            return (out,) + vjp(t[-1].astype(out.dtype))
        return compiled(run)

    def timed(name, what, fn, *args):
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        print(f"  {name}, {what}: "
              f"{(time.perf_counter() - t0) / calls * 1e3:.3f} ms a call")

    keys = jax.random.split(jax.random.PRNGKey(11), 6)
    q, k = (jax.random.normal(key, (1, seq, heads, width)) for key in keys[:2])
    q = (q / jnp.linalg.norm(q, axis=-1, keepdims=True)
         * width ** -0.5).astype(dtype)
    k = (k / jnp.linalg.norm(k, axis=-1, keepdims=True)).astype(dtype)
    v = jax.random.normal(keys[2], (1, seq, heads, width)).astype(dtype)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (1, seq, heads)))
    o_bar = jax.random.normal(keys[5], v.shape).astype(dtype)
    forms = {"recurrence": (recurrence, jax.jit),
             "plain": (rule, plain_form), "kernel": (rule, jax.jit)}
    for rate in (0.05, 1.6):
        g = -rate * jax.random.uniform(keys[3], q.shape, minval=0.5,
                                       maxval=1.0)
        args = (q, k, v, g, beta)
        got = {name: [np.asarray(t, np.float32)
                      for t in both(*form)(*args, o_bar)]
               for name, form in forms.items()}
        print(f" kimi_delta_rule at [1, {seq}, {heads}, {width}], chunk "
              f"{size}, {rate} a token:")
        for n, name in enumerate(("o", "dq", "dk", "dv", "dg", "dbeta")):
            want = got["recurrence"][n]
            scale = float(np.abs(want).max())
            off = {form: float(np.abs(got[form][n] - want).max())
                   for form in ("plain", "kernel")}
            apart = float(np.abs(got["kernel"][n] - got["plain"][n]).max())
            print(f"  {name}: max |kernel - recurrence| {off['kernel']:.3e}, "
                  f"|plain - recurrence| {off['plain']:.3e}, |kernel - plain| "
                  f"{apart:.3e} (recurrence max {scale:.3g})")
            assert np.isfinite(got["kernel"][n]).all(), name
            np.testing.assert_allclose(
                got["kernel"][n], want, rtol=0,
                atol=max(BF16_TOL * scale, 2 * off["plain"]), err_msg=name)
    for name in ("plain", "kernel"):
        form, compiled = forms[name]
        timed(name + " rule", "forward", compiled(form), *args)
        timed(name + " rule", "forward and backward", both(form, compiled),
              *args, o_bar)

    # the chunk loop alone: gamma a chunk's running sums where g lies, beta
    # a head a row, the pair terms the kernels'
    gamma = kernel_parts.running_sum("bnij,bnjx->bnix", (1, chunks), size)(
        g.reshape(1, chunks, size, -1)).reshape(g.shape)
    operands = [q, k, v, gamma,
                jnp.moveaxis(beta.reshape(1, chunks, size, heads), 3, 1)[
                    ..., None],
                *linear_attention.pair_terms_kernel(q, k, gamma, size, sub,
                                                    dtype)]
    for name, compiled in (("chunk_scan_kernel's plain form", plain_form),
                           ("chunk_scan_kernel", jax.jit)):
        form = linear_attention.chunk_scan_kernel
        timed(name, "forward", compiled(form), *operands)
        timed(name, "forward and backward", both(form, compiled), *operands,
              o_bar)


def check_ssd(calls=20, cells=(("nemotron-h", 8192, 8, 128),
                              ("granite", 4096, 1, 256))) -> None:
    """``ops.ssd.ssd_scan_kernel`` against its plain form
    (:func:`plain_form`) and against the float32 token-by-token
    recurrence, all compiled on the chip, at the
    two cells' mixer shapes (Nemotron-H: 8,192 tokens, 64 heads of 64 on 8
    groups of 128, chunk 128; Granite: 4,096 tokens, one group, chunk 256)
    in bfloat16 with float32 steps, at the initial draw's steps (0.001 to
    0.1, rates 1 to 16): ``y`` and the six gradients under a random
    cotangent, each form's largest difference from the recurrence beside
    the other's; then each form timed, forward alone and forward with
    backward, ``calls`` dispatched back to back with only the last result
    kept. The VLIW bundles a grid step come from the sandbox's compile
    (``--xla_jf_dump_to``: PERF.md), not from here."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from horovod_tpu.ops import ssd

    f32, dtype = jnp.float32, jnp.bfloat16
    heads, width, state = 64, 64, 128

    def recurrence(x, dt, a, b, c, d):
        """Token by token in float32; 128 tokens at a time under
        ``jax.checkpoint``, so that its gradient keeps a state every 128
        tokens and not all 8,192 of them (17 GB)."""
        share = heads // b.shape[2]

        def one_token(carry, xs):
            x, dt, b, c = xs
            b, c = jnp.repeat(b, share, 1), jnp.repeat(c, share, 1)
            carry = jnp.exp(dt * a)[..., None, None] * carry + (
                (dt[..., None] * x)[..., None] * b[..., None, :])
            return carry, (carry * c[..., None, :]).sum(-1) + d[:, None] * x

        @jax.checkpoint
        def some_tokens(carry, xs):
            return lax.scan(one_token, carry, xs)

        start = jnp.zeros((x.shape[0], heads, width, state), f32)
        out = lax.scan(some_tokens, start, jax.tree.map(
            lambda t: jnp.moveaxis(t.astype(f32), 1, 0).reshape(
                (-1, 128) + t.shape[:1] + t.shape[2:]), (x, dt, b, c)))[1]
        return jnp.moveaxis(out.reshape((-1,) + out.shape[2:]), 0, 1)

    for cell, seq, groups, chunk in cells:
        keys = jax.random.split(jax.random.PRNGKey(7), 7)
        x = jax.random.normal(keys[0], (1, seq, heads, width)).astype(dtype)
        dt = jnp.exp(jax.random.uniform(
            keys[1], (1, seq, heads), minval=np.log(1e-3), maxval=np.log(0.1)))
        a = -jax.random.uniform(keys[2], (heads,), minval=1.0, maxval=16.0)
        b, c = (jax.random.normal(key, (1, seq, groups, state)).astype(dtype)
                for key in keys[3:5])
        d = jax.random.normal(keys[5], (heads,))
        y_bar = jax.random.normal(keys[6], x.shape).astype(dtype)
        args = (x, dt, a, b, c, d)
        def scan(*t):
            return ssd.ssd_scan_kernel(*t, chunk)

        forms = {"recurrence": (recurrence, jax.jit),
                 "plain": (scan, plain_form), "kernel": (scan, jax.jit)}

        def both(form, compiled):
            def run(*t):
                out, vjp = jax.vjp(form, *t[:-1])
                return (out,) + vjp(t[-1].astype(out.dtype))
            return compiled(run)

        got = {name: [np.asarray(t, np.float32)
                      for t in both(*form)(*args, y_bar)]
               for name, form in forms.items()}
        print(f" ssd scan at {cell}'s [1, {seq}, {heads}, {width}], "
              f"{groups} groups of {state}, chunk {chunk}:")
        for n, name in enumerate(("y", "dx", "ddt", "da", "db", "dc", "dd")):
            want = got["recurrence"][n]
            scale = float(np.abs(want).max())
            off = {form: float(np.abs(got[form][n] - want).max())
                   for form in ("plain", "kernel")}
            print(f"  {name}: max |kernel - recurrence| {off['kernel']:.3e}, "
                  f"|plain - recurrence| {off['plain']:.3e} (recurrence max "
                  f"{scale:.3g})")
            assert np.isfinite(got["kernel"][n]).all(), name
            np.testing.assert_allclose(
                got["kernel"][n], want, rtol=0,
                atol=max(BF16_TOL * scale, 2 * off["plain"]), err_msg=name)
        for name in ("plain", "kernel"):
            form, compiled = forms[name]
            for what, fn, more in (("forward", compiled(form), ()),
                                   ("forward and backward",
                                    both(form, compiled), (y_bar,))):
                jax.block_until_ready(fn(*args, *more))
                t0 = time.perf_counter()
                for _ in range(calls):
                    out = fn(*args, *more)
                jax.block_until_ready(out)
                print(f"  {name}, {what}: "
                      f"{(time.perf_counter() - t0) / calls * 1e3:.3f} ms a "
                      f"call")


def check_tokens_major() -> None:
    """BERT's two shapes as its projections write them, an odd group of
    causal pairs, and a head a block."""
    check_flash(4, 16, 512, 64, causal=False, tokens_major=True)
    check_flash(96, 16, 128, 64, causal=False, tokens_major=True)
    check_flash(3, 6, 128, 64, causal=True, tokens_major=True)
    check_flash(2, 4, 128, 128, causal=False, tokens_major=True)


def check_tiles_as_they_lie() -> None:
    """The multi-tile kernels on tokens-major operands (PR 40: a head the
    128-lane block the index maps find) against the same kernels on the
    transposed operands, compiled: the context, the log-sum-exp and the
    three gradients **bit for bit**, at SmallThinker's windowed and full
    layers (28 heads on 4, one sequence of 16,384), at SDAR's two calls (32
    on 4, 8,192, blocks of 4) and at a plain causal shape; and
    ``rope_tokens_major`` against ``rope``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.models import parts
    from horovod_tpu.ops.attention import (flash_attention_lse,
                                           flash_attention_tokens_major_lse)

    def across(x, dim):  # [B, S, H * D] -> [B, H, S, D]
        return x.reshape(x.shape[:2] + (-1, dim)).transpose(0, 2, 1, 3)

    def back(x):  # [B, H, S, D] -> [B, S, H * D]
        return x.transpose(0, 2, 1, 3).reshape(x.shape[0], x.shape[2], -1)

    for name, (batch, heads, kv_heads, seq), call in (
            ("causal", (2, 4, 4, 2048), dict(causal=True)),
            ("SmallThinker's window", (1, 28, 4, 16384),
             dict(causal=True, window=4096)),
            ("SmallThinker's full layer", (1, 28, 4, 16384),
             dict(causal=True)),
            ("SDAR's clean stream", (1, 32, 4, 8192),
             dict(causal=True, block_length=4)),
            ("SDAR's noisy stream", (1, 32, 4, 8192),
             dict(causal=True, block_length=4, before_block=True))):
        dim = 128
        keys = jax.random.split(jax.random.PRNGKey(3), 5)
        q, k, v, g = (
            jax.random.normal(key, (batch, seq, n * dim), jnp.bfloat16)
            for key, n in zip(keys, (heads, kv_heads, kv_heads, heads)))
        g_lse = jax.random.normal(keys[4], (batch, heads, seq), jnp.float32)

        def lying(q, k, v):
            return flash_attention_tokens_major_lse(q, k, v, heads, **call)

        def transposed(q, k, v):
            out, lse = flash_attention_lse(
                across(q, dim), across(k, dim), across(v, dim), **call)
            return back(out), lse

        found = []
        for fn in (lying, transposed):
            outs, pull_back = jax.vjp(jax.jit(fn), q, k, v)
            found.append(tuple(outs) + pull_back((g, g_lse)))
        for what, a, b in zip(("out", "lse", "dq", "dk", "dv"), *found):
            np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                err_msg=f"{name}: {what}")
        print(f"  tokens-major == head-major bit for bit: {name} "
              f"(H{heads} on KV{kv_heads} S{seq} D{dim})")

    x = jax.random.normal(jax.random.PRNGKey(4), (1, 16384, 28 * 128),
                          jnp.bfloat16)
    got = jax.jit(lambda x: parts.rope_tokens_major(
        x, 28, 1.5e6, jnp.float32))(x)
    want = jax.jit(lambda x: parts.rope(
        x.reshape(1, 16384, 28, 128), 1.5e6).reshape(x.shape))(x)
    worst = float(jnp.abs(got - want).max())
    print(f"  rope_tokens_major against rope, float32 results of bf16 "
          f"heads: max |difference| {worst:.3e}")
    assert worst <= 1e-6 * float(jnp.abs(want).max()), worst


def check_mla_rope(calls=20) -> None:
    """Latent attention's operands on their way to the kernels at the JoyAI
    Flash cell's shapes (``tools/mla_rope_forms.py``'s, and its two forms):
    ``ops/rotary_split.py``'s four kernels (compiled) against
    ``latent.turn`` + reshape + the adapter's transposes as XLA compiles
    them for the chip, ``q``, ``k``, ``v`` and the three cotangents; how
    many elements differ at all is printed (the arithmetic is the same
    float32 with the same roundings, the heads' sum of ``d k_r`` apart:
    XLA's order is its own), and both forms are timed forward and forward
    + backward, ``calls`` dispatched back to back."""
    import time

    import jax
    import jax.numpy as jnp
    import mla_rope_forms as forms  # beside this file
    import numpy as np

    heads, seq, dtype = forms.HEADS, forms.SEQ, forms.DTYPE
    wide, values = forms.NOPE + forms.ROPE, forms.V_DIM
    keys = jax.random.split(jax.random.PRNGKey(5), 6)
    shapes = [(1, seq, heads * wide), (1, seq, forms.ROPE),
              (1, seq, heads * (forms.NOPE + values)), (1, heads, seq, wide),
              (1, heads, seq, wide), (1, heads, seq, values)]
    *projected, q_bar, k_bar, v_bar = (
        jax.random.normal(key, shape, jnp.float32).astype(dtype)
        for key, shape in zip(keys, shapes))

    def head_major(form):
        def operands(q, shared, up):
            q, k, v, as_the_kernels_read = forms.operands(q, shared, up, form)
            if as_the_kernels_read:
                return q, k, v
            return tuple(x.transpose(0, 2, 1, 3) for x in (q, k, v))

        return operands

    def both(form):
        def run(q, shared, up, *bars):
            out, pull = jax.vjp(head_major(form), q, shared, up)
            return out + pull(bars)

        return jax.jit(run)

    names = ("q", "k", "v", "dq", "d k_r", "d kv_b")
    got = both("one_pass")(*projected, q_bar, k_bar, v_bar)
    want = both("plain")(*projected, q_bar, k_bar, v_bar)
    for name, a, b in zip(names, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        a, b = (np.asarray(x.astype(jnp.float32)) for x in (a, b))
        differ = int((a != b).sum())
        print(f"  {name}: {differ} of {a.size} elements differ from "
              f"latent.turn's, at most by {float(np.abs(a - b).max()):.3e}")
        _close(f"one pass, {name}", a, b)
    for form, what in (("one_pass", "ops/rotary_split.py's kernels"),
                       ("plain", "latent.turn, reshape and transposes")):
        for run, step, args in (
                ("forward", jax.jit(head_major(form)), projected),
                ("forward + backward", both(form),
                 projected + [q_bar, k_bar, v_bar])):
            jax.block_until_ready(step(*args))
            t0 = time.perf_counter()
            for _ in range(calls):
                out = step(*args)
            jax.block_until_ready(out)
            print(f"  {what}, {run}: "
                  f"{(time.perf_counter() - t0) / calls * 1e3:.3f} ms a call")


def main() -> None:
    import jax

    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"chip_kernel_check: needs a TPU, JAX found "
            f"{jax.default_backend()!r}; the kernels are only ever checked "
            "compiled here (tests/ covers interpret mode)")
    d = jax.devices()[0]
    print(f"device: platform={d.platform} device_kind={d.device_kind!r} "
          f"count={len(jax.devices())}")
    alone = {"two_widths": check_two_widths,  # ~2 minutes
             "pair_terms": check_pair_terms,
             "kda_scan": check_kda_scan,
             "ssd": check_ssd,
             "mla_rope": check_mla_rope}
    if len(sys.argv) == 2 and sys.argv[1] in alone:
        alone[sys.argv[1]]()
        print("kernels ok")
        return
    check_flash(4, 16, 512, 64, causal=False)
    # BERT's S=128: 1,536 single-tile slices, a group of them a grid step;
    # and a count of slices that no power of two divides
    check_flash(96, 16, 128, 64, causal=False)
    check_flash(3, 5, 128, 64, causal=True)
    check_tokens_major()
    check_flash(2, 4, 2048, 128, causal=True)
    check_flash(1, 16, 4096, 128, causal=True)
    check_masks()
    check_sdar_rows()
    check_tiles_as_they_lie()
    check_two_widths()
    check_pair_terms()
    check_kda_scan()
    check_ssd()
    check_mla_rope()
    print("kernels ok")


if __name__ == "__main__":
    main()
