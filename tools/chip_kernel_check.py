"""The Pallas kernels, compiled on the chip, against their references.

    python tools/chip_kernel_check.py

``flash_attention`` forward and backward in bf16 against
``blockwise_attention_reference`` in float32 at the single-tile shapes
BERT-Large uses (S512 and S128, D64: a grid step takes a group of slices),
at 15 causal single-tile slices, the same two kernels on tokens-major
operands (``flash_attention_tokens_major``: ``[B, S, H * D]``, two heads of
D64 or one of D128 a 128-lane block), at a multi-tile causal shape (S2048
D128) and at OLMoE's (one sequence of S4096, 16 heads of D128: a grid of
16 x 8 x 8 tiles of 512). Compiled, never ``interpret=True``: off a TPU
this exits non-zero.

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


def check_tokens_major() -> None:
    """BERT's two shapes as its projections write them, an odd group of
    causal pairs, and a head a block."""
    check_flash(4, 16, 512, 64, causal=False, tokens_major=True)
    check_flash(96, 16, 128, 64, causal=False, tokens_major=True)
    check_flash(3, 6, 128, 64, causal=True, tokens_major=True)
    check_flash(2, 4, 128, 128, causal=False, tokens_major=True)


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
    check_flash(4, 16, 512, 64, causal=False)
    # BERT's S=128: 1,536 single-tile slices, a group of them a grid step;
    # and a count of slices that no power of two divides
    check_flash(96, 16, 128, 64, causal=False)
    check_flash(3, 5, 128, 64, causal=True)
    check_tokens_major()
    check_flash(2, 4, 2048, 128, causal=True)
    check_flash(1, 16, 4096, 128, causal=True)
    print("kernels ok")


if __name__ == "__main__":
    main()
