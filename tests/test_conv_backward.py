"""Pallas 1x1-conv backward kernels vs jax autodiff (interpret mode on
CPU; the real-chip perf measurements live in docs/benchmarks.md)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.conv_backward import conv1x1, dw_1x1


def _ref_conv(x, w, strides):
    return jax.lax.conv_general_dilated(
        x, w, strides, "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def test_dw_kernel_matches_exact_matmul():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(6000, 16).astype(np.float32))
    dy = jnp.asarray(rng.randn(6000, 24).astype(np.float32))
    got = np.asarray(dw_1x1(x, dy, tile=1024, interpret=True))
    want = np.asarray(x).T @ np.asarray(dy)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("strides", [(1, 1), (2, 2)])
def test_conv1x1_forward_and_grads_match_autodiff(strides):
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(2, 8, 8, 12).astype(np.float32))
    w = jnp.asarray(rng.randn(1, 1, 12, 20).astype(np.float32) * 0.1)

    out = conv1x1(x, w, strides, True)
    want = _ref_conv(x, w, strides)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)

    def loss_ours(x, w):
        return jnp.sum(conv1x1(x, w, strides, True) ** 2)

    def loss_ref(x, w):
        return jnp.sum(_ref_conv(x, w, strides) ** 2)

    gx, gw = jax.grad(loss_ours, argnums=(0, 1))(x, w)
    rx, rw = jax.grad(loss_ref, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                               rtol=1e-3, atol=1e-3)


def test_conv1x1_bf16_path():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(2, 4, 4, 8).astype(np.float32), jnp.bfloat16)
    w = jnp.asarray(rng.randn(1, 1, 8, 16).astype(np.float32) * 0.1,
                    jnp.bfloat16)
    gw = jax.grad(lambda w: jnp.sum(
        conv1x1(x, w, (1, 1), True).astype(jnp.float32)))(w)
    assert gw.dtype == jnp.bfloat16 and gw.shape == (1, 1, 8, 16)
