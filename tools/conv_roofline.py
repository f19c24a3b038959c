"""Per-shape ResNet-50 conv roofline: fwd / dx / dw MXU utilisation.

Times every distinct conv shape in ResNet-50 (batch 128, bf16, NHWC) on
the real chip — forward, input-grad (dx) and filter-grad (dw) separately
via ``jax.linear_transpose`` (conv is linear in each argument, so the
transpose map runs WITHOUT the forward pass) — and attributes the
backward-conv time the step-level roofline (docs/benchmarks.md) can only
report in aggregate. This names the shapes a Pallas backward kernel must
beat.

Timing: REPEAT iterations inside one jitted program, the window closed
by ``jax.block_until_ready``.
"""

from __future__ import annotations

import functools
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

BATCH = 128
DTYPE = jnp.bfloat16

# (H, k, stride, Cin, Cout, count) — every distinct conv in ResNet-50
# v1.5 at 224**2 input (H = input spatial size of the conv).
SHAPES = [
    (224, 7, 2, 3, 64, 1),      # stem
    # stage 1 (56x56, filters 64)
    (56, 1, 1, 64, 64, 1),
    (56, 3, 1, 64, 64, 3),
    (56, 1, 1, 64, 256, 4),     # 3 expand + 1 projection
    (56, 1, 1, 256, 64, 2),
    # stage 2 (filters 128)
    (56, 1, 1, 256, 128, 1),
    (56, 3, 2, 128, 128, 1),
    (28, 1, 1, 128, 512, 4),
    (56, 1, 2, 256, 512, 1),    # projection
    (28, 1, 1, 512, 128, 3),
    (28, 3, 1, 128, 128, 3),
    # stage 3 (filters 256)
    (28, 1, 1, 512, 256, 1),
    (28, 3, 2, 256, 256, 1),
    (14, 1, 1, 256, 1024, 6),
    (28, 1, 2, 512, 1024, 1),   # projection
    (14, 1, 1, 1024, 256, 5),
    (14, 3, 1, 256, 256, 5),
    # stage 4 (filters 512)
    (14, 1, 1, 1024, 512, 1),
    (14, 3, 2, 512, 512, 1),
    (7, 1, 1, 512, 2048, 3),
    (14, 1, 2, 1024, 2048, 1),  # projection
    (7, 1, 1, 2048, 512, 2),
    (7, 3, 1, 512, 512, 2),
]


def conv(x, w, stride, k):
    # bf16 in/out with no preferred_element_type — exactly what
    # flax nn.Conv(dtype=bf16) emits in the ResNet model (the MXU still
    # accumulates bf16 passes in f32 internally).
    pad = "SAME" if k != 7 else [(3, 3), (3, 3)]
    return lax.conv_general_dilated(
        x, w, (stride, stride), pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


# One program per window: Python-dispatched per-op loops measure the
# host dispatch floor, not the op.
REPEAT = 100


def make_repeated(fn):
    """Run ``fn`` REPEAT times inside ONE jit program.

    ``optimization_barrier`` ties each iteration's input to the
    loop carry so XLA can neither hoist the loop-invariant op nor CSE
    the iterations; the carry consumes one scalar of each output so
    nothing is dead."""
    def run(a):
        def body(carry, _):
            ab, c = jax.lax.optimization_barrier((a, carry))
            out = fn(ab)
            # Barrier the OUTPUT as well: consuming one element of a
            # bare conv lets XLA's slice-of-conv rewrite shrink the conv
            # to that element's receptive field (measured: "100 reps" in
            # 0.1 ms). A barrier operand must materialize in full.
            outb = jax.lax.optimization_barrier(
                jax.tree.leaves(out)[0])
            c2 = c + outb.ravel()[0].astype(jnp.float32) * 1e-30
            return c2, None
        c, _ = jax.lax.scan(
            body, jnp.zeros((), jnp.float32), None, length=REPEAT)
        return c
    return jax.jit(run)


def time_op(fn, arg) -> float:
    rep = make_repeated(fn)
    jax.block_until_ready(rep(arg))  # compile + drain
    reps = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(rep(arg))
        reps.append((time.perf_counter() - t0) / REPEAT)
    return statistics.median(reps)


def main() -> None:
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import horovod_tpu as hvd
    from horovod_tpu.attribution import peak_flops_for_kind

    hvd.enable_compile_cache()
    dev = jax.devices()[0]
    peak = peak_flops_for_kind(dev.device_kind)
    print(f"device: {dev.device_kind}, peak {peak/1e12:.0f} TF/s bf16, "
          f"batch {BATCH}")
    header = (f"{'shape':>28} {'cnt':>3} | {'GFLOP':>6} |"
              f" {'fwd ms':>7} {'mxu%':>5} | {'dx ms':>7} {'mxu%':>5} |"
              f" {'dw ms':>7} {'mxu%':>5}")
    print(header)
    print("-" * len(header))
    tot = {"fwd": 0.0, "dx": 0.0, "dw": 0.0}
    tot_bound = 0.0
    rows = []
    for (H, k, s, cin, cout, count) in SHAPES:
        rng = np.random.RandomState(0)
        x = jnp.asarray(
            rng.randn(BATCH, H, H, cin).astype(np.float32), DTYPE)
        w = jnp.asarray(
            rng.randn(k, k, cin, cout).astype(np.float32) * 0.05, DTYPE)
        hout = -(-H // s)
        gflop = 2 * BATCH * hout * hout * k * k * cin * cout / 1e9
        dy = jnp.asarray(
            rng.randn(BATCH, hout, hout, cout).astype(np.float32), DTYPE)

        cfn = functools.partial(conv, stride=s, k=k)
        fwd = jax.jit(lambda xx: cfn(xx, w))
        # vjp instead of linear_transpose: the trailing astype makes the
        # cotangent dtype mismatch under pure transposition; the vjp fn
        # applies ONLY the backward ops at call time either way.
        _, vjp_x = jax.vjp(lambda xx: cfn(xx, w), x)
        _, vjp_w = jax.vjp(lambda ww: cfn(x, ww), w)
        dx_t = jax.jit(lambda gy: vjp_x(gy)[0])
        dw_t = jax.jit(lambda gy: vjp_w(gy)[0])

        t_f = time_op(fwd, x)
        t_dx = time_op(dx_t, dy)
        t_dw = time_op(dw_t, dy)

        bound = gflop * 1e9 / peak * 1e3  # ms at peak
        row = (H, k, s, cin, cout, count, gflop, t_f, t_dx, t_dw, bound)
        rows.append(row)
        tot["fwd"] += t_f * count * 1e3
        tot["dx"] += t_dx * count * 1e3
        tot["dw"] += t_dw * count * 1e3
        tot_bound += bound * count
        print(f"{H:>4}x{H:<4} k{k} s{s} {cin:>4}->{cout:<4} {count:>3} |"
              f" {gflop:6.1f} |"
              f" {t_f*1e3:7.3f} {bound/ (t_f*1e3) * 100:5.1f} |"
              f" {t_dx*1e3:7.3f} {bound/(t_dx*1e3)*100:5.1f} |"
              f" {t_dw*1e3:7.3f} {bound/(t_dw*1e3)*100:5.1f}",
              flush=True)
    print("-" * len(header))
    print(f"totals (weighted): fwd {tot['fwd']:.2f} ms"
          f" ({tot_bound/tot['fwd']*100:.1f}% mxu), "
          f"dx {tot['dx']:.2f} ms ({tot_bound/tot['dx']*100:.1f}%), "
          f"dw {tot['dw']:.2f} ms ({tot_bound/tot['dw']*100:.1f}%)")
    print(f"peak-bound per pass: {tot_bound:.2f} ms")
    # The worst backward offenders, cost-weighted.
    scored = sorted(
        rows, key=lambda r: -(r[8] + r[9]) * r[5])
    print("top backward offenders (count-weighted dx+dw ms):")
    for r in scored[:6]:
        H, k, s, cin, cout, count, gflop, t_f, t_dx, t_dw, bound = r
        print(f"  {H}x{H} k{k} s{s} {cin}->{cout} x{count}: "
              f"{(t_dx+t_dw)*count*1e3:.2f} ms "
              f"(dx {bound/(t_dx*1e3)*100:.0f}%, "
              f"dw {bound/(t_dw*1e3)*100:.0f}% mxu)")


if __name__ == "__main__":
    main()
