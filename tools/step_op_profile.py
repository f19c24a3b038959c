"""Capture an xprof trace of the ResNet-50 train step (step 1 of 2).

The step-level roofline (docs/benchmarks.md) attributes by subtraction
(fwd+bwd − fwd = "conv backward"), which cannot separate conv kernels
from BN/elementwise backward; the per-shape microbench
(tools/conv_roofline.py) times convs hot-in-VMEM, which understates the
streaming regime. This captures a REAL profiler trace of the compiled
step into ``/tmp/xprof_step``; run ``tools/step_attribution.py``
afterwards to join it with the step's HLO for the category rollup.
"""

from __future__ import annotations

import glob
import os
import sys
import time


def main() -> int:
    import numpy as np

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax

    import horovod_tpu as hvd
    from tools.resnet_step import TRACE_STEPS, build_step

    hvd.enable_compile_cache()

    step, (p_, s_, o_, batch) = build_step()
    for _ in range(4):
        p_, s_, o_, loss = step(p_, s_, o_, batch)
    float(np.asarray(loss))

    logdir = "/tmp/xprof_step"
    os.system(f"rm -rf {logdir}")
    with jax.profiler.trace(logdir):
        for _ in range(TRACE_STEPS):
            p_, s_, o_, loss = step(p_, s_, o_, batch)
        float(np.asarray(loss))
        time.sleep(0.5)

    traces = glob.glob(f"{logdir}/**/*.trace.json.gz", recursive=True)
    print("trace files:", traces)
    if not traces:
        print("NO PROFILE CAPTURED")
        return 1
    print("now run: python tools/step_attribution.py")
    return 0


if __name__ == "__main__":
    sys.exit(main())
