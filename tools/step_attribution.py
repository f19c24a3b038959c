"""Join the xprof op timeline with the step's compiled HLO (step 2 of 2).

Category attribution of the ResNet-50 train step (conv fwd/dx, conv dw,
BN+elementwise, copies, maxpool, reductions), settling what the
subtraction roofline could not — how much of "backward" is actually
conv kernels. Run ``tools/step_op_profile.py`` first; it writes the
trace this script reads from ``/tmp/xprof_step``.
"""

from __future__ import annotations

import collections
import glob
import gzip
import json
import os
import re
import sys


def main() -> int:
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import horovod_tpu as hvd
    from tools.resnet_step import TRACE_STEPS, build_step

    traces = sorted(glob.glob(
        "/tmp/xprof_step/**/*.trace.json.gz", recursive=True))
    if not traces:
        print("no trace found under /tmp/xprof_step — run "
              "tools/step_op_profile.py first")
        return 1

    hvd.enable_compile_cache()

    step, args = build_step()
    hlo = step.lower(*args).compile().as_text()

    # Map each fused computation name to its body text.
    comp_bodies: dict[str, str] = {}
    cur = None
    buf: list[str] = []
    for line in hlo.splitlines():
        m = re.match(r"%?(\S+)\s+\([^)]*\)\s*->.*\{", line)
        if m and not line.startswith("ENTRY"):
            if cur:
                comp_bodies[cur] = "\n".join(buf)
            cur = m.group(1).rstrip(" {")
            buf = []
        elif cur is not None:
            buf.append(line)
    if cur:
        comp_bodies[cur] = "\n".join(buf)

    # Instruction name -> its defining line.
    inst_info: dict[str, str] = {}
    for line in hlo.splitlines():
        m = re.match(r"\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)", line)
        if m:
            inst_info[m.group(1)] = m.group(2)

    def category_of(name: str) -> str:
        info = inst_info.get(name, "")
        if "fusion(" in info:
            cm = re.search(r"calls=%?([\w.\-]+)", info)
            body = comp_bodies.get(cm.group(1), "") if cm else ""
            joint = info + "\n" + body
        else:
            joint = info
        if "convolution" in joint:
            # dw outputs are [k, k, Cin, Cout] — tiny leading dims
            # (the defining line's first shape; possibly a tuple).
            om = re.search(r"^\(?(\w+)\[([\d,]+)\]", info)
            dims = [int(d) for d in om.group(2).split(",")] if om else []
            if len(dims) == 4 and dims[0] <= 7 and dims[1] <= 7:
                return "conv_dw"
            # Fallback: dw convolutions carry transposed dim labels
            # (batch as the contraction) in the fused body.
            lm = re.search(r"dim_labels=(\S+)", joint)
            labels = lm.group(1) if lm else ""
            if "f01b" in labels or "o01i->01io" in labels:
                return "conv_dw"
            return "conv (fwd or dx)"
        if "select-and-scatter" in joint:
            return "maxpool_bwd"
        if "reduce-window" in joint:
            return "maxpool_fwd"
        if re.search(r"reduce\(|reduce-", joint):
            return "reduce (BN stats/means)"
        if "dot(" in joint:
            return "matmul (head)"
        if "all-reduce" in joint:
            return "allreduce"
        if "copy" in joint and "add" not in joint:
            return "copy"
        return "elementwise/other"

    with gzip.open(traces[-1], "rt") as f:
        data = json.load(f)
    meta = {e["pid"]: e["args"].get("name", "")
            for e in data.get("traceEvents", [])
            if e.get("ph") == "M" and e.get("name") == "process_name"}
    envelope = {str(i) for i in range(TRACE_STEPS)}
    agg: collections.Counter = collections.Counter()
    names: dict = collections.defaultdict(collections.Counter)
    for e in data.get("traceEvents", []):
        if e.get("ph") != "X" or "TPU" not in meta.get(e.get("pid"), ""):
            continue
        nm = e.get("name", "?")
        if nm.startswith("jit_") or nm in envelope:
            continue  # per-step envelope events, not ops
        cat = category_of(nm)
        agg[cat] += e.get("dur", 0)
        names[cat][nm] += e.get("dur", 0)
    total = sum(agg.values())
    print(f"device op time per step: {total/TRACE_STEPS/1e3:.2f} ms")
    for cat, us in agg.most_common():
        print(f"  {us/TRACE_STEPS/1e3:8.2f} ms  {cat}")
    print("\ntop ops per category:")
    for cat, _ in agg.most_common():
        print(f"[{cat}]")
        for nm, us in names[cat].most_common(6):
            info = inst_info.get(nm, "")[:110]
            print(f"   {us/TRACE_STEPS/1e3:7.2f} ms  {nm}: {info}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
