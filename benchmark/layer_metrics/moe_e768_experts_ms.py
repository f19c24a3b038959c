"""Device time of the grouped expert matmuls, a step, where an expert's
width is ``moe_intermediate_size`` and ``intermediate_size`` is the dense
layer's (``moe_experts_ms.py`` reads the latter and would print nine times
the share here)."""

import math

import cells

scope_ms = cells.load_code(
    cells.HERE, "layer_metrics", "linattn_scan_ms.py").scope_ms


def slot_flops_per_step(cell) -> float:
    """What the expert matmuls execute, occupied slots and empty ones
    alike: three projections of hidden x width a slot, forward and twice
    that backward, ``experts_here x capacity`` slots a sequence an expert
    layer, the prediction module's among them."""
    config, job = cell.config, cell.job
    capacity = math.ceil(
        config["capacity_factor"] * job["seq_len"]
        * config["num_experts_per_tok"] / config["n_routed_experts"])
    layers = sum(ffn == "experts" for _, ffn in cell.code.kinds(config))
    slots = job["rows_per_chip"] * layers * config["experts_here"] * capacity
    return slots * 3 * 3 * 2.0 * (
        config["hidden_size"] * config["moe_intermediate_size"])


def read(run, params):
    ms = scope_ms(run, params["scopes"])
    if ms is not None and run.peak:
        flops = slot_flops_per_step(run.cell)
        share = flops / (ms * 1e-3) / run.peak["bf16_flops_per_s"]
        print(f"moe_e768_experts_ms: the slots' {flops / 1e12:.3f} TFLOP a "
              f"step in {ms:.3f} ms under {params['scopes'][0]}: "
              f"{100 * share:.1f}% of the bf16 peak (weight casts, the "
              "gate's silu and the recomputed forward are under the scope "
              "too)", flush=True)
    return ms
