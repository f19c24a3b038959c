"""Device time of the two-matrix experts' grouped matmuls, a step."""

import math

import cells

scope_ms = cells.load_code(
    cells.HERE, "layer_metrics", "linattn_scan_ms.py").scope_ms


def slot_flops_per_step(config, job):
    """What the expert matmuls execute, occupied slots and empty ones
    alike: two projections of hidden x width a slot, forward and twice
    that backward, ``experts_here x capacity`` slots a sequence an ``E``
    layer."""
    capacity = math.ceil(
        config["capacity_factor"] * job["seq_len"]
        * config["num_experts_per_tok"] / config["n_routed_experts"])
    slots = (job["rows_per_chip"]
             * config["hybrid_override_pattern"].count("E")
             * config["experts_here"] * capacity)
    return slots * 2 * 3 * 2.0 * (
        config["hidden_size"] * config["moe_intermediate_size"])


def read(run, params):
    ms = scope_ms(run, params["scopes"])
    if ms is not None and run.peak:
        flops = slot_flops_per_step(run.cell.config, run.cell.job)
        share = flops / (ms * 1e-3) / run.peak["bf16_flops_per_s"]
        print(f"moe_plain_experts_ms: the slots' {flops / 1e12:.3f} TFLOP a "
              f"step in {ms:.3f} ms under {params['scopes'][0]}: "
              f"{100 * share:.1f}% of the bf16 peak (weight casts, relu^2 "
              "and the recomputed forward are under the scope too)",
              flush=True)
    return ms
