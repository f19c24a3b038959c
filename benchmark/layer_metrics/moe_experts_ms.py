"""Device time of the grouped expert matmuls, a step."""

import math

import program_spans

SCOPE = "hvd.moe.experts"


def slot_flops_per_step(config, job):
    """What the expert matmuls execute, occupied slots and empty ones
    alike: three projections of hidden x width a slot, forward and twice
    that backward, ``experts_here x capacity`` slots a sequence a layer."""
    capacity = math.ceil(
        config["capacity_factor"] * job["seq_len"]
        * config["num_experts_per_tok"] / config["num_experts"])
    slots = (job["rows_per_chip"] * config["num_hidden_layers"]
             * config["experts_here"] * capacity)
    return slots * 3 * 3 * 2.0 * (
        config["hidden_size"] * config["intermediate_size"])


def read(run, params):
    found = program_spans.device(run)
    ms = None if found is None else found.phases.get(SCOPE)
    if ms is None:
        return None
    if run.peak:
        flops = slot_flops_per_step(run.cell.config, run.cell.job)
        share = flops / (ms * 1e-3) / run.peak["bf16_flops_per_s"]
        print(f"moe_experts_ms: the slots' {flops / 1e12:.3f} TFLOP a step "
              f"in {ms:.3f} ms under {SCOPE}: {100 * share:.1f}% of the "
              "bf16 peak (weight casts and the gate's silu are under the "
              "scope too)", flush=True)
    return ms
