"""Device time of the ungated shared expert, a step: the dense two-matrix
feed-forward every token takes beside its routed experts."""

import cells

scope_ms = cells.load_code(
    cells.HERE, "layer_metrics", "linattn_scan_ms.py").scope_ms


def shared_flops_per_step(cell) -> float:
    """What the shared expert's two projections need, forward and backward
    (three forwards), in every ``E`` layer, as the configuration's own
    ``macs_per_token`` counts them."""
    config, job = cell.config, cell.job
    layers = config["hybrid_override_pattern"].count("E")
    macs = cell.code.macs_per_token(config, job["seq_len"])["shared_expert"]
    return 3.0 * 2.0 * macs * layers * job["seq_len"] * job["rows_per_chip"]


def read(run, params):
    ms = scope_ms(run, params["scopes"])
    if ms is not None and run.peak:
        flops = shared_flops_per_step(run.cell)
        share = flops / (ms * 1e-3) / run.peak["bf16_flops_per_s"]
        print(f"moe_plain_shared_ms: the shared expert's {flops / 1e12:.3f} "
              f"TFLOP a step in {ms:.3f} ms under {params['scopes'][0]}: "
              f"{100 * share:.1f}% of the bf16 peak (weight casts and "
              "relu^2 are under the scope too)", flush=True)
    return ms
