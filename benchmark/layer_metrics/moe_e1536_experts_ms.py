"""Device time of the grouped expert matmuls, a step, in the family whose
config counts its experts under ``num_experts`` and gives an expert's width
as ``moe_intermediate_size`` beside the dense layers' ``intermediate_size``:
``moe_e768_experts_ms.py``'s reader and count, loaded from there, with the
experts under this family's key (that file reads ``n_routed_experts``,
``moe_experts_ms.py`` the dense width)."""

import types

import cells

e768 = cells.load_code(cells.HERE, "layer_metrics", "moe_e768_experts_ms.py")


def slot_flops_per_step(cell) -> float:
    """What the expert matmuls execute, occupied slots and empty ones
    alike: three projections of hidden x width a slot, forward and twice
    that backward, ``experts_here x capacity`` slots a sequence an expert
    layer."""
    return e768.slot_flops_per_step(types.SimpleNamespace(
        config=dict(cell.config, n_routed_experts=cell.config["num_experts"]),
        job=cell.job, code=cell.code))


def read(run, params):
    ms = e768.scope_ms(run, params["scopes"])
    if ms is not None and run.peak:
        flops = slot_flops_per_step(run.cell)
        share = flops / (ms * 1e-3) / run.peak["bf16_flops_per_s"]
        print(f"moe_e1536_experts_ms: the slots' {flops / 1e12:.3f} TFLOP a "
              f"step in {ms:.3f} ms under {params['scopes'][0]}: "
              f"{100 * share:.1f}% of the bf16 peak (weight casts, the "
              "gate's silu and the recomputed forward are under the scope "
              "too)", flush=True)
    return ms
