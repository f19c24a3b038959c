"""Device time of the multi-token-prediction module, a step: the operations
of the most idle device whose name stack holds the program's ``hvd.mtp``,
as the union of their intervals (``recompute_ms.py``'s reader over another
component), with the module's share of the step's model FLOPs printed
beside it."""

import cells

recompute = cells.load_code(cells.HERE, "layer_metrics", "recompute_ms.py")


def read(run, params):
    ms = recompute.read(run, params)
    if ms is not None:
        cell = run.cell
        module = cell.code.mtp_flops_per_step(cell.config, cell.job,
                                              cell.rows)
        whole = cell.code.flops_per_step(cell.config, cell.job, cell.rows)
        print(f"mtp_ms: {ms:.3f} ms a step under {params['scope']}; the "
              f"module is {module / 1e12:.3f} of the step's "
              f"{whole / 1e12:.3f} model TFLOP, {100 * module / whole:.1f}%",
              flush=True)
    return ms
