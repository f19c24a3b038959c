"""Device time booked to the model's ``hvd.block.head`` scope, a step,
with the head's model FLOPs over the bf16 peak beside it."""

import owners


def head_flops_per_step(cell) -> float:
    """What the head's matrix products need on one chip, forward and
    backward (three forwards): BERT's transform and tied logits on the
    masked positions; a decoder's projection on every position, as its
    configuration's own ``macs_per_token`` counts it."""
    config, job = cell.config, cell.job
    if "masked_positions" in job:
        positions = job["masked_positions"]
        macs = config["hidden_size"] * (
            config["hidden_size"] + config["vocab_size"])
    else:
        positions = job["seq_len"]
        macs = cell.code.macs_per_token(config, job["seq_len"])["head"]
    return 3.0 * 2.0 * macs * positions * job["rows_per_chip"]


def read(run, params):
    ms = owners.booked_ms(run, params["owner"])
    if ms is not None and run.peak:
        flops = head_flops_per_step(run.cell)
        share = flops / (ms * 1e-3) / run.peak["bf16_flops_per_s"]
        print(f"head_ms: the head's {flops / 1e12:.3f} TFLOP a step in "
              f"{ms:.3f} ms under {params['owner']}: {100 * share:.1f}% of "
              "the bf16 peak (log-softmax, the loss and the norm are under "
              "the scope too)", flush=True)
    return ms
