"""Device time booked to the model's ``hvd.block.attn_proj`` scope, a step."""

import owners


def read(run, params):
    return owners.booked_ms(run, params["owner"])
