"""Device time booked to the model's ``hvd.block.ffn`` scope, a step."""

import owners


def read(run, params):
    return owners.booked_ms(run, params["owner"])
