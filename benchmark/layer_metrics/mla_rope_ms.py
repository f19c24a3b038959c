"""Device time booked to the program's ``hvd.mla.rope`` scope, a step: the
rotary split of latent attention beside its projections and its kernels."""

import owners


def read(run, params):
    return owners.booked_ms(run, params["owner"])
