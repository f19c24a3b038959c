"""Seconds the host spent in the program's own set-up calls: init, the
compile cache, the optimizer's init, placing, building the step."""

import setup_account


def read(run, params):
    return setup_account.named_seconds(params["names"])
