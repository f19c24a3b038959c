"""Seconds ``import horovod_tpu`` took, by the program's own span."""

import setup_account


def read(run, params):
    return setup_account.named_seconds(params["names"])
