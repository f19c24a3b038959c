"""Seconds the factory step's first call took, by its own span."""

import setup_account


def read(run, params):
    return setup_account.first_call_seconds(params["step"])
