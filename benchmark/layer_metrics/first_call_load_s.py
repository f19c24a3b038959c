"""Seconds of the step's first call inside backend compile: compiling, or
reading and loading what the persistent cache holds."""

import setup_account


def read(run, params):
    return setup_account.first_call_seconds(params["step"], params["names"])
