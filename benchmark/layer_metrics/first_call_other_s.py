"""Seconds of the step's first call that are neither tracing, lowering,
reading the cache nor backend compile."""

import setup_account


def read(run, params):
    return setup_account.first_call_seconds(params["step"], other=True)
