"""Seconds of the step's first call inside tracing and lowering, nested
tracings counted once."""

import setup_account


def read(run, params):
    return setup_account.first_call_seconds(params["step"], params["names"])
