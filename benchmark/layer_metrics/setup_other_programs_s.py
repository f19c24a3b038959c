"""Seconds of set-up inside tracing, lowering and compiling programs other
than the factory step: those no ``hvd.step`` span is above."""

import setup_account


def read(run, params):
    return setup_account.named_seconds(params["names"], outside=True)
