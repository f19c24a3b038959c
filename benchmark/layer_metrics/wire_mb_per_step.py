"""Bytes the gradient wire carries a step, by the program's count."""


def read(run, params):
    from horovod_tpu import metrics

    gauge = getattr(metrics, "GRAD_SYNC_LAST_BYTES", None)
    if gauge is None:
        return None
    nbytes = gauge.labels(sync_mode=run.cell.job["sync_mode"]).get()
    return nbytes / 1e6 if nbytes else None
