"""Seconds the step's first call spent tracing and lowering."""


def read(run, params):
    import horovod_tpu as hvd

    account = hvd.cache_stats().get("compile")
    first = account and account["steps"].get(
        params["step"], {}).get("first_call")
    return first["trace_s"] + first["lower_s"] if first else None
