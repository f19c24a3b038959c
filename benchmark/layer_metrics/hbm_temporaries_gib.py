"""The loaded programs' temporaries, by the program's own account."""


def read(run, params):
    from horovod_tpu import memory

    nbytes = memory.summary()["resident"].get("program_temporaries")
    return None if nbytes is None else nbytes / 2.0 ** 30
