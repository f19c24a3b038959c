"""Seconds spent compiling, or loading what was compiled before."""


def read(run, params):
    return run.compile["backend_compile_s"]
