"""Set-up seconds: process start (before JAX loads) to the window's start."""


def read(rec):
    return rec["setup_s"]
