"""Seconds from the start of the process to the start of the window:
JAX's start, data made on the device, the build, cache fills, warm-up
and any compilation."""


def read(ctx):
    return ctx["setup_s"]
