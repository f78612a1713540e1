"""Wall time of the fenced index build call, measured during set-up."""


def read(ctx):
    return ctx["build_s"]
