"""1 - (union of device-op intervals) / window, in percent, from the
profiler trace; with several chips the mean over them (each chip's value
is printed on standard error)."""


def read(ctx):
    t = ctx["trace"]
    if t is None or not t.devices:
        return None
    return t.idle_share() * 100.0
