"""Queries answered in the window over the window (closed loop): every
batch issued, over the time from the first issue to the last fence."""


def read(ctx):
    if ctx["loop"] != "closed":
        return None
    return ctx["n_done"] / ctx["result"]["window_s"]
