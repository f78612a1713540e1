"""Share of the least time each chip could take for its local exact scan
(``counts/brute_force.py`` over its shard, at the chip's peaks) in that
chip's device time outside the collective operations, over the window's
``sharded.knn`` calls; the mean over the chips, in percent."""

import re
import sys

COLLECTIVE = re.compile(
    r"all-gather|all-reduce|collective-permute|all-to-all|reduce-scatter")


def read(ctx):
    t = ctx["trace"]
    if t is None or not t.devices or ctx["loop"] != "closed":
        return None
    cfg = ctx["config"]
    ds, sx = cfg["dataset"], cfg["search"]
    calls = len(ctx["result"]["which"])
    n_dev = len(t.devices)
    counts = ctx["counts"]("brute_force")
    c = counts.knn(int(sx["batch"]), int(ds["rows"]) // n_dev,
                   int(ds["dim"]), int(sx["k"]))
    least, bound = counts.least_seconds(c, ctx["peaks"])
    shares = []
    for name, dev in t.devices.items():
        local = sum(v for k, v in dev.op_s.items()
                    if not COLLECTIVE.search(k))
        if local > 0:
            shares.append(100.0 * calls * least / local)
    if not shares:
        return None
    print(f"bench: sharded_knn_roofline: {calls} calls, least {least!r} s "
          f"per call per chip, bound by {bound}; per chip {shares!r}",
          file=sys.stderr)
    return sum(shares) / len(shares)
