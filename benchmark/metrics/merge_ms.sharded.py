"""Device time of the collective operations of the cross-chip merge per
``sharded.knn`` call, from the trace; the mean over the chips, in ms."""

import re

COLLECTIVE = re.compile(
    r"all-gather|all-reduce|collective-permute|all-to-all|reduce-scatter")


def read(ctx):
    t = ctx["trace"]
    if t is None or not t.devices or ctx["loop"] != "closed":
        return None
    calls = len(ctx["result"]["which"])
    per = [sum(v for k, v in d.op_s.items() if COLLECTIVE.search(k))
           for d in t.devices.values()]
    if not calls or not any(per):
        return None
    return 1e3 * sum(per) / len(per) / calls
