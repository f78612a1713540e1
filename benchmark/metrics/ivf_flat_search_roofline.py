"""Share of the least time the chip could take for the window's IVF-Flat
searches (``counts/ivf_flat.py`` at the chip's peaks) in the device time
of the search programs in the trace (modules named ``jit__search_*core``:
every search engine of ``neighbors/ivf_flat.py``; a cell of this reader
runs no other family's search). Percent; standard error says whether
operations or bytes bind."""

import re
import sys

PROGRAM = re.compile(r"jit__search_\w*core")


def read(ctx):
    t = ctx["trace"]
    if t is None or not t.devices or ctx["loop"] != "closed":
        return None
    cfg = ctx["config"]
    ix, sx, ds = cfg["index"], cfg["search"], cfg["dataset"]
    dev = next(iter(t.devices.values()))
    secs = sum(v for k, v in dev.module_s.items() if PROGRAM.search(k))
    calls = sum(v for k, v in dev.module_n.items() if PROGRAM.search(k))
    if not calls or secs <= 0:
        return None
    counts = ctx["counts"]("ivf_flat")
    c = counts.search(int(sx["batch"]), int(ds["rows"]), int(ds["dim"]),
                      int(ix["nlist"]), int(sx["nprobe"]), int(sx["k"]))
    least, bound = counts.least_seconds(c, ctx["peaks"])
    print(f"bench: ivf_flat_search_roofline: {calls} calls, {secs!r} s on "
          f"the device, least {least!r} s per call, bound by {bound}",
          file=sys.stderr)
    return 100.0 * calls * least / secs
