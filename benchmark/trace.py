"""Profiler capture and the reduction from a trace to numbers.

The JAX profiler writes an XSpace (``*.xplane.pb``). Device planes are
named ``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one event
per operation that ran and the ``XLA Modules`` line one per program. The
host plane ``/host:CPU`` holds the ``bench.*`` annotations this
benchmark's own files place around each call into a layer.

:func:`reduce` keeps, per device, the busy time (the union of the op
intervals inside the window), the self time by op name (an op that
holds others, such as a while loop, keeps only its own time), the time
by module name,
and the idle gaps, each labelled by the innermost ``bench.*`` host span
that covers its middle. Nothing here knows a cell: the per-layer readers
under ``metrics/`` take what they need from the summary.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import tempfile
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


@contextlib.contextmanager
def capture():
    """Profile the enclosed block; yields a dict whose ``path`` is set to
    the written ``.xplane.pb`` once the block ends. The directory is a
    temporary one and is removed by :func:`load`'s caller via
    ``cleanup``."""
    import jax

    d = tempfile.mkdtemp(prefix="bench-trace-")
    got = {"dir": d, "path": None}
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # annotations only, no call per function
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        yield got
    finally:
        jax.profiler.stop_trace()
        found = sorted(glob.glob(os.path.join(
            d, "plugins", "profile", "*", "*.xplane.pb")))
        got["path"] = found[-1] if found else None


def cleanup(got: dict) -> None:
    shutil.rmtree(got["dir"], ignore_errors=True)


def _merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


class DeviceSummary:
    def __init__(self, name: str):
        self.name = name
        self.busy_s = 0.0
        self.op_s: Dict[str, float] = {}
        self.module_s: Dict[str, float] = {}
        self.module_n: Dict[str, int] = {}
        self.gaps: List[Tuple[float, float]] = []  # (start_ns, end_ns)


class TraceSummary:
    """What a traced window held. ``devices`` maps a device plane's name
    to its :class:`DeviceSummary`; ``idle_by_span`` maps a host span's
    name to the idle device seconds under it, averaged over devices."""

    def __init__(self, window: Tuple[float, float],
                 devices: Dict[str, DeviceSummary],
                 idle_by_span: Dict[str, float]):
        self.window_ns = window
        self.devices = devices
        self.idle_by_span = idle_by_span

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        if not self.devices:
            return 0.0
        return sum(d.busy_s for d in self.devices.values()) / len(
            self.devices)

    def idle_share(self, device: Optional[str] = None) -> float:
        """1 - busy / window, as a fraction (one device, or the mean)."""
        busy = (self.devices[device].busy_s if device is not None
                else self.busy_s)
        return 1.0 - busy / self.window_s

    def top_ops(self, n: int = 10) -> List[list]:
        tot: Dict[str, float] = {}
        for d in self.devices.values():
            for k, v in d.op_s.items():
                tot[k] = tot.get(k, 0.0) + v / len(self.devices)
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 10) -> List[list]:
        return [[k, v] for k, v in sorted(self.idle_by_span.items(),
                                          key=lambda kv: -kv[1])[:n]]


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.start_ns + e.duration_ns)


def op_name(text: str) -> str:
    """``%fusion.2 = f32[...] fusion(...)`` -> ``fusion.2``: an XLA op's
    event carries its whole HLO text; the name is what precedes " = "."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def _self_times(ops):
    """Ops nest on the ``XLA Ops`` line (a while loop holds its body's
    ops). Returns (name, self seconds): each op's time less the time of
    the ops it holds. Ops that overlap without one holding the other
    (asynchronous ones) are siblings."""
    out = []
    stack = []  # [name, start, end, child time]
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and (s >= stack[-1][2] or e > stack[-1][2]):
            n, s0, e0, child = stack.pop()
            out.append((n, (e0 - s0 - child) * 1e-9))
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0.0])
    while stack:
        n, s0, e0, child = stack.pop()
        out.append((n, (e0 - s0 - child) * 1e-9))
    return out


def reduce(pd, window_name: str = WINDOW) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`TraceSummary`
    over the host span ``window_name``."""
    spans: List[Tuple[str, float, float]] = []
    device_planes = []
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            device_planes.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name.startswith("bench."):
                        spans.append((name, s, e))
    wins = [(s, e) for n, s, e in spans if n == window_name]
    if not wins:
        raise ValueError(f"no host span {window_name!r} in the trace")
    w0, w1 = min(s for s, _ in wins), max(e for _, e in wins)
    inner = sorted((s, e, n) for n, s, e in spans if n != window_name)

    devices: Dict[str, DeviceSummary] = {}
    idle: Dict[str, float] = {}
    for plane in sorted(device_planes, key=lambda p: int(
            _DEVICE.match(p.name).group(1))):
        ds = DeviceSummary(plane.name)
        ops = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for name, s, e in _events(line):
                    s, e = max(s, w0), min(e, w1)
                    if e > s:
                        ops.append((op_name(name), s, e))
            elif line.name == MODULES_LINE:
                for name, s, e in _events(line):
                    s, e = max(s, w0), min(e, w1)
                    if e > s:
                        ds.module_s[name] = (ds.module_s.get(name, 0.0)
                                             + (e - s) * 1e-9)
                        ds.module_n[name] = ds.module_n.get(name, 0) + 1
        for name, sec in _self_times(ops):
            ds.op_s[name] = ds.op_s.get(name, 0.0) + sec
        merged = _merge([(s, e) for _, s, e in ops])
        ds.busy_s = sum(e - s for s, e in merged) * 1e-9
        t = w0
        for s, e in merged + [(w1, w1)]:
            if s > t:
                ds.gaps.append((t, s))
            t = max(t, e)
        devices[plane.name] = ds
    n_dev = max(len(devices), 1)
    gaps = sorted(((s + e) / 2.0, (e - s) * 1e-9) for ds in devices.values()
                  for s, e in ds.gaps)
    for name, sec in zip(_labels([t for t, _ in gaps], inner, window_name),
                         (sec for _, sec in gaps)):
        idle[name] = idle.get(name, 0.0) + sec / n_dev
    return TraceSummary((w0, w1), devices, idle)


def _labels(times, spans, default: str) -> List[str]:
    """For each of the ascending ``times``, the name of the shortest span
    of ``spans`` (ascending (start, end, name)) that covers it."""
    out, active, j = [], [], 0
    for t in times:
        while j < len(spans) and spans[j][0] <= t:
            active.append(spans[j])
            j += 1
        active = [sp for sp in active if sp[1] >= t]
        best = min(active, key=lambda sp: sp[1] - sp[0], default=None)
        out.append(best[2] if best else default)
    return out


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)
