"""Reduce a JAX profiler trace to the numbers the metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler.stop_trace`` writes,
read with ``jax.profiler.ProfileData``. Device planes are those named
``/device:<platform>:<n>`` that hold operations. On each, the line ``XLA Modules`` holds one
event per run of a compiled program (named after the jitted function) and
the line ``XLA Ops`` one event per operation. Host spans are the
benchmark's own ``jax.profiler.TraceAnnotation`` events, named
``bench.*``, on the host plane. All times are in seconds on the host's
clock of the trace.

The device's clock in the trace runs ahead of the host's by about a
millisecond on a v5e (a program ran before the host dispatched it), which
is the length of a short tick. So device events are shifted onto the host
clock by the least offset that puts every program's run after its
dispatch: the k-th ``PjitFunction(f)`` host event against the k-th run of
``jit_f`` on the device.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
_DISPATCH = re.compile(r"^PjitFunction\((.+)\)$")
_PROGRAM = re.compile(r"^jit_(.+)\(\d+\)$")


class Trace:
    """Device operations, program runs and host spans of one trace."""

    def __init__(self, ops_by_device, modules, spans):
        self.ops_by_device = ops_by_device   # [[(t0, t1, name)], ...] per chip
        self.modules = modules               # [(t0, t1, name)] all chips
        self.spans = spans                   # {name: [(t0, t1)]}

    @property
    def n_devices(self) -> int:
        return len(self.ops_by_device)


def find_xplane(profile_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read a trace from an ``.xplane.pb`` file or a profile directory."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    ops_by_device, modules, spans = [], [], defaultdict(list)
    dispatch = defaultdict(list)
    for plane in pd.planes:
        lines = {line.name for line in plane.lines}
        if plane.name.startswith(DEVICE_PREFIX) and OPS_LINE in lines:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                               for e in line.events)
                elif line.name == MODULES_LINE:
                    modules.extend((e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                                   for e in line.events)
            ops_by_device.append(ops)
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans[e.name].append((e.start_ns * 1e-9, e.end_ns * 1e-9))
                    m = _DISPATCH.match(e.name)
                    if m:
                        dispatch[m.group(1)].append(
                            (e.start_ns * 1e-9, e.end_ns * 1e-9))
    d = clock_offset(dispatch, modules)
    shift = lambda evs: sorted((a + d, b + d, n) for a, b, n in evs)
    return Trace([shift(ops) for ops in ops_by_device], shift(modules),
                 {k: sorted(v) for k, v in spans.items()})


def clock_offset(dispatch: dict, modules: list) -> float:
    """Seconds to add to device times so that no program runs before the
    host dispatched it (0 where the trace pairs no dispatch with a run)."""
    runs = defaultdict(list)
    for t0, _, name in modules:
        m = _PROGRAM.match(name)
        if m:
            runs[m.group(1)].append(t0)
    best = None
    for fn, starts in runs.items():
        # one call shows as nested events of the same name: keep the outer
        host = [a for a, _ in union(dispatch.get(fn, []))]
        starts = sorted(starts)
        n = min(len(host), len(starts))
        if n == 0:
            continue
        # a run whose dispatch came before the trace began has no pair at
        # the start; a dispatch whose run ended after it, none at the end
        for h, s in zip(host[:n], starts[len(starts) - n:]):
            best = h - s if best is None else max(best, h - s)
    return max(0.0, best or 0.0)


def union(intervals) -> list:
    """Merge (t0, t1[, ...]) intervals into disjoint sorted (t0, t1)."""
    out = []
    for iv in sorted(intervals):
        t0, t1 = iv[0], iv[1]
        if out and t0 <= out[-1][1]:
            if t1 > out[-1][1]:
                out[-1][1] = t1
        else:
            out.append([t0, t1])
    return [(a, b) for a, b in out]


def intersect(a, b) -> list:
    """Intersection of two disjoint sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def length(intervals) -> float:
    return sum(t1 - t0 for t0, t1 in intervals)


def window(trace: Trace, span: str = "bench.window") -> list:
    """The traced window: the union of the host spans named ``span``."""
    return union(trace.spans.get(span, []))


def inside(t: float, within) -> bool:
    """Whether time ``t`` lies in the disjoint sorted intervals ``within``."""
    i = bisect.bisect_right(within, (t, float("inf"))) - 1
    return i >= 0 and within[i][0] <= t < within[i][1]


def busy_s(trace: Trace, within) -> float:
    """Seconds in which an operation ran on the device, inside ``within``
    (disjoint intervals), averaged over the chips."""
    if not trace.ops_by_device:
        return 0.0
    per_chip = [length(intersect(union(ops), within))
                for ops in trace.ops_by_device]
    return sum(per_chip) / len(per_chip)


def programs(trace: Trace, substring: str, within) -> list:
    """Runs (t0, t1) of the compiled programs whose name holds
    ``substring``, that start inside ``within``."""
    out = []
    for t0, t1, name in trace.modules:
        if substring in name and inside(t0, within):
            out.append((t0, t1))
    return out


def top_ops(trace: Trace, within, n: int = 10) -> list:
    """[[op name, seconds]] of the device operations that took most time."""
    tot = defaultdict(float)
    for ops in trace.ops_by_device:
        for t0, t1, name in ops:
            if inside(t0, within):
                tot[name] += t1 - t0
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / max(1, trace.n_devices)] for k, v in ranked]


def idle_gaps(trace: Trace, within, n: int = 10) -> list:
    """[[host span, seconds]]: the device's idle time inside ``within``,
    split by the benchmark span (other than the window; they do not nest)
    that the host was in, longest first; "none" for idle time outside
    every span. Measured on the first chip."""
    if not trace.ops_by_device or not within:
        return []
    busy = union(trace.ops_by_device[0])
    edges = [within[0][0]] + [t for iv in busy for t in iv] + [within[-1][1]]
    free = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if a < b]
    idle = intersect(free, within)
    tot = {name: length(intersect(idle, union(ivs)))
           for name, ivs in trace.spans.items() if name != "bench.window"}
    tot["none"] = length(idle) - sum(tot.values())
    ranked = sorted(((k, v) for k, v in tot.items() if v > 0),
                    key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in ranked]
