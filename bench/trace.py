"""Reduce a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy and idle time in a window, time per device operation,
kernel time by name, collective time and the part of it no compute hides,
and the idle gaps labelled with what the host was doing.

A trace is read into plain ``(name, start_ns, end_ns)`` tuples first
(``read``), so that the reduction runs on recorded events in the tests.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW = "bench.window"  # the host annotation around the measured window
# the host's own annotations, by the benchmark's files; idle gaps are
# labelled with the innermost of these active at the gap's middle
HOST_LABELS = ("data", "dispatch", "wait", "readback", "verdict")
# control flow whose events enclose the ops they run: left out, so that
# every device interval is counted once, by the op that does the work
CONTAINERS = ("while", "conditional", "call")
COLLECTIVE_WORDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all", "allreduce",
                    "all_reduce", "all_gather", "reduce_scatter")


@dataclass
class Events:
    """Events of one trace: ops per device, and host annotations."""

    device_ops: dict = field(default_factory=dict)  # device -> [(name, t0, t1)]
    # device -> [(t0, t1)] of asynchronous ops (copies, slices in flight):
    # they keep the device busy but overlap the ops above
    device_async: dict = field(default_factory=dict)
    host: list = field(default_factory=list)  # [(name, t0, t1)]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def op_name(event_name: str) -> str:
    """The HLO instruction's name: "%fusion.3 = f32[..] fusion(..)" ->
    "fusion.3"."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def read(path: str) -> Events:
    """Device ops from each device plane's "XLA Ops" line, by instruction
    name and without control-flow containers, and the intervals of its
    "Async XLA Ops" line; host annotations from every host thread."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    ev = Events()
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops, asyncs = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        n = op_name(e.name)
                        if not n.startswith(CONTAINERS):
                            ops.append((n, e.start_ns, e.start_ns + e.duration_ns))
                elif line.name == "Async XLA Ops":
                    asyncs.extend((e.start_ns, e.start_ns + e.duration_ns)
                                  for e in line.events)
            ev.device_ops[plane.name] = ops
            ev.device_async[plane.name] = asyncs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                ev.host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events
                               if e.name == WINDOW or e.name in HOST_LABELS)
    return ev


def union(intervals, lo: float, hi: float) -> list:
    """Merged intervals, clipped to [lo, hi)."""
    out = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def subtract(xs, ys) -> list:
    """Parts of merged intervals ``xs`` not covered by merged ``ys``."""
    out, j = [], 0
    for a, b in xs:
        cur = a
        while j < len(ys) and ys[j][1] <= cur:
            j += 1
        k = j
        while k < len(ys) and ys[k][0] < b:
            if ys[k][0] > cur:
                out.append([cur, ys[k][0]])
            cur = max(cur, ys[k][1])
            k += 1
        if cur < b:
            out.append([cur, b])
    return out


def is_collective(name: str) -> bool:
    n = name.lower()
    return any(w in n for w in COLLECTIVE_WORDS)


@dataclass
class Summary:
    window_s: float
    busy_s: float  # union of device-op and async-op intervals, mean over devices
    op_s: dict  # op name -> summed seconds, mean over devices
    # op name -> [calls, seconds] of its events wholly inside the window,
    # mean over devices: the window may cut a call short at either end
    op_calls: dict
    collective_s: float  # union of collective ops, mean over devices
    exposed_collective_s: float  # collective time with no other op running
    gaps: list  # [(label, seconds)] idle gaps, longest first

    def kernel_s(self, word: str) -> float:
        """Summed device time of the ops whose name contains ``word``."""
        return sum(s for n, s in self.op_s.items() if word in n)

    def kernel_calls(self, word: str) -> tuple:
        """(calls, seconds) of the ops whose name contains ``word``, over
        their events wholly inside the window."""
        hits = [v for n, v in self.op_calls.items() if word in n]
        return sum(c for c, _ in hits), sum(s for _, s in hits)

    def top_ops(self, k: int = 10) -> list:
        return sorted(self.op_s.items(), key=lambda kv: -kv[1])[:k]

    def top_gaps(self, k: int = 10) -> list:
        by = defaultdict(float)
        for label, s in self.gaps:
            by[label] += s
        return sorted(by.items(), key=lambda kv: -kv[1])[:k]


def window_of(ev: Events) -> tuple:
    wins = [(a, b) for n, a, b in ev.host if n == WINDOW]
    if not wins:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    return min(a for a, _ in wins), max(b for _, b in wins)


def _label(host, t: float) -> str:
    """Innermost host annotation active at time ``t``."""
    best = None
    for n, a, b in host:
        if n != WINDOW and a <= t < b and (best is None or a >= best[1]):
            best = (n, a)
    return best[0] if best else "other"


def summarize(ev: Events, devices=None) -> Summary:
    lo, hi = window_of(ev)
    devs = [d for d in sorted(ev.device_ops) if devices is None or d in devices]
    if not devs:
        raise ValueError("no device plane in the trace")
    busy = coll = exposed = 0.0
    op_s = defaultdict(float)
    op_calls = defaultdict(lambda: [0.0, 0.0])
    gaps = []
    host = [h for h in ev.host if h[2] > lo and h[1] < hi]
    for d in devs:
        ops = ev.device_ops[d]
        allu = union([(a, b) for _, a, b in ops] + ev.device_async.get(d, []),
                     lo, hi)
        busy += length(allu)
        cu = union([(a, b) for n, a, b in ops if is_collective(n)], lo, hi)
        other = union([(a, b) for n, a, b in ops if not is_collective(n)], lo, hi)
        coll += length(cu)
        exposed += length(subtract(cu, other))
        for n, a, b in ops:
            a2, b2 = max(a, lo), min(b, hi)
            if b2 > a2:
                op_s[n] += (b2 - a2) / 1e9 / len(devs)
            if lo <= a and b <= hi:
                op_calls[n][0] += 1 / len(devs)
                op_calls[n][1] += (b - a) / 1e9 / len(devs)
        idle = subtract([[lo, hi]], allu)
        gaps.extend((_label(host, (a + b) / 2), (b - a) / 1e9 / len(devs))
                    for a, b in idle)
    n = len(devs)
    gaps.sort(key=lambda g: -g[1])
    return Summary(window_s=(hi - lo) / 1e9, busy_s=busy / n / 1e9,
                   op_s=dict(op_s), op_calls=dict(op_calls), collective_s=coll / n / 1e9,
                   exposed_collective_s=exposed / n / 1e9, gaps=gaps)
