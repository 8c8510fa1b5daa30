"""Reduction of a profiler trace to the benchmark's device numbers.

``normalize`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
what the reduction needs, in a plain form that a test can hold:

    {"devices": {plane name: [[op name, start_ns, dur_ns, tag], ...]},
     "host": [[span name, start_ns, dur_ns], ...],       # bench.* spans
     "window": [start_ns, end_ns]}

An op's name is its HLO instruction's name (``fusion.12``).  A custom call
(a Pallas kernel among them) also keeps a tag: its text statistics and its
HLO text, where the kernel's name is found.

``reduce`` turns that into the busy and idle time of the window, the device
time of named kernels, and the breakdown of where device time and idle
time went.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
HLO_NAME = re.compile(r"^%?([\w.\-]+) = ")


def normalize(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    devices: dict = {}
    host: list = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(_op(ev) for ev in line.events)
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([ev.name, float(ev.start_ns), float(ev.duration_ns)]
                            for ev in line.events if ev.name.startswith(SPAN_PREFIX))
    win = [s for s in host if s[0] == WINDOW_SPAN]
    if win:
        window = [win[0][1], win[0][1] + win[0][2]]
    else:
        pts = [e[1] for ops in devices.values() for e in ops] + [h[1] for h in host]
        ends = [e[1] + e[2] for ops in devices.values() for e in ops] + [h[1] + h[2] for h in host]
        window = [min(pts), max(ends)] if pts else [0.0, 0.0]
    return {"devices": devices, "host": host, "window": window}


def _op(ev) -> list:
    short = HLO_NAME.match(ev.name)
    name = short.group(1) if short else ev.name
    tag = ""
    if name.startswith("custom-call"):
        tag = "|".join([str(v) for _, v in ev.stats if isinstance(v, str)] + [ev.name])
    return [name, float(ev.start_ns), float(ev.duration_ns), tag]


def _union(intervals, lo: float, hi: float) -> list:
    """Merged [start, end] intervals clipped to [lo, hi]."""
    out: list = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(ops) -> dict:
    """Device time by op name, each op charged only for what its nested ops
    (the body of a while loop, say) do not cover."""
    by_name: dict = {}
    stack: list = []                      # [end, name, self_ns]

    def close(item):
        by_name[item[1]] = by_name.get(item[1], 0.0) + max(item[2], 0.0)

    for name, start, dur, *_ in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start:
            close(stack.pop())
        if stack:
            stack[-1][2] -= dur
        stack.append([start + dur, name, dur])
    while stack:
        close(stack.pop())
    return by_name


def op_family(name: str) -> str:
    """``fusion.123`` and ``fusion.7`` are one family: ``fusion``."""
    return re.sub(r"[.:]\d+$", "", name)


def kernel_seconds(norm: dict, pattern: str) -> float:
    """Device seconds of the ops whose name or tag contains ``pattern``,
    summed over the devices and clipped to the window."""
    lo, hi = norm["window"]
    total = 0.0
    for ops in norm["devices"].values():
        spans = [(op[1], op[1] + op[2]) for op in ops
                 if pattern in op[0] or pattern in op[3]]
        total += sum(e - s for s, e in _union(spans, lo, hi))
    return total / 1e9


def reduce(norm: dict, top: int = 10) -> dict:
    lo, hi = norm["window"]
    window_s = (hi - lo) / 1e9
    busy_by_dev = {}
    gaps: list = []
    fam: dict = {}
    for plane, ops in norm["devices"].items():
        busy = _union(((op[1], op[1] + op[2]) for op in ops), lo, hi)
        busy_by_dev[plane] = sum(e - s for s, e in busy) / 1e9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps.extend((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
        inside = [o for o in ops if o[1] + o[2] > lo and o[1] < hi]
        for name, t in _self_times(inside).items():
            f = op_family(name)
            fam[f] = fam.get(f, 0.0) + t / 1e9
    busy_s = (sum(busy_by_dev.values()) / len(busy_by_dev)) if busy_by_dev else 0.0
    host = sorted(norm["host"], key=lambda h: h[1])

    def doing(t: float) -> str:
        best = None
        for name, s, d in host:
            if s > t:
                break
            if s <= t < s + d and name != WINDOW_SPAN:
                best = name                 # the latest-starting span wins
        return best or "host:other"

    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    ops_list = sorted(fam.items(), key=lambda kv: -kv[1])
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": (1.0 - busy_s / window_s) if window_s > 0 and busy_by_dev else None,
        "device_ops": [[n, t] for n, t in ops_list[:top]],
        "idle_gaps": [[doing((s + e) / 2), (e - s) / 1e9] for s, e in longest],
    }
