"""Profiler trace to device busy time, idle share and where the gaps go.

``load(path)`` reads an ``.xplane.pb`` with JAX's own reader into plain
event lists: device operations (the "XLA Ops" line of each TPU plane) and
the benchmark's host spans (``bench.*`` TraceAnnotations), on the trace's
common nanosecond clock.  ``reduce(events)`` takes the traced window as
the span from the first to the last ``bench.*`` span and returns:

* ``busy_s``: the union of device-operation intervals inside the window,
  averaged over the devices;
* ``window_s`` and ``idle_share`` (1 - busy / window);
* ``device_ops``: device time per operation name, the largest first;
* ``idle_gaps``: each stretch of the window with no device operation on
  the first device, named after the ``bench.*`` span that overlaps it
  most, the longest first.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

Interval = Tuple[int, int]

#: the chips' planes, their operation line, and the host spans' prefix
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


def name_ops(ops: list, modules: list) -> list:
    """Short stable names for device operations: ``<program>/<op>``, the
    program without its hash suffix and the op without its HLO text
    (``jit_one/fusion.12``)."""
    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    out = []
    for name, a, b in ops:
        op = name.split(" = ")[0].lstrip("%")
        k = bisect.bisect_right(starts, a) - 1
        if k >= 0 and a < mods[k][2]:
            op = re.sub(r"\(\d+\)$", "", mods[k][0]) + "/" + op
        out.append((op, a, b))
    return out


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> dict:
    """``{"devices": {plane: [(name, start_ns, end_ns)]}, "spans":
    [(name, start_ns, end_ns)]}`` from an xplane file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    spans = []
    seen = []
    for plane in data.planes:
        seen.append(f"{plane.name}: {[ln.name for ln in plane.lines][:8]}")
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: [(ev.name, int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns))
                               for ev in ln.events] for ln in plane.lines}
            if OPS_LINE in lines:
                devices[plane.name] = name_ops(lines[OPS_LINE],
                                               lines.get(MODULES_LINE, []))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, int(ev.start_ns),
                                      int(ev.start_ns + ev.duration_ns)))
    if not devices or not spans:
        raise ValueError(f"{path}: no {OPS_LINE!r} line on a TPU plane or no "
                         f"{SPAN_PREFIX}* span; planes: {'; '.join(seen)}")
    return dict(devices=devices, spans=sorted(spans, key=lambda s: s[1]))


def union(intervals: List[Interval]) -> List[Interval]:
    """Merge intervals into disjoint sorted ones."""
    out: List[list] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    """The parts of ``[lo, hi)`` that no busy interval covers."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def attribute(gap: Interval, spans) -> str:
    """The span name that overlaps ``gap`` most ("none" if none does)."""
    best, name = 0, "none"
    for s, a, b in spans:
        ov = min(b, gap[1]) - max(a, gap[0])
        if ov > best:
            best, name = ov, s
    return name


def reduce(events: dict, top: int = 10) -> dict:
    spans = events["spans"]
    if not spans or not events["devices"]:
        raise ValueError("the trace holds no bench spans or no device plane")
    lo = min(a for _, a, _ in spans)
    hi = max(b for _, _, b in spans)
    per_dev = {}
    op_time: Dict[str, int] = defaultdict(int)
    for plane, evs in sorted(events["devices"].items()):
        inside = clip([(a, b) for _, a, b in evs], lo, hi)
        per_dev[plane] = union(inside)
        for name, a, b in evs:
            d = min(b, hi) - max(a, lo)
            if d > 0:
                op_time[name] += d
    busy = [sum(b - a for a, b in u) for u in per_dev.values()]
    busy_ns = sum(busy) / len(busy)
    first = per_dev[sorted(per_dev)[0]]
    g = sorted(gaps(first, lo, hi), key=lambda x: x[0] - x[1])
    window_ns = hi - lo
    return dict(
        window_s=window_ns * 1e-9,
        busy_s=busy_ns * 1e-9,
        idle_share=1.0 - busy_ns / window_ns,
        device_ops=[[n, t * 1e-9] for n, t in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[attribute(x, spans), (x[1] - x[0]) * 1e-9]
                   for x in g[:top]],
    )
