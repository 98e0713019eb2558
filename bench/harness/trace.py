"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy time, device time per XLA program and per
kernel, and where the device sat idle by what the host was doing.

Busy time is the union of the intervals in which an operation ran on a
device (the "XLA Ops" line of each device plane), clipped to the traced
window, averaged over the devices. The window is the harness's own
``window`` span on the host; a trace without it, or one whose device
clock cannot be placed on the host's (most device time falls outside the
window), is refused rather than read over another span. A program's time is the summed duration of
its executions ("XLA Modules" line); a kernel's is the summed duration
of the operations whose name or metadata matches it.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "window"


@dataclasses.dataclass
class Reduced:
    window_s: float                      # traced window length
    busy_s: float                        # mean over devices
    program_s: Dict[str, float]          # per program pattern, summed
    kernel_s: Dict[str, float]           # per kernel pattern, summed
    top_ops: List[Tuple[str, float]]     # longest device operations
    idle_by_host: List[Tuple[str, float]]  # idle time by host activity
    devices: int


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals; returns them sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(iv: Interval, lo: float, hi: float) -> Optional[Interval]:
    s, e = max(iv[0], lo), min(iv[1], hi)
    return (s, e) if e > s else None


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of disjoint sorted ``busy`` inside [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return [g for g in out if g[1] > g[0]]


def covers(merged: Sequence[Interval], t: float) -> bool:
    """Whether disjoint sorted ``merged`` contains the instant ``t``."""
    i = bisect.bisect_right(merged, (t, float("inf"))) - 1
    return i >= 0 and merged[i][0] <= t <= merged[i][1]


def check_aligned(busy_in_window: float, busy_all: float) -> None:
    """Refuse a trace whose device time mostly lies outside the host's
    window: its device clock is then not on the host's, and no span of it
    stands for the window."""
    if busy_in_window < 0.5 * busy_all:
        raise ValueError(
            f"device and host clocks disagree: {busy_in_window:.6f} s of "
            f"{busy_all:.6f} s of device time falls inside the window")


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(paths, key=os.path.getmtime)


def _event(ev) -> Tuple[str, float, float]:
    s = float(ev.start_ns) * 1e-9
    return ev.name, s, s + float(ev.duration_ns) * 1e-9


def short_name(name: str, width: int = 120) -> str:
    """An operation's HLO text cut to its name and the start of its
    result type (TPU traces name each operation by its whole text)."""
    return name if len(name) <= width else name[:width - 3] + "..."


def _is_device(plane) -> bool:
    """A chip's plane: a device plane that holds XLA operations (a TPU
    trace also has device planes of other kinds, such as Megascale's)."""
    return plane.name.startswith("/device:") and any(
        line.name == OPS_LINE for line in plane.lines)


def _matches(ev, name: str, pattern: re.Pattern, cache: Dict) -> bool:
    hit = cache.get(name)
    if hit is None:
        hit = bool(pattern.search(name)) or any(
            isinstance(v, str) and pattern.search(v) for _, v in ev.stats)
        cache[name] = hit
    return hit


def reduce_trace(path: str, programs: Dict[str, str],
                 kernels: Dict[str, str],
                 host_labels: Sequence[str] = ()) -> Reduced:
    """Reduce one trace. ``programs`` and ``kernels`` map a name to a
    regular expression searched in the event name (and, for kernels, in
    the operation's string metadata). ``host_labels`` are the harness's
    host spans, innermost first: each idle gap of the (first) device is
    attributed to the first of them that holds the gap's midpoint."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host: Dict[str, List[Interval]] = {}
    device_planes = []
    for plane in pd.planes:
        if _is_device(plane):
            device_planes.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN or ev.name in host_labels:
                        name, s, e = _event(ev)
                        host.setdefault(name, []).append((s, e))
    if not device_planes:
        raise ValueError(f"no device plane in {path}")
    pats_p = {k: re.compile(v) for k, v in programs.items()}
    pats_k = {k: re.compile(v) for k, v in kernels.items()}

    per_dev = []
    for plane in device_planes:
        lines = {line.name: line for line in plane.lines}
        ops = [(_event(ev), ev) for ev in lines[OPS_LINE].events]
        mods = [_event(ev) for ev in lines[MODULES_LINE].events] \
            if MODULES_LINE in lines else []
        per_dev.append((ops, mods))

    if WINDOW_SPAN not in host:
        raise ValueError(f"no {WINDOW_SPAN!r} span on the host in {path}")
    lo = min(s for s, _ in host[WINDOW_SPAN])
    hi = max(e for _, e in host[WINDOW_SPAN])

    busy_tot, unclipped, prog, kern, top = 0.0, 0.0, {}, {}, {}
    busy_sets = []
    for ops, mods in per_dev:
        kept, every = [], []
        caches = {k: {} for k in pats_k}
        for (name, s, e), ev in ops:
            every.append((s, e))
            iv = clip((s, e), lo, hi)
            if iv is None:
                continue
            kept.append(iv)
            d = iv[1] - iv[0]
            top[short_name(name)] = top.get(short_name(name), 0.0) + d
            for k, pat in pats_k.items():
                if _matches(ev, name, pat, caches[k]):
                    kern[k] = kern.get(k, 0.0) + d
        for name, s, e in mods:
            iv = clip((s, e), lo, hi)
            if iv is None:
                continue
            for k, pat in pats_p.items():
                if pat.search(name):
                    prog[k] = prog.get(k, 0.0) + iv[1] - iv[0]
        b = union(kept)
        busy_sets.append(b)
        busy_tot += total(b)
        unclipped += total(union(every))
    check_aligned(busy_tot, unclipped)
    n = len(per_dev)
    merged = {lab: union(host.get(lab, [])) for lab in host_labels}
    idle: Dict[str, float] = {}
    for b in busy_sets[:1]:
        for g in gaps(b, lo, hi):
            mid = 0.5 * (g[0] + g[1])
            who = next((lab for lab in host_labels
                        if covers(merged[lab], mid)), "none")
            idle[who] = idle.get(who, 0.0) + (g[1] - g[0])
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:10]
    return Reduced(window_s=hi - lo, busy_s=busy_tot / n,
                   program_s={k: v / n for k, v in prog.items()},
                   kernel_s={k: v / n for k, v in kern.items()},
                   top_ops=[(k, v / n) for k, v in rank(top)],
                   idle_by_host=rank(idle), devices=n)
