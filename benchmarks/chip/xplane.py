"""Reduction of a profiler trace (``*.xplane.pb``) to device metrics.

``spans.planes`` turns the trace into plain data, {plane: {line: [[name,
start_ns, dur_ns], ...]}} (an "XLA Ops" event carries its HLO
``op_name`` as a fourth field, which this module ignores); ``reduce``
computes from that:

  window_s    the traced window: the host span ``chipbench.window``
  busy_s      the union of the intervals in which an operation ran on a
              device (its "XLA Ops" line), inside the window, averaged
              over the devices that ran any
  programs    device program executions ("XLA Modules" events) that
              started inside the window, summed over devices
  device_ops  the 10 operations that took most device time
  idle_gaps   idle device time by what the host was doing: each gap
              between busy intervals goes to the innermost host span
              (``TraceAnnotation``) that covers its midpoint
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW = "chipbench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Planes = Dict[str, Dict[str, List[list]]]


def find(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _is_device(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def _merge(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _window_line(pl: Planes) -> Optional[Tuple[Tuple[float, float], list]]:
    """The window and the events of the host thread that ran it."""
    for name, lines in pl.items():
        if _is_device(name):
            continue
        for evs in lines.values():
            for ev_name, start, dur in evs:
                if ev_name == WINDOW:
                    return (start, start + dur), evs
    return None


def attribute(gaps: List[Tuple[float, float]], evs: list) -> Dict[str, float]:
    """Seconds of each gap by the innermost host span over its midpoint.
    The spans of one thread nest, so a stack sweep finds it."""
    spans = sorted((s, s + d, n) for n, s, d in evs if d > 0 and n != WINDOW)
    out: Dict[str, float] = defaultdict(float)
    stack: list = []
    j = 0
    for a, b in sorted(gaps, key=lambda g: g[0] + g[1]):
        mid = (a + b) / 2
        while j < len(spans) and spans[j][0] <= mid:
            while stack and stack[-1][1] <= spans[j][0]:
                stack.pop()
            stack.append(spans[j])
            j += 1
        while stack and stack[-1][1] <= mid:
            stack.pop()
        out[stack[-1][2] if stack else "no host span"] += (b - a) * 1e-9
    return out


def reduce(pl: Planes, top: int = 10) -> Optional[Dict]:
    """Device numbers of the traced window; None when no device ran."""
    found = _window_line(pl)
    devices = {n: l for n, l in pl.items() if _is_device(n) and l.get(OPS_LINE)}
    if found is None or not devices:
        return None
    (w0, w1), host = found
    busy_ns, programs = [], 0
    op_time: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[float, float]] = []
    for lines in devices.values():
        iv = []
        for n, s, d, *_ in lines[OPS_LINE]:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                iv.append((a, b))
                op_time[n] += (b - a) * 1e-9
        merged = _merge(iv)
        busy_ns.append(sum(b - a for a, b in merged))
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        programs += sum(1 for _, s, _ in lines.get(MODULES_LINE, [])
                        if w0 <= s < w1)
    idle = attribute(gaps, host)
    busy = [b for b in busy_ns if b > 0]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": (sum(busy) / len(busy) if busy else 0.0) * 1e-9,
        "programs": programs,
        "device_ops": sorted(([n, t] for n, t in op_time.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": sorted(([n, t] for n, t in idle.items()),
                            key=lambda x: -x[1])[:top],
    }
