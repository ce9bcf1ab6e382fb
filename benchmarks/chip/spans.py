"""Reduction of a profiler trace (``*.xplane.pb``) to the program's own
layers, beside ``xplane.py``'s device totals:

  idle_by_region   device idle seconds in the traced window by the
                   innermost program region over each gap's midpoint.
                   The gaps are those behind ``idle_share``; the regions
                   are the program's ``Tracer.region`` annotations
                   (``sched.*``, ``spec.*``, ``<role>.<op>[.<phase>]``,
                   serving/telemetry.py), never the profiler's Python
                   frames or runtime events; a gap under none reads
                   ``outside program``.
  device_by_scope  device seconds of leaf operations (an "XLA Ops" event
                   that contains no other, so a loop is not counted
                   beside its body) inside the window, by the first of
                   the cell's scopes (its architecture module's
                   ``SCOPES``) in the op's name-scope path (its HLO
                   ``op_name``); the rest reads ``other``.

Each part is None for a trace with no program region or no scoped op (a
program that predates them).  A TPU trace names each op event by its
HLO instruction and carries no ``op_name``; the compiled programs'
``HloProto``s on the trace's ``/host:metadata`` plane do, so ``planes``
joins the two through the program ("XLA Modules" event) each op ran in.
"""

from __future__ import annotations

import bisect
import mmap
import re
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

import xplane

REGION = re.compile(r"(sched|spec|base|draft)(\.[a-z_]+)+$")
OUTSIDE = "outside program"
OTHER = "other"
METADATA_PLANE = "/host:metadata"
HLO_PROTO = "Hlo Proto"


# ------------------------------------------------------- the raw trace

def _varint(b, i: int) -> Tuple[int, int]:
    r = s = 0
    while True:
        x = b[i]
        i += 1
        r |= (x & 0x7F) << s
        if x < 0x80:
            return r, i
        s += 7


def _fields(b, i: int, end: int) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message in ``b[i:end]``; a
    length-delimited value is its (start, end) in ``b``."""
    while i < end:
        key, i = _varint(b, i)
        f, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wt in (1, 5):
            n = 8 if wt == 1 else 4
            v, i = b[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wt} in the trace")
        yield f, v


def _text(b, span) -> str:
    return bytes(b[span[0]:span[1]]).decode("utf-8", "replace")


def _hlo_protos(b) -> Dict[str, tuple]:
    """Program name -> its ``HloProto``'s span in ``b``, from the
    metadata plane (XSpace.planes = 1; XPlane.name = 2, event_metadata
    = 4 as map entries (key 1, XEventMetadata 2), stat_metadata = 5 as
    map entries (key 1, XStatMetadata 2); XEventMetadata.name = 2,
    stats = 5; XStat.metadata_id = 1, bytes_value = 6; XStatMetadata.name
    = 2)."""
    out: Dict[str, tuple] = {}
    for f, plane in _fields(b, 0, len(b)):
        if f != 1:
            continue
        parts = list(_fields(b, *plane))
        if not any(g == 2 and _text(b, v) == METADATA_PLANE
                   for g, v in parts):
            continue
        ids = set()
        for g, v in parts:
            if g == 5:
                entry = dict(_fields(b, *v))
                meta = dict(_fields(b, *entry[2]))
                if _text(b, meta.get(2, (0, 0))) == HLO_PROTO:
                    ids.add(entry.get(1, 0))
        for g, v in parts:
            if g != 4:
                continue
            meta = list(_fields(b, *dict(_fields(b, *v))[2]))
            name = next((_text(b, x) for h, x in meta if h == 2), "")
            for h, x in meta:
                if h == 5:
                    stat = dict(_fields(b, *x))
                    if stat.get(1, 0) in ids and 6 in stat:
                        out[name] = stat[6]
    return out


def _op_names(b, span) -> Dict[str, str]:
    """Instruction name -> ``op_name`` in one ``HloProto`` (hlo_module =
    1; HloModuleProto.computations = 3; HloComputationProto.instructions
    = 2; HloInstructionProto.name = 1, metadata = 7; OpMetadata.op_name =
    2)."""
    out: Dict[str, str] = {}
    for f, module in _fields(b, *span):
        if f != 1:
            continue
        for g, comp in _fields(b, *module):
            if g != 3:
                continue
            for h, ins in _fields(b, *comp):
                if h != 2:
                    continue
                name = op = None
                for k, v in _fields(b, *ins):
                    if k == 1:
                        name = _text(b, v)
                    elif k == 7:
                        op = dict(_fields(b, *v)).get(2)
                if name is not None and op is not None:
                    out[name] = _text(b, op)
    return out


INSTRUCTION = re.compile(r"%?([^\s=]+)")


def planes(path: str) -> xplane.Planes:
    """The trace as plain data (``xplane.Planes``), with a fourth element
    on every "XLA Ops" event: its HLO ``op_name`` ("" where the trace
    holds none)."""
    import jax
    with open(path, "rb") as fh:
        raw = mmap.mmap(fh.fileno(), 0, access=mmap.ACCESS_READ)
    try:
        protos = _hlo_protos(raw)
        parsed: Dict[str, Dict[str, str]] = {}
        data = jax.profiler.ProfileData.from_file(path)
        out: xplane.Planes = {}
        for plane in data.planes:
            lines = {line.name: [[ev.name, float(ev.start_ns),
                                  float(ev.duration_ns)]
                                 for ev in line.events]
                     for line in plane.lines}
            ops = lines.get(xplane.OPS_LINE)
            if ops:
                mods = sorted(lines.get(xplane.MODULES_LINE, []),
                              key=lambda e: e[1])
                starts = [m[1] for m in mods]
                for ev in ops:
                    k = bisect.bisect_right(starts, ev[1]) - 1
                    name = ""
                    if k >= 0 and ev[1] < mods[k][1] + mods[k][2]:
                        prog = mods[k][0]
                        if prog not in parsed:
                            parsed[prog] = _op_names(raw, protos[prog]) \
                                if prog in protos else {}
                        m = INSTRUCTION.match(ev[0])
                        name = parsed[prog].get(m.group(1), "") if m else ""
                    ev.append(name)
            out[plane.name] = lines
        return out
    finally:
        raw.close()


# ------------------------------------------------------------ reduction

def scope_of(op_name: str, scopes: Sequence[str]) -> str:
    """The first of ``scopes`` in an ``op_name`` path (``a/b/c``), else
    ``other``."""
    for part in op_name.split("/"):
        if part in scopes:
            return part
    return OTHER


def _leaf_time(evs: list, w0: float, w1: float,
               scopes: Sequence[str]) -> Dict[str, float]:
    """Seconds inside [w0, w1) of the events that contain no other event
    of their line, by scope.  Events of one line nest."""
    kinds = tuple(scopes) + (OTHER,)
    code: Dict[str, int] = {}
    c = np.array([code[e[3]] if e[3] in code else
                  code.setdefault(e[3], kinds.index(scope_of(e[3], scopes)))
                  for e in evs])
    s = np.array([e[1] for e in evs])
    d = np.array([e[2] for e in evs])
    order = np.lexsort((-d, s))                 # a parent before its child
    s, d, c = s[order], d[order], c[order]
    e = s + d
    leaf = np.append(s[1:] >= e[:-1], True)
    inside = np.clip(np.minimum(e, w1) - np.maximum(s, w0), 0.0, None)
    t = np.bincount(c[leaf], weights=inside[leaf], minlength=len(kinds))
    return {k: float(v) * 1e-9 for k, v in zip(kinds, t) if v > 0}


def reduce(pl: xplane.Planes, scopes: Sequence[str]) -> Optional[Dict]:
    """``idle_by_region`` and ``device_by_scope`` (by ``scopes``) of the
    traced window, each as [name, seconds] pairs, largest first; None
    when no device ran."""
    found = xplane._window_line(pl)
    devices = [lines[xplane.OPS_LINE] for n, lines in pl.items()
               if xplane._is_device(n) and lines.get(xplane.OPS_LINE)]
    if found is None or not devices:
        return None
    (w0, w1), host = found
    gaps = []
    scoped: Dict[str, float] = defaultdict(float)
    for evs in devices:
        iv = [(max(s, w0), min(s + d, w1)) for _, s, d, _ in evs
              if min(s + d, w1) > max(s, w0)]
        edges = [w0] + [x for ab in xplane._merge(iv) for x in ab] + [w1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for k, v in _leaf_time(evs, w0, w1, scopes).items():
            scoped[k] += v
    regions = [ev for ev in host if REGION.match(ev[0])]
    idle = None
    if regions:
        idle = {OUTSIDE if k == "no host span" else k: v
                for k, v in xplane.attribute(gaps, regions).items()}
    return {
        "idle_by_region": _pairs(idle) if idle is not None else None,
        "device_by_scope": _pairs(scoped) if set(scoped) - {OTHER}
        else None,
    }


def _pairs(d: Dict[str, float]) -> List[list]:
    return sorted(([k, v] for k, v in d.items()), key=lambda x: -x[1])


# --------------------------------------------------------------- readers

def idle_class(region: str) -> Optional[str]:
    """The class of idle time under ``region``: ``sync`` (waiting on the
    device, copying results back), ``stage`` (staging inputs, the jitted
    call), ``sched`` (any other program region: the scheduler, the spec
    round, an engine call's own bookkeeping), None outside the program."""
    if region == OUTSIDE:
        return None
    if region.endswith((".wait", ".pull")):
        return "sync"
    if region.endswith((".put", ".dispatch")) or region == "spec.stage":
        return "stage"
    return "sched"


def idle_ms_per_tick(rec, cls: str) -> Optional[float]:
    """Idle milliseconds of class ``cls`` over the window's ticks."""
    by = (rec.trace or {}).get("idle_by_region")
    if not by or not rec.ticks:
        return None
    return 1e3 * sum(t for n, t in by if idle_class(n) == cls) \
        / len(rec.ticks)


def dev_ms_per_tick(rec, scope: str) -> Optional[float]:
    """Device milliseconds of leaf ops in ``scope`` over the window's
    ticks."""
    by = (rec.trace or {}).get("device_by_scope")
    if not by or not rec.ticks:
        return None
    return 1e3 * dict(by).get(scope, 0.0) / len(rec.ticks)
