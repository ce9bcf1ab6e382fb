"""Trace analyzer: turn a ``--trace out.json`` Chrome trace-event file
from the serving driver into human-readable tables or one JSON doc.

  python tools/trace_report.py out.json
  python tools/trace_report.py out.json --json > report.json

Four views, all from the one artifact:

* **Waterfall** — per request, the phase timeline in submission order:
  queued / prefill chunks / speculate / verify / fallback / close /
  answer spans with start offset and duration, so "where did this
  request's wall time go" reads top to bottom.
* **Phase attribution** — per track (scheduler, spec rounds, each
  engine, requests pooled), total span time per region or phase name
  and its share of the trace's wall window.  Engine rows attribute
  engine calls (``<role>.prefill`` / ``.extend`` / ``.decode`` /
  ``.feed`` / ``.cache_seed``); request rows attribute scheduler
  phases.  The ``.put`` / ``.dispatch`` / ``.wait`` / ``.pull`` phases
  are EXCLUDED here — they tile their engine call, so summing them
  alongside it would double-count.
* **Engine-call phases** (``hostdev``) — per engine call
  (``<role>.<op>``, and the spec engine's ``spec.accept``), calls and
  milliseconds in each phase: ``put`` (host staging and copies to the
  device), ``dispatch`` (the jitted call, which returns once the work
  is enqueued), ``wait`` (``block_until_ready``) and ``pull`` (copies
  back and host bookkeeping), plus the static cost annotations summed
  off the call spans (tokens, est. KV MB moved).
* **Speculation funnel** — proposed vs accepted draft tokens summed
  over every spec_round span, step-level accept/reject instants, and
  fallback regenerations: the proposed → accepted → fallback shape of
  the run.

``--json`` emits all four as one machine-readable document
(``{meta, waterfall, attribution, hostdev, funnel}``) so CI
and scripts gate on trace contents instead of scraping stdout.

The loader *validates* before it renders — required keys per event
type, non-negative complete-event durations, in-window timestamps, a
thread_name metadata row for every tid, and a full phase chain
(queued → prefill → … → answer → done) for every ok-completed request
— and exits nonzero on malformed input.  CI runs this against a
micro-testbed serve run; treat a failure as a telemetry regression,
not a flake.
"""

from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict

# event names that appear on request tracks and mark scheduler phases
REQUEST_PHASES = ("queued", "prefill", "speculate", "verify", "fallback",
                  "close", "answer", "spec_round")

# the phases that tile one engine call (serving/telemetry.py's regions)
PHASES = ("put", "dispatch", "wait", "pull")
_SUB_SUFFIXES = tuple("." + p for p in PHASES)


def _is_subspan(name: str) -> bool:
    return name.endswith(_SUB_SUFFIXES)


class TraceError(Exception):
    """Structural problem in the trace file (malformed export)."""


def load(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        raise TraceError("missing traceEvents array")
    return doc


def validate(doc: dict) -> dict:
    """Structural checks; returns {tid: track_name} on success."""
    events = doc["traceEvents"]
    tracks = {}
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "thread_name":
            tracks[ev["tid"]] = ev["args"]["name"]
    seen_tids = set()
    for i, ev in enumerate(events):
        ph = ev.get("ph")
        if ph is None:
            raise TraceError(f"event {i}: no ph")
        if ph == "M":
            continue
        for key in ("name", "ts", "pid", "tid"):
            if key not in ev:
                raise TraceError(f"event {i} ({ph}): missing {key!r}")
        if ev["ts"] < 0:
            raise TraceError(f"event {i} ({ev['name']}): ts < 0")
        if ph == "X":
            if "dur" not in ev:
                raise TraceError(f"event {i} ({ev['name']}): X without dur")
            if ev["dur"] < 0:
                raise TraceError(f"event {i} ({ev['name']}): dur < 0")
        elif ph == "i":
            if ev.get("s") not in ("t", "p", "g"):
                raise TraceError(f"event {i} ({ev['name']}): instant "
                                 f"scope {ev.get('s')!r}")
        elif ph == "C":
            if not isinstance(ev.get("args"), dict):
                raise TraceError(f"event {i} ({ev['name']}): counter "
                                 "without args")
        elif ph not in ("B", "E"):
            raise TraceError(f"event {i}: unknown ph {ph!r}")
        seen_tids.add(ev["tid"])
    missing = seen_tids - set(tracks)
    if missing:
        raise TraceError(f"tids without thread_name metadata: "
                         f"{sorted(missing)}")
    # every ok-completed request must carry its full phase chain: the
    # queued span, at least one prefill chunk, and the answer span that
    # produced its output (speculate/verify may be absent for requests
    # that fell straight through, fallback/close for ones that did not)
    done_ok = {tracks[ev["tid"]]
               for ev in events
               if ev.get("ph") == "i" and ev.get("name") == "done"
               and ev.get("args", {}).get("status") == "ok"}
    for track in sorted(done_ok):
        names = {ev["name"] for ev in events
                 if ev.get("ph") == "X" and tracks[ev["tid"]] == track}
        for need in ("queued", "prefill", "answer"):
            if need not in names:
                raise TraceError(f"{track}: ok-completed but no "
                                 f"{need!r} span")
    return tracks


def _fmt_ms(us: float) -> str:
    return f"{us / 1e3:.1f}ms"


# ------------------------------------------------------------ waterfall
def waterfall_data(events: list, tracks: dict) -> list:
    by_req = defaultdict(list)
    for ev in events:
        track = tracks.get(ev.get("tid"))
        if (ev.get("ph") == "X" and track and track.startswith("req:")
                and ev["name"] != "spec_round"):
            by_req[track].append(ev)
    out = []
    # submission order = start of each request's queued span
    for track in sorted(by_req,
                        key=lambda r: min(e["ts"] for e in by_req[r])):
        evs = sorted(by_req[track], key=lambda e: (e["ts"], e["dur"]))
        t0 = evs[0]["ts"]
        out.append({
            "request": track[len("req:"):],
            "total_ms": round((max(e["ts"] + e["dur"] for e in evs) - t0)
                              / 1e3, 3),
            "spans": [{"name": e["name"],
                       "offset_ms": round((e["ts"] - t0) / 1e3, 3),
                       "dur_ms": round(e["dur"] / 1e3, 3),
                       "args": e.get("args") or {}} for e in evs],
        })
    return out


def waterfall_text(data: list) -> str:
    lines = ["== per-request waterfall =="]
    if not data:
        return "\n".join(lines + ["(no request spans)"])
    for req in data:
        lines.append(f"req:{req['request']}  ({req['total_ms']:.1f}ms "
                     f"total)")
        for s in req["spans"]:
            args = s["args"]
            extra = ""
            if s["name"] == "prefill" and "to" in args:
                extra = f"  [{args.get('from', '?')}..{args['to']}" \
                        f"/{args.get('prompt', '?')}]"
            lines.append(f"  +{s['offset_ms']:>9.1f}ms  "
                         f"{s['name']:<10} {s['dur_ms']:>9.1f}ms{extra}")
    return "\n".join(lines)


# ---------------------------------------------------------- attribution
def attribution_data(events: list, tracks: dict) -> dict:
    # host/device sub-spans tile their parent bracket — summing them
    # alongside it would double-count every engine call, so they are
    # excluded here (the hostdev view is built from them instead)
    xs = [e for e in events
          if e.get("ph") == "X" and not _is_subspan(e["name"])]
    if not xs:
        return {"wall_ms": 0.0, "tracks": {}}
    wall = (max(e["ts"] + e["dur"] for e in xs)
            - min(e["ts"] for e in xs)) or 1.0
    # requests pool into one row-group; engines and scheduler stay apart
    groups = defaultdict(lambda: defaultdict(float))
    for e in xs:
        track = tracks.get(e["tid"], "?")
        group = "requests" if track.startswith("req:") else track
        groups[group][e["name"]] += e["dur"]
    return {
        "wall_ms": round(wall / 1e3, 3),
        "tracks": {
            group: [{"phase": name, "ms": round(dur / 1e3, 3),
                     "share": round(dur / wall, 4)}
                    for name, dur in sorted(groups[group].items(),
                                            key=lambda kv: -kv[1])]
            for group in sorted(groups)
        },
    }


def attribution_text(data: dict) -> str:
    lines = ["== phase attribution =="]
    if not data["tracks"]:
        return "\n".join(lines + ["(no spans)"])
    lines.append(f"{'track':<28} {'phase':<12} {'time':>10} {'share':>7}")
    for group, rows in data["tracks"].items():
        for r in rows:
            lines.append(f"{group:<28} {r['phase']:<12} "
                         f"{r['ms']:>8.1f}ms {r['share']:>6.1%}")
    return "\n".join(lines)


# -------------------------------------------------- engine-call phases
def hostdev_data(events: list, tracks: dict) -> dict:
    """Milliseconds in each phase (put / dispatch / wait / pull) per
    engine call, from the phase spans; calls / tokens / KV bytes are
    summed off the call spans' static annotations."""
    per = defaultdict(lambda: {"calls": 0, "tokens": 0, "kv_bytes": 0,
                               **{p: 0.0 for p in PHASES}})
    calls = []
    for e in events:
        if e.get("ph") != "X":
            continue
        op, _, phase = e["name"].rpartition(".")
        if phase in PHASES:
            per[op][phase] += e["dur"]
        else:
            calls.append(e)
    for e in calls:
        d = per.get(e["name"])
        if d is not None:
            d["calls"] += 1
            args = e.get("args") or {}
            d["tokens"] += args.get("tokens", 0)
            d["kv_bytes"] += args.get("kv_bytes", 0)
    ops = []
    for op, d in sorted(per.items(),
                        key=lambda kv: -sum(kv[1][p] for p in PHASES)):
        ops.append({"op": op, "calls": d["calls"],
                    **{f"{p}_ms": round(d[p] / 1e3, 3) for p in PHASES},
                    "tokens": d["tokens"],
                    "kv_mb": round(d["kv_bytes"] / (1 << 20), 3)})
    return {"ops": ops}


def hostdev_text(data: dict) -> str:
    lines = ["== engine-call phases =="]
    if not data["ops"]:
        return "\n".join(lines + ["(no engine-call phase spans)"])
    lines.append(f"{'op':<18} {'calls':>6} {'put':>9} {'dispatch':>9} "
                 f"{'wait':>9} {'pull':>9} {'tokens':>8} {'kv MB':>8}")
    for r in data["ops"]:
        lines.append(
            f"{r['op']:<18} {r['calls']:>6} {r['put_ms']:>7.1f}ms "
            f"{r['dispatch_ms']:>7.1f}ms {r['wait_ms']:>7.1f}ms "
            f"{r['pull_ms']:>7.1f}ms {r['tokens']:>8} {r['kv_mb']:>8.2f}")
    return "\n".join(lines)


# --------------------------------------------------------------- funnel
def funnel_data(events: list, tracks: dict) -> dict:
    proposed = accepted = rounds = 0
    step_accept = step_reject = fallbacks = 0
    for ev in events:
        name, args = ev.get("name"), ev.get("args") or {}
        if ev.get("ph") == "X" and name == "spec_round":
            rounds += 1
            proposed += args.get("proposed", 0)
            accepted += args.get("accepted", 0)
        elif ev.get("ph") == "X" and name == "fallback":
            fallbacks += 1
        elif ev.get("ph") == "i" and name == "accept":
            step_accept += 1
        elif ev.get("ph") == "i" and name == "reject":
            step_reject += 1
    return {
        "steps": {"accepted": step_accept, "rejected": step_reject,
                  "fallbacks": fallbacks},
        "decode": {"rounds": rounds, "proposed": proposed,
                   "accepted": accepted},
    }


def funnel_text(data: dict) -> str:
    lines = ["== speculation funnel =="]
    st, dec = data["steps"], data["decode"]
    steps = st["accepted"] + st["rejected"]
    if steps:
        lines.append(f"steps   : {st['accepted']}/{steps} accepted "
                     f"({st['accepted'] / steps:.0%}), "
                     f"{st['fallbacks']} fallback regenerations")
    else:
        lines.append("steps   : none recorded")
    if dec["rounds"]:
        lines.append(f"decode  : {dec['accepted']}/{dec['proposed']} "
                     f"draft tokens accepted over {dec['rounds']} rounds "
                     f"(mean {dec['accepted'] / dec['rounds']:.2f}"
                     f"/round)")
    else:
        lines.append("decode  : no spec_round spans (token-level spec "
                     "decode off)")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Analyze a serving trace (Chrome trace-event JSON "
                    "written by --trace).")
    ap.add_argument("trace", help="path to the trace JSON")
    ap.add_argument("--validate-only", action="store_true",
                    help="run the structural checks and exit (CI mode)")
    ap.add_argument("--json", action="store_true",
                    help="emit all views as one machine-readable JSON "
                         "doc ({meta, waterfall, attribution, hostdev, "
                         "funnel}) instead of text tables")
    args = ap.parse_args(argv)
    try:
        doc = load(args.trace)
        tracks = validate(doc)
    except (TraceError, OSError, json.JSONDecodeError, KeyError,
            TypeError) as e:
        print(f"trace_report: malformed trace: {e}", file=sys.stderr)
        return 1
    events = doc["traceEvents"]
    n_req = sum(1 for t in tracks.values() if t.startswith("req:"))
    meta = {
        "trace": args.trace,
        "events": len(events),
        "tracks": len(tracks),
        "requests": n_req,
        "recorded": doc.get("otherData", {}).get("recorded"),
        "dropped": doc.get("otherData", {}).get("dropped"),
    }
    if args.json:
        print(json.dumps({
            "meta": meta,
            "waterfall": waterfall_data(events, tracks),
            "attribution": attribution_data(events, tracks),
            "hostdev": hostdev_data(events, tracks),
            "funnel": funnel_data(events, tracks),
        }, indent=1))
        return 0
    print(f"{args.trace}: {len(events)} events, {len(tracks)} tracks "
          f"({n_req} requests); recorded={meta['recorded'] or '?'} "
          f"dropped={meta['dropped'] if meta['dropped'] is not None else '?'}")
    if args.validate_only:
        print("structure ok")
        return 0
    print()
    print(waterfall_text(waterfall_data(events, tracks)))
    print()
    print(attribution_text(attribution_data(events, tracks)))
    print()
    print(hostdev_text(hostdev_data(events, tracks)))
    print()
    print(funnel_text(funnel_data(events, tracks)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
