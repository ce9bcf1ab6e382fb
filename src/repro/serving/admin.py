"""Admin plane: live HTTP observability endpoints for a serving run.

A stdlib-only (``http.server``) daemon-threaded HTTP server that exposes
the telemetry substrate (serving/telemetry.py) and the scheduler's
per-tick state while the run is live:

    GET /healthz          -> "ok" once the server is up (liveness)
    GET /metrics          -> Prometheus text exposition, rendered live
                             from the MetricsRegistry (same bytes the
                             end-of-run --metrics-out file gets)
    GET /status           -> JSON SchedulerSnapshot: queue depth, active
                             rows (phase + cursor), pool occupancy,
                             pressure, ladder level, fault counters,
                             monitor values
    GET /requests/<id>    -> span timeline for one request (the req:<id>
                             tracer track as a JSON event list)
    GET /trace?last=N     -> Chrome-trace JSON of the last N ring events
                             (full ring without ?last=)
    GET /profile?seconds=S-> run a jax.profiler capture for S seconds
                             into the attached profiler's directory and
                             return the artifact path (409 while another
                             capture is in flight)

**Snapshot locking contract.**  The scheduler thread publishes one
immutable :class:`SchedulerSnapshot` per tick through a
:class:`StatusBoard` — the ONLY state shared mutably between the
scheduler and admin threads, guarded by a ``threading.Lock`` held just
for the reference swap/read.  The snapshot itself is built from plain
ints/floats/strings copied out of scheduler state on the scheduler
thread, so the admin thread never walks live scheduler objects.
/metrics and /trace read the MetricsRegistry counters and the tracer
ring directly: both are safe without locks because their underlying
mutations are GIL-atomic (dict item writes, ``deque.append`` with
maxlen) and the readers take one-shot copies (``list(deque)``,
``sorted(dict)``) — a scrape sees a consistent point-in-time view and
never blocks the tick loop.

The server binds 127.0.0.1 by default and port 0 means OS-assigned
(``.port`` reports the real one) — serve.py prints it for CI discovery.
Every endpoint except ``/profile`` is read-only; ``/profile`` mutates
nothing in the serving plane (it starts/stops a profiler capture whose
artifacts land outside the scheduler's state), is latched to one
capture at a time, and only exists when serve.py was given
``--xla-profile-dir``."""

from __future__ import annotations

import dataclasses
import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, List, Optional
from urllib.parse import parse_qs, urlparse


@dataclasses.dataclass
class SchedulerSnapshot:
    """Immutable per-tick copy of scheduler state, built by
    ``ContinuousScheduler.snapshot()`` on the scheduler thread.  Plain
    scalars/strings only — safe to serialize from any thread."""
    tick: int
    time_s: float                       # perf_counter at publish
    queue_depth: int
    active: List[Dict[str, Any]]        # per-row: request/phase/cursor/...
    pools: Dict[str, float]             # pool -> occupancy fraction
    pressure: float
    level: int                          # degradation-ladder level L0..L4
    counts: Dict[str, int]              # timeouts/shed/quarantines/...
    monitors: Optional[Dict[str, Any]]  # Monitors.as_dict() or None
    # compile/device plane (both None unless the watches are attached):
    # MemoryWatch.sample() and CompileWatch.as_dict() of the tick
    memory: Optional[Dict[str, Any]] = None
    compile: Optional[Dict[str, Any]] = None
    # tensor-parallel plane: mesh axes / tp_size / devices / per-device
    # memory watermarks (None when serving unsharded)
    mesh: Optional[Dict[str, Any]] = None

    def as_dict(self) -> Dict[str, Any]:
        return {
            "tick": self.tick,
            "time_s": self.time_s,
            "queue_depth": self.queue_depth,
            "active": self.active,
            "pools": self.pools,
            "pressure": self.pressure,
            "level": self.level,
            "counts": self.counts,
            "monitors": self.monitors,
            "memory": self.memory,
            "compile": self.compile,
            "mesh": self.mesh,
        }


class StatusBoard:
    """The scheduler->admin handoff point: holds the latest snapshot
    behind a lock held only for the reference swap.  ``latest()``
    returns the immutable snapshot (or None before the first tick)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._snap: Optional[SchedulerSnapshot] = None

    def publish(self, snap: SchedulerSnapshot) -> None:
        with self._lock:
            self._snap = snap

    def latest(self) -> Optional[SchedulerSnapshot]:
        with self._lock:
            return self._snap


class _AdminHandler(BaseHTTPRequestHandler):
    # the ThreadingHTTPServer instance carries board/metrics/tracer refs
    server_version = "specreason-admin/1.0"

    def log_message(self, fmt: str, *args: Any) -> None:  # noqa: A003
        pass  # scrapes must not spam the serving console

    # ------------------------------------------------------- responses
    def _send(self, code: int, body: bytes, ctype: str) -> None:
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _text(self, code: int, text: str,
              ctype: str = "text/plain; charset=utf-8") -> None:
        self._send(code, text.encode("utf-8"), ctype)

    def _json(self, code: int, obj: Any) -> None:
        self._send(code, json.dumps(obj, indent=1).encode("utf-8"),
                   "application/json")

    # ---------------------------------------------------------- routes
    def do_GET(self) -> None:  # noqa: N802 (http.server API)
        try:
            url = urlparse(self.path)
            path = url.path.rstrip("/") or "/"
            if path == "/healthz":
                self._text(200, "ok\n")
            elif path == "/metrics":
                self._route_metrics()
            elif path == "/status":
                self._route_status()
            elif path.startswith("/requests/"):
                self._route_request(path[len("/requests/"):])
            elif path == "/trace":
                self._route_trace(url.query)
            elif path == "/profile":
                self._route_profile(url.query)
            else:
                self._json(404, {"error": f"no route {path!r}",
                                 "routes": ["/healthz", "/metrics",
                                            "/status", "/requests/<id>",
                                            "/trace?last=N",
                                            "/profile?seconds=S"]})
        except (BrokenPipeError, ConnectionResetError):
            pass  # client went away mid-scrape

    def _route_metrics(self) -> None:
        metrics = self.server.metrics  # type: ignore[attr-defined]
        if metrics is None:
            self._json(404, {"error": "metrics registry not attached "
                                      "(run with --metrics-out or "
                                      "--admin-port)"})
            return
        self._text(200, metrics.render(),
                   "text/plain; version=0.0.4; charset=utf-8")

    def _route_status(self) -> None:
        board = self.server.board  # type: ignore[attr-defined]
        snap = board.latest() if board is not None else None
        if snap is None:
            # the scheduler has not published a tick yet (or no board):
            # a valid, scrapeable answer — not an error
            self._json(200, {"published": False})
            return
        self._json(200, {"published": True, **snap.as_dict()})

    def _route_request(self, request_id: str) -> None:
        tracer = self.server.tracer  # type: ignore[attr-defined]
        if tracer is None:
            self._json(404, {"error": "tracer not attached "
                                      "(run with --trace)"})
            return
        track = f"req:{request_id}"
        events = [
            {"ph": ph, "name": name, "ts_us": ts, "dur_us": dur,
             "args": args}
            for (ph, trk, name, ts, dur, args) in tracer.entries()
            if trk == track
        ]
        if not events:
            self._json(404, {"error": f"no spans for request "
                                      f"{request_id!r} in the ring"})
            return
        self._json(200, {"request": request_id, "events": events})

    def _route_trace(self, query: str) -> None:
        tracer = self.server.tracer  # type: ignore[attr-defined]
        if tracer is None:
            self._json(404, {"error": "tracer not attached "
                                      "(run with --trace)"})
            return
        last: Optional[int] = None
        qs = parse_qs(query)
        if "last" in qs:
            try:
                last = max(0, int(qs["last"][0]))
            except ValueError:
                self._json(400, {"error": "?last= must be an integer"})
                return
        self._json(200, tracer.chrome_trace(last=last))

    def _route_profile(self, query: str) -> None:
        profiler = self.server.profiler  # type: ignore[attr-defined]
        if profiler is None:
            self._json(404, {"error": "profiler not attached "
                                      "(run with --xla-profile-dir)"})
            return
        qs = parse_qs(query)
        try:
            seconds = float(qs["seconds"][0]) if "seconds" in qs else 1.0
        except ValueError:
            self._json(400, {"error": "?seconds= must be a number"})
            return
        # lazy import: only reachable with a profiler attached, which
        # implies the jax-backed serving stack is loaded anyway — the
        # module itself stays stdlib-only for everything else
        from .compile_watch import ProfilerBusyError
        try:
            self._json(200, profiler.capture(seconds))
        except ValueError as e:                  # bad seconds range
            self._json(400, {"error": str(e)})
        except ProfilerBusyError as e:
            self._json(409, {"error": str(e)})
        except Exception as e:                   # profiler backend failure
            self._json(500, {"error": f"{type(e).__name__}: {e}"})


class AdminServer:
    """Owns the ThreadingHTTPServer + its daemon serve thread.  Every
    attachment is optional: endpoints whose substrate is absent
    answer 404 with a hint instead of failing to start."""

    def __init__(self, board: Optional[StatusBoard] = None,
                 metrics: Any = None, tracer: Any = None,
                 host: str = "127.0.0.1", port: int = 0,
                 profiler: Any = None):
        self._httpd = ThreadingHTTPServer((host, port), _AdminHandler)
        self._httpd.daemon_threads = True
        # the handler reads these off the server instance
        self._httpd.board = board          # type: ignore[attr-defined]
        self._httpd.metrics = metrics      # type: ignore[attr-defined]
        self._httpd.tracer = tracer        # type: ignore[attr-defined]
        self._httpd.profiler = profiler    # type: ignore[attr-defined]
        self._thread: Optional[threading.Thread] = None

    @property
    def host(self) -> str:
        return self._httpd.server_address[0]

    @property
    def port(self) -> int:
        """The bound port (resolves port 0 to the OS-assigned one)."""
        return self._httpd.server_address[1]

    def start(self) -> "AdminServer":
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="specreason-admin",
            daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self._httpd.shutdown()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        self._httpd.server_close()
