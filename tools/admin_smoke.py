"""CI smoke test for the live observability plane.

Launches a micro-testbed continuous serve run as a subprocess with the
admin server on an OS-assigned port (``--admin-port 0``), then scrapes
the endpoints while the run is live:

1. discover the bound port from the ``[admin] listening on ...`` line
2. ``/healthz`` answers "ok"
3. ``/status`` eventually publishes (``published: true``) and carries
   the scheduler snapshot keys (tick, queue_depth, pools, pressure,
   level, counts)
4. ``/metrics`` parses as Prometheus text (every non-comment line is
   ``name{labels} float``) and exposes ``specreason_`` series
5. ``/trace?last=50`` returns a Chrome trace-event doc
6. a 1-second ``/profile`` capture writes a profiler artifact dir
7. after drain (the ``--admin-linger`` window) the terminal ``/metrics``
   scrape byte-matches the crash-safe ``.prom`` artifact on disk
8. the terminal ``/status`` compile summary reports ZERO post-warmup
   recompiles — the steady-state bucketed-engine contract
   (serving/engine.py): a drain that keeps compiling after warmup is a
   recompile storm, i.e. a telemetry-visible perf regression

Exit 0 on success; raises / exits nonzero with context otherwise.
Needs only the repo + jax[cpu]; run as ``python tools/admin_smoke.py``
from the repo root.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LISTEN_RE = re.compile(r"\[admin\] listening on http://127\.0\.0\.1:(\d+)")
LINGER_S = 25.0
DEADLINE_S = 600.0


def get(port: int, path: str, timeout: float = 5.0) -> tuple:
    req = urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=timeout)
    return req.status, req.read().decode()


def parse_prometheus(text: str) -> dict:
    """Minimal Prometheus text-format parser; raises on malformed
    lines, returns {sample_name_with_labels: value}."""
    out = {}
    for ln in text.splitlines():
        if not ln or ln.startswith("#"):
            continue
        name, _, val = ln.rpartition(" ")
        if not name:
            raise AssertionError(f"unparseable metrics line: {ln!r}")
        float(val)  # must be a float
        out[name] = float(val)
    return out


def main() -> int:
    tmp = tempfile.mkdtemp(prefix="admin_smoke_")
    prom_path = os.path.join(tmp, "metrics.prom")
    trace_path = os.path.join(tmp, "trace.json")
    profile_dir = os.path.join(tmp, "xla_profile")
    cmd = [
        sys.executable, "-u", "-m", "repro.launch.serve",
        "--scheduler", "continuous", "--testbed", "micro",
        "-n", "4", "--batch", "2", "--budget", "32",
        "--spec-decode", "--gamma", "3",
        "--monitor-window", "16",
        "--admin-port", "0", "--admin-linger", str(LINGER_S),
        "--metrics-out", prom_path, "--trace", trace_path,
        "--xla-profile-dir", profile_dir,
    ]
    env = dict(os.environ,
               PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS=os.environ.get("JAX_PLATFORMS", "cpu"))
    proc = subprocess.Popen(cmd, cwd=REPO, env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    lines: list = []
    port_box: list = []
    drained = threading.Event()

    def pump() -> None:
        for ln in proc.stdout:
            lines.append(ln.rstrip("\n"))
            print(f"  | {ln.rstrip()}", flush=True)
            m = LISTEN_RE.search(ln)
            if m:
                port_box.append(int(m.group(1)))
            if ln.startswith("[metrics] "):
                drained.set()

    reader = threading.Thread(target=pump, daemon=True)
    reader.start()
    t0 = time.monotonic()
    try:
        # -- 1: discover the admin port -------------------------------
        while not port_box:
            if proc.poll() is not None:
                raise AssertionError(
                    f"serve exited rc={proc.returncode} before "
                    "announcing the admin port")
            if time.monotonic() - t0 > DEADLINE_S:
                raise AssertionError("timed out waiting for admin port")
            time.sleep(0.2)
        port = port_box[0]
        print(f"[smoke] admin port {port}", flush=True)

        # -- 2: /healthz ----------------------------------------------
        status, body = get(port, "/healthz")
        assert status == 200 and body.strip() == "ok", (status, body)
        print("[smoke] /healthz ok", flush=True)

        # -- 3: /status publishes within the run ----------------------
        snap = None
        while time.monotonic() - t0 < DEADLINE_S:
            status, body = get(port, "/status")
            assert status == 200, (status, body)
            doc = json.loads(body)
            if doc.get("published"):
                snap = doc
                break
            time.sleep(0.5)
        assert snap is not None, "/status never published a snapshot"
        for key in ("tick", "queue_depth", "active", "pools",
                    "pressure", "level", "counts"):
            assert key in snap, f"/status missing {key!r}: {snap}"
        assert isinstance(snap["pools"], dict) and snap["pools"]
        print(f"[smoke] /status ok (tick={snap['tick']} "
              f"level={snap['level']} pressure={snap['pressure']})",
              flush=True)

        # -- 4: live /metrics parses as Prometheus --------------------
        status, text = get(port, "/metrics")
        assert status == 200, status
        live = parse_prometheus(text)
        assert any(k.startswith("specreason_") for k in live), \
            f"no specreason_ series in live scrape: {sorted(live)[:5]}"
        print(f"[smoke] /metrics ok ({len(live)} live samples)",
              flush=True)

        # -- 5: /trace ring slice -------------------------------------
        status, body = get(port, "/trace?last=50")
        assert status == 200, status
        tdoc = json.loads(body)
        assert "traceEvents" in tdoc and tdoc["traceEvents"]
        print(f"[smoke] /trace ok ({len(tdoc['traceEvents'])} events)",
              flush=True)

        # -- 6: a 1s /profile capture -----------------------------------
        status, body = get(port, "/profile?seconds=1", timeout=30.0)
        assert status == 200, (status, body)
        pdoc = json.loads(body)
        assert os.path.isdir(pdoc["dir"]), pdoc
        captured = [f for _, _, fs in os.walk(pdoc["dir"]) for f in fs]
        assert captured, f"/profile wrote no artifact under {pdoc['dir']}"
        print(f"[smoke] /profile ok ({pdoc['dir']}, "
              f"{len(captured)} files)", flush=True)

        # -- 7: terminal scrape matches the artifact ------------------
        assert drained.wait(DEADLINE_S), \
            "timed out waiting for the [metrics] artifact flush"
        _, final_text = get(port, "/metrics")
        with open(prom_path) as f:
            on_disk = f.read()
        assert final_text == on_disk, (
            "terminal /metrics scrape differs from the .prom artifact "
            f"({len(final_text)} vs {len(on_disk)} bytes)")
        print("[smoke] terminal scrape == .prom artifact", flush=True)

        # -- 8: zero post-warmup recompiles in steady state -----------
        _, body = get(port, "/status")
        final = json.loads(body)
        comp = final.get("compile")
        assert comp is not None, "/status terminal snapshot lost compile"
        assert comp["post_warmup"] == 0, (
            f"recompile storm: {comp['post_warmup']} post-warmup "
            f"compiles after a steady-state drain ({comp})")
        print(f"[smoke] compile sentinel ok ({comp['programs']} programs"
              f", 0 post-warmup recompiles)", flush=True)

        rc = proc.wait(timeout=DEADLINE_S)
        assert rc == 0, f"serve exited rc={rc}"
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    print("[smoke] admin plane OK", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
