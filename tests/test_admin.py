"""Admin plane: in-process HTTP endpoint tests against a real drained
scheduler (no subprocess — tools/admin_smoke.py covers the live-run
path in CI).  Exercises all six routes (including the latched
/profile), the 404 hints for absent substrates, ?last= ring
slicing, the StatusBoard publish/latest handoff, and the crash-safe
atomic artifact write."""

import json
import os
import random
import urllib.error
import urllib.request

import jax
import pytest

from repro.core.controller import SpecReason, SpecReasonConfig
from repro.core.policies import StaticThreshold
from repro.data import tasks
from repro.models.config import ModelConfig
from repro.models.model import Model
from repro.sampling.sample import SamplingParams
from repro.serving.engine import Engine
from repro.serving.kv_manager import KVBudget, KVManager
from repro.serving.admin import AdminServer, SchedulerSnapshot, StatusBoard
from repro.serving.compile_watch import CompileWatch, ProfilerCapture
from repro.serving.monitors import MonitorConfig, Monitors
from repro.serving.scheduler import ContinuousScheduler
from repro.serving.telemetry import ServingMetrics, Tracer, atomic_write
from repro.tokenizer import toy as tk

BASE_CFG = ModelConfig(name="tb", family="dense", n_layers=2, d_model=64,
                       n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                       vocab_size=tk.VOCAB_SIZE).validate()
SMALL_CFG = ModelConfig(name="ts", family="dense", n_layers=1, d_model=32,
                        n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64,
                        vocab_size=tk.VOCAB_SIZE).validate()


def _get(port, path, timeout=5.0):
    """GET -> (status, body_text); 4xx bodies are returned, not raised."""
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=timeout) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One drained scheduler with the full observability substrate and a
    live AdminServer on an OS-assigned port."""
    bm, sm = Model(BASE_CFG), Model(SMALL_CFG)
    base = Engine(bm, bm.init(jax.random.PRNGKey(0)), max_len=256)
    small = Engine(sm, sm.init(jax.random.PRNGKey(1)), max_len=256)
    ctrl = SpecReason(base, small, SpecReasonConfig(
        policy=StaticThreshold(5.0), token_budget=48, max_steps=6,
        use_spec_decode=True, spec_gamma=3,
        sampling=SamplingParams(temperature=0.0)))
    tracer = Tracer(buffer=4096)
    metrics = ServingMetrics()
    board = StatusBoard()
    mon = Monitors(MonitorConfig(window=8, min_samples=1))
    watch = CompileWatch(tracer=tracer, metrics=metrics)
    kv = KVManager(BASE_CFG, SMALL_CFG, KVBudget(total_bytes=1 << 26))
    cs = ContinuousScheduler(ctrl, kv, max_batch=4, context_capacity=128,
                             chunked_prefill=True, max_prefill_tokens=16,
                             tracer=tracer, metrics=metrics,
                             monitors=mon, status_board=board,
                             compile_watch=watch)
    rng = random.Random(5)
    reqs = [tasks.sample_task(rng, min_steps=8, max_steps=10)
            for _ in range(2)]
    handles = [cs.submit(t, key=jax.random.PRNGKey(50 + i))
               for i, t in enumerate(reqs)]
    cs.drain(jax.random.PRNGKey(9))
    profiler = ProfilerCapture(str(tmp_path_factory.mktemp("xla_prof")))
    admin = AdminServer(board=board, metrics=metrics.registry,
                        tracer=tracer, profiler=profiler).start()
    yield {"admin": admin, "cs": cs, "tracer": tracer,
           "metrics": metrics, "handles": handles, "watch": watch,
           "profiler": profiler}
    admin.stop()


def test_healthz(served):
    status, body = _get(served["admin"].port, "/healthz")
    assert status == 200 and body.strip() == "ok"


def test_status_reflects_scheduler_snapshot(served):
    cs = served["cs"]
    status, body = _get(served["admin"].port, "/status")
    assert status == 200
    doc = json.loads(body)
    assert doc["published"] is True
    assert doc["tick"] == cs.ticks           # last published tick
    assert doc["queue_depth"] == 0 and doc["active"] == []
    assert doc["level"] == cs.res.level
    assert doc["pools"] and all(0.0 <= v <= 1.0
                                for v in doc["pools"].values())
    assert doc["counts"]["done"] == 2
    assert "token_accept" in doc["monitors"]


def test_status_unpublished_board_is_not_an_error():
    admin = AdminServer(board=StatusBoard()).start()
    try:
        status, body = _get(admin.port, "/status")
        assert status == 200
        assert json.loads(body) == {"published": False}
    finally:
        admin.stop()


def test_board_latest_returns_most_recent_publish():
    board = StatusBoard()
    assert board.latest() is None
    for t in (1, 2):
        board.publish(SchedulerSnapshot(
            tick=t, time_s=0.0, queue_depth=0, active=[], pools={},
            pressure=0.0, level=0, counts={}, monitors=None))
    assert board.latest().tick == 2


def test_metrics_is_prometheus_text(served):
    status, text = _get(served["admin"].port, "/metrics")
    assert status == 200
    names = set()
    for ln in text.splitlines():
        if not ln or ln.startswith("#"):
            continue
        name, _, val = ln.rpartition(" ")
        float(val)                            # every sample parses
        names.add(name.split("{")[0])
    assert "specreason_requests_total" in names
    assert "specreason_ticks_total" in names
    # the live scrape is byte-identical to a direct render
    assert text == served["metrics"].render()


def test_request_timeline_roundtrip(served):
    rid = served["handles"][0].request_id
    status, body = _get(served["admin"].port, f"/requests/{rid}")
    assert status == 200
    doc = json.loads(body)
    assert doc["request"] == rid
    names = {e["name"] for e in doc["events"]}
    assert {"queued", "prefill", "answer"} <= names
    assert all(e["dur_us"] >= 0 for e in doc["events"]
               if e["ph"] == "X")


def test_request_unknown_id_404(served):
    status, body = _get(served["admin"].port, "/requests/not-a-request")
    assert status == 404 and "no spans" in json.loads(body)["error"]


def test_trace_full_and_sliced(served):
    port = served["admin"].port
    status, body = _get(port, "/trace")
    assert status == 200
    full = json.loads(body)["traceEvents"]
    assert full
    status, body = _get(port, "/trace?last=5")
    sliced = json.loads(body)["traceEvents"]
    # metadata (thread_name) rows ride along with the 5 ring events
    data_rows = [e for e in sliced if e.get("ph") != "M"]
    assert len(data_rows) == 5
    # the slice is the 5 most recent RING entries (recording order);
    # the render re-sorts by ts, so compare as (name, ts) sets
    expect = {(name, round(ts * 1e6, 3))
              for (_, _, name, ts, _, _) in served["tracer"].entries()[-5:]}
    assert {(e["name"], e["ts"]) for e in data_rows} == expect
    status, body = _get(port, "/trace?last=nope")
    assert status == 400


def test_status_carries_compile_summary(served):
    status, body = _get(served["admin"].port, "/status")
    doc = json.loads(body)
    assert doc["compile"] == served["watch"].as_dict()
    assert doc["compile"]["programs"] > 0


def test_profile_endpoint_captures_and_latches(served, tmp_path):
    import os
    port = served["admin"].port
    # generous HTTP timeout: profiler start/stop walks every device of
    # the forced 8-device CPU platform (tests/conftest.py) and can take
    # well over the default 5s on a loaded suite run
    status, body = _get(port, "/profile?seconds=0.05", timeout=60.0)
    assert status == 200
    doc = json.loads(body)
    assert os.path.isdir(doc["dir"]) and doc["capture"] == 0
    status, body = _get(port, "/profile?seconds=nope")
    assert status == 400
    status, body = _get(port, "/profile?seconds=0")
    assert status == 400 and "seconds" in json.loads(body)["error"]
    # a held latch maps to 409, not a hang
    assert served["profiler"]._lock.acquire(blocking=False)
    try:
        status, body = _get(port, "/profile?seconds=0.05", timeout=60.0)
        assert status == 409
    finally:
        served["profiler"]._lock.release()


def test_unknown_route_lists_routes(served):
    status, body = _get(served["admin"].port, "/nope")
    assert status == 404
    routes = json.loads(body)["routes"]
    assert "/status" in routes
    assert "/profile?seconds=S" in routes


def test_missing_substrates_404_with_hint():
    admin = AdminServer().start()            # nothing attached
    try:
        for path in ("/metrics", "/trace", "/requests/x", "/profile"):
            status, body = _get(admin.port, path)
            assert status == 404, path
            assert "error" in json.loads(body), path
        status, body = _get(admin.port, "/status")
        assert status == 200                 # board absent != error
        assert json.loads(body) == {"published": False}
    finally:
        admin.stop()


def test_atomic_write_leaves_no_tmp(tmp_path):
    path = str(tmp_path / "out.prom")
    atomic_write(path, "specreason_x 1\n")
    atomic_write(path, "specreason_x 2\n")   # overwrite is atomic too
    with open(path) as f:
        assert f.read() == "specreason_x 2\n"
    assert os.listdir(tmp_path) == ["out.prom"]


def test_tracer_chrome_trace_last_slicing():
    tr = Tracer(buffer=64)
    for i in range(10):
        tr.span("scheduler", f"tick", float(i), float(i) + 0.5,
                {"n": i})
    full = [e for e in tr.chrome_trace()["traceEvents"]
            if e.get("ph") != "M"]
    assert len(full) == 10
    tail = [e for e in tr.chrome_trace(last=3)["traceEvents"]
            if e.get("ph") != "M"]
    assert tail == full[-3:]
    assert [e for e in tr.chrome_trace(last=0)["traceEvents"]
            if e.get("ph") != "M"] == []


def test_status_mesh_section_for_sharded_run():
    """A tp_size=2 scheduler publishes a ``mesh`` section in /status:
    mesh axes, tp degree, device list and per-device memory watermarks
    (MemoryWatch.per_device — accounted-bytes fallback on CPU, where the
    allocator exposes no stats)."""
    from repro.serving.compile_watch import MemoryWatch

    bm, sm = Model(BASE_CFG), Model(SMALL_CFG)
    base = Engine(bm, bm.init(jax.random.PRNGKey(0)), max_len=256)
    small = Engine(sm, sm.init(jax.random.PRNGKey(1)), max_len=256)
    ctrl = SpecReason(base, small, SpecReasonConfig(
        policy=StaticThreshold(5.0), token_budget=16, max_steps=2,
        sampling=SamplingParams(temperature=0.0)))
    kv = KVManager(BASE_CFG, SMALL_CFG, KVBudget(total_bytes=1 << 26))
    board = StatusBoard()
    cs = ContinuousScheduler(ctrl, kv, max_batch=2, context_capacity=128,
                             status_board=board,
                             memory_watch=MemoryWatch(), tp_size=2)
    cs.submit(tasks.sample_task(random.Random(0)),
              key=jax.random.PRNGKey(0))
    cs.drain(jax.random.PRNGKey(1))
    admin = AdminServer(board=board).start()
    try:
        status, body = _get(admin.port, "/status")
        assert status == 200
        doc = json.loads(body)
        mesh = doc["mesh"]
        assert mesh is not None
        assert mesh["tp_size"] == 2
        assert mesh["axes"] == {"model": 2}
        assert len(mesh["devices"]) == 2
        marks = mesh["watermarks"]
        assert len(marks) == 2
        for m in marks:
            assert m["platform"] == "cpu"
            assert m["peak_bytes"] >= 0      # accounted fallback on CPU
    finally:
        admin.stop()
