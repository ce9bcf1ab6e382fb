"""Structured tracing & metrics: Chrome-trace schema round-trip (spans
nest inside request lifetimes, analyzer validation passes), ring-buffer
bounding, the region API on the profiler's clock (nesting and parent
links in the ring and in the xplane host line, nothing recorded with
tracing off), the engines' device scopes, the committed-token counter,
structured-event back-compat rendering, Prometheus exposition, token
identity of traced vs untraced runs (greedy / sampled / spec-decode /
prefix-cache), and the sequential-path ok-status stamping regression."""

import glob
import importlib.util
import json
import os
import random
import re
from collections import Counter, defaultdict

import jax
import jax.numpy as jnp
import pytest

from repro.core.controller import SpecReason, SpecReasonConfig
from repro.core.policies import StaticThreshold
from repro.data import tasks
from repro.models.config import ModelConfig
from repro.models.model import Model
from repro.sampling.sample import SamplingParams
from repro.serving.engine import Engine
from repro.serving.kv_manager import KVBudget, KVManager
from repro.serving.scheduler import ContinuousScheduler, Scheduler
from repro.serving.telemetry import (MetricsRegistry, SchedEvent,
                                     ServingMetrics, Tracer)
from repro.serving.workload import expand_best_of_n, summarize
from repro.tokenizer import toy as tk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BASE_CFG = ModelConfig(name="tb", family="dense", n_layers=2, d_model=64,
                       n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                       vocab_size=tk.VOCAB_SIZE).validate()
SMALL_CFG = ModelConfig(name="ts", family="dense", n_layers=1, d_model=32,
                        n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64,
                        vocab_size=tk.VOCAB_SIZE).validate()


def _load_trace_report():
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(ROOT, "tools", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def engine_pair():
    bm, sm = Model(BASE_CFG), Model(SMALL_CFG)
    return (Engine(bm, bm.init(jax.random.PRNGKey(0)), max_len=256),
            Engine(sm, sm.init(jax.random.PRNGKey(1)), max_len=256))


def _mk_controller(engine_pair, temperature=0.0, spec=False, gamma=3,
                   threshold=5.0, token_budget=48, max_steps=6):
    base, small = engine_pair
    cfg = SpecReasonConfig(policy=StaticThreshold(threshold),
                           token_budget=token_budget, max_steps=max_steps,
                           use_spec_decode=spec, spec_gamma=gamma,
                           sampling=SamplingParams(temperature=temperature))
    return SpecReason(base, small, cfg)


def _mk_sched(ctrl, *, tracer=None, metrics=None, prefix_cache=True,
              max_prefill_tokens=16, on_event=None):
    kv = KVManager(BASE_CFG, SMALL_CFG, KVBudget(total_bytes=1 << 26))
    return ContinuousScheduler(ctrl, kv, max_batch=4,
                               context_capacity=128,
                               prefix_cache=prefix_cache,
                               chunked_prefill=True,
                               max_prefill_tokens=max_prefill_tokens,
                               on_event=on_event,
                               tracer=tracer, metrics=metrics)


def _workload(n_requests=3, seed=0, min_steps=8, max_steps=10):
    rng = random.Random(seed)
    reqs = [tasks.sample_task(rng, min_steps=min_steps, max_steps=max_steps)
            for _ in range(n_requests)]
    keys = [jax.random.PRNGKey(100 * seed + i) for i in range(n_requests)]
    return reqs, keys


def _drain(cs, reqs, keys):
    handles = [cs.submit(t, key=k) for t, k in zip(reqs, keys)]
    cs.drain(jax.random.PRNGKey(9))
    return handles


def _assert_identical(traced, untraced):
    for h_on, h_off in zip(traced, untraced):
        assert h_on.result is not None and h_off.result is not None
        assert h_on.result.thinking_ids == h_off.result.thinking_ids
        assert h_on.result.answer_ids == h_off.result.answer_ids


# ------------------------------------------------- structured events


def test_sched_event_is_backward_compatible_string():
    """on_event consumers that pattern-match strings keep working: the
    event IS the legacy message; structured consumers read kind/fields."""
    ev = SchedEvent("admit", "admit ab12cd34: prompt=20 cached=0 "
                    "first_chunk=16", {"request": "ab12cd34",
                                       "prompt": 20, "cached": 0})
    assert isinstance(ev, str)
    assert ev == "admit ab12cd34: prompt=20 cached=0 first_chunk=16"
    assert ev.startswith("admit ")
    assert ev.kind == "admit"
    assert ev.fields["request"] == "ab12cd34"
    assert ev.as_dict()["prompt"] == 20
    assert ev.as_dict()["message"].startswith("admit ")


def test_on_event_receives_legacy_strings_and_structure(engine_pair):
    """The scheduler's on_event sink still sees the legacy line formats
    — now as SchedEvent instances carrying kind + fields."""
    reqs, keys = _workload(n_requests=1, seed=8, min_steps=12,
                           max_steps=12)
    events = []
    ctrl = _mk_controller(engine_pair)
    _drain(_mk_sched(ctrl, on_event=events.append), reqs, keys)
    assert all(isinstance(e, SchedEvent) for e in events)
    admits = [e for e in events if e.kind == "admit"]
    assert admits and admits[0].startswith("admit ")
    assert "request" in admits[0].fields
    chunks = [e for e in events if e.kind == "prefill"]
    assert any(e.startswith("prefill ") and "/" in e for e in chunks)
    assert any("done" in e for e in chunks)


# ------------------------------------------------------------- tracer


def test_ring_buffer_bounds_a_long_run():
    tr = Tracer(buffer=16)
    t = tr.now()
    for i in range(200):
        tr.span("scheduler", f"tick", t, t + 1e-4, {"tick": i})
    assert len(tr.entries()) == 16
    assert tr.recorded == 200
    assert tr.dropped == 184
    # oldest entries were the ones overwritten
    kept = [args["tick"] for _, _, _, _, _, args in tr.entries()]
    assert kept == list(range(184, 200))
    # the export reports the loss instead of hiding it
    doc = tr.chrome_trace()
    assert doc["otherData"]["dropped"] == 184
    with pytest.raises(ValueError):
        Tracer(buffer=0)


def test_chrome_trace_schema():
    """Exporter structure: process/thread metadata for every track,
    microsecond complete events sorted by ts, instants with scope."""
    tr = Tracer()
    t = tr.now()
    tr.span("engine:base", "prefill", t, t + 0.25, {"rows": 2})
    tr.span("req:r1", "queued", t - 99.0, t)    # pre-epoch start clamps
    tr.instant("req:r1", "done", {"status": "ok"}, t=t + 0.5)
    tr.counter("pressure", {"pressure": 0.5}, t=t + 0.1)
    doc = tr.chrome_trace()
    evs = doc["traceEvents"]
    tracks = {e["tid"]: e["args"]["name"] for e in evs
              if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert set(tracks.values()) == {"engine:base", "req:r1", "counters"}
    body = [e for e in evs if e["ph"] != "M"]
    assert [e["ts"] for e in body] == sorted(e["ts"] for e in body)
    assert all(e["ts"] >= 0 for e in body)
    x = next(e for e in body if e["ph"] == "X" and e["name"] == "prefill")
    assert x["dur"] == pytest.approx(0.25e6, rel=1e-3)
    assert x["args"] == {"rows": 2}
    i = next(e for e in body if e["ph"] == "i")
    assert i["s"] == "t" and i["args"]["status"] == "ok"
    assert any(e["ph"] == "C" for e in body)


def test_trace_round_trip_spans_nest_and_cover_lifetime(engine_pair,
                                                        tmp_path):
    """The acceptance bar: a traced serving run exports a trace that (a)
    passes the analyzer's structural validation, (b) gives every
    ok-request the full queued -> prefill -> ... -> answer chain, and
    (c) nests every request-phase span inside [queued start, done]."""
    reqs, keys = _workload(seed=3)
    ctrl = _mk_controller(engine_pair, spec=True)
    tr = Tracer()
    handles = _drain(_mk_sched(ctrl, tracer=tr), reqs, keys)
    assert all(h.status == "ok" for h in handles)
    path = tmp_path / "trace.json"
    tr.export(str(path))
    doc = json.load(open(path))

    rep = _load_trace_report()
    tracks = rep.validate(doc)          # raises TraceError on malformed
    spans = defaultdict(list)
    instants = defaultdict(list)
    for ev in doc["traceEvents"]:
        track = tracks.get(ev.get("tid"), "")
        if not track.startswith("req:"):
            continue
        if ev["ph"] == "X":
            spans[track].append(ev)
        elif ev["ph"] == "i":
            instants[track].append(ev)
    assert len(spans) == len(handles)
    for track, evs in spans.items():
        names = {e["name"] for e in evs}
        assert {"queued", "prefill", "speculate", "answer"} <= names
        done = [e for e in instants[track] if e["name"] == "done"]
        assert len(done) == 1 and done[0]["args"]["status"] == "ok"
        q = next(e for e in evs if e["name"] == "queued")
        lo, hi = q["ts"], done[0]["ts"]
        for e in evs:
            assert lo <= e["ts"] and e["ts"] + e["dur"] <= hi + 1.0, \
                f"{track}: {e['name']} outside request lifetime"
    # the full analyzer also renders from it without failing
    assert rep.main([str(path)]) == 0


# ----------------------------------------------------- regions


REGION = re.compile(r"(sched|spec|base|draft)(\.[a-z_]+)+$")


def _xplane_regions(trace_dir):
    """[name, start_ns, end_ns] of every program region on the
    profiler's host threads, by start (a parent before its child)."""
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            out += [[ev.name, ev.start_ns, ev.start_ns + ev.duration_ns]
                    for ev in line.events if REGION.match(ev.name)]
    return sorted(out, key=lambda e: (e[1], -e[2]))


@pytest.fixture(scope="module")
def profiled(engine_pair, tmp_path_factory):
    """One spec-decode workload drained twice under the profiler: with an
    annotating tracer, then with tracing off."""
    reqs, keys = _workload(n_requests=2, seed=10, min_steps=4, max_steps=5)
    ctrl = _mk_controller(engine_pair, spec=True)
    # the program's regions are host annotations: no Python tracer needed
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    out = {}
    for arm, tr in (("on", Tracer(annotate=True)), ("off", None)):
        d = str(tmp_path_factory.mktemp(f"xplane_{arm}"))
        cs = _mk_sched(ctrl, tracer=tr)
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            handles = _drain(cs, reqs, keys)
        finally:
            jax.profiler.stop_trace()
        out[arm] = (tr, handles, _xplane_regions(d))
    return out


def _ancestors(evs, i):
    """Names of the events that contain ``evs[i]``, innermost first
    (``evs`` as ``_xplane_regions`` orders them)."""
    _, s, e = evs[i]
    return [n for n, s2, e2 in reversed(evs[:i]) if s2 <= s and e <= e2]


def test_regions_nest_on_the_profiler_clock(profiled):
    tr, _, evs = profiled["on"]
    ring = sorted((x for x in tr.entries() if x[0] == "X" and x[5]
                   and "parent" in x[5]), key=lambda x: (x[3], -x[4]))
    # every region reached the profiler's host line, once per ring span
    assert Counter(e[0] for e in evs) == Counter(x[2] for x in ring)
    by_id = {x[5]["id"]: x for x in ring}
    for i, x in enumerate(ring):
        # the ring's parent link names the innermost region over it on
        # the profiler's clock
        inner = _ancestors(evs, i)
        if x[5]["parent"] is None:
            assert x[2] == "sched.tick" and not inner
        else:
            parent = by_id[x[5]["parent"]]
            assert inner[0] == parent[2] and evs[i][0] == x[2]
            assert parent[3] <= x[3] <= x[3] + x[4] <= parent[3] + parent[4]
    # engines go by their role: the drafter drafts inside spec rounds
    draft = [_ancestors(evs, i) for i, e in enumerate(evs)
             if e[0] == "draft.decode"]
    assert any("spec.draft" in c for c in draft)
    chains = [_ancestors(evs, i) for i, e in enumerate(evs)
              if e[0] == "base.extend.pull"]
    want = ["base.extend", "spec.round", "sched.tick"]
    assert any([n for n in c if n in want] == want
               and {"sched.speculate", "sched.decode"} & set(c)
               for c in chains)


def test_untraced_run_records_no_region(profiled):
    _, on, _ = profiled["on"]
    tr, off, evs = profiled["off"]
    assert tr is None and evs == []
    _assert_identical(on, off)


def test_engine_programs_carry_device_scopes(engine_pair):
    """The base extend's lowered HLO names every layer of the device
    work (the scopes leave the arithmetic alone)."""
    be = _mk_sched(_mk_controller(engine_pair)).base_be
    toks = jnp.zeros((be.batch, 8), jnp.int32)
    text = be._prefill_fn(64).lower(be.params, toks, be.state).as_text(
        debug_info=True)
    paths = {part for loc in re.findall(r'loc\("([^"]*)"', text)
             for part in loc.split("/")}
    assert {"attn", "kv_write", "mlp", "lm_head"} <= paths


def test_snapshot_rows_count_committed_tokens(engine_pair):
    reqs, keys = _workload(seed=11)
    cs = _mk_sched(_mk_controller(engine_pair, spec=True))
    handles = [cs.submit(t, key=k) for t, k in zip(reqs, keys)]
    key, rows = jax.random.PRNGKey(9), 0
    while True:
        key, sub = jax.random.split(key)
        working = cs.tick(sub)
        snap = cs.snapshot()
        live = {a.req.request_id: a.state for a in cs.active}
        for row in snap.active:
            st = live[row["request"]]
            assert row["tokens"] == len(st.thinking) + len(st.answer_ids)
            rows += 1
        if not working:
            break
    assert rows
    assert snap.counts["committed_tokens"] == sum(
        len(h.result.thinking_ids) + len(h.result.answer_ids)
        for h in handles)


def test_region_records_parent_and_args():
    tr = Tracer()
    with tr.region("scheduler", "sched.tick", tick=1) as outer:
        with tr.region("engine:e", "base.feed") as inner:
            inner.args["rows"] = 2
    feed, tick = tr.entries()                  # recorded as they close
    assert tick[2] == "sched.tick" and tick[5] == {
        "tick": 1, "id": outer.id, "parent": None}
    assert feed[1:3] == ("engine:e", "base.feed")
    assert feed[5] == {"rows": 2, "id": inner.id, "parent": outer.id}
    assert tick[3] <= feed[3] and feed[3] + feed[4] <= tick[3] + tick[4]


def test_trace_report_hostdev_reads_engine_phases():
    """The engine-call view splits each call into its four phases and
    counts the call once, wherever the phases sit."""
    rep = _load_trace_report()
    tracks = {1: "engine:cb-tb", 2: "spec"}
    events = [
        {"ph": "X", "tid": 1, "name": "base.extend", "ts": 0.0,
         "dur": 100.0, "args": {"tokens": 4, "kv_bytes": 1 << 20}},
        {"ph": "X", "tid": 1, "name": "base.extend.put", "ts": 0.0,
         "dur": 10.0},
        {"ph": "X", "tid": 1, "name": "base.extend.dispatch", "ts": 10.0,
         "dur": 20.0},
        {"ph": "X", "tid": 1, "name": "base.extend.wait", "ts": 30.0,
         "dur": 60.0},
        {"ph": "X", "tid": 1, "name": "base.extend.pull", "ts": 90.0,
         "dur": 10.0},
        {"ph": "X", "tid": 2, "name": "spec.accept", "ts": 100.0,
         "dur": 50.0},
        {"ph": "X", "tid": 2, "name": "spec.accept.wait", "ts": 110.0,
         "dur": 40.0},
        {"ph": "X", "tid": 2, "name": "spec.round", "ts": 0.0,
         "dur": 150.0},
    ]
    rows = {r["op"]: r for r in rep.hostdev_data(events, tracks)["ops"]}
    assert set(rows) == {"base.extend", "spec.accept"}
    ext = rows["base.extend"]
    assert ext["calls"] == 1 and ext["tokens"] == 4 and ext["kv_mb"] == 1.0
    assert (ext["put_ms"], ext["dispatch_ms"], ext["wait_ms"],
            ext["pull_ms"]) == (0.01, 0.02, 0.06, 0.01)
    assert rows["spec.accept"]["wait_ms"] == 0.04
    # the attribution view leaves the phases out, the calls in
    attr = rep.attribution_data(events, tracks)["tracks"]
    assert [r["phase"] for r in attr["engine:cb-tb"]] == ["base.extend"]
    assert "(no engine-call" in rep.hostdev_text({"ops": []})


# ----------------------------------------------------- token identity


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_traced_run_token_identical(engine_pair, temperature):
    """Tracing must observe, never perturb: greedy and sampled runs
    produce identical tokens with the tracer on vs off."""
    reqs, keys = _workload(seed=4)
    ctrl = _mk_controller(engine_pair, temperature=temperature)
    on = _drain(_mk_sched(ctrl, tracer=Tracer()), reqs, keys)
    off = _drain(_mk_sched(ctrl), reqs, keys)
    _assert_identical(on, off)


def test_traced_spec_decode_token_identical(engine_pair):
    """Hierarchical speculation with per-round telemetry (on_round spans
    + accepted-length metrics) stays token- and stats-identical."""
    reqs, keys = _workload(seed=5)
    ctrl = _mk_controller(engine_pair, spec=True)
    on = _drain(_mk_sched(ctrl, tracer=Tracer(), metrics=ServingMetrics()),
                reqs, keys)
    off = _drain(_mk_sched(ctrl), reqs, keys)
    _assert_identical(on, off)
    for h_on, h_off in zip(on, off):
        s_on, s_off = h_on.result.spec_stats, h_off.result.spec_stats
        assert (s_on.proposed, s_on.accepted, s_on.rounds) == \
            (s_off.proposed, s_off.accepted, s_off.rounds)


def test_traced_prefix_cache_token_identical(engine_pair):
    """Best-of-N through the radix prefix cache: hits and outputs are
    unchanged by tracing."""
    rng = random.Random(7)
    task = tasks.sample_task(rng, min_steps=10, max_steps=10)
    pairs = expand_best_of_n([(task, jax.random.PRNGKey(0))], 3)
    reqs = [t for t, _ in pairs]
    keys = [k for _, k in pairs]
    ctrl = _mk_controller(engine_pair, temperature=0.8)
    on = _drain(_mk_sched(ctrl, tracer=Tracer()), reqs, keys)
    off = _drain(_mk_sched(ctrl), reqs, keys)
    _assert_identical(on, off)
    assert [h.cache_hit_tokens for h in on] == \
        [h.cache_hit_tokens for h in off]


# ------------------------------------------------------------ metrics


def test_metrics_registry_exposition():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "Requests.", labelnames=("status",))
    c.inc(status="ok")
    c.inc(2, status="shed")
    g = reg.gauge("pressure", "Pressure.")
    g.set(0.75)
    h = reg.histogram("lat_seconds", "Latency.", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    text = reg.render()
    assert '# TYPE reqs_total counter' in text
    assert 'reqs_total{status="ok"} 1' in text
    assert 'reqs_total{status="shed"} 2' in text
    assert "pressure 0.75" in text
    # histogram buckets are cumulative and +Inf counts everything
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 3' in text
    assert "lat_seconds_count 3" in text
    assert h.sum == pytest.approx(5.55)
    # re-registering returns the same metric; kind mismatch raises
    assert reg.counter("reqs_total", labelnames=("status",)) is c
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("reqs_total")


def test_serving_metrics_populated_by_run(engine_pair, tmp_path):
    reqs, keys = _workload(seed=6)
    ctrl = _mk_controller(engine_pair, spec=True)
    mt = ServingMetrics()
    handles = _drain(_mk_sched(ctrl, metrics=mt), reqs, keys)
    n_ok = sum(h.status == "ok" for h in handles)
    assert mt.requests.value(status="ok") == n_ok == len(handles)
    assert mt.ticks.value() > 0
    assert mt.ttft.count == n_ok and mt.ttft.sum > 0
    assert mt.chunk_latency.count > 0
    assert mt.spec_rounds.value() > 0
    assert mt.accepted_length.count == mt.spec_rounds.value()
    text = mt.render()
    for name in ("specreason_ttft_seconds_bucket",
                 "specreason_requests_total",
                 "specreason_kv_pool_occupancy",
                 "specreason_pressure"):
        assert name in text, name


# ------------------------------------------------ signal-safe flushing


def test_sigterm_mid_run_flushes_trace_artifact(tmp_path):
    """Satellite regression: an orchestrator SIGTERM mid-run still
    leaves a valid --trace artifact — serve.py's signal handler flushes
    the telemetry artifacts, then re-raises the default disposition so
    the exit status still reports the signal."""
    import signal
    import subprocess
    import sys
    import time

    trace = tmp_path / "sig_trace.json"
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.serve",
         "--scheduler", "continuous", "--testbed", "micro",
         "-n", "8", "--batch", "2", "--budget", "48",
         "--admin-port", "0", "--trace", str(trace)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, env=env, cwd=ROOT)
    try:
        # the admin banner prints right before the workload starts
        for line in proc.stdout:
            if "[admin] listening" in line:
                break
        else:
            pytest.fail("serve exited before the admin banner: "
                        + str(proc.wait(timeout=5)))
        time.sleep(4.0)                      # well inside the run
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
    finally:
        proc.kill()
        proc.stdout.close()
    assert rc == -signal.SIGTERM             # died BY the signal
    assert trace.exists(), "SIGTERM did not flush the trace artifact"
    doc = json.load(open(trace))
    assert "traceEvents" in doc and isinstance(doc["traceEvents"], list)


# -------------------------------------- sequential status regression


def test_sequential_path_stamps_ok_status(engine_pair):
    """Regression (ISSUE 7 satellite): sequentially-served requests
    finish with status 'ok', and summarize counts them WITHOUT the old
    result-but-still-queued workaround."""
    base, small = engine_pair
    ctrl = _mk_controller(engine_pair, max_steps=2, token_budget=16)
    kv = KVManager(BASE_CFG, SMALL_CFG, KVBudget(total_bytes=1 << 26))
    sched = Scheduler(ctrl, kv, context_capacity=256)
    rng = random.Random(0)
    for _ in range(3):
        sched.submit(tasks.sample_task(rng))
    done = sched.drain(jax.random.PRNGKey(2))
    assert [d.status for d in done] == ["ok"] * 3
    stats = summarize(done, wall_s=1.0)
    assert stats["requests"] == 3
