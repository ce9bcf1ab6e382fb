"""Compile/device-plane observability: the recompilation sentinel
(signature hashing, per-op compile counting, warmup window, recompile
monitor feed, steady-state zero-recompile drain), the device-memory
watch (host accounting + None-guarded allocator stats), the on-demand
profiler capture latch, and token identity of full-plane-on vs
plane-off serving in greedy / sampled / spec-decode modes."""

import random
import threading

import jax
import jax.numpy as jnp
import pytest

from repro.core.controller import SpecReason, SpecReasonConfig
from repro.core.policies import StaticThreshold
from repro.data import tasks
from repro.models.config import ModelConfig
from repro.models.model import Model
from repro.sampling.sample import SamplingParams
from repro.serving.compile_watch import (CompileWatch, MemoryWatch,
                                         ProfilerBusyError,
                                         ProfilerCapture, call_signature)
from repro.serving.engine import Engine
from repro.serving.kv_manager import KVBudget, KVManager
from repro.serving.monitors import MonitorConfig, Monitors
from repro.serving.scheduler import ContinuousScheduler
from repro.serving.telemetry import ServingMetrics, Tracer
from repro.tokenizer import toy as tk

BASE_CFG = ModelConfig(name="tb", family="dense", n_layers=2, d_model=64,
                       n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                       vocab_size=tk.VOCAB_SIZE).validate()
SMALL_CFG = ModelConfig(name="ts", family="dense", n_layers=1, d_model=32,
                        n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64,
                        vocab_size=tk.VOCAB_SIZE).validate()


@pytest.fixture(scope="module")
def engine_pair():
    bm, sm = Model(BASE_CFG), Model(SMALL_CFG)
    return (Engine(bm, bm.init(jax.random.PRNGKey(0)), max_len=256),
            Engine(sm, sm.init(jax.random.PRNGKey(1)), max_len=256))


def _mk_controller(engine_pair, temperature=0.0, spec=False):
    base, small = engine_pair
    cfg = SpecReasonConfig(policy=StaticThreshold(5.0), token_budget=48,
                           max_steps=6, use_spec_decode=spec, spec_gamma=3,
                           sampling=SamplingParams(temperature=temperature))
    return SpecReason(base, small, cfg)


def _mk_sched(ctrl, *, tracer=None, metrics=None, monitors=None,
              compile_watch=None, memory_watch=None, prefix_cache=True):
    kv = KVManager(BASE_CFG, SMALL_CFG, KVBudget(total_bytes=1 << 26))
    return ContinuousScheduler(ctrl, kv, max_batch=4,
                               context_capacity=128,
                               prefix_cache=prefix_cache,
                               chunked_prefill=True,
                               max_prefill_tokens=16,
                               tracer=tracer, metrics=metrics,
                               monitors=monitors,
                               compile_watch=compile_watch,
                               memory_watch=memory_watch)


def _workload(n_requests=3, seed=0):
    rng = random.Random(seed)
    reqs = [tasks.sample_task(rng, min_steps=8, max_steps=10)
            for _ in range(n_requests)]
    keys = [jax.random.PRNGKey(100 * seed + i) for i in range(n_requests)]
    return reqs, keys


def _drain(cs, reqs, keys):
    handles = [cs.submit(t, key=k) for t, k in zip(reqs, keys)]
    cs.drain(jax.random.PRNGKey(9))
    return handles


# ----------------------------------------------------------- signatures


def test_call_signature_shapes_dtypes_and_statics():
    a = jnp.ones((4, 8))
    b = jnp.ones((4, 8), dtype=jnp.int32)
    sig = call_signature((a, 3, "greedy"))
    assert sig == (((4, 8), "float32"), ("static", "3"),
                   ("static", "'greedy'"))
    # shape change, dtype change, and static change each re-sign
    assert call_signature((a,)) != call_signature((jnp.ones((4, 16)),))
    assert call_signature((a,)) != call_signature((b,))
    assert call_signature((a, 1)) != call_signature((a, 2))
    # nested pytrees flatten to the same leaves
    assert call_signature(({"x": a, "y": 1},)) == call_signature(((a, 1),))


def test_sentinel_counts_distinct_signatures_once():
    cw = CompileWatch(warmup_ticks=2)
    fn = jax.jit(lambda x: x * 2 + 1)
    for _ in range(3):
        cost = cw.observe("e", "op", fn, (jnp.ones((4, 8)),))
    assert cw.as_dict() == {"programs": 1, "compiles": 1,
                            "post_warmup": 0}
    # the cost dict is returned on every call, cached after the first
    assert cost is not None and cost["flops"] and cost["bytes"]
    cw.observe("e", "op", fn, (jnp.ones((4, 16)),))   # new length bucket
    assert cw.as_dict()["programs"] == 2
    assert cw.as_dict()["compiles"] == 2
    assert len(cw.signatures("e", "op")) == 2


def test_sentinel_warmup_window_and_monitor_feed():
    mon = Monitors(MonitorConfig(window=4, min_samples=1))
    cw = CompileWatch(warmup_ticks=2, monitors=mon)
    fn = jax.jit(lambda x: x + 1)
    cw.begin_tick(1)
    cw.observe("e", "op", fn, (jnp.ones((2,)),))      # warmup compile
    assert cw.post_warmup_compiles == 0
    cw.begin_tick(5)                                  # past the window
    cw.observe("e", "op", fn, (jnp.ones((3,)),))      # recompile!
    assert cw.post_warmup_compiles == 1
    assert mon.recompile._this_tick == 1
    mon.on_tick(5)
    assert mon.as_dict()["recompile"]["value"] == pytest.approx(1.0)


def test_sentinel_never_raises_on_unjitted_fn():
    cw = CompileWatch()
    # a plain python callable has no .lower — the twin compile fails,
    # counting still works and the dispatch path never sees the error
    cost = cw.observe("e", "op", lambda x: x, (jnp.ones((2,)),))
    assert cost == {"flops": None, "bytes": None}
    assert cw.as_dict() == {"programs": 1, "compiles": 1,
                            "post_warmup": 0}


def test_sentinel_metrics_and_trace_spans():
    tr, mt = Tracer(), ServingMetrics()
    cw = CompileWatch(tracer=tr, metrics=mt, warmup_ticks=0)
    fn = jax.jit(lambda x: x * x)
    cw.begin_tick(3)
    cw.observe("eng", "decode", fn, (jnp.ones((2, 4)),))
    assert mt.compiles.labels(engine="eng", op="decode").value() == 1
    assert mt.post_warmup_compiles.labels(engine="eng",
                                          op="decode").value() == 1
    spans = [e for e in tr.entries() if e[1] == "compile"]
    assert len(spans) == 1
    _, _, name, _, _, args = spans[0]
    assert name == "eng.decode"
    assert args["post_warmup"] is True and args["tick"] == 3
    assert args["flops"] is not None and "signature" in args
    text = mt.render()
    assert 'specreason_compiles_total{engine="eng",op="decode"} 1' in text


# -------------------------------------------------- scheduler steady state


def test_steady_state_drain_has_zero_post_warmup_recompiles(engine_pair):
    """The bucketed-engine contract (serving/engine.py): after a first
    drain has populated every (shape, dtype) signature the workload
    touches, an identical second drain compiles NOTHING — the sentinel
    reports zero post-warmup recompiles.  (Prefix cache off: a cache
    seeded by the first drain changes the second drain's prefill/seed
    shapes, which is a real signature change, not noise.)"""
    reqs, keys = _workload(seed=11)
    ctrl = _mk_controller(engine_pair, spec=True)
    cw = CompileWatch(warmup_ticks=10 ** 9)       # first drain = warmup
    cs = _mk_sched(ctrl, compile_watch=cw, prefix_cache=False)
    _drain(cs, reqs, keys)
    warm = cw.as_dict()
    assert warm["programs"] > 0 and warm["compiles"] == warm["programs"]
    assert cw.tick == cs.ticks                    # begin_tick is wired
    # steady state: everything after this point counts as post-warmup
    cw.warmup_ticks = cs.ticks
    _drain(cs, reqs, keys)
    after = cw.as_dict()
    assert after["post_warmup"] == 0, \
        f"recompile storm in steady state: {after}"
    assert after["compiles"] == warm["compiles"]
    # the spec-decode acceptance program is among the watched ops
    assert cw.signatures(cs.base_be.name, "accept_prog")
    assert cw.signatures(cs.base_be.name, "prefill")


# ------------------------------------------------------- token identity


@pytest.mark.parametrize("temperature,spec", [(0.0, False), (0.8, False),
                                              (0.0, True)])
def test_full_plane_token_identical(engine_pair, temperature, spec):
    """The whole compile/device plane — tracer + metrics + monitors +
    sentinel + memory watch — observes, never perturbs: greedy, sampled
    and spec-decode runs produce identical tokens plane-on vs off."""
    reqs, keys = _workload(seed=13)
    ctrl = _mk_controller(engine_pair, temperature=temperature, spec=spec)
    tr, mt = Tracer(), ServingMetrics()
    mon = Monitors(MonitorConfig(window=8, min_samples=1))
    on = _drain(_mk_sched(ctrl, tracer=tr, metrics=mt, monitors=mon,
                          compile_watch=CompileWatch(tracer=tr, metrics=mt,
                                                     monitors=mon),
                          memory_watch=MemoryWatch(metrics=mt)),
                reqs, keys)
    off = _drain(_mk_sched(ctrl), reqs, keys)
    for h_on, h_off in zip(on, off):
        assert h_on.result is not None and h_off.result is not None
        assert h_on.result.thinking_ids == h_off.result.thinking_ids
        assert h_on.result.answer_ids == h_off.result.answer_ids


# ------------------------------------------------------------- memory


def test_memory_watch_accounting_and_cpu_guard():
    mt = ServingMetrics()
    mw = MemoryWatch(metrics=mt)
    mw.note_model(1000)
    mw.note_model(500)
    mw.note_pool("base", 4096)
    mw.note_pool("small", 1024)
    snap = mw.sample()
    assert snap["model_bytes"] == 1500
    assert snap["accounted_bytes"] == 1500 + 4096 + 1024
    assert snap["peak_bytes"] >= snap["accounted_bytes"]
    if snap["backend"] == "cpu":
        # the None-guard: CPU backends keep no allocator stats
        assert snap["device_bytes_in_use"] is None
    assert mt.memory_bytes.labels(kind="model").value() == 1500.0
    assert mt.memory_bytes.labels(kind="kv_pool_base").value() == 4096.0
    assert mt.memory_peak_bytes.value() == float(snap["peak_bytes"])


def test_memory_watch_no_device_never_raises():
    mw = MemoryWatch(device=None)
    mw.note_model(10)
    snap = mw.sample()
    assert snap["accounted_bytes"] == 10
    assert snap["device_bytes_in_use"] is None


def test_scheduler_wires_memory_watch_and_snapshot(engine_pair):
    reqs, keys = _workload(n_requests=2, seed=14)
    ctrl = _mk_controller(engine_pair)
    mw = MemoryWatch()
    cw = CompileWatch()
    cs = _mk_sched(ctrl, compile_watch=cw, memory_watch=mw)
    # static accounting lands at construction: params + dense state of
    # both engines, one paged pool per engine
    assert mw.model_bytes > 0
    assert set(mw.pool_bytes) == {"base", "small"}
    assert all(v > 0 for v in mw.pool_bytes.values())
    _drain(cs, reqs, keys)
    assert cs.last_memory is not None
    assert cs.last_memory["accounted_bytes"] == \
        mw.model_bytes + sum(mw.pool_bytes.values())
    snap = cs.snapshot()
    assert snap.memory["accounted_bytes"] == \
        cs.last_memory["accounted_bytes"]
    assert snap.compile == cw.as_dict()
    assert snap.as_dict()["memory"] is not None


# ------------------------------------------------------------ profiler


def test_profiler_capture_roundtrip(tmp_path):
    import os
    pc = ProfilerCapture(str(tmp_path))
    out = pc.capture(0.05)
    assert out["capture"] == 0 and pc.captures == 1
    assert os.path.isdir(out["dir"])
    # the capture wrote a trace artifact under the run dir
    files = [f for _, _, fs in os.walk(out["dir"]) for f in fs]
    assert files, "profiler capture produced no artifact"
    out2 = pc.capture(0.05)
    assert out2["capture"] == 1 and out2["dir"] != out["dir"]


def test_profiler_capture_validates_and_latches(tmp_path):
    pc = ProfilerCapture(str(tmp_path))
    for bad in (0.0, -1.0, pc.MAX_SECONDS + 1):
        with pytest.raises(ValueError):
            pc.capture(bad)
    held = pc._lock
    assert held.acquire(blocking=False)
    try:
        with pytest.raises(ProfilerBusyError):
            pc.capture(0.05)
    finally:
        held.release()


def test_profiler_concurrent_second_capture_409s(tmp_path):
    pc = ProfilerCapture(str(tmp_path))
    errs = []

    def second():
        try:
            pc.capture(0.05)
        except ProfilerBusyError as e:
            errs.append(e)

    t = threading.Thread(target=second)
    # hold the latch through a real capture while the second fires
    assert pc._lock.acquire(blocking=False)
    t.start()
    t.join(timeout=5.0)
    pc._lock.release()
    assert len(errs) == 1
