"""The reduction to the program's layers (spans.py) on a hand-made trace
in the layout of a TPU profile: idle gaps go to the innermost program
region, never to a Python frame; device time goes to the scope of leaf
ops only; the HLO op names come from a real trace's programs.  Beside
it: every new reader is silent without a trace or counter, and
``xplane.reduce`` reads the hand trace exactly as before."""

import json
import sys
import tempfile
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE)]

import harness  # noqa: E402
import spans  # noqa: E402
import xplane  # noqa: E402
from test_chipbench_xplane import MS, hand_trace  # noqa: E402

LOOP = "jit(extend)/while/body"


def region_trace():
    """100 ms window, one tick: device busy [0, 10), [20, 40), [60, 70)
    and [90, 95); gaps [10, 20), [40, 60), [70, 90), [95, 100)."""
    return {
        "/host:CPU": {"python": [
            ["chipbench.window", 0, 100 * MS],
            ["sched.tick", 0, 90 * MS],
            ["sched.decode", 1 * MS, 84 * MS],
            ["spec.round", 2 * MS, 83 * MS],
            ["base.extend", 3 * MS, 42 * MS],
            ["base.extend.put", 4 * MS, 13 * MS],
            ["$batch_engine.py:1 extend_rows", 8 * MS, 30 * MS],  # frame
            ["base.extend.wait", 20 * MS, 25 * MS],
            ["DevicePutWithSharding", 46 * MS, 28 * MS],          # runtime
            ["spec.stage", 45 * MS, 30 * MS],
        ]},
        "/device:TPU:0": {
            "XLA Ops": [
                ["%while.2 = (...) while(...)", 0, 10 * MS, ""],
                ["%fusion.1 = bf16[] fusion()", 0, 4 * MS,
                 f"{LOOP}/attn/dot"],
                ["%fusion.2 = bf16[] fusion()", 4 * MS, 2 * MS,
                 f"{LOOP}/kv_write/select"],
                ["%fusion.3 = bf16[] fusion()", 6 * MS, 4 * MS,
                 f"{LOOP}/mlp/dot"],
                ["%copy.79 = bf16[] copy()", 20 * MS, 20 * MS,
                 "jit(extend)/kv_copy/dynamic_update_slice"],
                ["%fusion.4 = bf16[] fusion()", 60 * MS, 10 * MS,
                 "jit(extend)/lm_head/dot"],
                ["%copy.1 = s32[] copy()", 90 * MS, 5 * MS, ""],
            ],
            "XLA Modules": [["jit_extend(1)", 0, 95 * MS]],
        },
    }


def test_idle_goes_to_the_innermost_program_region():
    red = spans.reduce(region_trace())
    idle = dict(red["idle_by_region"])
    # mid 15: put (the Python frame over it is ignored); mid 50:
    # spec.stage (not the runtime's device put); mid 80: spec.round;
    # mid 97.5: outside every region
    assert idle == pytest.approx({"base.extend.put": 0.010,
                                  "spec.stage": 0.020, "spec.round": 0.020,
                                  spans.OUTSIDE: 0.005})
    total = 1e-3 * (100 - 10 - 20 - 10 - 5)
    assert sum(idle.values()) == pytest.approx(total)
    assert sum(idle.values()) == pytest.approx(
        xplane.reduce(_three(region_trace()))["window_s"]
        - xplane.reduce(_three(region_trace()))["busy_s"])


def test_device_time_goes_to_the_scope_of_leaf_ops():
    dev = dict(spans.reduce(region_trace())["device_by_scope"])
    # the loop's 10 ms is its body's, counted once
    assert dev == pytest.approx({"attn": 0.004, "kv_write": 0.002,
                                 "mlp": 0.004, "kv_copy": 0.020,
                                 "lm_head": 0.010, spans.OTHER: 0.005})


def test_a_program_without_regions_or_scopes_reduces_to_none():
    tr = region_trace()
    tr["/host:CPU"]["python"] = [
        ev for ev in tr["/host:CPU"]["python"]
        if not spans.REGION.match(ev[0])]
    tr["/device:TPU:0"]["XLA Ops"] = [
        ev[:3] + [""] for ev in tr["/device:TPU:0"]["XLA Ops"]]
    assert spans.reduce(tr) == {"idle_by_region": None,
                                "device_by_scope": None}


def test_idle_classes():
    assert [spans.idle_class(n) for n in (
        "draft.decode.wait", "spec.accept.pull", "base.feed.put",
        "base.extend.dispatch", "spec.stage", "sched.admit", "base.feed",
        spans.OUTSIDE)] == ["sync", "sync", "stage", "stage", "stage",
                            "sched", "sched", None]


def test_op_names_come_from_the_traces_programs():
    """A CPU trace carries the compiled programs' HloProtos too: the
    wire reader finds each instruction's op_name and its scope."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("attn"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("mlp"):
            return jnp.sin(y) @ y

    g = jax.jit(f)
    x = jnp.ones((32, 32))
    g(x).block_until_ready()
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    g(x).block_until_ready()
    jax.profiler.stop_trace()
    with open(xplane.find(d), "rb") as fh:
        raw = fh.read()
    protos = spans._hlo_protos(raw)
    prog = next(p for p in protos if p.startswith("jit_f("))
    scopes = {spans.scope_of(op)
              for op in spans._op_names(raw, protos[prog]).values()}
    assert {"attn", "mlp"} <= scopes


def _three(pl):
    return {p: {ln: [e[:3] for e in evs] for ln, evs in lines.items()}
            for p, lines in pl.items()}


def test_xplane_reduce_reads_the_hand_trace_as_before():
    assert json.dumps(xplane.reduce(hand_trace())) == (
        '{"window_s": 0.1, "busy_s": 0.04, "programs": 2, "device_ops": '
        '[["dot.3", 0.02], ["fusion.1", 0.015], ["fusion.2", 0.01]], '
        '"idle_gaps": [["chipbench.tick", 0.060000000000000005]]}')


READERS = ["idle_ms_per_tick.sync", "idle_ms_per_tick.stage",
           "idle_ms_per_tick.sched", "dev_ms_per_tick.attn",
           "dev_ms_per_tick.kv_write", "dev_ms_per_tick.kv_copy",
           "dev_ms_per_tick.mlp", "tok_per_tick"]


def fake_rec(trace=None, counts=None):
    snap = types.SimpleNamespace(active=[], counts=counts or {})
    return types.SimpleNamespace(trace=trace, ticks=[(0, 1), (1, 2)],
                                 snap_start=snap, snap_end=snap)


@pytest.mark.parametrize("name", READERS)
def test_new_readers_are_silent_without_their_source(name):
    """Without a trace, or against a program that has no regions,
    scopes or counter (the parent's), a new reader returns None."""
    read = harness.reader(name)
    assert read(fake_rec()) is None
    old = xplane.reduce(hand_trace())
    assert read(fake_rec(trace=old)) is None


def test_new_readers_read_per_tick():
    red = spans.reduce(region_trace())
    rec = fake_rec(trace=dict(xplane.reduce(_three(region_trace())), **red))
    rec.snap_start = types.SimpleNamespace(counts={"committed_tokens": 10})
    rec.snap_end = types.SimpleNamespace(counts={"committed_tokens": 70})
    got = {n: harness.reader(n)(rec) for n in READERS}
    assert got == pytest.approx({
        "idle_ms_per_tick.sync": 0.0, "idle_ms_per_tick.stage": 15.0,
        "idle_ms_per_tick.sched": 10.0, "dev_ms_per_tick.attn": 2.0,
        "dev_ms_per_tick.kv_write": 1.0, "dev_ms_per_tick.kv_copy": 10.0,
        "dev_ms_per_tick.mlp": 2.0, "tok_per_tick": 30.0})
