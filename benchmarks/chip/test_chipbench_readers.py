"""The arithmetic of the end-to-end and per-layer readers on hand-made
records: tails with unfinished and failed requests, the partial-step
count of committed tokens, and mfu's FLOPs against a hand count."""

import math
import sys
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import arrivals  # noqa: E402
import harness  # noqa: E402
from architectures import dense  # noqa: E402


def handle(rid, status=None, first=None, finished=None, admitted=None,
           out=0, steps=1, hit=0, prompt=0):
    status = status or ("ok" if finished is not None else "running")
    res = None
    if finished is not None:
        res = types.SimpleNamespace(thinking_ids=[1] * (out - 2),
                                    answer_ids=[1, 2], steps=[0] * steps)
    return types.SimpleNamespace(request_id=rid, status=status,
                                 first_token_at=first, finished_at=finished,
                                 admitted_at=admitted, result=res,
                                 cache_hit_tokens=hit, prompt_tokens=prompt)


def req(due, h, ops=4):
    spec = arrivals.Spec(0, 0, 0, due, 1, [("plus", 1)] * ops)
    return harness.Req(spec, due, submitted=due + 0.001, handle=h)


def rec(reqs, start_active=(), end_active=(), t=(10.0, 20.0)):
    cell = harness.load_cell("phi3-think1k-backlog")
    snap = lambda act: types.SimpleNamespace(  # noqa: E731
        active=[{"request": r, "steps": s} for r, s in act])
    return harness.Rec(cell=cell, seed=1, reqs=reqs, t_start=t[0],
                       t_end=t[1], ticks=[(10, 15), (15, 20)],
                       snap_start=snap(start_active),
                       snap_end=snap(end_active), setup_s=1.0,
                       compiles_in_window=0,
                       device_kind="TPU v5 lite")


def test_nearest_rank_keeps_infinity():
    assert harness.nearest_rank([1, 2, 3, 4], 0.5) == 2
    assert harness.nearest_rank(list(range(1, 11)), 0.9) == 9
    assert harness.nearest_rank([1, 2, 3, 4, 5, 6, 7, 8, 9, math.inf],
                                0.95) == math.inf


def test_ttft_counts_waiting_and_failed_requests():
    r = rec([req(11.0, handle("a", first=11.5)),       # 500 ms
             req(12.0, handle("b")),                   # waiting: 8000 ms
             req(13.0, handle("c", status="failed")),  # infinite
             req(9.0, handle("d", first=14.0))])       # due before window
    vals = [harness.reader("ttft_p90_ms")(r)]
    assert vals == [1e12]
    r.reqs[2].handle.status = "running"                # now waiting 7 s
    assert harness.reader("ttft_p90_ms")(r) == pytest.approx(8000.0)
    # nobody was admitted: a has waited 9 s since its due time
    assert harness.reader("queue_wait_p90_ms")(r) == pytest.approx(9000.0)
    assert harness.reader("gen_lag_p99_ms")(r) == pytest.approx(1.0)


def test_tpot_of_finished_and_unfinished_requests():
    done = handle("a", first=11.0, finished=13.0, out=21, steps=1)
    live = handle("b", first=14.0)
    r = rec([req(10.5, done), req(12.0, live)], end_active=[("b", 2)])
    # tokens per record from finished requests: 21 tokens / 1 record
    assert r.tokens_per_record() == 21.0
    # a: 2 s / 20 tokens = 100 ms; b: 6 s / (2 x 21) tokens = 142.9 ms
    assert harness.reader("tpot_p90_ms")(r) == pytest.approx(6000 / 42)


def test_out_tok_s_counts_partial_steps_at_both_edges():
    a = handle("a", finished=15.0, first=5.0, out=50, steps=2)   # 25/record
    b = handle("b", first=12.0)                                  # in flight
    c = handle("c", finished=8.0, first=2.0, out=25, steps=1)    # before
    r = rec([req(0, a), req(0, b), req(0, c)],
            start_active=[("a", 1)], end_active=[("b", 3)])
    tpr = r.tokens_per_record()
    assert tpr == pytest.approx(75 / 3)
    # a: 50 - 1 x 25; b: 3 x 25 - 0; c: finished before the window
    want = ((50 - tpr) + 3 * tpr) / 10.0
    assert harness.reader("out_tok_s")(r) == pytest.approx(want)


def test_prefix_hit_share_over_admissions_in_window():
    r = rec([req(0, handle("a", admitted=11, hit=96, prompt=128)),
             req(0, handle("b", admitted=12, hit=0, prompt=64)),
             req(0, handle("c", admitted=5, hit=64, prompt=64))])
    assert harness.reader("prefix_hit_share")(r) == pytest.approx(50.0)


def test_phi3_flops_by_hand():
    dims = harness.load_cell("phi3-think1k-backlog").dims
    d, ff, v, layers = 3072, 8192, 32064, 32
    per_layer = 4 * d * d + 3 * d * ff          # q, k, v, o; gate, up, down
    assert dense.matmul_params(dims) == layers * per_layer + d * v
    # one token at position 999 (1000 keys, inside the 2047 window)
    want = 2 * (layers * per_layer + d * v) + 4 * layers * 32 * 96 * 1000
    assert dense.tokens_flops(dims, 999, 1000) == want
    assert want == pytest.approx(7.84e9, rel=0.01)
    # past the window every token sees 2047 keys
    assert dense.tokens_flops(dims, 3000, 3001) - dense.tokens_flops(
        dims, 2999, 3000) == 0


def test_mfu_reads_committed_tokens():
    a = handle("a", finished=15.0, first=5.0, out=48, steps=2)
    r = rec([req(0, a, ops=10)], start_active=[("a", 1)])
    # 24 tokens at positions 47 + 24 .. 47 + 48 in 10 s on 197 TFLOP/s
    f = dense.tokens_flops(r.dims, 47 + 24, 47 + 48)
    assert harness.reader("mfu")(r) == pytest.approx(
        100 * f / (10 * 197e12))
