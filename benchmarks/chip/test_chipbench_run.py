"""A whole run of a tiny cell on the CPU, past the look for a chip: the
sound program is judged correct, the float8 control and a program that
alters the tokens it produces are judged not correct."""

import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import check  # noqa: E402
import harness  # noqa: E402
import run  # noqa: E402

SEED = 2**31 + 11
# long enough on a loaded CPU for the window to finish a few requests
WINDOW_S = 12.0


def tiny_cell(config: str = "tiny.json") -> harness.Cell:
    t = HERE / "testdata"
    e2e = [{"name": n, "unit": u} for n, u in
           (("setup_s", "s"), ("ttft_p90_ms", "ms"), ("tpot_p90_ms", "ms"))]
    return harness.Cell("tiny", 1, harness.load_json(t / config),
                        harness.load_json(t / "tiny-mix.json"),
                        harness.load_json(t / "tiny-cell.json"), e2e, [])


@pytest.mark.parametrize("config", ["tiny.json", "tiny-gelu.json"])
def test_sound_run_is_correct(config):
    """SwiGLU/RMSNorm/MHA-like, and GELU/LayerNorm/GQA with a binding
    sliding window (the starcoder2 path)."""
    res = run.run(tiny_cell(config), SEED, WINDOW_S, False, time.perf_counter(),
                  require_chip=False)
    assert res["correct"] is True
    assert list(res)[-1] == "check"
    assert {"setup_s", "ttft_p90_ms"} <= set(res["metrics"])
    assert res["device"]["platform"] == "cpu"
    assert res["attempted"] > 0 and res["failed"] == 0


def test_float8_control_is_not_correct():
    cell = tiny_cell()
    rec, prog = harness.run_window(cell, SEED, WINDOW_S, False,
                                   time.perf_counter(), None, lambda m: None)
    reqs = check.sample(rec, cell.serve["check_requests"])
    harness.free(prog)
    got = check.readings(rec, reqs, harness.reference_params(rec),
                         precisions=("program", "fp8"))
    limits = cell.serve["limits"]
    assert check.judge(got["program"], limits)
    assert not check.judge(got["fp8"], limits)


def _alter(outs):
    """Every row's last token replaced by another ordinary token."""
    return [o[:-1] + [(o[-1] + 1) % 450 + 30] if o else o for o in outs]


@pytest.mark.parametrize("where", ["draft", "base"])
def test_altered_token_is_not_correct(monkeypatch, where):
    """A token altered where it is produced: by the drafter's fused
    decode, or by the base's token-level spec decode."""
    from repro.serving.batch_engine import BatchEngine
    from repro.serving.spec_engine import BatchSpecEngine
    if where == "draft":
        orig = BatchEngine.generate_rows

        def broken(self, *a, **k):
            out = orig(self, *a, **k)
            if k.get("collect_probs") or "draft" not in self.name:
                return out
            return _alter(out)
        monkeypatch.setattr(BatchEngine, "generate_rows", broken)
    else:
        orig = BatchSpecEngine.decode_rows

        def broken(self, *a, **k):
            outs, stats = orig(self, *a, **k)
            return _alter(outs), stats
        monkeypatch.setattr(BatchSpecEngine, "decode_rows", broken)
    res = run.run(tiny_cell(), SEED, WINDOW_S, False, time.perf_counter(),
                  require_chip=False)
    assert res["correct"] is False
