"""Everything a cell needs is found by the names in BENCHMARK.json, and
the file keeps to the benchmark's contract."""

import json
import re
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import harness  # noqa: E402
import peaks  # noqa: E402
import weights  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
METRICS = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
# every reader and configuration kept under benchmarks/chip, those of
# cells not in BENCHMARK.json yet among them
READERS = sorted(p.stem for p in (HERE / "metrics").glob("*.py"))
CONFIGS = sorted(str(p.relative_to(ROOT))
                 for p in (HERE / "configs").glob("*.json"))


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_loads_by_name(workload):
    cell = harness.load_cell(workload)
    dims = cell.dims
    assert dims.n_layers >= cell.config["drafter"]["num_hidden_layers"]
    for key in ("rows", "slots", "kv_budget_mb", "prefix_cache_blocks",
                "max_prefill_tokens", "step_accept_share", "gamma",
                "warmup_done", "check_requests", "limits"):
        assert key in cell.serve, key
    assert cell.serve["max_prefill_tokens"] <= 256   # the engine's buckets
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    moved = {m["name"] for m in cell.end_to_end}
    assert all(m["moves"] in moved for m in cell.per_layer)
    assert harness.reference(cell.config).logits


@pytest.mark.parametrize("metric", READERS)
def test_every_metric_has_a_reader(metric):
    assert callable(harness.reader(metric))


def test_every_benchmark_metric_has_a_reader_file():
    assert set(METRICS) <= set(READERS)


def test_names_units_and_files():
    names = WORKLOADS + METRICS + [c["name"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names)
    for n in names + [w["traffic"] for w in BENCH["workloads"]]:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
        assert not any(k.endswith(("_dim", "_rank", "_size")) for k in
                       c["reduced"])


@pytest.mark.parametrize("config", CONFIGS)
def test_config_matches_program_layout(config):
    from repro.models.model import Model
    dims = weights.Dims.from_config(json.loads((ROOT / config).read_text()))
    want = Model(harness.model_config(dims)).abstract()
    got = weights.layout(dims)
    import jax
    assert jax.tree.map(lambda s: s.shape, want) == jax.tree.map(
        tuple, got, is_leaf=lambda x: isinstance(x, tuple))


def test_unknown_device_has_no_peak():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit):
        harness.load_cell("no-such-cell")
