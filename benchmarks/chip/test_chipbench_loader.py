"""Everything a cell needs is found by the names in BENCHMARK.json, and
the file keeps to the benchmark's contract.  A configuration names its
architecture module; the dense module draws, lays out and counts exactly
what the harness did before it had modules (golden values below)."""

import dataclasses
import hashlib
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
from architectures import dense  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
METRICS = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
# every reader and configuration kept under benchmarks/chip, those of
# cells not in BENCHMARK.json yet among them
READERS = sorted(p.stem for p in (HERE / "metrics").glob("*.py"))
CONFIGS = sorted(str(p.relative_to(ROOT))
                 for p in (HERE / "configs").glob("*.json")) + [
    f"benchmarks/chip/testdata/{n}" for n in ("tiny.json", "tiny-gelu.json")]


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
    cfg, arch = harness.load_config(ROOT / config)
    dims = arch.dims(cfg)
    want = Model(arch.model_config(dims)).abstract()
    got = arch.layout(dims)
    import jax
    assert jax.tree.map(lambda s: s.shape, want) == jax.tree.map(
        tuple, got, is_leaf=lambda x: isinstance(x, tuple))


# sha256 over (path, bytes) of every leaf in sorted path order, as the
# harness drew the tiny trees before architectures had modules
GOLDEN_TREES = {
    ("tiny.json", harness.WEIGHTS_SEED):
        "7c340bcf1bdf1071d87493ab44487a8c7fb74a713672fe16053e495fc140238e",
    ("tiny.json", 2**40 + 3):
        "4e8715722cbe00cd2f326b64d4596519143ff687e8ea70f53e6b498cb73547c0",
    ("tiny-gelu.json", harness.WEIGHTS_SEED):
        "b5642c04fa813a2a6a5866ce58cb1a2c3b7c224876ba9cc24fa34b4096dbf955",
    ("tiny-gelu.json", 2**40 + 3):
        "62570f4afdaa95daa2d4533a300c26aeffdb70ac5759c264e161999decdc356e",
}


@pytest.mark.parametrize("config,seed", sorted(GOLDEN_TREES))
def test_dense_draws_the_tree_bit_for_bit(config, seed):
    import numpy as np
    cfg, arch = harness.load_config(HERE / "testdata" / config)
    dims = arch.dims(cfg)
    params = weights.make_params(arch.layout(dims), dims.jnp_dtype, seed)
    h = hashlib.sha256()
    for path, _ in weights._paths(arch.layout(dims)):
        leaf = params
        for k in path.split("/"):
            leaf = leaf[k]
        h.update(path.encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == GOLDEN_TREES[(config, seed)]


def test_dense_reads_phi3_as_before():
    """phi3's leaf paths and shapes in draw order, the fields the program
    config is built from, and the FLOP counts behind ``mfu``."""
    cell = harness.load_cell("phi3-think1k-backlog")
    assert cell.arch.SCOPES == ("attn", "kv_write", "mlp", "lm_head")
    dims = cell.dims
    lay = json.dumps(list(weights._paths(cell.arch.layout(dims))))
    assert hashlib.sha256(lay.encode()).hexdigest() == (
        "cc94f08248c18e44c19fc9b4bb554125a69420ee8f8f2ee47c9392f64de1d595")
    want = dict(name="phi3-mini-3.8b", family="dense", n_layers=32,
                d_model=3072, n_heads=32, n_kv_heads=32, head_dim=96,
                d_ff=8192, vocab_size=32064, rmsnorm_eps=1e-05,
                norm_type="rmsnorm", act="swiglu", rope_theta=10000.0,
                sliding_window=2047)
    for n_layers, suffix in ((None, ""), (2, "-draft")):
        got = dataclasses.asdict(cell.arch.model_config(dims, n_layers,
                                                        suffix))
        assert {k: got[k] for k in want} == dict(
            want, name=want["name"] + suffix, n_layers=n_layers or 32)
    assert dense.matmul_params(dims) == 3722379264
    for (a, b), f in {(0, 1): 7445151744, (999, 1000): 7837974528,
                      (128, 1152): 7881332097024,
                      (0, 4096): 32967229046784,
                      (3000, 3001): 8249671680,
                      (2046, 2049): 24749015040}.items():
        assert cell.arch.tokens_flops(dims, a, b) == f


def _bench_with_config(tmp_path, cfg: dict) -> Path:
    """A root whose BENCHMARK.json runs phi3's cell with ``cfg``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"][0]["file"] = "cfg.json"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "cfg.json").write_text(json.dumps(cfg))
    return tmp_path


@pytest.mark.parametrize("arch", [None, "no-such-architecture"])
def test_config_without_a_known_architecture_is_refused(tmp_path, arch):
    cfg = json.loads((HERE / "configs" / "phi3-mini-3.8b.json").read_text())
    del cfg["architecture"]
    if arch:
        cfg["architecture"] = arch
    root = _bench_with_config(tmp_path, cfg)
    with pytest.raises(SystemExit, match="cfg.json"):
        harness.load_cell("phi3-think1k-backlog", root)


def test_architecture_lacking_a_name_is_refused(tmp_path):
    (tmp_path / "half.py").write_text("SCOPES = ()\n")
    cfg = json.loads((HERE / "configs" / "phi3-mini-3.8b.json").read_text())
    cfg["architecture"] = "half"
    root = _bench_with_config(tmp_path, cfg)
    with pytest.raises(SystemExit, match="half.py.*lacks"):
        harness.load_cell("phi3-think1k-backlog", root, tmp_path)


def test_unknown_device_has_no_peak():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit):
        harness.load_cell("no-such-cell")
