"""Roofline infrastructure: the HLO cost parser must agree with
cost_analysis() on unrolled programs and correctly multiply while-loop
bodies by trip counts (which cost_analysis does NOT); the compile
sentinel's live cost capture must join against the same parser on the
engines' paged prefill/extend/feed jits."""

import random

import jax
import jax.numpy as jnp
import pytest

from repro.roofline.hlo_cost import HloModule, module_cost
from repro.roofline.analysis import model_flops_estimate
from repro.models.config import INPUT_SHAPES
from repro.configs.registry import ARCHS


def _ca(compiled):
    """cost_analysis() compat: newer jaxlibs return a per-program list of
    dicts (analysis.py handles this the same way)."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return ca


def _scan_prog(n_layers, unroll=1):
    def f(ws, x):
        def body(x, w):
            return jnp.tanh(x @ w), None
        x, _ = jax.lax.scan(body, x, ws, unroll=unroll)
        return x
    ws = jnp.ones((n_layers, 128, 128))
    x = jnp.ones((4, 128))
    return jax.jit(f).lower(ws, x).compile()


def test_cost_analysis_undercounts_scans():
    """Document the XLA behavior this module exists to correct."""
    c2 = _scan_prog(2)
    c8 = _scan_prog(8)
    assert _ca(c2)["flops"] == _ca(c8)["flops"], \
        "XLA started counting while trip counts; revisit hlo_cost usage"


@pytest.mark.parametrize("n_layers", [2, 8, 24])
def test_parser_matches_unrolled_cost_analysis(n_layers):
    """Parsed flops of the SCANNED program == cost_analysis of the UNROLLED
    program (the ground truth)."""
    scanned = _scan_prog(n_layers)
    unrolled = _scan_prog(n_layers, unroll=n_layers)
    parsed = module_cost(scanned.as_text())
    truth = _ca(unrolled)["flops"]
    assert parsed.flops == pytest.approx(truth, rel=1e-6), \
        f"L={n_layers}: parsed {parsed.flops} vs truth {truth}"


def test_parser_nested_scans():
    def f(ws, x):
        def outer(x, w):
            def inner(x, _):
                return jnp.tanh(x @ w), None
            x, _ = jax.lax.scan(inner, x, None, length=3)
            return x, None
        x, _ = jax.lax.scan(outer, x, ws)
        return x
    ws = jnp.ones((4, 64, 64))
    x = jnp.ones((2, 64))
    c = jax.jit(f).lower(ws, x).compile()
    parsed = module_cost(c.as_text())
    # 4 outer x 3 inner matmuls of 2x64x64
    assert parsed.flops == pytest.approx(4 * 3 * 2 * 2 * 64 * 64, rel=1e-6)


def test_collective_bytes_on_synthetic_hlo():
    txt = """
ENTRY %main (p: f32[16]) -> f32[16] {
  %p = f32[16]{0} parameter(0)
  %ar = f32[16]{0} all-reduce(%p), to_apply=%add
  %ag = f32[32]{0} all-gather(%ar), dimensions={0}
  ROOT %out = f32[16]{0} slice(%ag), slice={[0:16]}
}
"""
    cost = module_cost(txt)
    assert cost.coll["all-reduce"] == 16 * 4
    assert cost.coll["all-gather"] == 32 * 4


def test_dot_flops_with_batch_dims():
    def f(a, b):
        return jnp.einsum("bij,bjk->bik", a, b)
    a = jnp.ones((4, 8, 16))
    b = jnp.ones((4, 16, 32))
    c = jax.jit(f).lower(a, b).compile()
    parsed = module_cost(c.as_text())
    assert parsed.flops == pytest.approx(2 * 4 * 8 * 16 * 32, rel=1e-6)


def test_sentinel_cost_matches_hlo_cost_on_loopless_program():
    """The live join's static side: the sentinel's cost_analysis()
    capture and the HLO parser agree on a program without loops."""
    from repro.serving.compile_watch import CompileWatch
    cw = CompileWatch(keep_hlo=True)
    fn = jax.jit(lambda a, b: jnp.tanh(a @ b))
    args = (jnp.ones((4, 64)), jnp.ones((64, 32)))
    cost = cw.observe("e", "mm", fn, args)
    assert cost["flops"] > 0 and cost["bytes"] > 0
    (sig,) = cw.signatures("e", "mm")
    parsed = module_cost(cw.hlo_text[("e", "mm")][sig])
    assert parsed.flops == pytest.approx(cost["flops"], rel=1e-6)
    # and both agree with a direct cost_analysis of the same program
    truth = _ca(fn.lower(*args).compile())["flops"]
    assert cost["flops"] == pytest.approx(truth, rel=1e-6)


def test_sentinel_cost_joins_hlo_cost_on_engine_jits():
    """On a 1-layer micro pair (scan trip count 1, so cost_analysis's
    scan undercount is moot) the sentinel's captured cost for the paged
    prefill / extend / feed jits matches the trip-count-aware HLO
    parser within tolerance.  The fused decode loop is excluded by
    construction: its while_loop body is exactly what cost_analysis
    undercounts (see test_cost_analysis_undercounts_scans).  Tolerance
    is 10%: the parser models dot/collective flops while
    cost_analysis also counts elementwise lanes, a few-percent skew
    that is largest on micro-sized layers like these."""
    from repro.core.controller import SpecReason, SpecReasonConfig
    from repro.core.policies import StaticThreshold
    from repro.data import tasks
    from repro.models.config import ModelConfig
    from repro.models.model import Model
    from repro.sampling.sample import SamplingParams
    from repro.serving.compile_watch import CompileWatch
    from repro.serving.engine import Engine
    from repro.serving.kv_manager import KVBudget, KVManager
    from repro.serving.scheduler import ContinuousScheduler
    from repro.tokenizer import toy as tk

    b_cfg = ModelConfig(name="rb", family="dense", n_layers=1, d_model=64,
                        n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                        vocab_size=tk.VOCAB_SIZE).validate()
    s_cfg = ModelConfig(name="rs", family="dense", n_layers=1, d_model=32,
                        n_heads=2, n_kv_heads=2, head_dim=16, d_ff=64,
                        vocab_size=tk.VOCAB_SIZE).validate()
    bm, sm = Model(b_cfg), Model(s_cfg)
    base = Engine(bm, bm.init(jax.random.PRNGKey(0)), max_len=256)
    small = Engine(sm, sm.init(jax.random.PRNGKey(1)), max_len=256)
    ctrl = SpecReason(base, small, SpecReasonConfig(
        policy=StaticThreshold(5.0), token_budget=32, max_steps=4,
        sampling=SamplingParams(temperature=0.0)))
    cw = CompileWatch(keep_hlo=True)
    kv = KVManager(b_cfg, s_cfg, KVBudget(total_bytes=1 << 26))
    cs = ContinuousScheduler(ctrl, kv, max_batch=2, context_capacity=128,
                             chunked_prefill=True, max_prefill_tokens=16,
                             compile_watch=cw)
    rng = random.Random(3)
    for i in range(2):
        cs.submit(tasks.sample_task(rng, min_steps=6, max_steps=8),
                  key=jax.random.PRNGKey(i))
    cs.drain(jax.random.PRNGKey(9))
    checked = 0
    for (engine, op), sigs in cw.hlo_text.items():
        if op not in ("prefill", "extend", "feed"):
            continue
        costs = cw.signature_costs(engine, op)
        for sig, hlo in sigs.items():
            cost = costs[sig]
            assert cost is not None and cost["flops"]
            parsed = module_cost(hlo)
            assert parsed.flops == pytest.approx(cost["flops"],
                                                 rel=0.10), \
                f"{engine}.{op}: parsed {parsed.flops} vs " \
                f"cost_analysis {cost['flops']}"
            checked += 1
    assert checked > 0, "no prefill/extend/feed programs captured"


def test_model_flops_estimate_scaling():
    cfg = ARCHS["yi-34b"]
    tr = model_flops_estimate(cfg, INPUT_SHAPES["train_4k"])
    de = model_flops_estimate(cfg, INPUT_SHAPES["decode_32k"])
    n = cfg.param_count()
    assert tr == pytest.approx(6 * n * 256 * 4096)
    assert de == pytest.approx(2 * n * 128)
    # MoE counts active params only
    moe_cfg = ARCHS["qwen3-moe-235b-a22b"]
    active = moe_cfg.param_count(active_only=True)
    assert model_flops_estimate(moe_cfg, INPUT_SHAPES["decode_32k"]) == \
        pytest.approx(2 * active * 128)
    assert active < 0.15 * moe_cfg.param_count()
