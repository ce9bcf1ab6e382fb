"""Tensor-parallel serving equivalence suite.

The acceptance bar for sharded serving: a ContinuousScheduler built
with ``tp_size=2`` on the forced 8-device CPU mesh (tests/conftest.py)
produces, per request, IDENTICAL tokens to ``tp_size=1`` — greedy,
sampled, spec-decode and prefix-cache modes, plus preemption/rollback
under sharding.  This works because exact-TP shards only non-contraction
dims and all-gathers before every contraction (models/sharding.py
``exact_tp_activation_rules``), so the sharded computation performs the
same arithmetic in the same reduction order as the single-device one —
equivalence is bitwise, not approximate, hence token equality is exact
and these tests carry no tolerances.

Also covered here: the shard_map kernel wrappers (kernels/paged_tp.py)
against the unsharded references, the tp_size divisibility contract,
mixed-TP engine-pair rejection, per-device page views, and the
snapshot's mesh section.
"""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.controller import SpecReason, SpecReasonConfig
from repro.core.policies import StaticThreshold
from repro.data import tasks
from repro.kernels import ref
from repro.kernels.ops import interpret_mode
from repro.kernels.paged_tp import (tp_paged_append_attention,
                                    tp_paged_decode_attention)
from repro.launch.mesh import make_tp_mesh
from repro.models.config import ModelConfig
from repro.models.model import Model
from repro.sampling.sample import SamplingParams
from repro.serving.batch_engine import BatchEngine
from repro.serving.engine import Engine
from repro.serving.kv_manager import KVBudget, KVManager
from repro.serving.paged_kv import PagedKVPool, PagedKVStore
from repro.serving.scheduler import ContinuousScheduler
from repro.serving.spec_engine import BatchSpecEngine
from repro.serving.tp import TPContext
from repro.tokenizer import toy as tk

# both configs divide tp=2 on heads AND kv_heads (the exact-TP contract)
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


def _serve(engine_pair, tp_size, n_requests=3, temperature=0.0,
           spec=False, gamma=3, seed=0, max_batch=4, kv_bytes=1 << 26,
           kv_fraction=0.8, context_capacity=128, prefix_cache=True,
           resubmit=False):
    """One workload through a fresh ContinuousScheduler at the given
    tp_size; returns (handles, scheduler).  With ``resubmit`` the same
    tasks go through a second drain (exercising prefix-cache hits)."""
    base, small = engine_pair
    cfg = SpecReasonConfig(policy=StaticThreshold(5.0), token_budget=32,
                           max_steps=4, use_spec_decode=spec,
                           spec_gamma=gamma,
                           sampling=SamplingParams(temperature=temperature))
    ctrl = SpecReason(base, small, cfg)
    rng = random.Random(seed)
    reqs = [tasks.sample_task(rng) for _ in range(n_requests)]
    keys = [jax.random.PRNGKey(100 * seed + i) for i in range(n_requests)]
    kv = KVManager(BASE_CFG, SMALL_CFG,
                   KVBudget(total_bytes=kv_bytes,
                            base_fraction=kv_fraction))
    cs = ContinuousScheduler(ctrl, kv, max_batch=max_batch,
                             context_capacity=context_capacity,
                             prefix_cache=prefix_cache, tp_size=tp_size)
    handles = [cs.submit(t, key=k) for t, k in zip(reqs, keys)]
    cs.drain(jax.random.PRNGKey(9))
    if resubmit:
        handles += [cs.submit(t, key=k) for t, k in zip(reqs, keys)]
        cs.drain(jax.random.PRNGKey(9))
    return handles, cs


def _assert_token_identical(h1, h2, spec=False):
    """Per-request token identity between two serving regimes."""
    assert len(h1) == len(h2)
    for a, b in zip(h1, h2):
        ra, rb = a.result, b.result
        assert ra is not None and rb is not None
        assert ra.thinking_ids == rb.thinking_ids
        assert ra.answer_ids == rb.answer_ids
        assert len(ra.steps) == len(rb.steps)
        for sa, sb in zip(ra.steps, rb.steps):
            assert (sa.source, sa.accepted, sa.tokens) == \
                (sb.source, sb.accepted, sb.tokens)
        if spec:
            assert (ra.spec_stats.proposed, ra.spec_stats.accepted,
                    ra.spec_stats.rounds) == \
                (rb.spec_stats.proposed, rb.spec_stats.accepted,
                 rb.spec_stats.rounds)


# ------------------------------------------------ scheduler equivalence


def test_tp_greedy_identical(engine_pair):
    h1, cs1 = _serve(engine_pair, tp_size=1)
    h2, cs2 = _serve(engine_pair, tp_size=2)
    _assert_token_identical(h1, h2)
    # sharded run reports its mesh in the snapshot (admin /status)
    snap = cs2.snapshot()
    assert snap.mesh is not None
    assert snap.mesh["tp_size"] == 2
    assert snap.mesh["axes"] == {"model": 2}
    assert len(snap.mesh["devices"]) == 2
    assert cs1.snapshot().mesh is None
    # sharded pools drain clean, same as unsharded
    for cs in (cs1, cs2):
        cs.clear_prefix_cache()
        assert cs.pool_utilization() == {"base": 0.0, "small": 0.0}


def test_tp_sampled_identical(engine_pair):
    h1, _ = _serve(engine_pair, tp_size=1, temperature=0.8, seed=3)
    h2, _ = _serve(engine_pair, tp_size=2, temperature=0.8, seed=3)
    _assert_token_identical(h1, h2)


def test_tp_spec_decode_identical(engine_pair):
    """Hierarchical spec decode under sharding: draft proposal, base
    verification and the fused acceptance program all run on the shared
    mesh; acceptance counts must match the unsharded run exactly."""
    h1, _ = _serve(engine_pair, tp_size=1, spec=True, seed=4)
    h2, cs2 = _serve(engine_pair, tp_size=2, spec=True, seed=4)
    _assert_token_identical(h1, h2, spec=True)
    assert cs2.spec_be is not None and cs2.spec_be.tp_size == 2


def test_tp_prefix_cache_identical(engine_pair):
    """Resubmitting the same tasks hits the (sharded) prefix cache —
    cache-restored rows must continue token-identically too."""
    h1, cs1 = _serve(engine_pair, tp_size=1, seed=5, resubmit=True)
    h2, cs2 = _serve(engine_pair, tp_size=2, seed=5, resubmit=True)
    _assert_token_identical(h1, h2)
    for cs in (cs1, cs2):
        assert cs.caches["base"].stats.hits > 0


def test_tp_preemption_rollback_identical(engine_pair):
    """A pool too small for the whole workload preempts under sharding
    (block-table truncation + row restore on sharded state) and still
    finishes every request with the tp_size=1 tokens."""
    h1, cs1 = _serve(engine_pair, tp_size=1, n_requests=4,
                     kv_bytes=90_000, kv_fraction=0.5, prefix_cache=False)
    h2, cs2 = _serve(engine_pair, tp_size=2, n_requests=4,
                     kv_bytes=90_000, kv_fraction=0.5, prefix_cache=False)
    assert cs1.preemptions > 0 and cs2.preemptions > 0
    _assert_token_identical(h1, h2)
    assert cs2.pool_utilization() == {"base": 0.0, "small": 0.0}


# ------------------------------------------------- shard_map kernels


def _decode_case(rng, b=3, h=4, k=2, hd=8, pages=16, nb=3, bs=4):
    q = jnp.asarray(rng.standard_normal((b, h, hd)), jnp.float32)
    k_pages = jnp.asarray(rng.standard_normal((pages, k, bs, hd)),
                          jnp.float32)
    v_pages = jnp.asarray(rng.standard_normal((pages, k, bs, hd)),
                          jnp.float32)
    tbl = jnp.asarray(
        rng.permutation(pages)[:b * nb].reshape(b, nb), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, nb * bs + 1, size=(b,)),
                          jnp.int32)
    return q, k_pages, v_pages, tbl, lengths


def test_tp_decode_kernel_bitwise_vs_reference():
    """The sharded decode gather (the reference body, asked for
    explicitly) is BITWISE equal to the unsharded reference: per-shard local
    head slices see whole GQA groups and no cross-head reduction
    exists, so sharding moves no arithmetic."""
    mesh = make_tp_mesh(2)
    q, kp, vp, tbl, lens = _decode_case(np.random.default_rng(0))
    want = ref.paged_decode_reference(q, kp, vp, tbl, lens)
    got = tp_paged_decode_attention(mesh, q, kp, vp, tbl, lens,
                                    use_kernel=False)
    assert got.shape == want.shape
    assert jnp.array_equal(got, want)


def test_tp_append_kernel_bitwise_vs_reference():
    mesh = make_tp_mesh(2)
    rng = np.random.default_rng(1)
    b, t, h, k, hd, pages, nb, bs = 2, 4, 4, 2, 8, 8, 3, 4
    q = jnp.asarray(rng.standard_normal((b, t, h, hd)), jnp.float32)
    k_new = jnp.asarray(rng.standard_normal((b, t, k, hd)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((b, t, k, hd)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((pages, k, bs, hd)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((pages, k, bs, hd)), jnp.float32)
    tbl = jnp.asarray(rng.permutation(pages)[:b * nb].reshape(b, nb),
                      jnp.int32)
    ctx = jnp.asarray([5, 3], jnp.int32)
    span = jnp.asarray([4, 2], jnp.int32)
    want = ref.paged_append_reference(q, k_new, v_new, kp, vp, tbl,
                                      ctx, span)
    got = tp_paged_append_attention(mesh, q, k_new, v_new, kp, vp, tbl,
                                    ctx, span, use_kernel=False)
    assert got.shape == want.shape
    # positions past each row's span_len are undefined garbage in both
    # implementations — compare only the defined prefix per row
    for i, s in enumerate([4, 2]):
        assert jnp.array_equal(got[i, :s], want[i, :s])


def test_tp_decode_kernel_interpret_matches_reference():
    """The Pallas kernel body under shard_map (interpret mode on CPU)
    agrees with the reference within float32 softmax tolerance."""
    mesh = make_tp_mesh(2)
    q, kp, vp, tbl, lens = _decode_case(np.random.default_rng(2))
    want = ref.paged_decode_reference(q, kp, vp, tbl, lens)
    got = tp_paged_decode_attention(mesh, q, kp, vp, tbl, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_sharded_kernel_support_gate():
    """The backend alone picks compiled vs interpreted kernels; the
    sharded wrappers run the kernel by default and take the reference
    body only when asked for (never on tpu)."""
    assert not interpret_mode("tpu")
    assert interpret_mode("cpu")
    mesh = make_tp_mesh(2)
    q, kp, vp, tbl, lens = _decode_case(np.random.default_rng(3))
    kernel = tp_paged_decode_attention(mesh, q, kp, vp, tbl, lens)
    oracle = tp_paged_decode_attention(mesh, q, kp, vp, tbl, lens,
                                       use_kernel=False)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(oracle),
                               rtol=2e-5, atol=2e-5)


# ----------------------------------------------------- contract checks


def test_make_tp_mesh_validates():
    with pytest.raises(ValueError, match="tp_size must be >= 1"):
        make_tp_mesh(0)
    with pytest.raises(ValueError, match="devices"):
        make_tp_mesh(10_000)
    mesh = make_tp_mesh(2)
    assert dict(mesh.shape) == {"model": 2}


def test_make_tp_mesh_axis_is_auto():
    """The TP mesh's axis is Auto (GSPMD propagation from the constrain
    hints): under jax.make_mesh's Explicit default the exact-TP einsums
    raise "Contracting dimensions are sharded" instead of gathering."""
    mesh = make_tp_mesh(2)
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,)


def test_tp_divisibility_contract():
    """tp_size must divide heads AND kv-heads — otherwise the param
    specs would fall back to sharding head_dim (a contraction dim) and
    silently break bitwise equivalence.  Refused up front."""
    tp = TPContext.build(2)
    bad = ModelConfig(name="odd", family="dense", n_layers=1, d_model=32,
                      n_heads=2, n_kv_heads=1, head_dim=16, d_ff=64,
                      vocab_size=tk.VOCAB_SIZE).validate()
    with pytest.raises(ValueError, match="kv_heads"):
        tp.check_model(bad)
    tp.check_model(BASE_CFG)  # divisible: fine


def test_spec_engine_rejects_mixed_tp(engine_pair):
    base, small = engine_pair
    tp = TPContext.build(2)
    be_tp = BatchEngine(base.model, base.params, batch=2, capacity=64,
                        tp=tp)
    be_plain = BatchEngine(small.model, small.params, batch=2,
                           capacity=64)
    with pytest.raises(ValueError, match="share one TPContext"):
        BatchSpecEngine(be_tp, be_plain)


def test_paged_store_device_views():
    """Per-device page views: the head-split KV layout gives each mesh
    device a contiguous kv-head slice; block tables stay replicated
    (one block id addresses the same page on every device)."""
    tp = TPContext.build(2)
    pool = PagedKVPool(num_blocks=8, block_size=4, tp_size=2)
    store = PagedKVStore(pool, n_layers=2, kv_heads=2, head_dim=16,
                         tp=tp)
    views = store.device_views()
    assert len(views) == 2
    assert [v["kv_head_start"] for v in views] == [0, 1]
    assert all(v["kv_heads"] == 1 for v in views)
    # unsharded: one view over all heads
    plain = PagedKVStore(PagedKVPool(8, 4), n_layers=2, kv_heads=2,
                         head_dim=16)
    assert len(plain.device_views()) == 1
    assert plain.device_views()[0]["kv_heads"] == 2
    # indivisible kv-heads refused at store construction
    with pytest.raises(ValueError, match="kv"):
        PagedKVStore(pool, n_layers=1, kv_heads=3, head_dim=16, tp=tp)
