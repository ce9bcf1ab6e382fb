"""BatchEngine semantics: ragged batched rows must reproduce the
sequential engine token-for-token (greedy AND sampled), isolate rows from
each other, and honor per-row budgets/stops/keys."""

import re

import jax
import numpy as np
import pytest

from repro.models.config import ModelConfig
from repro.models.model import Model
from repro.sampling.sample import SamplingParams
from repro.serving.batch_engine import BatchEngine
from repro.serving.engine import Engine
from repro.tokenizer import toy as tk

CAP = 256


def _mk(family="dense"):
    base = dict(name=f"be-{family}", family=family, n_layers=2, d_model=64,
                n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                vocab_size=tk.VOCAB_SIZE)
    if family in ("ssm", "hybrid"):
        base.update(ssm_state=16, ssm_head_dim=16, ssm_chunk=16)
    if family == "ssm":
        base.update(n_heads=1, n_kv_heads=1, d_ff=0)
    cfg = ModelConfig(**base).validate()
    m = Model(cfg)
    return m, m.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def pair():
    m, params = _mk()
    return (Engine(m, params, max_len=CAP),
            BatchEngine(m, params, batch=4, capacity=CAP))


PROMPTS = [
    [tk.BOS, tk.THINK] + tk.num_ids(42),
    [tk.BOS, tk.THINK] + tk.num_ids(7) + tk.num_ids(13),
    [tk.BOS, tk.THINK] + tk.num_ids(99) + [tk.STEP] + tk.num_ids(1),
]


def test_batched_greedy_equals_sequential(pair):
    """Ragged batched prefill + fused multi-row decode reproduces the
    sequential engine exactly — tokens AND final logits."""
    eng, be = pair
    rows = [be.alloc_row() for _ in PROMPTS]
    be.extend_rows(rows, PROMPTS)
    sp = SamplingParams(temperature=0.0)
    keys = [jax.random.PRNGKey(i) for i in range(3)]
    budgets = [12, 5, 9]
    outs = be.generate_rows(rows, budgets, [tk.EOS, tk.THINK_END], sp, keys)
    for i, p in enumerate(PROMPTS):
        s = eng.extend(eng.new_session(), p)
        ids, s2, _ = eng.generate_fused(s, budgets[i],
                                        [tk.EOS, tk.THINK_END], sp, keys[i])
        assert outs[i] == ids
        np.testing.assert_allclose(be.last_logits[rows[i]],
                                   np.asarray(s2.last_logits)[0],
                                   rtol=2e-5, atol=2e-5)
    for r in rows:
        be.free_row(r)


def test_batched_sampled_equals_sequential(pair):
    """Per-row PRNG keys split on-device in the sequential loop's order:
    sampled batched rows reproduce the sequential token stream."""
    eng, be = pair
    rows = [be.alloc_row() for _ in PROMPTS]
    be.extend_rows(rows, PROMPTS)
    sp = SamplingParams(temperature=0.8, top_k=20)
    keys = [jax.random.PRNGKey(100 + i) for i in range(3)]
    outs = be.generate_rows(rows, 10, [tk.EOS], sp, keys)
    for i, p in enumerate(PROMPTS):
        s = eng.extend(eng.new_session(), p)
        ids, _, _ = eng.generate_fused(s, 10, [tk.EOS], sp, keys[i])
        assert outs[i] == ids
    for r in rows:
        be.free_row(r)


def test_subset_ops_do_not_disturb_other_rows(pair):
    """Extending/decoding a subset of rows must leave the other rows'
    positions, logits and future generations untouched."""
    eng, be = pair
    rows = [be.alloc_row() for _ in PROMPTS]
    be.extend_rows(rows, PROMPTS)
    sp = SamplingParams(temperature=0.0)
    frozen = rows[2]
    logits_before = be.last_logits[frozen].copy()
    pos_before = be.pos[frozen]
    # ops on the OTHER rows only
    be.extend_rows(rows[:2], [[tk.STEP, *tk.num_ids(3)], [tk.STEP]])
    be.generate_rows(rows[:2], 6, [], sp,
                     [jax.random.PRNGKey(0), jax.random.PRNGKey(1)])
    assert be.pos[frozen] == pos_before
    np.testing.assert_array_equal(be.last_logits[frozen], logits_before)
    # the frozen row still generates exactly like a fresh sequential run
    out = be.generate_rows([frozen], 8, [], sp, [jax.random.PRNGKey(5)])
    s = eng.extend(eng.new_session(), PROMPTS[2])
    ids, _, _ = eng.generate_fused(s, 8, [], sp, jax.random.PRNGKey(5))
    assert out[0] == ids
    for r in rows:
        be.free_row(r)


def test_row_snapshot_restore_matches_replay(pair):
    """O(1) row truncate + regenerate == never having speculated."""
    eng, be = pair
    r = be.alloc_row()
    be.extend_rows([r], [PROMPTS[0]])
    sp = SamplingParams(temperature=0.0)
    snap = be.snapshot_row(r)
    be.extend_rows([r], [tk.num_ids(50) + [tk.STEP]])    # rejected spec
    be.restore_row(r, snap)
    out = be.generate_rows([r], 6, [], sp, [jax.random.PRNGKey(3)])
    s = eng.extend(eng.new_session(), PROMPTS[0])
    ids, _, _ = eng.generate_fused(s, 6, [], sp, jax.random.PRNGKey(3))
    assert out[0] == ids
    be.free_row(r)


def test_per_row_stop_sets(pair):
    """One fused call can mix rows with different stop sets."""
    eng, be = pair
    rows = [be.alloc_row(), be.alloc_row()]
    be.extend_rows(rows, [PROMPTS[0], PROMPTS[0]])
    sp = SamplingParams(temperature=0.0)
    keys = [jax.random.PRNGKey(4)] * 2
    free = eng.generate_fused(eng.extend(eng.new_session(), PROMPTS[0]),
                              12, [], sp, keys[0])[0]
    stop_tok = free[4]
    outs = be.generate_rows(rows, 12, [], sp, keys,
                            stop_ids_rows=[[stop_tok], []])
    k = free.index(stop_tok)
    assert outs[0] == free[:k + 1]     # row 0 stops at its own stop id
    assert outs[1] == free             # row 1 ignores it
    for r in rows:
        be.free_row(r)


def test_per_row_budgets_and_zero_budget(pair):
    _, be = pair
    rows = [be.alloc_row(), be.alloc_row()]
    be.extend_rows(rows, [PROMPTS[0], PROMPTS[1]])
    sp = SamplingParams(temperature=0.0)
    outs = be.generate_rows(rows, [5, 0], [], sp,
                            [jax.random.PRNGKey(0)] * 2)
    assert len(outs[0]) == 5 and outs[1] == []
    for r in rows:
        be.free_row(r)


def test_ssm_rejected():
    m, params = _mk("ssm")
    with pytest.raises(ValueError, match="attention-only"):
        BatchEngine(m, params, batch=2, capacity=64)


def test_row_overflow_raises():
    m, params = _mk()
    be = BatchEngine(m, params, batch=2, capacity=32)
    r = be.alloc_row()
    be.extend_rows([r], [list(range(2)) * 8])      # 16 tokens
    with pytest.raises(ValueError, match="overflow"):
        be.extend_rows([r], [list(range(2)) * 10])  # 16+32-bucket > 32


def test_row_reuse_after_free():
    """A freed row starts clean: a new request on the same slot sees no
    residue from the previous occupant."""
    m, params = _mk()
    be = BatchEngine(m, params, batch=1, capacity=CAP)
    eng = Engine(m, params, max_len=CAP)
    sp = SamplingParams(temperature=0.0)
    r = be.alloc_row()
    be.extend_rows([r], [PROMPTS[0]])
    be.generate_rows([r], 8, [], sp, [jax.random.PRNGKey(0)])
    be.free_row(r)
    r2 = be.alloc_row()
    assert r2 == r
    be.extend_rows([r2], [PROMPTS[1]])
    out = be.generate_rows([r2], 8, [], sp, [jax.random.PRNGKey(1)])
    s = eng.extend(eng.new_session(), PROMPTS[1])
    ids, _, _ = eng.generate_fused(s, 8, [], sp, jax.random.PRNGKey(1))
    assert out[0] == ids


def test_engine_calls_donate_the_cache():
    """extend_rows, generate_rows and feed_rows donate the state they are
    given: the previous K/V buffers are gone after each call, and the
    prefix-cache calls that read and write ``be.state`` between calls see
    the current one."""
    m, params = _mk()
    be = BatchEngine(m, params, batch=3, capacity=64)
    sp = SamplingParams(temperature=0.0)
    a, b = be.alloc_row(), be.alloc_row()

    def donated(call):
        k, v = be.state.k, be.state.v
        call()
        assert k.is_deleted() and v.is_deleted()
        assert not (be.state.k.is_deleted() or be.state.v.is_deleted())

    donated(lambda: be.extend_rows([a], [PROMPTS[1]]))
    donated(lambda: be.generate_rows([a], 4, [], sp,
                                     [jax.random.PRNGKey(0)]))
    donated(lambda: be.feed_rows([a], [tk.STEP]))
    # export the row as it stands, seed a fresh row with it, and the two
    # continue alike
    n = int(be.pos[a])
    k, v = be.export_prefix(a, 0, n)
    be.load_prefix(b, k, v)
    donated(lambda: be.extend_rows([a, b], [[tk.STEP], [tk.STEP]]))
    np.testing.assert_allclose(be.last_logits[b], be.last_logits[a],
                               rtol=1e-5, atol=1e-5)
    c = be.alloc_row()
    be.load_prefix_pages_rows([c], k[:, None], v[:, None], [[0]])
    assert be.pos[c] == n
    donated(lambda: be.feed_rows([c], [tk.STEP]))
    # a decode step against the prefill's extend: same context, other
    # arithmetic order
    np.testing.assert_allclose(be.last_logits[c], be.last_logits[a],
                               rtol=1e-4, atol=1e-4)


def _copies_of(text, shape):
    """HLO instructions of a compiled program that copy a ``shape``d
    buffer (``f32[2,4,64,2,16]``-style shape strings)."""
    return [ln for ln in text.splitlines()
            if re.search(r"= " + re.escape(shape) + r"\{[^}]*\} copy\(", ln)]


def test_extend_program_updates_the_cache_in_place():
    """The compiled extend aliases the cache input to its output and
    copies no buffer of the whole cache's shape (no restack of the layer
    scan, no slice merged back)."""
    m, params = _mk()
    be = BatchEngine(m, params, batch=4, capacity=CAP)
    toks = jax.numpy.zeros((be.batch, 8), jax.numpy.int32)
    text = be._prefill_fn(64).lower(be.params, toks,
                                    be.state).compile().as_text()
    header = text.splitlines()[0]
    n_in = len(jax.tree_util.tree_leaves((be.params, toks)))
    # outputs 1 and 2 are the new K and V, inputs n_in, n_in + 1 the old
    assert re.search(r"\{1\}: \(%d, \{\}" % n_in, header), header
    assert re.search(r"\{2\}: \(%d, \{\}" % (n_in + 1), header), header
    shape = "f32[%s]" % ",".join(str(d) for d in be.state.k.shape)
    assert shape in text
    assert _copies_of(text, shape) == []
