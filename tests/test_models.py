"""Per-architecture smoke tests (deliverable f): a REDUCED variant of each
assigned architecture's family runs one forward + one train step on CPU,
asserting output shapes and no NaNs — plus the strong consistency property
forward == prefill+decode for every family."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS, ASSIGNED, reduced
from repro.models.model import Model
from repro.training.loss import make_train_step
from repro.training.optimizer import AdamWConfig, init as opt_init


def _aux_inputs(cfg, batch, key):
    out = {}
    if cfg.family == "vlm":
        out["image_embeds"] = jax.random.normal(
            key, (batch, cfg.n_image_tokens, cfg.d_model)) * 0.1
    if cfg.family == "encdec":
        out["encoder_embeds"] = jax.random.normal(
            key, (batch, cfg.encoder_seq_len, cfg.d_model)) * 0.1
    return out


@pytest.mark.parametrize("arch", ASSIGNED)
def test_arch_smoke_forward_and_train_step(arch):
    cfg = reduced(arch)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    b, s = 2, 32
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                              cfg.vocab_size)
    aux = _aux_inputs(cfg, b, jax.random.PRNGKey(2))
    logits, _ = model.forward(params, toks, **aux)
    assert logits.shape == (b, s, cfg.vocab_size)
    assert not bool(jnp.any(jnp.isnan(logits))), f"NaN in {arch} forward"

    # one train step on CPU
    step = jax.jit(make_train_step(model, AdamWConfig(lr=1e-3)))
    batch = {"tokens": toks,
             "targets": jnp.roll(toks, -1, axis=1),
             "weights": jnp.ones((b, s), jnp.float32), **aux}
    params2, _, metrics = step(params, opt_init(params), batch)
    assert np.isfinite(float(metrics["loss"])), f"{arch} loss not finite"
    # params actually changed
    delta = sum(float(jnp.sum(jnp.abs(a - bb)))
                for a, bb in zip(jax.tree.leaves(params),
                                 jax.tree.leaves(params2)))
    assert delta > 0.0


@pytest.mark.parametrize("arch", ASSIGNED)
def test_arch_prefill_decode_matches_forward(arch):
    """prefill(16) + decode(8) must reproduce the full-sequence forward
    logits — exercises KV caches, SSM states, ring masks, cross-attn caches
    for every family."""
    cfg = reduced(arch)
    if cfg.family == "moe":
        # capacity drops are dispatch-group-dependent; the exact
        # forward==decode property requires dropless routing
        import dataclasses
        cfg = dataclasses.replace(cfg, capacity_factor=16.0)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    b, s, pre = 2, 24, 16
    toks = jax.random.randint(jax.random.PRNGKey(4), (b, s), 0,
                              cfg.vocab_size)
    aux = _aux_inputs(cfg, b, jax.random.PRNGKey(5))
    logits, _ = model.forward(params, toks, **aux)

    ncs = (cfg.n_image_tokens if cfg.family == "vlm"
           else cfg.encoder_seq_len if cfg.family == "encdec" else 0)
    st = model.init_state(b, 64, n_cross_src=ncs)
    if ncs:
        src = aux.get("image_embeds")
        if cfg.family == "encdec":
            src = model.encode(params, aux["encoder_embeds"])
        st = model.prep_cross(params, st, src)
    lg, st = model.prefill(params, toks[:, :pre], st)
    errs = [float(jnp.max(jnp.abs(lg - logits[:, :pre])))]
    for t in range(pre, s):
        lg1, st = model.decode_step(params, st, toks[:, t:t + 1])
        errs.append(float(jnp.max(jnp.abs(lg1 - logits[:, t]))))
    assert max(errs) < 5e-4, f"{arch}: decode/forward mismatch {max(errs)}"


def test_sliding_window_ring_decode_matches_linear():
    """Ring-buffer sliding-window decode == linear-cache decode with window
    masking (the long_500k serving path)."""
    import dataclasses
    cfg = dataclasses.replace(reduced("starcoder2-7b"), sliding_window=8)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(6))
    b, s = 1, 32
    toks = jax.random.randint(jax.random.PRNGKey(7), (b, s), 0,
                              cfg.vocab_size)
    # linear cache decode
    st_lin = model.init_state(b, 64)
    lg, st_lin = model.prefill(params, toks[:, :1], st_lin)
    outs_lin = [lg[:, -1]]
    for t in range(1, s):
        o, st_lin = model.decode_step(params, st_lin, toks[:, t:t + 1])
        outs_lin.append(o)
    # ring cache decode (capacity == window)
    st_ring = model.init_state(b, cfg.sliding_window, ring=True)
    lg, st_ring = model.prefill(params, toks[:, :1], st_ring)
    outs_ring = [lg[:, -1]]
    for t in range(1, s):
        o, st_ring = model.decode_step(params, st_ring, toks[:, t:t + 1])
        outs_ring.append(o)
    err = max(float(jnp.max(jnp.abs(a - bb)))
              for a, bb in zip(outs_lin, outs_ring))
    assert err < 5e-4, f"ring vs linear window decode mismatch: {err}"


def test_param_counts_match_model_cards():
    """Config param_count() must land near the nominal sizes."""
    expected = {
        "mamba2-1.3b": 1.3e9, "llama-3.2-vision-11b": 10.1e9,
        "minitron-4b": 4.2e9, "phi3-mini-3.8b": 3.8e9,
        "granite-moe-1b-a400m": 1.3e9, "whisper-base": 0.08e9,
        "hymba-1.5b": 1.6e9, "starcoder2-7b": 7.1e9,
        "qwen3-moe-235b-a22b": 235e9, "yi-34b": 34e9,
    }
    for arch, nominal in expected.items():
        got = ARCHS[arch].param_count()
        assert 0.7 * nominal < got < 1.45 * nominal, \
            f"{arch}: {got/1e9:.2f}B vs nominal {nominal/1e9:.2f}B"


def test_blockwise_gqa_matches_direct_sdpa():
    """Grouped-GQA blockwise attention (perf-optimized path) must equal the
    direct masked softmax with repeated kv heads."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import attention as attn

    b, sq, h, kh, hd = 2, 96, 6, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, sq, h, hd))
    k = jax.random.normal(ks[1], (b, sq, kh, hd))
    v = jax.random.normal(ks[2], (b, sq, kh, hd))
    out = attn.blockwise_sdpa(q, k, v, jnp.zeros((), jnp.int32), causal=True,
                              block_q=32, block_k=16)
    kf = attn._repeat_kv(k, h // kh)
    vf = attn._repeat_kv(v, h // kh)
    exp = attn.sdpa(q, kf, vf, attn.causal_mask(sq, sq))
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-5,
                               atol=2e-5)
    # windowed variant
    out_w = attn.blockwise_sdpa(q, k, v, jnp.zeros((), jnp.int32),
                                causal=True, window=24, block_q=32,
                                block_k=16)
    exp_w = attn.sdpa(q, kf, vf, attn.causal_mask(sq, sq, window=24))
    np.testing.assert_allclose(np.asarray(out_w), np.asarray(exp_w),
                               rtol=2e-5, atol=2e-5)


def _window_case(kind):
    """(model, params, state, tokens, width) for one window case: a
    small dense model, a cache of 32 slots filled with noise, ragged
    per-row positions or one shared one."""
    from repro.models.config import ModelConfig
    cfg = ModelConfig(name="window", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, head_dim=8, d_ff=64,
                      vocab_size=48).validate()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(11))
    b, cap = 3, 32
    st = model.init_state(b, cap)
    kk, kv = jax.random.split(jax.random.PRNGKey(12))
    st = dataclasses.replace(
        st, k=jax.random.normal(kk, st.k.shape),
        v=jax.random.normal(kv, st.v.shape))
    s = 1 if kind.startswith("decode") else 6
    # ragged: rows at 0, 5 and 9 of a 16-slot window, so the chunks'
    # trailing pads land inside it; "cap": the window is the whole cache
    # and the last row's pads run past it, clamped onto its last slot
    pos, width = {"prefill_ragged": ([0, 5, 9], 16),
                  "prefill_cap": ([0, 7, cap - 3], cap),
                  "prefill_shared": (4, 16),
                  "decode_ragged": ([0, 5, 15], 16),
                  "decode_shared": (11, 16)}[kind]
    st = dataclasses.replace(st, pos=jnp.asarray(pos, jnp.int32))
    toks = jax.random.randint(jax.random.PRNGKey(13), (b, s), 0,
                              cfg.vocab_size)
    return model, params, st, toks, width


@pytest.mark.parametrize("kind", ["prefill_ragged", "prefill_cap",
                                  "prefill_shared", "decode_ragged",
                                  "decode_shared"])
def test_window_matches_slice_run_merge(kind):
    """Attending the first ``width`` slots of the whole cache, written in
    place, gives the logits and cache of slicing the cache to ``width``,
    running on the slice and merging it back."""
    model, params, st, toks, width = _window_case(kind)

    @functools.partial(jax.jit, static_argnames="width")
    def run(state, width=None):
        if kind.startswith("decode"):
            return model.decode_step(params, state, toks, width=width)
        return model.prefill(params, toks, state, width=width)

    lg, new = run(st, width=width)
    lg_ref, ref = run(dataclasses.replace(st, k=st.k[:, :, :width],
                                          v=st.v[:, :, :width]))
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lg_ref),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(new.k), np.asarray(st.k.at[:, :, :width].set(ref.k)))
    np.testing.assert_array_equal(
        np.asarray(new.v), np.asarray(st.v.at[:, :, :width].set(ref.v)))
    np.testing.assert_array_equal(np.asarray(new.pos), np.asarray(ref.pos))
