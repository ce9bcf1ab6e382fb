#!/usr/bin/env python3
"""Chip smoke: the SpecReason serving path on a TPU at phi3-mini-3.8b widths.

    python chip_smoke.py             # one chip: device, kernels, served, cache
    python chip_smoke.py --chips 4   # four chips: the tensor-parallel phase only

Everything runs in this one process (a chip belongs to one process), from
files in this checkout: weights are random, drawn from fixed seeds.

Phases, each printing its own ``[phase]`` lines:

  device   ``jax.devices()`` must be TPU: anything else exits non-zero.
  kernels  every Pallas kernel of ``src/repro/kernels`` (the cases of
           ``repro.kernels.cases``) compiled, never interpreted: the lowered
           text must hold ``tpu_custom_call``, and the output must match
           ``kernels/ref.py`` run at highest matmul precision.
  served   ``launch/serve.py::serve_continuous`` (ContinuousScheduler with
           SpecReason step speculation and token-level spec decode) serves
           8 tasks x best-of-2 through phi3-mini-3.8b: all 32 layers at the
           published widths, bf16 weights from PRNGKey(0).  The draft is the
           same config cut to 2 layers, bf16 weights from PRNGKey(1): a
           stand-in that drives the path, not a real drafter.
  cache    one row's logits from prefill + 16 decode steps through the
           ``BatchEngine`` cache against the uncached full forward pass.
  tp       (--chips 4 only) the pair served greedy at tp=1 on device 0,
           then at tp=4; tokens per request and the logits after prefill
           compared.

A failed check raises and the script exits non-zero.  Times, compile
seconds and peak bytes are printed as information, not as benchmark
numbers.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import random
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.phi3_mini_3_8b import CONFIG as PHI3  # noqa: E402
from repro.data import tasks  # noqa: E402
from repro.kernels.cases import ALL_CASES  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import (parse_args, random_engine_pair,  # noqa: E402
                                serve_continuous)
from repro.serving.batch_engine import BatchEngine  # noqa: E402
from repro.serving.tp import TPContext  # noqa: E402

DRAFT = dataclasses.replace(PHI3, name=f"{PHI3.name}-draft2", n_layers=2)
# engine rows: a request's worst case is its prompt (15-27 toy tokens) plus
# 351 tokens at --budget 256 (ContinuousScheduler._worst_case_tokens)
MAX_LEN = 512
# 8 rows x 512 tokens of phi3 KV (393,216 B/token in bf16) is 1.6 GB; the
# base partition (80%) must hold every admitted row plus its prefix pages
SERVE_ARGV = ["--scheduler", "continuous", "-n", "8", "--temperature", "0.6",
              "--batch", "8", "--budget", "256", "--spec-decode",
              "--gamma", "4", "--num-samples", "2", "--kv-budget-mb", "2048"]
TP_ARGV = ["--scheduler", "continuous", "-n", "4", "--temperature", "0",
           "--batch", "4", "--budget", "64", "--spec-decode", "--gamma", "4",
           "--kv-budget-mb", "1024"]
DECODE_STEPS = 16
PROMPT_LEN = 32
# Logits of two bf16 computations of the same 32-layer function that round
# at different points (cached vs uncached attention, sharded vs unsharded
# dots): each op rounds at 2^-9 relative and the differences compound over
# the layers.  5% of the reference's largest |logit| bounds that drift and
# still fails a wrong cache position, mask or shard (an O(1) change).
LOGIT_TOL = 0.05


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


class CompileClock:
    """Backend compiles (persistent-cache loads included): how many, the
    seconds they took, and how many were persistent-cache hits."""

    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def device_bytes(stat: str = "peak_bytes_in_use") -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get(stat, 0))


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want))
                 / max(1.0, float(np.max(np.abs(want)))))


# ---------------------------------------------------------------- phases


def phase_device(chips: int) -> dict:
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log("device", json.dumps(dev))
    if dev["platform"] != "tpu":
        sys.exit(f"[device] no TPU: JAX found {dev['platform']}; this "
                 f"smoke does not fall back")
    check(len(devs) >= chips, f"{chips} chips asked for, {len(devs)} found")
    return dev


def phase_kernels() -> None:
    key = jax.random.PRNGKey(0)
    for case in ALL_CASES:
        key, sub = jax.random.split(key)
        args = jax.jit(case.make_args)(sub)
        lowered = case.kernel.lower(*args, **case.kwargs, interpret=False)
        check("tpu_custom_call" in lowered.as_text(),
              f"{case.name} lowered without tpu_custom_call")
        got = case.kernel(*args, **case.kwargs, interpret=False)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(case.reference)(*args)
        err = max(rel_err(g, w) for g, w in zip(jax.tree.leaves(got),
                                                jax.tree.leaves(want)))
        log("kernels", f"{case.name}: compiled (tpu_custom_call), "
                       f"max|kernel-ref|/max(1,|ref|) = {err:.3e} "
                       f"<= {case.tol} ({case.why})")
        check(err <= case.tol, f"{case.name} error {err} > {case.tol}")


def phase_served(base, small, clock: CompileClock) -> None:
    args = parse_args(SERVE_ARGV)
    rng = random.Random(args.seed)
    reqs = [tasks.sample_task(rng) for _ in range(args.num_requests)]
    log("served", f"base {base.model.cfg.name} ({base.model.cfg.n_layers} "
                  f"layers) / draft {small.model.cfg.name} (2-layer "
                  f"stand-in, random weights): {' '.join(SERVE_ARGV)}")
    n0, c0, t0 = clock.compiles, clock.seconds, time.perf_counter()
    handles, stats = serve_continuous(args, base, small, reqs, fused=True)
    wall = time.perf_counter() - t0
    check(len(handles) == args.num_requests * args.num_samples,
          f"{len(handles)} handles")
    bad = [h.status for h in handles if h.status != "ok"]
    check(not bad, f"requests not ok: {bad}")
    check(stats["resilience_quarantines"] == 0
          and stats["resilience_failed"] == 0,
          f"quarantines={stats['resilience_quarantines']} "
          f"failed={stats['resilience_failed']}")
    think = [h.result.n_thinking_tokens for h in handles]
    check(min(think) > 0, f"thinking tokens {think}")
    rounds = sum(h.result.spec_stats.rounds for h in handles)
    check(rounds > 0, "no spec rounds ran")
    hits = sum(h.cache_hit_tokens for h in handles)
    check(hits > 0, "no prompt token came from the prefix cache")
    log("served", f"{len(handles)} requests ok, thinking tokens {think}, "
                  f"spec rounds {rounds}, prefix-cache prompt tokens {hits}"
                  f"/{sum(h.prompt_tokens for h in handles)}")
    log("served", f"info: wall {wall:.1f} s, {clock.compiles - n0} "
                  f"compiles in {clock.seconds - c0:.1f} s, peak device "
                  f"bytes {device_bytes()}")


def phase_cache_path(base) -> None:
    model, params = base.model, base.params
    be = BatchEngine(model, params, batch=8, capacity=MAX_LEN,
                     name="cache-path")
    row = be.alloc_row()
    prompt = [int(t) for t in jax.random.randint(
        jax.random.PRNGKey(2), (PROMPT_LEN,), 0, model.cfg.vocab_size)]
    cached = list(be.extend_rows([row], [prompt], want_logits=True)[0])
    tokens = list(prompt)
    for _ in range(DECODE_STEPS):
        tokens.append(int(np.argmax(be.last_logits[row])))
        be.feed_rows([row], [tokens[-1]])
        cached.append(be.last_logits[row].copy())
    want, _ = jax.jit(model.forward)(params, jnp.asarray([tokens]))
    want = np.asarray(want[0], np.float32)
    cached = np.stack(cached)
    err = rel_err(cached, want)
    same = int(np.sum(cached.argmax(-1) == want.argmax(-1)))
    log("cache", f"prefill {PROMPT_LEN} + {DECODE_STEPS} decode steps vs "
                 f"uncached forward (bf16 params): max|d|/max(1,|ref|) = "
                 f"{err:.3e} <= {LOGIT_TOL} (bf16 rounding at different "
                 f"points, compounded over {model.cfg.n_layers} layers); "
                 f"argmax equal at {same}/{len(tokens)} positions; max|d| = "
                 f"{float(np.max(np.abs(cached - want))):.4g}, max|ref| = "
                 f"{float(np.max(np.abs(want))):.4g}")
    check(err <= LOGIT_TOL, f"cache-path error {err} > {LOGIT_TOL}")


def _prefill_logits(base, prompts, tp):
    be = BatchEngine(base.model, base.params, batch=len(prompts),
                     capacity=64, name=f"prefill-tp{tp}",
                     tp=TPContext.build(tp) if tp > 1 else None)
    rows = [be.alloc_row() for _ in prompts]
    be.extend_rows(rows, prompts)
    return be.last_logits[rows].copy()


def phase_tp(base, small) -> None:
    out = {}
    for tp in (1, 4):
        args = parse_args(TP_ARGV + ["--tp", str(tp)])
        rng = random.Random(args.seed)
        reqs = [tasks.sample_task(rng) for _ in range(args.num_requests)]
        t0 = time.perf_counter()
        handles, _ = serve_continuous(args, base, small, reqs, fused=True)
        bad = [h.status for h in handles if h.status != "ok"]
        check(not bad, f"tp={tp}: requests not ok: {bad}")
        toks = [(h.result.thinking_ids, h.result.answer_ids)
                for h in handles]
        del handles
        gc.collect()           # free this scheduler before the next one
        logits = _prefill_logits(
            base, [tasks.question_tokens(t) for t in reqs], tp)
        gc.collect()
        out[tp] = (toks, logits)
        log("tp", f"tp={tp}: {len(toks)} requests ok in "
                  f"{time.perf_counter() - t0:.1f} s (info), peak device-0 "
                  f"bytes {device_bytes()}")
    (t1, l1), (t4, l4) = out[1], out[4]
    same = sum(a == b for a, b in zip(t1, t4))
    diff = float(np.max(np.abs(l1 - l4)))
    err = rel_err(l4, l1)
    # greedy tokens are reported, not required: once a bf16 logit differs
    # in its last bit, a near-tie may pick another token and the request
    # continues from a different context
    log("tp", f"tp=4 vs tp=1: tokens identical for {same}/{len(t1)} "
              f"requests; logits after prefill max|d| = {diff:.4g} "
              f"({'bitwise equal' if diff == 0 else 'not bitwise'}), "
              f"max|d|/max(1,|ref|) = {err:.3e} <= {LOGIT_TOL} (sharded "
              f"dots may tile and round differently in bf16)")
    check(err <= LOGIT_TOL, f"tp logits error {err} > {LOGIT_TOL}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: device, kernel, served and cache phases; "
                         "4: the tensor-parallel phase only")
    opts = ap.parse_args(argv)
    log("setup", f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    dev = phase_device(opts.chips)
    if opts.chips == 1:
        phase_kernels()
    base, small = random_engine_pair(PHI3, DRAFT, MAX_LEN,
                                     dtype=jnp.bfloat16)
    log("setup", f"info: device bytes in use with the pair's weights "
                 f"{device_bytes('bytes_in_use')}, peak so far "
                 f"{device_bytes()}")
    if opts.chips == 1:
        phase_served(base, small, clock)
        gc.collect()           # the served run's scheduler and KV
        phase_cache_path(base)
    else:
        phase_tp(base, small)
    log("done", f"info: total {time.perf_counter() - t0:.1f} s, "
                f"{clock.compiles} compiles in {clock.seconds:.1f} s "
                f"({clock.cache_hits} persistent-cache hits), peak device "
                f"bytes {device_bytes()}")
    print(json.dumps({"ok": True, "device": dev}), flush=True)


if __name__ == "__main__":
    main()
