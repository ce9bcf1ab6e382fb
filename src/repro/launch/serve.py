"""Serving driver CLI — the end-to-end example the paper's kind dictates:
serve a batch of reasoning requests through SpecReason on the trained toy
testbed pair, printing per-request latency/accuracy and aggregate stats.

All schemes decode through the engines' fused on-device loop by default
(one jitted while_loop per generate call, see DESIGN.md); pass
``--decode-loop eager`` to fall back to the per-token reference loop and
see how much of the "latency" is pure host dispatch.

``--scheduler continuous`` serves the requests through the
continuous-batching scheduler instead of one-at-a-time: every tick batches
all drafting requests into one small-model call and all verifying /
regenerating requests into one base-model call (``--batch`` concurrent
rows, paged-KV admission control).  ``--arrival-rate`` simulates Poisson
arrivals (req/s; 0 = all at t=0).

``--spec-decode`` turns on *hierarchical speculation* on the continuous
scheduler (SpecReason+Decode, §4.2): every fallback regeneration and
final answer decodes through batched token-level speculative decoding —
one fused gamma-token draft proposal, one base verification prefill and
one fused acceptance program per round across all in-flight rows, with
rejected suffixes rolled back by paged block-table truncation.  Outputs
stay token-identical to spec-off greedy serving; per-request acceptance
rate and mean accepted length are reported alongside the meters.

The continuous scheduler carries a radix-tree **prefix cache** over its
paged KV pools (on by default; ``--no-prefix-cache`` to disable): prompts
sharing a block-aligned prefix — best-of-N samples, template families,
preempted-and-readmitted requests — prefill only their suffix, the rest
restored from shared refcounted cached blocks.  Per-request lines show
``cache[hit=H/P]`` and the summary reports the aggregate hit rate.

``--num-samples N --vote`` turns the workload into best-of-N
self-consistency: every prompt is sampled N times (the N-1 re-prefills
are cache hits) and the final answer is the majority vote over the N
sampled answers, with the per-task vote breakdown printed.

Admission prefill on the continuous scheduler is **chunked** by default
(stall-free decode scheduling): each tick prefills at most
``--max-prefill-tokens`` prompt tokens across all admitting requests and
still runs every in-flight request's decode/speculation phases, so a
long prompt never stalls the batch.  ``--no-chunked-prefill`` restores
monolithic admission prefill; outputs are token-identical either way.
The summary reports p50/p95 TTFT (time to first output token), TPOT
(per-output-token latency) and prefill-stall time.  ``--verbose`` logs
admission, per-chunk prefill progress and preemption events.

**Overload resilience** (continuous scheduler): ``--deadline S`` gives
every request a wall-clock deadline (expired requests are cancelled
mid-flight with status ``timeout`` and their KV reclaimed),
``--shed-policy priority`` sheds queued requests that cannot meet their
deadline or overflow the queue (lowest priority first, best-of-N
siblings whose group still has survivors preferred — the vote then runs
over the survivors), ``--slo-tpot S`` feeds the overload controller and
the goodput accounting, and ``--degrade`` enables the graceful
speculation-degradation ladder (shrink gamma -> token-level spec off ->
smaller prefill chunks -> no cache insertion, stepping back up with
hysteresis).  ``--inject-faults SEED[:N]`` runs deterministic chaos
(NaN logits / engine raises / pool exhaustion / stalled ticks;
quarantine + one retry with speculation disabled), and ``--audit``
verifies the pool-refcount / block-table / radix-cache invariants every
tick.  The ``[resilience]`` line and per-request ``status=`` report the
outcome mix.

**Observability** (continuous scheduler): ``--trace out.json`` records a
per-request / per-tick span timeline into a bounded ring buffer
(``--trace-buffer N`` events) and exports it as Chrome trace-event JSON
— open it in Perfetto / chrome://tracing, or run
``tools/trace_report.py out.json`` for a per-request waterfall, a
phase-attribution table and the speculation funnel.  ``--metrics-out
metrics.prom`` writes a Prometheus-style text exposition of the serving
metrics (TTFT/TPOT/chunk-latency/accepted-length histograms, request
and token counters, pressure/occupancy gauges) after the run.  Tracing
never alters outputs: traced runs are token-identical to untraced ones.

**Live observability plane** (continuous scheduler): ``--admin-port P``
starts a daemon-threaded read-only HTTP server (port 0 = OS-assigned,
printed as ``[admin] listening on ...``) exposing ``/healthz``,
``/metrics`` (live Prometheus scrape), ``/status`` (the per-tick
scheduler snapshot: queue depth, active rows with phase+cursor, pool
occupancy, pressure, ladder level, fault counters, monitor values),
``/requests/<id>`` (one request's span timeline) and ``/trace?last=N``
(a rolling ring slice); ``--admin-linger S`` keeps it up S seconds
after the run for terminal scrapes.  ``--snapshot-every S`` flushes the
``--trace``/``--metrics-out`` artifacts periodically during the run
(atomic renames — an interrupted run still leaves valid telemetry);
both artifacts are also always flushed in a ``finally``.
``--monitor-window N`` sizes the rolling speculation-quality monitors
(token/step acceptance, SLO burn, quarantine rate, recompile storms; 0
disables) that ride along whenever the plane is active — a firing
monitor feeds the overload controller as a pressure input, so sustained
acceptance collapse walks the ``--degrade`` ladder.  Artifacts for the
sequential scheduler: ``--metrics-out`` serves end-of-run meter-derived
metrics (``--trace`` is ignored with a warning — no tick timeline
exists there).

**Compile & device plane** (continuous scheduler): whenever tracing or
metrics are on, a compile sentinel (serving/compile_watch.py) watches
every engine dispatch's abstract signature — each distinct signature is
one XLA compilation, counted per op, costed via ``cost_analysis()``,
spanned on the ``compile`` tracer track and summarized in the
``[compile]`` end-of-run line; post-warmup recompiles feed the
recompile monitor (bucket churn walks the ``--degrade`` ladder) and a
device-memory watch samples ``device.memory_stats()`` + model/KV-pool
byte accounting into gauges and ``/status``.
``--xla-profile-dir DIR`` additionally arms the admin ``/profile?
seconds=S`` endpoint (an on-demand ``jax.profiler`` capture into DIR).
SIGTERM/SIGINT flush the telemetry artifacts before exiting, so an
orchestrator kill still leaves valid traces/metrics.

  PYTHONPATH=src python -m repro.launch.serve --scheme specreason -n 8
  PYTHONPATH=src python -m repro.launch.serve --scheme all -n 4 --threshold 5
  PYTHONPATH=src python -m repro.launch.serve --decode-loop eager -n 2
  PYTHONPATH=src python -m repro.launch.serve --scheduler continuous \\
      --batch 8 -n 16 --arrival-rate 2
  PYTHONPATH=src python -m repro.launch.serve --scheduler continuous \\
      --spec-decode --gamma 4 --batch 8 -n 16
  PYTHONPATH=src python -m repro.launch.serve --scheduler continuous \\
      --num-samples 4 --vote -n 4
"""

from __future__ import annotations

import argparse
import json
import os
import random
import signal

import jax
import jax.numpy as jnp

from ..core.baselines import spec_decode_reason, vanilla_reason
from ..core.controller import SpecReason, SpecReasonConfig
from ..core.policies import StaticThreshold
from ..data import tasks
from ..data.evaluate import is_correct
from ..models.model import Model
from ..sampling.sample import SamplingParams
from ..serving.admin import AdminServer, StatusBoard
from ..serving.compile_watch import (CompileWatch, MemoryWatch,
                                     ProfilerCapture)
from ..serving.engine import Engine
from ..serving.faults import FaultInjector, FaultPlan
from ..serving.kv_manager import KVBudget, KVManager
from ..serving.loader import load_testbed_engines
from ..serving.monitors import MonitorConfig, Monitors
from ..serving.resilience import ResilienceConfig
from ..serving.scheduler import ContinuousScheduler
from ..serving.telemetry import (TTFT_BUCKETS, MetricsRegistry,
                                 ServingMetrics, Tracer, atomic_write)
from ..serving.workload import (expand_best_of_n, majority_vote,
                                poisson_arrivals, run_workload, summarize)
from ..tokenizer import toy as tk
from .compile_cache import enable_compile_cache

SCHEMES = ("base", "small", "specdecode", "specreason", "specreason+decode")


def run_scheme(scheme: str, base, small, task, key, budget: int,
               threshold: float, temperature: float, fused: bool = True):
    prompt = tasks.question_tokens(task)
    sp = SamplingParams(temperature=temperature)
    if scheme == "base":
        return vanilla_reason(base, prompt, key, budget, sp, fused=fused)
    if scheme == "small":
        return vanilla_reason(small, prompt, key, budget, sp, fused=fused)
    if scheme == "specdecode":
        return spec_decode_reason(base, small, prompt, key, budget, sp,
                                  fused=fused)
    cfg = SpecReasonConfig(policy=StaticThreshold(threshold),
                           token_budget=budget, sampling=sp,
                           use_spec_decode=(scheme == "specreason+decode"),
                           fused_decode=fused)
    return SpecReason(base, small, cfg).run(prompt, key)


def _meter_line(name: str, m: dict) -> str:
    dt, dc = m.get("decode_tokens", 0), m.get("decode_calls", 0)
    tok_s = dt / m["decode_time"] if m.get("decode_time") else 0.0
    line = (f"    {name}: decode {dt} tok / {dc} calls "
            f"({tok_s:.0f} tok/s), prefill {m.get('prefill_tokens', 0)} tok "
            f"/ {m.get('prefill_calls', 0)} calls")
    if m.get("spec_rounds"):
        line += (f", spec {m['spec_accepted']}/{m['spec_proposed']} "
                 f"accepted over {m['spec_rounds']} rounds")
    if m.get("cache_lookup_tokens"):
        line += (f", cache {m['cache_hit_tokens']}"
                 f"/{m['cache_lookup_tokens']} prompt tok "
                 f"({m.get('cache_evictions', 0)} evictions)")
    return line


def _spec_suffix(res) -> str:
    """Per-request acceptance breakdown for hierarchical runs."""
    s = res.spec_stats
    if not s.rounds:
        return ""
    return (f" spec[acc={s.acceptance_rate:.2f} "
            f"len={s.mean_accepted_len:.1f}/{s.rounds}r]")


def _cache_suffix(h) -> str:
    """Per-request radix prefix-cache line: cached/total prompt tokens."""
    if not h.prompt_tokens:
        return ""
    return f" cache[hit={h.cache_hit_tokens}/{h.prompt_tokens}]"


def sequential_metrics(base, small, latencies, out_tokens: int) -> str:
    """End-of-run Prometheus exposition for the SEQUENTIAL path, derived
    from the engines' Meters — so an A/B pair of sequential/continuous
    runs produces comparable ``--metrics-out`` artifacts.  Per-tick
    gauges (queue depth, pressure, occupancy) do not exist here; the
    request/token counters, per-engine meter counters and an e2e
    latency histogram do."""
    reg = MetricsRegistry()
    req = reg.counter("specreason_requests_total",
                      "Terminal request outcomes.",
                      labelnames=("status",))
    req.inc(len(latencies), status="ok")
    out = reg.counter("specreason_output_tokens_total",
                      "Thinking + answer tokens across finished requests.")
    out.inc(out_tokens)
    e2e = reg.histogram("specreason_e2e_latency_seconds",
                        "End-to-end request latency (s; sequential "
                        "serving is one request start-to-finish).",
                        TTFT_BUCKETS)
    for s in latencies:
        e2e.observe(s)
    tok = reg.counter("specreason_engine_tokens_total",
                      "Engine tokens processed, from the Meters.",
                      labelnames=("engine", "op"))
    calls = reg.counter("specreason_engine_calls_total",
                        "Engine calls issued, from the Meters.",
                        labelnames=("engine", "op"))
    spec = reg.counter("specreason_spec_tokens_total",
                       "Token-level spec-decode draft tokens.",
                       labelnames=("engine", "kind"))
    for e in (base, small):
        m = e.meter
        tok.inc(m.decode_tokens, engine=e.name, op="decode")
        tok.inc(m.prefill_tokens, engine=e.name, op="prefill")
        calls.inc(m.decode_calls, engine=e.name, op="decode")
        calls.inc(m.prefill_calls, engine=e.name, op="prefill")
        if m.spec_rounds:
            spec.inc(m.spec_proposed, engine=e.name, kind="proposed")
            spec.inc(m.spec_accepted, engine=e.name, kind="accepted")
    return reg.render()


def serve_continuous(args, base, small, reqs, fused: bool):
    """Continuous-batching serving path: paged-KV admission + per-tick
    speculate/verify batching (serving.scheduler.ContinuousScheduler).
    Returns ``(handles, stats)``: every request's handle and the summary
    dict printed as the run's last JSON line."""
    import time
    cfg = SpecReasonConfig(policy=StaticThreshold(args.threshold),
                           token_budget=args.budget,
                           sampling=SamplingParams(
                               temperature=args.temperature),
                           use_spec_decode=args.spec_decode,
                           spec_gamma=args.gamma,
                           fused_decode=fused)
    ctrl = SpecReason(base, small, cfg)
    kv = KVManager(base.model.cfg, small.model.cfg,
                   KVBudget(total_bytes=args.kv_budget_mb << 20))
    res_cfg = ResilienceConfig(slo_tpot_s=args.slo_tpot,
                               shed_policy=args.shed_policy,
                               degrade=args.degrade)
    injector = None
    if args.inject_faults:
        seed, _, nf = args.inject_faults.partition(":")
        injector = FaultInjector(FaultPlan.random(
            seed=int(seed), n_faults=int(nf) if nf else 4,
            n_requests=len(reqs) * args.num_samples, max_tick=8))
    tracer = Tracer(buffer=args.trace_buffer) if args.trace else None
    # the admin plane serves /metrics live, so --admin-port implies a
    # registry even without --metrics-out
    admin_on = args.admin_port is not None
    metrics = ServingMetrics() if (args.metrics_out or admin_on) else None
    # rolling speculation-quality monitors ride along whenever any part
    # of the observability plane is active (--monitor-window 0 disables);
    # they only observe — token outputs are identical monitors-on/off
    monitors = None
    if args.monitor_window > 0 and (tracer is not None
                                    or metrics is not None):
        monitors = Monitors(MonitorConfig(window=args.monitor_window,
                                          slo_tpot_s=args.slo_tpot))
    board = StatusBoard() if admin_on else None
    # compile/device plane: the recompilation sentinel + device-memory
    # watch ride along whenever any plane substrate is active.  Both only
    # observe (the sentinel's cost-model compile is an abstract twin that
    # never executes), so outputs stay token-identical plane-on/off.
    plane_on = tracer is not None or metrics is not None
    compile_watch = CompileWatch(tracer=tracer, metrics=metrics,
                                 monitors=monitors) if plane_on else None
    memory_watch = MemoryWatch(metrics=metrics) if plane_on else None
    profiler = (ProfilerCapture(args.xla_profile_dir)
                if args.xla_profile_dir else None)

    def _flush_artifacts() -> None:
        # crash-safe flush: atomic tmp-file renames, shared by the
        # end-of-run finally, the periodic --snapshot-every path and the
        # SIGTERM/SIGINT handlers
        if tracer is not None and args.trace:
            tracer.export(args.trace)
        if metrics is not None and args.metrics_out:
            atomic_write(args.metrics_out, metrics.render())

    def _on_signal(signum, frame) -> None:
        # orchestrator kill (SIGTERM) / Ctrl-C: flush the artifacts,
        # then die by the default disposition so the exit status still
        # reports the signal truthfully
        _flush_artifacts()
        signal.signal(signum, signal.SIG_DFL)
        os.kill(os.getpid(), signum)

    if args.trace or args.metrics_out:
        for _sig in (signal.SIGTERM, signal.SIGINT):
            signal.signal(_sig, _on_signal)

    on_tick = None
    if args.snapshot_every is not None and (args.trace
                                            or args.metrics_out):
        last_flush = [time.monotonic()]

        def on_tick(snap) -> None:
            now = time.monotonic()
            if now - last_flush[0] >= args.snapshot_every:
                last_flush[0] = now
                _flush_artifacts()

    sched = ContinuousScheduler(ctrl, kv, max_batch=args.batch,
                                context_capacity=min(base.max_len,
                                                     args.budget + 64),
                                prefix_cache=not args.no_prefix_cache,
                                chunked_prefill=args.chunked_prefill,
                                max_prefill_tokens=args.max_prefill_tokens,
                                resilience=res_cfg, faults=injector,
                                audit=args.audit,
                                on_event=(lambda s: print(f"[sched] {s}"))
                                if args.verbose else None,
                                tracer=tracer, metrics=metrics,
                                monitors=monitors, status_board=board,
                                on_tick=on_tick,
                                compile_watch=compile_watch,
                                memory_watch=memory_watch,
                                tp_size=args.tp)
    admin = None
    if admin_on:
        admin = AdminServer(board=board, metrics=metrics, tracer=tracer,
                            profiler=profiler,
                            port=args.admin_port).start()
        # flush: CI smoke discovers the OS-assigned port from this line
        # through a block-buffered subprocess pipe
        print(f"[admin] listening on http://{admin.host}:{admin.port}",
              flush=True)
    rng = random.Random(args.seed)
    pairs = [(t, jax.random.PRNGKey(1000 * args.seed + i))
             for i, t in enumerate(reqs)]
    if args.num_samples > 1:
        # best-of-N / self-consistency: every prompt becomes N sampled
        # reasoning chains whose prefills share one set of cached blocks
        pairs = expand_best_of_n(pairs, args.num_samples)
    # per-request submit opts: deadline + best-of-N sibling group (the
    # shed policy prefers victims whose group still has survivors)
    opts = [{"deadline_s": args.deadline,
             "group": f"task{i // args.num_samples}"
             if args.num_samples > 1 else None}
            for i in range(len(pairs))]
    arrivals = poisson_arrivals(len(pairs), args.arrival_rate, rng)
    try:
        t0 = time.perf_counter()
        handles = run_workload(sched, pairs, arrivals, opts=opts)
        wall = time.perf_counter() - t0
    finally:
        # telemetry artifacts land even when the run is interrupted or
        # faults out (the crash-safe flush contract); prints are flushed
        # so a piped CI smoke can sequence its scrapes on them
        _flush_artifacts()
        if tracer is not None:
            print(f"[trace] {args.trace}: {len(tracer.entries())} "
                  f"events ({tracer.dropped} dropped)", flush=True)
        if metrics is not None and args.metrics_out:
            print(f"[metrics] {args.metrics_out}", flush=True)
    tag = "hierspec" if args.spec_decode else "continuous"
    for i, h in enumerate(handles):
        res = h.result
        if res is None:
            # shed / timed out / failed: no output to grade, print the
            # structured outcome instead
            print(f"[{tag}] req{i}: --- status={h.status}"
                  f" ({h.error if h.error else 'no error'})")
            continue
        ok = is_correct(h.task, res.answer_ids)
        print(f"[{tag}] req{i}: {'OK ' if ok else 'BAD'} "
              f"status={h.status} "
              f"lat={h.e2e_latency:.2f}s think={res.n_thinking_tokens}"
              f"{_spec_suffix(res)}{_cache_suffix(h)} "
              f"answer={tk.detok(res.answer_ids)}")
        if args.meters:
            for name, m in res.meters.items():
                print(_meter_line(name, m))
    stats = summarize(handles, wall, slo_tpot_s=args.slo_tpot)
    graded = [h for h in handles if h.result is not None]
    accuracy = sum(is_correct(h.task, h.result.answer_ids)
                   for h in graded) / max(len(graded), 1)
    if args.vote:
        votes = majority_vote(handles, args.num_samples)
        for i, v in enumerate(votes):
            ok = is_correct(v.task, v.winner_ids)
            breakdown = ", ".join(
                f"{tk.detok(list(a))}x{c}"
                for a, c in sorted(v.counts.items(),
                                   key=lambda kv_: -kv_[1]))
            print(f"[vote] task{i}: {'OK ' if ok else 'BAD'} "
                  f"agree={v.agreement:.2f} [{breakdown}] "
                  f"-> {tk.detok(v.winner_ids)}")
        accuracy = sum(is_correct(v.task, v.winner_ids)
                       for v in votes) / max(len(votes), 1)
    stats.update({
        "scheduler": "continuous", "batch": args.batch,
        "spec_decode": args.spec_decode, "gamma": args.gamma,
        "arrival_rate": args.arrival_rate, "ticks": sched.ticks,
        "preemptions": sched.preemptions,
        "prefix_cache": not args.no_prefix_cache,
        "chunked_prefill": args.chunked_prefill,
        "max_prefill_tokens": args.max_prefill_tokens,
        "prefill_chunks": sched.prefill_chunks,
        "num_samples": args.num_samples, "vote": args.vote,
        "accuracy": accuracy,
    })
    if "p95_ttft_s" in stats:
        print(f"[latency] ttft p50={stats['p50_ttft_s']:.3f}s "
              f"p95={stats['p95_ttft_s']:.3f}s | tpot "
              f"p50={stats.get('p50_tpot_s', 0.0) * 1e3:.1f}ms "
              f"p95={stats.get('p95_tpot_s', 0.0) * 1e3:.1f}ms | "
              f"prefill stall "
              f"mean={stats.get('mean_prefill_stall_s', 0.0):.3f}s "
              f"p95={stats.get('p95_prefill_stall_s', 0.0):.3f}s")
    rs = sched.resilience_stats()
    print(f"[resilience] goodput={stats['goodput_req_s']:.3f} req/s "
          f"(slo_met={stats['slo_met']}/{len(handles)}) | "
          f"timeout={rs['timeouts']} shed={rs['shed']} "
          f"failed={rs['failed']} | quarantines={rs['quarantines']} "
          f"retries={rs['retries']} stalled_ticks={rs['stalled_ticks']} | "
          f"degrade_level={rs['level']} pressure={rs['pressure']:.2f} "
          f"audit_violations={rs['audit_violations']}")
    stats.update({f"resilience_{k}": v for k, v in rs.items()
                  if k in ("timeouts", "shed", "failed", "quarantines",
                           "retries", "stalled_ticks", "level",
                           "audit_violations")})
    stats.update({f"cache_{w}_{k}": v
                  for w, s in sched.cache_stats().items()
                  for k, v in s.items() if k in ("hit_rate",
                                                 "evicted_blocks")})
    if monitors is not None and monitors.alerts:
        for ev in monitors.alerts:
            print(f"[monitor] {ev}")
    if compile_watch is not None:
        cs = compile_watch.as_dict()
        print(f"[compile] {cs['programs']} programs / {cs['compiles']} "
              f"compiles ({cs['post_warmup']} post-warmup)", flush=True)
        stats.update({"compile_programs": cs["programs"],
                      "compiles": cs["compiles"],
                      "post_warmup_compiles": cs["post_warmup"]})
    if memory_watch is not None and sched.last_memory is not None:
        mem = sched.last_memory
        print(f"[memory] model={mem['model_bytes']} "
              f"kv={sum(mem['pool_bytes'].values())} "
              f"accounted={mem['accounted_bytes']} "
              f"peak={mem['peak_bytes']} bytes "
              f"({mem['backend']})", flush=True)
        stats.update({"memory_accounted_bytes": mem["accounted_bytes"],
                      "memory_peak_bytes": mem["peak_bytes"]})
    print(json.dumps(stats), flush=True)
    if admin is not None:
        if args.admin_linger > 0:
            # keep the endpoints up so a terminal scrape deterministically
            # sees the same bytes the .prom file got
            print(f"[admin] lingering {args.admin_linger:g}s for final "
                  f"scrapes", flush=True)
            time.sleep(args.admin_linger)
        admin.stop()
    return handles, stats


def parse_args(argv=None) -> argparse.Namespace:
    """The CLI's argument namespace, validated — what ``main`` serves
    from, and what a script driving ``serve_continuous`` itself builds."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--scheme", choices=SCHEMES + ("all",),
                    default="specreason")
    ap.add_argument("-n", "--num-requests", type=int, default=8)
    ap.add_argument("--budget", type=int, default=160)
    ap.add_argument("--threshold", type=float, default=7.0)
    ap.add_argument("--temperature", type=float, default=0.6)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="exp/ckpt")
    ap.add_argument("--testbed", choices=("trained", "micro"),
                    default="trained",
                    help="trained = load (or lazily train) the testbed "
                         "checkpoint pair; micro = the random-init "
                         "dispatch-bound micro pair (instant startup, "
                         "nonsense answers — scheduling/latency smoke "
                         "runs only)")
    ap.add_argument("--decode-loop", choices=("fused", "eager"),
                    default="fused",
                    help="fused = one jitted while_loop per generate call "
                         "(default); eager = per-token reference loop")
    ap.add_argument("--meters", action="store_true",
                    help="print the per-engine meter breakdown per request")
    ap.add_argument("--scheduler", choices=("sequential", "continuous"),
                    default="sequential",
                    help="sequential = one request start-to-finish per turn "
                         "(the paper's regime); continuous = step-"
                         "interleaved continuous batching with paged-KV "
                         "admission")
    ap.add_argument("--batch", type=int, default=8,
                    help="continuous scheduler: max concurrent rows")
    ap.add_argument("--tp", type=int, default=1, metavar="N",
                    help="continuous scheduler: tensor-parallel degree — "
                         "shard both engines, their KV state and the "
                         "page stores over an N-device ('model',) mesh "
                         "(bit-exact vs --tp 1: outputs are "
                         "token-identical per request; N must divide "
                         "both models' heads AND kv-heads; on CPU use "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=8 to fake devices)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="Poisson arrival rate in req/s (0 = burst at t=0)")
    ap.add_argument("--kv-budget-mb", type=int, default=64,
                    help="continuous scheduler: HBM budget for the static "
                         "base/small KV partition")
    ap.add_argument("--spec-decode", action="store_true",
                    help="continuous scheduler: hierarchical speculation "
                         "— batched token-level spec decode for fallback "
                         "regenerations and final answers (§4.2)")
    ap.add_argument("--gamma", type=int, default=4,
                    help="spec decode: draft tokens proposed per "
                         "verification round")
    ap.add_argument("--num-samples", type=int, default=1,
                    help="best-of-N / self-consistency: sample N "
                         "reasoning chains per prompt (continuous "
                         "scheduler; the radix prefix cache makes the "
                         "N-1 extra prefills cache hits)")
    ap.add_argument("--vote", action="store_true",
                    help="majority-vote the N sampled answers per prompt "
                         "(accuracy is then per-task, over the voted "
                         "answers)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable the radix prefix cache over the paged "
                         "KV pools (continuous scheduler)")
    ap.add_argument("--chunked-prefill", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="continuous scheduler: chunk admission prefill "
                         "so no tick prefills more than "
                         "--max-prefill-tokens prompt tokens and decode "
                         "never stalls behind a long prompt (default on; "
                         "outputs are token-identical either way)")
    ap.add_argument("--max-prefill-tokens", type=int, default=64,
                    help="chunked prefill: per-tick prompt-prefill token "
                         "budget across all admitting requests")
    ap.add_argument("--verbose", action="store_true",
                    help="log admission / chunk-progress / preemption "
                         "scheduler events (continuous scheduler)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="continuous scheduler: per-request deadline in "
                         "seconds — a request still unfinished this long "
                         "after submission is cancelled with status "
                         "'timeout' and its KV blocks reclaimed")
    ap.add_argument("--slo-tpot", type=float, default=None,
                    help="per-output-token latency SLO in seconds: feeds "
                         "the overload controller's strain signal and the "
                         "goodput accounting (an over-SLO completion does "
                         "not count toward goodput)")
    ap.add_argument("--shed-policy", choices=("none", "priority"),
                    default="none",
                    help="overload shedding: 'priority' sheds queued "
                         "requests (lowest priority first, best-of-N "
                         "siblings with surviving group members "
                         "preferred) when a request cannot meet its "
                         "deadline or the queue exceeds capacity; "
                         "'none' never sheds (default)")
    ap.add_argument("--degrade", action="store_true",
                    help="enable the graceful speculation-degradation "
                         "ladder: under sustained pressure the scheduler "
                         "steps down gamma -> token-level spec off -> "
                         "smaller prefill chunks -> no cache insertion, "
                         "and back up with hysteresis")
    ap.add_argument("--inject-faults", default=None, metavar="SEED[:N]",
                    help="deterministic chaos mode: inject N (default 4) "
                         "seeded faults (NaN logits, engine raise, pool "
                         "exhaustion, stalled tick) into the run; faulted "
                         "requests are quarantined and retried once with "
                         "speculation disabled")
    ap.add_argument("--audit", action="store_true",
                    help="run the per-tick invariant audits (pool "
                         "refcount ledger, block-table consistency, "
                         "radix-cache agreement); any violation raises")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="continuous scheduler: record a per-request / "
                         "per-tick span timeline and export it as Chrome "
                         "trace-event JSON (open in Perfetto or "
                         "chrome://tracing; analyze with "
                         "tools/trace_report.py)")
    ap.add_argument("--metrics-out", default=None, metavar="OUT.prom",
                    help="continuous scheduler: write a Prometheus-style "
                         "text exposition of the serving metrics "
                         "(TTFT/TPOT/chunk-latency/acceptance "
                         "histograms, request/token counters, "
                         "pressure/occupancy gauges) after the run")
    ap.add_argument("--trace-buffer", type=int, default=65536,
                    help="tracer ring-buffer capacity in events; the "
                         "oldest events are dropped beyond this "
                         "(default 65536)")
    ap.add_argument("--admin-port", type=int, default=None, metavar="PORT",
                    help="continuous scheduler: start the read-only admin "
                         "HTTP plane on 127.0.0.1:PORT (0 = OS-assigned, "
                         "printed) — /healthz, /metrics (live Prometheus "
                         "scrape), /status (per-tick scheduler snapshot), "
                         "/requests/<id>, /trace?last=N, and "
                         "— with --xla-profile-dir — /profile?seconds=S")
    ap.add_argument("--admin-linger", type=float, default=0.0, metavar="S",
                    help="keep the admin endpoints up S seconds after the "
                         "run drains (terminal scrapes see the same bytes "
                         "the artifacts got); default 0")
    ap.add_argument("--snapshot-every", type=float, default=None,
                    metavar="S",
                    help="flush the --trace/--metrics-out artifacts every "
                         "S seconds during the run (atomic renames) in "
                         "addition to the end-of-run flush")
    ap.add_argument("--xla-profile-dir", default=None, metavar="DIR",
                    help="arm the admin /profile?seconds=S endpoint: an "
                         "on-demand jax.profiler capture written under "
                         "DIR/capture_NNN (one capture at a time; needs "
                         "--admin-port)")
    ap.add_argument("--monitor-window", type=int, default=64, metavar="N",
                    help="rolling speculation-quality monitor window in "
                         "samples (token/step acceptance, SLO burn, "
                         "quarantine rate; active whenever --trace/"
                         "--metrics-out/--admin-port is; 0 disables); a "
                         "firing monitor feeds the overload controller "
                         "as a pressure input (see --degrade)")
    args = ap.parse_args(argv)
    if args.max_prefill_tokens < 1:
        ap.error("--max-prefill-tokens must be >= 1")
    for flag, name in ((args.deadline, "--deadline"),
                       (args.slo_tpot, "--slo-tpot")):
        if flag is not None and flag <= 0:
            ap.error(f"{name} must be > 0")
    if args.scheduler != "continuous" and (
            args.deadline is not None or args.slo_tpot is not None
            or args.shed_policy != "none" or args.degrade
            or args.inject_faults or args.audit):
        ap.error("--deadline/--slo-tpot/--shed-policy/--degrade/"
                 "--inject-faults/--audit ride on the continuous "
                 "scheduler; add --scheduler continuous")
    if args.trace_buffer < 1:
        ap.error("--trace-buffer must be >= 1")
    if args.monitor_window < 0:
        ap.error("--monitor-window must be >= 0")
    if args.snapshot_every is not None and args.snapshot_every <= 0:
        ap.error("--snapshot-every must be > 0")
    if args.admin_linger < 0:
        ap.error("--admin-linger must be >= 0")
    if args.scheduler != "continuous" and (
            args.admin_port is not None or args.snapshot_every is not None):
        ap.error("--admin-port/--snapshot-every ride on the continuous "
                 "scheduler (the admin plane is fed by per-tick "
                 "snapshots); add --scheduler continuous")
    if args.xla_profile_dir is not None and args.admin_port is None:
        ap.error("--xla-profile-dir arms the admin /profile endpoint; "
                 "add --admin-port (and --scheduler continuous)")
    # --trace/--metrics-out on the sequential path: warn instead of
    # erroring so A/B runs produce comparable artifacts — the Meter
    # counters back an end-of-run exposition; a tick timeline does not
    # exist sequentially, so --trace is ignored
    if args.scheduler != "continuous" and args.trace:
        print("[warn] --trace is ignored on the sequential scheduler "
              "(no tick timeline exists); use --scheduler continuous "
              "for span traces", flush=True)
    if args.scheduler != "continuous" and args.metrics_out:
        print("[warn] sequential scheduler: --metrics-out serves "
              "end-of-run meter-derived metrics only (no per-tick "
              "gauges)", flush=True)
    if args.scheduler == "continuous" and args.scheme != "specreason":
        ap.error("--scheduler continuous serves the specreason scheme "
                 "only; drop --scheme or use the sequential scheduler")
    if args.tp < 1:
        ap.error("--tp must be >= 1")
    if args.tp > 1 and args.scheduler != "continuous":
        ap.error("--tp rides on the continuous scheduler (the sharded "
                 "BatchEngine pair); add --scheduler continuous")
    if args.spec_decode and args.scheduler != "continuous":
        ap.error("--spec-decode rides on the continuous scheduler; add "
                 "--scheduler continuous (the sequential regime's "
                 "specreason+decode scheme covers the one-at-a-time case)")
    if args.num_samples < 1:
        ap.error("--num-samples must be >= 1")
    if args.num_samples > 1 and args.scheduler != "continuous":
        ap.error("--num-samples rides on the continuous scheduler (the "
                 "prefix cache that makes best-of-N cheap lives there); "
                 "add --scheduler continuous")
    if args.vote and args.num_samples < 2:
        ap.error("--vote needs --num-samples >= 2")
    return args


def random_engine_pair(base_cfg, small_cfg, max_len: int = 1024,
                       fused: bool = True, dtype=jnp.float32):
    """Base/draft ``Engine`` pair with random weights from seeds 0 and 1
    — a smoke pair that drives the serving machinery (its answers are
    nonsense)."""
    engines = []
    for seed, cfg in enumerate((base_cfg, small_cfg)):
        model = Model(cfg)
        engines.append(Engine(model,
                              model.init(jax.random.PRNGKey(seed), dtype),
                              max_len=max_len, name=cfg.name, fused=fused))
    return engines[0], engines[1]


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    fused = args.decode_loop == "fused"
    if args.testbed == "micro":
        from ..configs import testbed
        base, small = random_engine_pair(testbed.MICRO, testbed.MICRO_SMALL,
                                         fused=fused)
    else:
        base, small = load_testbed_engines(args.ckpt_dir)
    rng = random.Random(args.seed)
    reqs = [tasks.sample_task(rng) for _ in range(args.num_requests)]

    if args.scheduler == "continuous":
        serve_continuous(args, base, small, reqs, fused)
        return

    schemes = SCHEMES if args.scheme == "all" else (args.scheme,)

    all_lat, all_out = [], 0
    try:
        for scheme in schemes:
            lat, acc, toks = [], [], []
            for i, task in enumerate(reqs):
                key = jax.random.PRNGKey(1000 * args.seed + i)
                res = run_scheme(scheme, base, small, task, key,
                                 args.budget, args.threshold,
                                 args.temperature, fused=fused)
                ok = is_correct(task, res.answer_ids)
                lat.append(res.wall_time)
                acc.append(ok)
                toks.append(res.n_thinking_tokens)
                all_lat.append(res.wall_time)
                all_out += res.n_thinking_tokens + len(res.answer_ids)
                print(f"[{scheme}] req{i}: {'OK ' if ok else 'BAD'} "
                      f"{res.wall_time:.2f}s think={res.n_thinking_tokens}"
                      f"{_spec_suffix(res)} "
                      f"answer={tk.detok(res.answer_ids)}")
                if args.meters:
                    for name, m in res.meters.items():
                        print(_meter_line(name, m))
            print(json.dumps({
                "scheme": scheme,
                "decode_loop": args.decode_loop,
                "mean_latency_s": sum(lat) / len(lat),
                "accuracy": sum(acc) / len(acc),
                "mean_thinking_tokens": sum(toks) / len(toks),
            }))
    finally:
        if args.metrics_out:
            # same crash-safe atomic flush as the continuous path
            atomic_write(args.metrics_out,
                         sequential_metrics(base, small, all_lat,
                                            all_out))
            print(f"[metrics] {args.metrics_out}", flush=True)


if __name__ == "__main__":
    main()
