"""Request scheduling over a SpecReason engine pair.

Two regimes:

``Scheduler`` — the paper's sequential regime: admission-controlled FIFO,
one request served start-to-finish per turn.  Kept as the semantic
reference; the continuous scheduler is tested token-equivalent to it.

``ContinuousScheduler`` — continuous batching at *reasoning-step*
granularity.  Every request is a resumable ``SpecReasonStepState`` (the
controller's state machine); each ``tick`` groups all active requests by
phase and executes each group as ONE batched engine call:

    speculate-batch : every drafting request  -> one small-model fused
                      multi-sequence decode call
    verify-batch    : every verifying request -> one base-model scoring
                      prefill ([body..., <score>] per row, then the score
                      token is dropped from every context)
    delim/close     : owed step delimiters + </think> closers -> one
                      merged base extend
    fallback/answer : rejected-step regenerations and final answers ->
                      one base-model fused decode with per-row stop sets
                      (+ one small-model sync extend) — or, with ``spec``
                      mode on, batched token-level speculative decoding
                      through serving.spec_engine (hierarchical
                      speculation, SpecReason+Decode §4.2): per round ONE
                      fused draft proposal, ONE base verification
                      prefill, ONE fused acceptance program, with
                      rejected suffixes rolled back by per-row
                      block-table truncation

so the tick costs a handful of device dispatches regardless of how many
requests are in flight — the step-granular structure of SpecReason (§4.1)
is exactly the right batching unit.  Spec-mode admission includes the
gamma in-flight draft tokens per row in its worst-case block headroom, so
a mid-verification grow always has a preemption victim.  Admission is by *block count*
(serving/paged_kv.py pools sized from the KVManager's static partition):
a request is admitted when its prompt plus one step of headroom fits, and
if the pool later runs dry the youngest request is preempted (blocks
freed, request requeued for recompute).  Per-request rollback on rejected
speculation is an O(1) row truncate plus a block-table restore that frees
the orphaned blocks.

Admission is also *cached-prefix-aware* (serving/prefix_cache.py): each
engine pool carries a radix-tree prefix cache, and a prompt whose
block-aligned prefix is cached adopts the shared refcounted blocks, seeds
its row's KV from the cache's page store in one dispatch, and prefills
only the suffix (per-row cached-length offsets in the batched prefill).
Freshly prefilled prompt blocks are inserted back, so best-of-N samples,
shared templates and preempted-then-readmitted requests (whose prompt
blocks survive in the cache) all skip repeated prefill; a queued request
whose prefix an in-round admission is about to insert defers one tick
and admits against the cache instead of duplicating the work.  Under
pool pressure idle cached blocks are evicted LRU-first — before an
admission is declared blocked and before a live request is preempted.

Admission prefill is **chunked** (Sarathi-style stall-free scheduling, on
by default): a newly admitted request's cache-miss prompt suffix is split
into chunks of at most ``max_prefill_tokens`` tokens and prefilled across
ticks — every tick runs ONE bounded batched prefill call per engine for
all mid-prefill rows (each row continuing at its own ``prefill`` cursor
offset over its own partially-filled paged blocks) *plus* the full
speculate/verify/fallback/answer phases for running rows, so a long
prompt arriving mid-burst can no longer stall every in-flight decode tick
behind its monolithic prefill.  Block reservation is incremental (one
chunk ahead), per-chunk full blocks are inserted into the prefix cache as
they land (so a preempted mid-prefill request restores its finished
chunks from the cache on readmission, and waiting best-of-N siblings
admit as hits the moment the cold prefill completes), and chunked output
is token-identical per request to unchunked serving — greedy, sampled,
spec-decode and prefix-cache modes (tested in tests/test_chunked.py).

Per-request greedy-token equivalence with the sequential regime is tested
in tests/test_serving.py (same tokens, same steps, same answers).

**Failure model** (serving/resilience.py, serving/faults.py): every
request carries a terminal ``status`` in {ok, timeout, shed, failed} with
a structured error; deadlines cancel rows mid-flight (mid-chunked-prefill
and mid-spec-verification included) through an idempotent release path;
an overload controller (per-tick EWMAs of TPOT/TTFT + pool occupancy)
drives an admission throttle, a priority/best-of-N-aware shed policy and
a speculation-degradation ladder with hysteresis; fault guards quarantine
poisoned rows (NaN logits, raised engine calls), retry once without
speculation, and fail with a structured error on the second hit —
per-tick refcount-ledger audits verify nothing leaks (DESIGN.md §Failure
model; chaos suite in tests/test_resilience.py)."""

from __future__ import annotations

import dataclasses
import time
import uuid
from collections import deque
from typing import Callable, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.controller import (SpecReason, SpecReasonResult,
                               SpecReasonStepState)
from ..core.verifier import mean_body_logprob
from ..data.tasks import Task, question_tokens
from ..tokenizer import toy as tk
from .admin import SchedulerSnapshot, StatusBoard
from .batch_engine import BatchEngine, RowSnapshot
from .faults import (AuditViolation, FaultInjector, InjectedEngineError,
                     audit_scheduler)
from .kv_manager import KVManager
from .monitors import Monitors
from .paged_kv import (BlockTableSnapshot, PagedKVPool, PagedSeq,
                       PoolExhausted)
from .prefix_cache import PrefixKVStore, RadixCache
from .resilience import (STATUS_FAILED, STATUS_OK, STATUS_SHED,
                         STATUS_TIMEOUT, TERMINAL_STATUSES,
                         OverloadController, RequestError, ResilienceConfig,
                         TickConfig)
from .spec_engine import BatchSpecEngine, SpecLedger, SpecRow
from .tp import TPContext
from .telemetry import (NO_REGION, TRACK_SCHED, SchedEvent, ServingMetrics,
                        Tracer, request_track)


@dataclasses.dataclass
class Request:
    """One submitted task's serving handle: identity, timing milestones
    (submission, admission, prefill completion, first output token,
    finish) and the per-request observability counters the workload
    summary aggregates (TTFT/TPOT percentiles, prefill stall, prefix-
    cache hit tokens)."""
    task: Task
    request_id: str = dataclasses.field(
        default_factory=lambda: uuid.uuid4().hex[:8])
    submitted_at: float = dataclasses.field(default_factory=time.perf_counter)
    key: Optional[jax.Array] = None
    result: Optional[SpecReasonResult] = None
    finished_at: Optional[float] = None
    # failure lifecycle (serving/resilience.py): "queued" -> "running" ->
    # one of the terminal outcomes ok | timeout | shed | failed, with a
    # structured error for every non-ok terminal.  ``deadline_s`` is a
    # wall-clock budget from submission (None = no deadline); higher
    # ``priority`` requests admit first and shed last; ``group`` marks
    # best-of-N sibling samples (the shed policy prefers dropping a
    # sample whose group keeps survivors — the vote runs over survivors)
    status: str = "queued"
    error: Optional[RequestError] = None
    deadline_s: Optional[float] = None
    priority: int = 0
    group: Optional[str] = None
    # submission order (the fault plan's targeting key) and the fault-
    # guard retry state: ``retries`` counts quarantine readmissions,
    # ``quarantined`` routes every later decode through the plain
    # (speculation-free) path
    arrival_idx: int = -1
    retries: int = 0
    quarantined: bool = False
    # why the scheduler could not (yet) run this request: admission block
    # ("blocked: need N..., have M...") or preemption — surfaced instead of
    # an opaque None
    blocked_reason: Optional[str] = None
    # radix prefix cache: prompt length and how many of its tokens were
    # restored from shared cached blocks instead of prefilled (set at
    # admission; a preempted request's counters reflect its LAST admission)
    prompt_tokens: int = 0
    cache_hit_tokens: int = 0
    # latency milestones (continuous scheduler): when the request was LAST
    # admitted, when its (possibly chunked) prompt prefill completed, and
    # when its first output token landed.  ``first_token_at`` is sticky
    # across preemptions — recompute re-derives tokens already streamed,
    # so TTFT keeps the first emission; ``admitted_at``/``prefill_done_at``
    # reflect the last admission (the recompute cost shows up in TPOT).
    admitted_at: Optional[float] = None
    prefill_done_at: Optional[float] = None
    first_token_at: Optional[float] = None

    @property
    def e2e_latency(self) -> Optional[float]:
        if self.finished_at is None:
            return None
        return self.finished_at - self.submitted_at

    @property
    def ttft(self) -> Optional[float]:
        """Time to first output token (seconds since submission)."""
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.submitted_at

    @property
    def prefill_stall_s(self) -> Optional[float]:
        """Seconds between (last) admission and prompt-prefill completion
        — the window in which the request occupied a row without decoding
        (under chunked prefill this is the chunk-spread; unchunked it is
        the monolithic prefill's tick share)."""
        if self.prefill_done_at is None or self.admitted_at is None:
            return None
        return self.prefill_done_at - self.admitted_at

    def tpot(self, n_output_tokens: int) -> Optional[float]:
        """Per-output-token latency: decode seconds per generated token
        after the first (None until finished)."""
        if self.first_token_at is None or self.finished_at is None:
            return None
        return (self.finished_at - self.first_token_at) \
            / max(n_output_tokens - 1, 1)

    @property
    def terminal(self) -> bool:
        """True once the request reached a terminal outcome (ok /
        timeout / shed / failed) — the drive-loop completion test
        (``result is not None`` misses the failure outcomes)."""
        return self.status in TERMINAL_STATUSES

    def expired(self, now: Optional[float] = None) -> bool:
        """True when the request's wall-clock deadline has passed."""
        if self.deadline_s is None:
            return False
        now = time.perf_counter() if now is None else now
        return now - self.submitted_at > self.deadline_s


class Scheduler:
    """Admission-controlled FIFO over a SpecReason engine pair (the
    paper's sequential regime)."""

    def __init__(self, controller: SpecReason, kv: KVManager,
                 context_capacity: int = 1024):
        self.controller = controller
        self.kv = kv
        self.context_capacity = context_capacity
        self.queue: Deque[Request] = deque()
        self.done: List[Request] = []

    def submit(self, task: Task, key: Optional[jax.Array] = None,
               deadline_s: Optional[float] = None, priority: int = 0,
               group: Optional[str] = None) -> Request:
        """Queue a task FIFO; returns its Request handle."""
        req = Request(task, key=key, deadline_s=deadline_s,
                      priority=priority, group=group)
        self.queue.append(req)
        return req

    def _admission_block_reason(self) -> str:
        cap = self.context_capacity
        parts = []
        for which in ("base", "small"):
            have = self.kv.max_context(which)
            if have < cap:
                parts.append(f"{which} needs {cap} tokens, has {have}")
        return "blocked: " + ("; ".join(parts) or
                              f"need {cap} tokens per engine")

    def step(self, key: jax.Array) -> Optional[Request]:
        """Admit + fully serve the next request (the paper's sequential
        regime).  Returns the finished request, or None if the queue is
        empty / admission is blocked — in which case the queued request
        carries ``blocked_reason`` ("blocked: need N tokens, have M")."""
        # expired requests terminate with a structured timeout instead of
        # being served past their deadline (the sequential regime's slice
        # of the failure lifecycle — no mid-flight cancellation here)
        while self.queue and self.queue[0].expired():
            req = self.queue.popleft()
            req.status = STATUS_TIMEOUT
            req.error = RequestError(
                "deadline", f"deadline {req.deadline_s:g}s exceeded "
                f"while queued")
            req.finished_at = time.perf_counter()
            self.done.append(req)
        if not self.queue:
            return None
        req = self.queue[0]
        ok_b = self.kv.allocate(req.request_id + ":b", "base",
                                self.context_capacity)
        ok_s = self.kv.allocate(req.request_id + ":s", "small",
                                self.context_capacity)
        if not (ok_b and ok_s):
            # release the half that DID fit before computing the reason,
            # so "have M" reflects the actually-free capacity
            self.kv.release(req.request_id + ":b")    # idempotent
            self.kv.release(req.request_id + ":s")
            req.blocked_reason = self._admission_block_reason()
            return None
        req.blocked_reason = None
        self.queue.popleft()
        try:
            req.result = self.controller.run(question_tokens(req.task),
                                             req.key if req.key is not None
                                             else key)
            req.status = STATUS_OK
            req.finished_at = time.perf_counter()
        finally:
            self.kv.release(req.request_id + ":b")
            self.kv.release(req.request_id + ":s")
        self.done.append(req)
        return req

    def drain(self, key: jax.Array) -> List[Request]:
        """Serve the queue to exhaustion (or to an admission block —
        the head request's ``blocked_reason`` then says why)."""
        out = []
        while self.queue:
            key, sub = jax.random.split(key)
            r = self.step(sub)
            if r is None:
                # admission blocked: the head request's blocked_reason
                # says why (need/have) — not an opaque stop
                break
            out.append(r)
        return out


# ---------------------------------------------------------------------------
# Continuous batching
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Active:
    """One admitted request's serving-side handles."""
    req: Request
    state: SpecReasonStepState
    base_row: int
    small_row: int
    base_seq: PagedSeq
    small_seq: PagedSeq
    alive: bool = True
    # chunked prefill: the full prompt and how many of its tokens are in
    # the engine rows so far (cached-seeded + prefilled).  While
    # ``cursor < len(prompt)`` the request sits in the serving-side
    # ``prefill`` phase; each tick's bounded prefill batch advances the
    # cursor by at most the tick's remaining token budget.  Block
    # reservation is incremental: the paged seqs' length always covers
    # exactly the reserved chunk (admission reserves chunk 1, the prefill
    # tick grows per chunk thereafter).
    prompt: List[int] = dataclasses.field(default_factory=list)
    cursor: int = 0
    # step-boundary rollback points (speculate -> verify window)
    b_snap: Optional[RowSnapshot] = None
    s_snap: Optional[RowSnapshot] = None
    b_seq_snap: Optional[BlockTableSnapshot] = None
    s_seq_snap: Optional[BlockTableSnapshot] = None
    # transient verify-phase scratch
    end: str = ""
    body: List[int] = dataclasses.field(default_factory=list)
    mean_lp: float = 0.0
    # base-context tokens owed before this row's next base op (accepted
    # step delimiters, </think> closers) — flushed once per tick in one
    # merged extend
    pending_base: List[int] = dataclasses.field(default_factory=list)
    # output tokens (thinking + answer) committed so far
    committed_tokens: int = 0


class _SchedulerLedger(SpecLedger):
    """Bridges the spec engine's in-flight cache growth/rollback to the
    scheduler's paged-pool accounting: every gamma-token verification
    chunk is charged as it lands (may preempt the youngest request —
    observed by the engine through ``alive``), every rejected suffix is
    rolled back by block-table truncation (orphaned speculation blocks
    freed, no copy)."""

    def __init__(self, sched: "ContinuousScheduler", acts: List[_Active]):
        self.sched = sched
        self.acts = acts

    def alive(self, i: int) -> bool:
        # deadline checks ride the engine's liveness probes: a request
        # whose deadline lands in the middle of a multi-round spec
        # verification cancels BETWEEN rounds (its blocks released, the
        # engine drops the row like any preemption) rather than running
        # the decode to completion first
        a = self.acts[i]
        if a.alive:
            self.sched._check_deadline(a)
        return a.alive

    def grow(self, i: int, which: str, n_tokens: int) -> None:
        a = self.acts[i]
        if a.alive:
            self.sched._grow(a, "base" if which == "base" else "small",
                             n_tokens)

    def truncate(self, i: int, which: str, length: int) -> None:
        a = self.acts[i]
        if a.alive:
            seq = a.base_seq if which == "base" else a.small_seq
            # the CoW copy list a shared-tail truncate emits is dropped:
            # the batched rows are dense (the pools are accounting +
            # prefix-cache identity), so there is no physical page to
            # copy — the row's own cache slots already hold the data
            seq.truncate(length)


# Per-tick prompt-prefill token budget (chunked prefill): bounds the
# prefill work any single tick performs so in-flight decode/speculation
# never stalls behind a long prompt.  Also the largest prefill bucket the
# chunked path ever compiles.
DEFAULT_MAX_PREFILL_TOKENS = 64


class ContinuousScheduler:
    """Step-interleaved continuous batching over a SpecReason pair.

    Public contract (per :meth:`tick`): one bounded chunked-prefill batch
    (``<= max_prefill_tokens`` prompt tokens across all mid-prefill rows,
    one ``prefill_rows`` call per engine), then every running request's
    current phase as per-phase batched calls — one small-model speculate
    decode, one base-model scoring prefill, one merged delim/close
    extend, one fallback+answer decode (or the batched spec-decode
    rounds).  Outputs are token-identical per request to the sequential
    controller, and chunked prefill is token-identical to unchunked
    (prefill consumes no PRNG keys and lands the same KV at the same
    positions, only spread across ticks).

    ``chunked_prefill=False`` restores monolithic admission prefill (the
    whole cache-miss suffix in the admission tick); ``on_event`` receives
    admission / chunk-progress / preemption events as
    :class:`telemetry.SchedEvent` — a ``str`` subclass rendering the
    same human-readable lines as always (the serve CLI's ``--verbose``),
    with ``.kind``/``.fields`` for structured consumers.

    **Observability** (serving/telemetry.py, DESIGN.md §Observability):
    an attached ``tracer`` records per-request span timelines (queued ->
    prefill chunks -> speculate/verify/close/fallback/answer ->
    spec-decode rounds with accepted lengths, plus preemption /
    degradation / cancellation instants) and per-tick scheduler spans
    (batch composition, pool occupancy, pressure, prefill budget spent)
    into a bounded ring buffer, exportable as Chrome trace-event JSON;
    an attached ``metrics`` bundle feeds a Prometheus-style registry
    (TTFT/TPOT/chunk-latency/accepted-length histograms and the serving
    counters/gauges).  Both are ``None`` by default and every recording
    site is guarded on that — tracing off costs nothing, tracing on
    performs no device dispatches, host syncs or PRNG use, so outputs
    stay token-identical (tested in tests/test_telemetry.py)."""

    def __init__(self, controller: SpecReason, kv: KVManager,
                 max_batch: int = 8, context_capacity: int = 256,
                 engine_capacity: Optional[int] = None,
                 spec_decode: Optional[bool] = None,
                 gamma: Optional[int] = None,
                 prefix_cache: bool = True,
                 cache_blocks: Optional[int] = None,
                 chunked_prefill: bool = True,
                 max_prefill_tokens: int = DEFAULT_MAX_PREFILL_TOKENS,
                 on_event: Optional[Callable[[str], None]] = None,
                 resilience: Optional[ResilienceConfig] = None,
                 faults: Optional[FaultInjector] = None,
                 audit: bool = False,
                 tracer: Optional[Tracer] = None,
                 metrics: Optional[ServingMetrics] = None,
                 monitors: Optional[Monitors] = None,
                 status_board: Optional[StatusBoard] = None,
                 on_tick: Optional[Callable[[SchedulerSnapshot],
                                            None]] = None,
                 compile_watch=None,
                 memory_watch=None,
                 tp_size: int = 1):
        cfg = controller.cfg
        if cfg.overlapped:
            raise NotImplementedError(
                "continuous batching covers the speculate/verify/fallback "
                "pipeline with optional hierarchical spec decode; use the "
                "sequential Scheduler for overlapped mode")
        self.controller = controller
        self.kv = kv
        # hierarchical speculation: route the tick's fallback+answer
        # decode batch through batched token-level spec decode
        # (SpecReason+Decode, §4.2).  Defaults follow the controller cfg.
        self.spec = cfg.use_spec_decode if spec_decode is None \
            else spec_decode
        self.gamma = gamma if gamma is not None else cfg.spec_gamma
        # engine capacity defaults to the sequential engines' max_len so a
        # batched row has the same reduction shapes as a sequential
        # session — the bit-exactness contract (batch_engine docstring)
        engine_capacity = engine_capacity or controller.base.max_len
        if context_capacity > engine_capacity:
            raise ValueError("context_capacity exceeds engine capacity")
        self.context_capacity = context_capacity
        self.tracer = tracer
        self.metrics = metrics
        # online observability: rolling speculation-quality monitors
        # (their pressure feeds the overload controller each tick), the
        # admin plane's snapshot board (one immutable SchedulerSnapshot
        # published per tick) and an optional per-tick snapshot callback
        # (serve.py's --snapshot-every periodic artifact flush)
        self.monitors = monitors
        self.status_board = status_board
        self.on_tick = on_tick
        # compile/device plane (serving/compile_watch.py): the sentinel
        # observes every engine dispatch's abstract signature (threaded
        # into both engines below); the memory watch samples
        # device.memory_stats() + the host-side byte accounting once per
        # tick.  Both None by default — same zero-cost-when-off contract
        # as tracer/metrics/monitors.
        self.compile_watch = compile_watch
        if compile_watch is not None and compile_watch.monitors is None:
            compile_watch.monitors = monitors
        self.memory_watch = memory_watch
        self.last_memory: Optional[Dict[str, object]] = None
        # tensor parallelism: ONE TPContext shared by both engines and
        # every page store (serving/tp.py — a split pair would mix arrays
        # committed to different device sets inside one spec round).
        # tp_size=1 keeps the exact single-device path: no mesh, no
        # placement, no rule context.
        if tp_size < 1:
            raise ValueError(f"tp_size must be >= 1, got {tp_size}")
        self.tp = TPContext.build(tp_size) if tp_size > 1 else None
        self.base_be = BatchEngine(controller.base.model,
                                   controller.base.params, max_batch,
                                   engine_capacity,
                                   name=f"cb-{controller.base.name}",
                                   tracer=tracer,
                                   compile_watch=compile_watch,
                                   tp=self.tp)
        self.small_be = BatchEngine(controller.small.model,
                                    controller.small.params, max_batch,
                                    engine_capacity,
                                    name=f"cb-{controller.small.name}",
                                    tracer=tracer,
                                    compile_watch=compile_watch,
                                    tp=self.tp, role="draft")
        self.spec_be = BatchSpecEngine(self.base_be, self.small_be,
                                       self.gamma) if self.spec else None
        self.pools = {
            "base": PagedKVPool(max(kv.capacity_blocks("base"), 1),
                                kv.block_size, tp_size=tp_size),
            "small": PagedKVPool(max(kv.capacity_blocks("small"), 1),
                                 kv.block_size, tp_size=tp_size),
        }
        # Radix prefix cache per engine: shared prompt prefixes (templates,
        # best-of-N samples, preempted-and-readmitted requests) resolve to
        # shared refcounted pool blocks whose KV seeds the row instead of
        # being prefilled.  ``cache_blocks`` caps the physical page store
        # (cached pages are a secondary copy; dense rows stay the working
        # copies) — defaults to KVManager.prefix_cache_blocks.
        self.caches: Optional[Dict[str, RadixCache]] = None
        if prefix_cache:
            self.caches = {}
            for which, be in (("base", self.base_be),
                              ("small", self.small_be)):
                ll, kh, hd = be.kv_dims()
                slots = cache_blocks if cache_blocks is not None \
                    else kv.prefix_cache_blocks(which)
                slots = max(1, min(slots, self.pools[which].num_blocks))
                store = PrefixKVStore(slots, ll, kh, hd, kv.block_size,
                                      dtype=be.state.k.dtype, tp=self.tp)
                self.caches[which] = RadixCache(self.pools[which], store,
                                                meter=be.meter)
        if max_prefill_tokens < 1:
            raise ValueError("max_prefill_tokens must be >= 1")
        self.chunked = chunked_prefill
        self.max_prefill_tokens = max_prefill_tokens
        self.on_event = on_event
        self.queue: Deque[Request] = deque()
        self.active: List[_Active] = []
        self.done: List[Request] = []
        self.preemptions = 0
        self.ticks = 0
        # output tokens (thinking + answer) committed by every request so
        # far; a preempted request's are taken back, since it recomputes
        # them
        self.committed_tokens = 0
        self.prefill_chunks = 0      # chunked-prefill batches dispatched
        # resilience: the overload controller folds per-tick signals into
        # a pressure scalar and walks the degradation ladder; a default
        # (inert) config keeps the exact pre-resilience behaviour.  The
        # fault injector and the per-tick invariant audits are debug
        # machinery — both off in production serving.
        self.res_cfg = resilience if resilience is not None \
            else ResilienceConfig()
        self.res = OverloadController(self.res_cfg, TickConfig(
            gamma=self.gamma, spec_decode=self.spec,
            max_prefill_tokens=max_prefill_tokens, cache_insert=True))
        self.faults = faults
        self.audit_enabled = audit
        self._submitted = 0          # arrival_idx assignment
        self.timeouts = 0            # requests past deadline
        self.shed_requests = 0       # dropped by the shed policy
        self.quarantines = 0         # fault-guard hits
        self.retries = 0             # quarantine readmissions
        self.failures = 0            # terminal ``failed`` outcomes
        self.stalled_ticks = 0       # injected stall ticks
        self.audit_violations = 0    # should stay 0; audits raise
        # one compiled batched key split per tick phase (an un-jitted vmap
        # would retrace per call; a per-request host split would dispatch
        # per request)
        self._split_jit = jax.jit(jax.vmap(jax.random.split))
        # static byte accounting for the memory watch: model params +
        # dense decode-state caches per engine, paged-pool capacity per
        # engine (num_blocks x per-block KV bytes)
        if memory_watch is not None:
            for be in (self.base_be, self.small_be):
                n = sum(int(x.nbytes)
                        for x in jax.tree_util.tree_leaves(be.params)
                        if hasattr(x, "nbytes"))
                for arr in (be.state.k, be.state.v):
                    if arr is not None:
                        n += int(arr.nbytes)
                memory_watch.note_model(n)
            for which, p in self.pools.items():
                memory_watch.note_pool(
                    which, p.num_blocks * kv.block_bytes(which))

    # ------------------------------------------------------------- intake
    def submit(self, task: Task, key: Optional[jax.Array] = None,
               deadline_s: Optional[float] = None, priority: int = 0,
               group: Optional[str] = None) -> Request:
        """Queue a task; returns its Request handle (admission happens
        at the next tick, subject to rows/blocks).  ``key`` pins the
        request's PRNG chain — same key, same tokens, any scheduler.
        ``deadline_s`` is a wall-clock budget from submission (expiry
        cancels the request mid-flight with status ``timeout``);
        ``priority`` orders admission and protects against shedding;
        ``group`` marks best-of-N siblings for the shed policy."""
        req = Request(task, key=key, deadline_s=deadline_s,
                      priority=priority, group=group,
                      arrival_idx=self._submitted)
        self._submitted += 1
        self.queue.append(req)
        return req

    def _headroom_blocks(self) -> int:
        seg = self.controller.segmenter.cfg
        return self.kv.headroom_blocks(seg.max_step_tokens,
                                       self.gamma if self.spec else 0)

    def _worst_case_tokens(self, prompt_len: int) -> int:
        """Upper bound on one request's context length: prompt + thinking
        (the budget may be overshot by one capped step) + the </think>
        closer + the answer, plus one extend bucket of padding slack —
        and, in spec mode, the gamma in-flight draft tokens a
        verification pass transiently writes past the committed
        context."""
        cfg = self.controller.cfg
        seg = self.controller.segmenter.cfg
        spec_slack = (self.gamma + 1) if self.spec else 0
        return (prompt_len + cfg.token_budget + 2 * seg.max_step_tokens
                + cfg.answer_max_tokens + 2 + 32 + spec_slack)

    def _common_block_prefix(self, p: List[int], q: List[int]) -> int:
        """Longest block-aligned common prefix of two prompts that the
        cache could serve ``p`` from after ``q`` is inserted: whole
        equal blocks only, capped at ``p``'s cacheable length."""
        bs = self.kv.block_size
        limit = min(self._cacheable_len(len(p)),
                    (len(q) // bs) * bs)
        n = 0
        while n + bs <= limit and p[n:n + bs] == q[n:n + bs]:
            n += bs
        return n

    def _cacheable_len(self, prompt_len: int) -> int:
        """Longest prefix of a prompt the radix cache could ever serve:
        whole blocks only, and never the entire prompt (the match rule
        leaves >= 1 token to prefill so the suffix refreshes the row's
        last_logits)."""
        nb = prompt_len // self.kv.block_size
        if nb * self.kv.block_size == prompt_len:
            nb -= 1
        return max(nb, 0) * self.kv.block_size

    def _emit(self, kind: str, msg: str, **fields) -> None:
        """Emit one structured scheduler event: ``on_event`` receives a
        :class:`SchedEvent` (a ``str`` subclass rendering exactly the
        legacy line, with ``.kind``/``.fields`` for structured
        consumers); an attached tracer records it as an instant on the
        owning track (the request's when ``fields`` name one).  With
        neither attached this is a no-op."""
        if self.on_event is None and self.tracer is None:
            return
        ev = SchedEvent(kind, msg, fields)
        if self.on_event is not None:
            self.on_event(ev)
        if self.tracer is not None:
            self.tracer.event(ev)

    def _admit(self, key: jax.Array, tc: TickConfig,
               quota: Optional[int] = None) -> None:
        admitted: List[_Active] = []
        # prompts that will newly insert cache blocks (wait-for-prefix: a
        # queued request whose cacheable prefix one of these inserts will
        # EXTEND defers one tick and admits against the cache instead of
        # duplicating the prefill — the best-of-N admission pattern.
        # Keyed on actual block overlap, not just a shared root: a
        # template-family request whose shared prefix is already cached
        # must NOT wait on a sibling whose pending insert only adds that
        # sibling's unique suffix).  Seeded with the prompts of requests
        # whose CHUNKED prefill is still in flight — their inserts land
        # over the coming ticks, and a sibling that admitted meanwhile
        # would duplicate the whole cold prefill.
        fresh_prompts: List[List[int]] = [
            a.prompt for a in self.active
            if a.state.phase == "prefill"] if self.caches is not None \
            else []
        # per-engine (rows, slot_lists) whose cached prefixes import in
        # one batched dispatch after the admission loop
        loads: Dict[str, Tuple[List[int], List[List[int]]]] = {
            "base": ([], []), "small": ([], [])}
        bs = self.kv.block_size
        # admission order: highest priority first, FIFO within a priority
        # class (stable — preempted/quarantined requeues sit at the queue
        # head, so they stay first among equals).  A blocked candidate
        # breaks the loop: lower-ordered requests never jump a blocked
        # one, which is what bounds every request's wait.
        order = [r for _, r in sorted(
            enumerate(self.queue), key=lambda t: (-t[1].priority, t[0]))]
        for req in order:
            if quota is not None and len(admitted) >= quota:
                break
            if not (self.base_be.free_rows and self.small_be.free_rows):
                break
            prompt = question_tokens(req.task)
            # a request whose worst-case context cannot fit an engine row
            # is refused HERE with a clear error, not with a mid-serve
            # row-overflow crash
            worst = self._worst_case_tokens(len(prompt))
            if worst > self.base_be.capacity:
                raise RuntimeError(
                    f"request {req.request_id} can never be served: "
                    f"worst-case context {worst} tokens exceeds the "
                    f"engine capacity {self.base_be.capacity}; raise "
                    f"engine_capacity or lower the token budget")
            # ---- prefix-cache resolution (common block-aligned hit
            # across the two engines, so one suffix list drives both
            # prefills) ----
            cached = 0
            cacheable = self._cacheable_len(len(prompt))
            if self.caches is not None and cacheable:
                cached = min(c.peek(prompt) for c in self.caches.values())
                if cached < cacheable and any(
                        self._common_block_prefix(prompt, q) > cached
                        for q in fresh_prompts):
                    # blocks beyond this prompt's current hit land in
                    # the cache when this round's prefill completes —
                    # skip this request for now (later arrivals with
                    # other prefixes may still admit this tick) and
                    # admit it as a deeper hit next tick
                    req.blocked_reason = ("deferred: waiting for shared "
                                          "prefix insert")
                    self._emit("defer",
                               f"defer {req.request_id}: waiting for "
                               f"shared prefix insert (hit {cached}"
                               f"/{cacheable} cacheable tokens)",
                               request=req.request_id, hit=cached,
                               cacheable=cacheable)
                    continue
            # chunked prefill reserves blocks INCREMENTALLY: admission
            # claims only the first chunk's blocks (+ headroom); each
            # later chunk reserves through _grow at its prefill tick,
            # preempting/evicting under pressure like any mid-serve grow.
            # Unchunked admission reserves the whole suffix up front.
            first = len(prompt) - cached
            if self.chunked:
                first = min(first, tc.max_prefill_tokens)
            need = self.kv.chunk_blocks(cached, first) \
                + self._headroom_blocks()
            # each pool must cover at least one context_capacity-sized
            # allotment (the admission-reservation unit), or no request
            # could ever run to completion without self-exhausting
            # (cache-independent: the cached prefix can be evicted away)
            min_blocks = max(
                self.pools["base"].blocks_for_tokens(len(prompt))
                + self._headroom_blocks(),
                self.pools["base"].blocks_for_tokens(
                    min(self.context_capacity, worst)))
            too_big = [w for w in ("base", "small")
                       if min_blocks > self.pools[w].num_blocks]
            if too_big:
                raise RuntimeError(
                    f"request {req.request_id} can never be admitted: "
                    f"needs {min_blocks} blocks, pool(s) {too_big} hold "
                    f"{[self.pools[w].num_blocks for w in too_big]}; "
                    f"provision a larger KV budget or lower "
                    f"context_capacity")
            if req.key is None:
                key, req.key = jax.random.split(key)
            st = SpecReasonStepState(key=req.key)
            st.started_at = time.perf_counter()
            a = _Active(req=req, state=st,
                        base_row=self.base_be.alloc_row(),
                        small_row=self.small_be.alloc_row(),
                        base_seq=PagedSeq(self.pools["base"]),
                        small_seq=PagedSeq(self.pools["small"]))
            chain_slots: Dict[str, List[int]] = {}
            if cached:
                # adopt the shared chain BEFORE any eviction below: the
                # adopted blocks are refcount >= 2 (cache + sequence), so
                # pressure eviction can reclaim idle entries but never
                # clip the very chain this admission is built on
                for which, seq in (("base", a.base_seq),
                                   ("small", a.small_seq)):
                    blocks, slots = self.caches[which].acquire(prompt,
                                                               cached)
                    seq.adopt(blocks, cached)
                    chain_slots[which] = slots
            short = []
            for w in ("base", "small"):
                if self.pools[w].num_free < need and self.caches:
                    # cached-but-idle blocks are reclaimable capacity:
                    # evict LRU-first before declaring the pool short
                    self.caches[w].evict(need - self.pools[w].num_free)
                if self.pools[w].num_free < need:
                    short.append(w)
            if short:
                a.base_seq.free()
                a.small_seq.free()
                self.base_be.free_row(a.base_row)
                self.small_be.free_row(a.small_row)
                req.blocked_reason = "; ".join(
                    f"blocked: need {need} {w} blocks, have "
                    f"{self.pools[w].num_free}" for w in short)
                break
            self.queue.remove(req)
            req.blocked_reason = None
            req.status = "running"
            req.admitted_at = time.perf_counter()
            req.prefill_done_at = None      # re-set when THIS admission's
            a.prompt = list(prompt)         # (possibly chunked) prefill
            a.cursor = cached               # completes
            if self.caches is not None:
                # cache-oriented per-request counters (summarize's hit
                # rate, the serve CLI's cache[hit=..] line); left zero
                # when the cache is disabled so reporting stays silent
                req.prompt_tokens = len(prompt)
                req.cache_hit_tokens = cached
            if self.caches is not None:
                for which, cache in self.caches.items():
                    cache.record(len(prompt), cached)
                if cached:
                    # queue the row seeds: the whole round's hits import
                    # in ONE batched dispatch per engine below
                    loads["base"][0].append(a.base_row)
                    loads["base"][1].append(chain_slots["base"])
                    loads["small"][0].append(a.small_row)
                    loads["small"][1].append(chain_slots["small"])
            # reserve the first chunk's blocks now (the admission `need`
            # check above guaranteed them); later chunks grow at their
            # prefill ticks
            a.base_seq.append(first)
            a.small_seq.append(first)
            if self.caches is not None and cached < cacheable:
                fresh_prompts.append(prompt)
            admitted.append(a)
            if self.tracer is not None:
                # the request's wait-for-admission window, on its track
                self.tracer.span(request_track(req.request_id), "queued",
                                 req.submitted_at, req.admitted_at)
            self._emit("admit",
                       f"admit {req.request_id}: prompt={len(prompt)} "
                       f"cached={cached} first_chunk={first}"
                       + ("" if first >= len(prompt) - cached else
                          f" (chunked, {len(prompt) - cached} suffix "
                          f"tokens over >= "
                          f"{-(-(len(prompt) - cached) // max(first, 1))} "
                          f"ticks)"),
                       request=req.request_id, prompt=len(prompt),
                       cached=cached, first_chunk=first)
        if admitted:
            for which, be in (("base", self.base_be),
                              ("small", self.small_be)):
                rows, slot_lists = loads[which]
                if rows:
                    store = self.caches[which].store
                    be.load_prefix_pages_rows(rows, store.k_pages,
                                              store.v_pages, slot_lists)
            # the prompt suffix prefill itself happens in the tick's
            # bounded chunked-prefill batch (_prefill_tick): newly
            # admitted rows enter the serving-side ``prefill`` phase at
            # their cached-prefix cursor
            for a in admitted:
                a.state.phase = "prefill"
                self.active.append(a)

    # ----------------------------------------------------------- prefill
    def _prefill_tick(self, tc: TickConfig) -> int:
        """The tick's bounded chunked-prefill batch: advance every
        mid-prefill row by its next chunk, FIFO over admission order,
        spending at most ``max_prefill_tokens`` prompt tokens per tick
        across the whole batch (unbounded when ``chunked_prefill`` is
        off) — ONE ``prefill_rows`` call per engine, each row continuing
        at its own cursor offset.  Per chunk: reserve the chunk's blocks
        (incremental — may evict cached prefixes or preempt the youngest
        victim), prefill, insert the now-complete full blocks into the
        prefix cache (so preempted mid-prefill requests restore finished
        chunks on readmission and wait-for-prefix siblings admit as hits
        as soon as the cold prefill lands).  A request whose cursor
        reaches its prompt end enters the controller's think phase.
        Returns the prompt tokens spent (the tick span's budget-spent
        field)."""
        acts = self._guard("prefill",
                           [a for a in self.active
                            if a.state.phase == "prefill"])
        if not acts:
            return 0
        budget = tc.max_prefill_tokens if self.chunked else None
        # FCFS budget packing (vLLM/Sarathi-style): the oldest mid-prefill
        # row takes as much of the tick's budget as it needs, younger rows
        # pack into the leftover.  Completion ORDER therefore matches
        # monolithic prefill — fair-share policies that slice the budget
        # across rows stretch the oldest (longest) prompt's prefill
        # unboundedly under a steady stream of short admissions, which is
        # exactly a head-of-line TTFT pathology in the other direction.
        chunks: List[Tuple[_Active, int]] = []
        spent = 0
        for a in acts:               # admission order (deterministic)
            if not a.alive:          # preempted by an earlier chunk's grow
                continue
            rest = len(a.prompt) - a.cursor
            take = rest if budget is None else min(rest, budget - spent)
            if take <= 0:
                continue             # tick budget spent; resumes next tick
            # incremental block reservation: the seqs' reserved length
            # must cover this chunk (admission reserved chunk 1 only)
            grow = a.cursor + take - a.base_seq.length
            if grow > 0:
                self._grow(a, "base", grow)
                if a.alive:
                    self._grow(a, "small", grow)
            if a.alive:
                chunks.append((a, take))
                spent += take
        # a later row's grow may have preempted an earlier chunked row
        chunks = [(a, t) for a, t in chunks if a.alive]
        if not chunks:
            return 0
        tr, mt = self.tracer, self.metrics
        t0 = time.perf_counter() if (tr is not None or mt is not None) \
            else 0.0
        for be, rows in ((self.base_be,
                          [a.base_row for a, _ in chunks]),
                         (self.small_be,
                          [a.small_row for a, _ in chunks])):
            be.prefill_rows(rows,
                            [a.prompt[a.cursor:a.cursor + t]
                             for a, t in chunks],
                            [a.cursor for a, _ in chunks])
        self.prefill_chunks += 1
        spent = sum(t for _, t in chunks)
        if tr is not None or mt is not None:
            t1 = time.perf_counter()
            if mt is not None:
                mt.chunk_latency.observe(t1 - t0)
                mt.prefill_tokens.inc(spent)
            if tr is not None:
                for a, take in chunks:       # cursors not yet advanced
                    tr.span(request_track(a.req.request_id), "prefill",
                            t0, t1, {"from": a.cursor,
                                     "to": a.cursor + take,
                                     "prompt": len(a.prompt)})
        bs = self.kv.block_size
        for a, take in chunks:
            a.cursor += take
            # cache_insert=False is the degradation ladder's deepest rung
            # short of plain SpecReason: under pressure, stop spending
            # store slots + export dispatches on caching fresh prefixes
            # (lookups still serve existing entries; outputs unchanged)
            if self.caches is not None and tc.cache_insert:
                # cache every full prompt block not already cached: the
                # cache retains the sequence's blocks (shared from here
                # on) and copies their KV out of the freshly prefilled
                # row (per chunk this fetches only the NEW full blocks)
                nb_full = a.cursor // bs
                if nb_full:
                    for cache, be, seq, row in (
                            (self.caches["base"], self.base_be,
                             a.base_seq, a.base_row),
                            (self.caches["small"], self.small_be,
                             a.small_seq, a.small_row)):
                        cache.insert(
                            a.prompt[:nb_full * bs], seq.blocks[:nb_full],
                            lambda t0, t1, be=be, row=row:
                                be.export_prefix(row, t0, t1))
            if a.cursor == len(a.prompt):
                a.req.prefill_done_at = time.perf_counter()
                a.state.phase = self.controller.think_phase(a.state)
                if a.cursor > take:      # took more than one chunk
                    self._emit("prefill",
                               f"prefill {a.req.request_id}: done "
                               f"({a.cursor} tokens)",
                               request=a.req.request_id,
                               cursor=a.cursor, prompt=len(a.prompt),
                               done=True)
            else:
                self._emit("prefill",
                           f"prefill {a.req.request_id}: "
                           f"{a.cursor}/{len(a.prompt)} tokens",
                           request=a.req.request_id, cursor=a.cursor,
                           prompt=len(a.prompt), done=False)
        return spent

    # ------------------------------------------------------------ blocks
    def _grow(self, a: _Active, which: str, n_tokens: int) -> None:
        """Grow a request's block table by n tokens; preempt the youngest
        other request (recompute-style) if the pool is exhausted.  A
        request that an earlier grow in the same batch loop preempted is
        skipped — growing its freed table would leak the blocks."""
        if n_tokens <= 0 or not a.alive:
            return
        seq = a.base_seq if which == "base" else a.small_seq
        while True:
            try:
                seq.append(n_tokens)
                return
            except PoolExhausted:
                # cheapest relief first: evict idle cached prefixes (the
                # cache's references are the only thing keeping them) and
                # retry before sacrificing a live request
                if self.caches is not None and self.caches[which].evict(
                        self.pools[which].blocks_for_tokens(n_tokens) + 1):
                    continue
                victim = next((v for v in reversed(self.active)
                               if v is not a and v.alive), None)
                if victim is None:
                    if self.faults is not None \
                            and self.faults.holding(which):
                        # TRANSIENT exhaustion (an injected hold owns the
                        # pool): requeue this request for recompute once
                        # the hold releases instead of crashing — genuine
                        # single-request-too-big is refused at admission
                        self._preempt(a)
                        return
                    raise RuntimeError(
                        f"{which} KV pool exhausted by a single request "
                        f"({self.pools[which].num_blocks} blocks, "
                        f"block_size {self.kv.block_size}); provision a "
                        f"larger budget or lower the token budget") from None
                self._preempt(victim)

    def _preempt(self, victim: _Active) -> None:
        self._release(victim)
        victim.req.blocked_reason = "preempted: KV block pool exhausted"
        victim.req.status = "queued"
        self.queue.appendleft(victim.req)
        self.preemptions += 1
        if self.metrics is not None:
            self.metrics.preemptions.inc()
        mid = f" (mid-prefill at {victim.cursor}/{len(victim.prompt)})" \
            if victim.state.phase == "prefill" else ""
        self._emit("preempt",
                   f"preempt {victim.req.request_id}: KV block pool "
                   f"exhausted{mid}; requeued for recompute",
                   request=victim.req.request_id,
                   phase=victim.state.phase, cursor=victim.cursor)

    def _commit(self, a: _Active, n: int) -> None:
        """Count ``n`` output tokens committed to ``a``'s thinking or
        answer."""
        a.committed_tokens += n
        self.committed_tokens += n

    def _release(self, a: _Active) -> None:
        """Release everything an admitted request holds: outstanding
        block-table snapshots, both paged sequences (their own block
        references only — shared cache/snapshot references survive, so a
        cached-hit-seeded row derefs its adopted radix blocks exactly
        once) and both engine rows.  IDEMPOTENT: cancellation paths can
        race (a deadline sweep, a fault quarantine and a preemption may
        all target one row in one tick) and a double release would
        corrupt the pool's refcount ledger — ``alive`` is the
        exactly-once latch."""
        if not a.alive:
            return
        a.alive = False
        if a.req.status != STATUS_OK:
            # its tokens leave the output (a requeued request recomputes
            # them)
            self.committed_tokens -= a.committed_tokens
        for snap, seq in ((a.b_seq_snap, a.base_seq),
                          (a.s_seq_snap, a.small_seq)):
            if snap is not None:
                seq.discard_snapshot(snap)
        a.b_seq_snap = a.s_seq_snap = None
        a.base_seq.free()
        a.small_seq.free()
        self.base_be.free_row(a.base_row)
        self.small_be.free_row(a.small_row)
        self.active = [x for x in self.active if x is not a]

    # ------------------------------------------------ failure lifecycle
    def _finalize(self, req: Request, status: str, code: str,
                  message: str) -> None:
        """Stamp a terminal non-ok outcome and move the request to
        ``done`` (the caller has already detached it from queue/active)."""
        req.status = status
        req.error = RequestError(code, message, self.ticks)
        req.finished_at = time.perf_counter()
        req.blocked_reason = None
        self.done.append(req)
        if status == STATUS_TIMEOUT:
            self.timeouts += 1
            self.base_be.meter.req_timeouts += 1
        elif status == STATUS_SHED:
            self.shed_requests += 1
            self.base_be.meter.req_shed += 1
        elif status == STATUS_FAILED:
            self.failures += 1
            self.base_be.meter.req_failed += 1
        if self.metrics is not None:
            self.metrics.requests.inc(status=status)
        self._emit(status, f"{status} {req.request_id}: {message}",
                   request=req.request_id, code=code)

    def _cancel(self, a: _Active, status: str, code: str,
                message: str) -> None:
        """Cancel an in-flight request mid-whatever-it-is-doing
        (chunked prefill, spec verification, decode) — release its pool
        blocks / block tables / radix references idempotently and stamp
        the terminal outcome."""
        if not a.alive:
            return
        self._release(a)
        self._finalize(a.req, status, code, message)

    def _check_deadline(self, a: _Active) -> None:
        """Mid-flight deadline check — called from tick sweeps AND from
        the spec ledger's ``alive`` callback, so a deadline landing in
        the middle of a multi-round spec verification cancels the row
        between rounds instead of after the whole decode."""
        if a.alive and a.req.expired():
            self._cancel(a, STATUS_TIMEOUT, "deadline",
                         f"deadline {a.req.deadline_s:g}s exceeded "
                         f"mid-flight (phase {a.state.phase})")

    def _expire_deadlines(self) -> None:
        now = time.perf_counter()
        for a in list(self.active):
            self._check_deadline(a)
        for req in [r for r in self.queue if r.expired(now)]:
            self.queue.remove(req)
            self._finalize(req, STATUS_TIMEOUT, "deadline",
                           f"deadline {req.deadline_s:g}s exceeded "
                           f"while queued")

    def _group_survivors(self, req: Request) -> int:
        """How many OTHER members of ``req``'s best-of-N group are still
        viable (queued, in flight, or finished ok) — the shed policy
        keeps at least ``min_group_survivors`` so the vote still has
        ballots."""
        if req.group is None:
            return 0
        return (sum(1 for r in self.queue
                    if r is not req and r.group == req.group)
                + sum(1 for a in self.active if a.req.group == req.group)
                + sum(1 for r in self.done
                      if r.group == req.group and r.status == STATUS_OK))

    def _shed_victim(self) -> Optional[Request]:
        """Shed order: lowest priority first; within a priority class,
        best-of-N sibling samples whose group keeps enough survivors go
        before singletons (vote over survivors — dropping a ballot beats
        dropping a whole request); youngest first breaks the final tie
        (LIFO protects the oldest waiters' FIFO position)."""
        cfg = self.res_cfg
        best, best_key = None, None
        for i, r in enumerate(self.queue):
            covered = r.group is not None \
                and self._group_survivors(r) >= cfg.min_group_survivors
            sort_key = (r.priority, 0 if covered else 1, -i)
            if best_key is None or sort_key < best_key:
                best, best_key = r, sort_key
        return best

    def _shed(self) -> None:
        """The tick's shed pass (policy "priority"): drop queued requests
        that can no longer convert capacity into goodput — first the
        deadline-infeasible (remaining budget below the EWMA service
        time), then, while the queue sits above ``max_queue``, the shed
        order above."""
        cfg = self.res_cfg
        if cfg.shed_policy == "none" or not self.queue:
            return
        now = time.perf_counter()
        for req in [r for r in self.queue if r.deadline_s is not None]:
            remaining = req.deadline_s - (now - req.submitted_at)
            if self.res.infeasible(remaining):
                self.queue.remove(req)
                self._finalize(req, STATUS_SHED, "shed_infeasible",
                               f"remaining deadline budget "
                               f"{remaining:.3f}s below the estimated "
                               f"service time")
        while cfg.max_queue is not None \
                and len(self.queue) > cfg.max_queue:
            victim = self._shed_victim()
            if victim is None:
                break
            self.queue.remove(victim)
            self._finalize(victim, STATUS_SHED, "shed_overload",
                           f"queue depth {len(self.queue) + 1} above "
                           f"max_queue={cfg.max_queue}")

    # -------------------------------------------------- fault guards
    def _guard(self, phase: str, acts: List[_Active]) -> List[_Active]:
        """Wrap a phase batch against injected engine-call failures: a
        ``raise`` fault targeting a row in this batch fires BEFORE the
        engine call (no state mutated, no PRNG keys burned); the guard
        quarantines exactly that row and the rest of the batch
        proceeds."""
        if self.faults is None or not acts:
            return [a for a in acts if a.alive]
        while True:
            try:
                self.faults.maybe_raise(phase,
                                        [a.req for a in acts if a.alive])
                break
            except InjectedEngineError as e:
                victim = next(a for a in acts
                              if a.req.request_id == e.request_id)
                self._quarantine(victim, "engine_error", str(e))
        return [a for a in acts if a.alive]

    def _quarantine(self, a: _Active, code: str, message: str) -> None:
        """The fault-guard contract: first hit releases the poisoned row
        and requeues the request for a speculation-free recompute
        (deterministic — the pinned key replays the same tokens); a hit
        past ``max_retries`` terminates it with a structured ``failed``."""
        if not a.alive:
            return
        req = a.req
        self.quarantines += 1
        self.base_be.meter.req_quarantines += 1
        if self.monitors is not None:
            self.monitors.observe_quarantine()
        if req.retries >= self.res_cfg.max_retries:
            self._cancel(a, STATUS_FAILED, code,
                         f"{message} (retries exhausted after "
                         f"{req.retries})")
            return
        req.retries += 1
        self.retries += 1
        self.base_be.meter.req_retries += 1
        req.quarantined = True
        self._release(a)
        req.status = "queued"
        req.blocked_reason = f"quarantined: {code}; retrying without " \
                             f"speculation"
        self.queue.appendleft(req)
        self._emit("quarantine",
                   f"quarantine {req.request_id}: {code} — requeued, "
                   f"speculation disabled (retry {req.retries})",
                   request=req.request_id, code=code, retry=req.retries)

    def _health_scan(self) -> None:
        """Per-tick engine-health guard: any live row whose host-side
        last_logits went non-finite (a corrupted engine step — or the
        fault injector's nan_logits) is quarantined before the next tick
        samples from it."""
        acts = [a for a in self.active if a.alive]
        if not acts:
            return
        ok_b = self.base_be.rows_finite([a.base_row for a in acts])
        ok_s = self.small_be.rows_finite([a.small_row for a in acts])
        for a, fb, fs in zip(acts, ok_b, ok_s):
            if not (fb and fs):
                which = "base" if not fb else "small"
                self._quarantine(a, "nan_logits",
                                 f"non-finite logits in the {which} "
                                 f"engine row")

    def _audit(self) -> None:
        """Per-tick invariant audit (``audit=True``): reconcile the pool
        refcount ledgers, block tables and radix cache against every
        enumerable holder; any divergence raises AuditViolation (a leak
        or double-free would otherwise surface as a far-away crash)."""
        viols = audit_scheduler(self)
        if viols:
            self.audit_violations += len(viols)
            raise AuditViolation(
                f"tick {self.ticks}: " + "; ".join(viols))

    # -------------------------------------------------------------- tick
    def tick(self, key: jax.Array) -> bool:
        """One continuous-batching turn: admit, run the bounded
        chunked-prefill batch, then execute every running request's
        current phase as per-phase batched calls.  Returns True while
        there is work left."""
        self.ticks += 1
        tr = self.tracer
        if self.compile_watch is not None:
            # compiles observed from here on belong to this tick (the
            # sentinel's post-warmup window is tick-based)
            self.compile_watch.begin_tick(self.ticks)
        with self._region("sched.tick") as rg_tick:
            with self._region("sched.admit"):
                # fault injection first: arm this tick's plan entries
                # (pool holds claim/release, stall windows open) so the
                # rest of the tick sees them; a stalled tick skips
                # admission/prefill/phases but still runs deadline
                # expiry, health scanning and audits — a stalled engine
                # must never stall the failure lifecycle
                stalled = False
                if self.faults is not None:
                    stalled = self.faults.begin_tick(self.ticks, self)
                    if stalled:
                        self.stalled_ticks += 1
                # failure lifecycle sweeps: expire deadlines (queued AND
                # mid-flight — cancellation releases blocks/tables/radix
                # refs idempotently), then shed what can no longer make
                # its SLO
                self._expire_deadlines()
                self._shed()
                # overload controller: fold this tick's signals into
                # pressure and walk the degradation ladder (hysteresis);
                # the resulting tick config drives gamma / spec / prefill
                # budget / cache insertion
                occ = max(p.num_used / p.num_blocks
                          for p in self.pools.values())
                # row pressure is DEMAND vs capacity (busy rows plus
                # waiting arrivals), not instantaneous occupancy: this
                # sweep runs before admission, so a row freed by last
                # tick's finish would read as idle here even while the
                # queue is about to refill it — the demand form stays
                # pinned at 1.0 for as long as arrivals genuinely exceed
                # the row budget
                busy = self.base_be.batch - min(self.base_be.free_rows,
                                                self.small_be.free_rows)
                rows_busy = min(1.0, (busy + len(self.queue))
                                / self.base_be.batch)
                # speculation-quality coupling: a firing monitor alarm
                # (evaluated at the end of the previous tick) raises the
                # pressure floor so sustained acceptance collapse walks
                # the same ladder occupancy does — the first rungs
                # (shrink gamma, spec off) are exactly the remedy for a
                # drafter that has stopped earning its keep
                mon = self.monitors
                mon_pressure = mon.pressure() if mon is not None else 0.0
                for ev in self.res.observe_tick(self.ticks, occ, rows_busy,
                                                len(self.queue),
                                                extra_pressure=mon_pressure):
                    # degradation-ladder transitions (either direction),
                    # rendered verbatim — the controller already formats
                    # the line
                    self._emit("degrade", ev, tick=self.ticks,
                               level=self.res.level,
                               pressure=round(self.res.pressure, 4))
                tc = self.res.tick_config()
                if not stalled:
                    self._admit(key, tc,
                                quota=self.res.admit_quota(len(self.active)))
            spent = 0
            comp: Dict[str, int] = {}
            if not stalled:
                if tr is not None:
                    # batch composition entering the tick's phase execution
                    for a in self.active:
                        comp[a.state.phase] = comp.get(a.state.phase, 0) + 1
                # Stall-free scheduling: the tick's prefill work is
                # bounded by the tick config's prefill budget (chunked
                # mode), so the decode/speculation phases below run EVERY
                # tick regardless of how long the queued prompts are — a
                # long admission never starves in-flight decodes.
                with self._region("sched.prefill"):
                    spent = self._prefill_tick(tc)
                # One tick = one reasoning step for every in-flight
                # request: each phase batch is collected FRESH so a
                # request drafted this tick is verified this tick (and, on
                # reject, regenerated this tick) — requests stay
                # phase-synchronized and every batched call is full.  Call
                # structure per tick: one small-model fused decode (every
                # drafting request), one base-model scoring prefill (every
                # verifying request), one base-model extend (accepted-step
                # delimiters + </think> closers, deferred and merged), one
                # base-model fused decode (fallback regenerations + final
                # answers, distinguished by per-row stop sets), one
                # small-model sync extend.
                with self._region("sched.speculate"):
                    self._phase_acts("speculate", self._speculate_batch)
                with self._region("sched.verify"):
                    self._phase_acts("verify", self._verify_batch)
                with self._region("sched.close"):
                    self._flush_close_batch()
                with self._region("sched.decode"):
                    fall = self._guard("fallback",
                                       [a for a in self.active
                                        if a.state.phase == "fallback"])
                    ans = self._guard("answer",
                                      [a for a in self.active
                                       if a.state.phase == "answer"])
                    if fall or ans:
                        self._base_decode_batch(fall, ans, tc)
            with self._region("sched.finish"):
                working = self._end_tick()
            if rg_tick is not None:
                rg_tick.args.update(
                    tick=self.ticks, queue=len(self.queue),
                    active=len(self.active), batch=comp,
                    occupancy=round(occ, 4),
                    pressure=round(self.res.pressure, 4),
                    level=self.res.level, prefill_tokens=spent)
        return working

    def _end_tick(self) -> bool:
        """The tick's closing sweep: health scan, TTFT stamps, finishing,
        audits, monitors, memory, metrics, counters and the admin
        snapshot.  Returns True while there is work left."""
        tr, mt = self.tracer, self.metrics
        # engine-health guard: injected NaN poisoning lands here
        # (simulating this tick's engine step having corrupted a row),
        # then the scan quarantines every non-finite row BEFORE finish
        # packaging or the next tick's sampling can consume it
        if self.faults is not None:
            self.faults.poison(self)
        self._health_scan()
        # TTFT bookkeeping: the first tick that left output tokens in a
        # request's trace stamps its first-token time (tick-granular —
        # the batched calls do not expose per-token host timestamps)
        now = time.perf_counter()
        for a in self.active:
            if a.req.first_token_at is None and (a.state.thinking or
                                                 a.state.answer_ids):
                a.req.first_token_at = now
        self._finish()
        if self.audit_enabled:
            self._audit()
        mon = self.monitors
        if mon is not None:
            # roll the per-tick windows, evaluate every alarm; alarm
            # transitions flow through the standard event funnel
            # (on_event + tracer instant on the scheduler track)
            for ev in mon.on_tick(self.ticks):
                self._emit(ev.kind, str(ev), **ev.fields)
        if self.memory_watch is not None:
            # one device-memory sample per tick (updates the gauges +
            # high-watermark internally; the snapshot embeds the dict)
            self.last_memory = self.memory_watch.sample()
        if mt is not None:
            mt.ticks.inc()
            mt.queue_depth.set(len(self.queue))
            mt.pressure.set(self.res.pressure)
            mt.degrade_level.set(self.res.level)
            for w, p in self.pools.items():
                mt.pool_occupancy.set(p.num_used / p.num_blocks, pool=w)
        if tr is not None:
            t = time.perf_counter()
            tr.counter("kv_occupancy",
                       {w: round(p.num_used / p.num_blocks, 4)
                        for w, p in self.pools.items()}, t=t)
            tr.counter("pressure",
                       {"pressure": round(self.res.pressure, 4),
                        "level": float(self.res.level)}, t=t)
            tr.counter("queue_depth",
                       {"queued": float(len(self.queue)),
                        "active": float(len(self.active))}, t=t)
            if self.last_memory is not None:
                mem_vals = {"accounted":
                            float(self.last_memory["accounted_bytes"]),
                            "peak": float(self.last_memory["peak_bytes"])}
                if self.last_memory["device_bytes_in_use"] is not None:
                    mem_vals["device_in_use"] = float(
                        self.last_memory["device_bytes_in_use"])
                tr.counter("memory_bytes", mem_vals, t=t)
        if self.status_board is not None or self.on_tick is not None:
            # admin plane: publish one immutable snapshot per tick (the
            # lock is held only for the reference swap) and fire the
            # periodic-flush callback with the same snapshot
            snap = self.snapshot()
            if self.status_board is not None:
                self.status_board.publish(snap)
            if self.on_tick is not None:
                self.on_tick(snap)
        working = bool(self.active or self.queue)
        if not working and self.faults is not None:
            # end of run: drop any pool holds whose expiry tick the
            # workload never reached, so drained pools reconcile to zero
            # regardless of where the fault plan ended
            self.faults.release_all(self)
        return working

    def _region(self, name: str):
        """A tracer region on the scheduler track; the shared no-op
        context when tracing is off."""
        tr = self.tracer
        return NO_REGION if tr is None else tr.region(TRACK_SCHED, name)

    def _phase_acts(self, phase: str, fn) -> None:
        acts = self._guard(phase, [a for a in self.active
                                   if a.state.phase == phase])
        if not acts:
            return
        tr = self.tracer
        if tr is None:
            fn(acts)
            return
        t0 = time.perf_counter()
        fn(acts)
        t1 = time.perf_counter()
        for a in acts:
            tr.span(request_track(a.req.request_id), phase, t0, t1)

    def drain(self, key: jax.Array) -> List[Request]:
        """Tick until queue and batch are empty; returns the requests
        finished by THIS drain (earlier finishes stay in ``done``)."""
        done_before = len(self.done)
        while True:
            key, sub = jax.random.split(key)
            if not self.tick(sub):
                break
        return self.done[done_before:]

    def _finish(self) -> None:
        meters = {"base": self.base_be.meter.as_dict(),
                  "small": self.small_be.meter.as_dict()}
        for a in [x for x in self.active if x.state.phase == "done"]:
            a.req.result = self.controller.result(a.state, meters=meters)
            a.req.status = STATUS_OK
            a.req.finished_at = time.perf_counter()
            n_out = len(a.req.result.thinking_ids) \
                + len(a.req.result.answer_ids)
            # service estimate = admission -> finish (EXECUTION time, not
            # e2e): feasibility shedding compares a queued request's
            # remaining deadline budget against this, and folding queue
            # wait into the estimate would feed back on itself under
            # overload (each slow finisher inflates the estimate that
            # sheds the next waiter)
            service = a.req.finished_at - a.req.admitted_at \
                if a.req.admitted_at is not None else a.req.e2e_latency
            self.res.observe_finish(a.req.ttft, a.req.tpot(n_out),
                                    service)
            if self.monitors is not None:
                self.monitors.observe_finish(a.req.ttft,
                                             a.req.tpot(n_out))
            if self.tracer is not None:
                self.tracer.instant(request_track(a.req.request_id),
                                    "done",
                                    {"status": STATUS_OK,
                                     "tokens": n_out,
                                     "steps": len(a.state.steps)},
                                    t=a.req.finished_at)
            if self.metrics is not None:
                mt = self.metrics
                mt.requests.inc(status=STATUS_OK)
                mt.output_tokens.inc(n_out)
                if a.req.ttft is not None:
                    mt.ttft.observe(a.req.ttft)
                tpot = a.req.tpot(n_out)
                if tpot is not None:
                    mt.tpot.observe(tpot)
            self.done.append(a.req)
            self._release(a)

    # ------------------------------------------------------ phase batches
    def _split_keys(self, acts: List[_Active]) -> List[np.ndarray]:
        """Advance every request's PRNG key with ONE vmapped split (a
        per-request host split costs a full dispatch each; threefry splits
        are row-independent so the batched result is bitwise the same)."""
        # pad to the batch width so every phase reuses ONE compiled split
        stacked = np.zeros((self.base_be.batch, 2), np.uint32)
        for i, a in enumerate(acts):
            stacked[i] = np.asarray(a.state.key)
        split = np.asarray(self._split_jit(jnp.asarray(stacked)))
        subs = []
        for a, row in zip(acts, split):
            a.state.key = row[0]
            subs.append(row[1])
        return subs

    def _speculate_batch(self, acts: List[_Active]) -> None:
        ctrl, cfg = self.controller, self.controller.cfg
        acts = [a for a in acts if a.alive]
        keys = self._split_keys(acts)
        rows, budgets = [], []
        for a in acts:
            st = a.state
            a.b_snap = self.base_be.snapshot_row(a.base_row)
            a.s_snap = self.small_be.snapshot_row(a.small_row)
            a.b_seq_snap = a.base_seq.snapshot()
            a.s_seq_snap = a.small_seq.snapshot()
            rows.append(a.small_row)
            budgets.append(ctrl.max_step_tokens(st))
        outs = self.small_be.generate_rows(
            rows, budgets, ctrl.segmenter.stop_ids, cfg.sampling, keys)
        for a, ids in zip(acts, outs):
            a.state.draft_ids = ids
            a.state.phase = "verify"
            self._grow(a, "small", len(ids))

    def _verify_batch(self, acts: List[_Active]) -> None:
        ctrl = self.controller
        seg = ctrl.segmenter
        verifier = ctrl.verifier
        acts = [a for a in acts if a.alive]
        judge: List[_Active] = []
        for a in acts:
            ids = a.state.draft_ids
            a.end = seg.classify_end(ids)
            a.body = seg.body(ids)
            if a.body and a.end in ("step", "final", "runaway"):
                judge.append(a)
            else:
                self._reject(a, 0.0)
        if not judge:
            return
        # ONE batched scoring prefill for the whole verify batch: each
        # row extends [body..., <score>]; the per-position logits give the
        # body logprobs AND the score readout of every request.  (The
        # sequential verifier uses two calls so its returned session needs
        # no position surgery; here the score token is dropped from every
        # row afterwards — same cache discipline, same math.)
        rows = [a.base_row for a in judge]
        prev_logits = [self.base_be.last_logits[r].copy() for r in rows]
        all_logits = self.base_be.extend_rows(
            rows, [a.body + [verifier.score_token] for a in judge],
            want_logits=True)
        for a in judge:
            self._grow(a, "base", len(a.body))
        entries = [(a, prev, al) for a, prev, al
                   in zip(judge, prev_logits, all_logits)
                   if a.alive]                   # _grow may have preempted
        for a, prev, al in entries:
            body_logits, score_row = al[:-1], al[-1]
            a.mean_lp = mean_body_logprob(prev, body_logits, a.body)
            # drop the score token from the context (the verifier's state
            # discipline: the returned context stops after the body)
            self.base_be.pos[a.base_row] -= 1
            self.base_be.last_logits[a.base_row] = body_logits[-1]
            utility, _ = verifier.utility_from_score_logits(score_row)
            verdict, utility = ctrl.judge_draft(utility, a.mean_lp)
            if verdict.accept:
                delim = ctrl.note_accept(a.state, a.body, a.end, utility)
                self._commit(a, len(a.body) + 1)
                a.base_seq.discard_snapshot(a.b_seq_snap)
                a.small_seq.discard_snapshot(a.s_seq_snap)
                a.b_seq_snap = a.s_seq_snap = None
                # delimiter owed to the base context; flushed in this
                # tick's merged close/delim extend
                a.pending_base.append(delim)
                if self.monitors is not None:
                    self.monitors.observe_step("accept")
                if self.tracer is not None:
                    self.tracer.instant(
                        request_track(a.req.request_id), "accept",
                        {"utility": round(utility, 4),
                         "tokens": len(a.body)})
            else:
                self._reject(a, utility)

    def _reject(self, a: _Active, utility: float) -> None:
        """Roll both contexts back to the step boundary: O(1) row truncate
        + block-table restore (frees the orphaned speculation blocks)."""
        self.base_be.restore_row(a.base_row, a.b_snap)
        self.small_be.restore_row(a.small_row, a.s_snap)
        a.base_seq.restore(a.b_seq_snap)
        a.small_seq.restore(a.s_seq_snap)
        a.b_seq_snap = a.s_seq_snap = None
        self.controller.note_reject(a.state, a.body, utility)
        if self.monitors is not None:
            self.monitors.observe_step("reject")
        if self.tracer is not None:
            self.tracer.instant(request_track(a.req.request_id), "reject",
                                {"utility": round(utility, 4),
                                 "tokens": len(a.body)})

    def _base_decode_batch(self, fall: List[_Active], ans: List[_Active],
                           tc: Optional[TickConfig] = None) -> None:
        """The tick's single base-model decode: fallback regenerations
        (stop at step boundaries) and final answers (stop at eos) run as
        one fused multi-sequence call with per-row stop sets/budgets — or,
        in spec mode, through batched token-level speculative decoding
        (hierarchical speculation: the small model drafts gamma tokens
        per row, the base model verifies every row's chunk in one
        prefill, rejected suffixes roll back by block-table truncation).

        Resilience splits the batch: quarantined rows (retrying after a
        fault hit) always take the plain path, and the degradation
        ladder's tick config can shrink gamma or turn the hierarchical
        path off for everyone — greedy outputs are identical either way
        (the lossless-speculation property), which is what makes
        spec-depth the system's safe shedding axis."""
        ctrl, cfg = self.controller, self.controller.cfg
        tc = tc if tc is not None else self.res.tick_config()
        fall = [a for a in fall if a.alive]
        ans = [a for a in ans if a.alive]
        acts = fall + ans
        if not acts:
            return
        tr, mt, mon = self.tracer, self.metrics, self.monitors
        t_dec0 = time.perf_counter() if tr is not None else 0.0
        keys = self._split_keys(acts)
        budgets = [ctrl.max_step_tokens(a.state) for a in fall] \
            + [cfg.answer_max_tokens] * len(ans)
        stops = [ctrl.segmenter.stop_ids] * len(fall) + [[tk.EOS]] * len(ans)
        outs: List[Optional[List[int]]] = [None] * len(acts)

        use_spec = self.spec_be is not None and tc.spec_decode
        spec_idx = [i for i, a in enumerate(acts)
                    if use_spec and not a.req.quarantined]
        spec_set = set(spec_idx)

        if spec_idx:
            # hierarchical path: the spec engine owns both engines' rows
            # for the whole decode (it keeps the small context in sync
            # token for token, like the sequential spec_decode routine)
            sub = [acts[i] for i in spec_idx]
            items = [SpecRow(acts[i].base_row, acts[i].small_row,
                             budgets[i], stops[i], keys[i])
                     for i in spec_idx]
            on_round = None
            if tr is not None or mt is not None or mon is not None:
                # per-round telemetry: one span per judged row on its
                # request track (proposed/accepted draft tokens), one
                # accepted-length observation per row per round, one
                # acceptance-rate sample per row per round
                def on_round(rnd, rt0, rt1, infos, _sub=sub):
                    for j, proposed, accepted in infos:
                        a = _sub[j]
                        if tr is not None:
                            tr.span(request_track(a.req.request_id),
                                    "spec_round", rt0, rt1,
                                    {"round": rnd, "proposed": proposed,
                                     "accepted": accepted})
                        if mt is not None:
                            mt.accepted_length.observe(accepted)
                            mt.spec_rounds.inc()
                        if mon is not None:
                            mon.observe_round(proposed, accepted)
            s_outs, round_stats = self.spec_be.decode_rows(
                items, cfg.sampling, _SchedulerLedger(self, sub),
                gamma=tc.gamma, on_round=on_round)
            for i, ids, s in zip(spec_idx, s_outs, round_stats):
                outs[i] = ids
                if acts[i].alive:
                    acts[i].state.spec_stats.merge(s)
        plain = [i for i in range(len(acts))
                 if i not in spec_set and acts[i].alive]
        if plain:
            p_outs = self.base_be.generate_rows(
                [acts[i].base_row for i in plain],
                [budgets[i] for i in plain], [], cfg.sampling,
                [keys[i] for i in plain],
                stop_ids_rows=[stops[i] for i in plain])
            for i, ids in zip(plain, p_outs):
                outs[i] = ids
                self._grow(acts[i], "base", len(ids))
            sync = [(acts[i], outs[i]) for i in plain
                    if i < len(fall) and acts[i].alive]
            if sync:
                # keep the small model's context in sync, batched
                self.small_be.extend_rows([a.small_row for a, _ in sync],
                                          [ids for _, ids in sync])
                for a, ids in sync:
                    self._grow(a, "small", len(ids))

        for i, a in enumerate(fall):
            if a.alive and outs[i] is not None:
                ctrl.note_base_step(a.state, outs[i])
                self._commit(a, len(outs[i]))
                if mon is not None:
                    mon.observe_step("fallback")
        for i, a in enumerate(ans):
            ids = outs[len(fall) + i]
            if a.alive and ids is not None:
                a.state.answer_ids = ids
                self._commit(a, len(ids))
                a.state.phase = "done"
        if tr is not None:
            t_dec1 = time.perf_counter()
            for a in fall:
                tr.span(request_track(a.req.request_id), "fallback",
                        t_dec0, t_dec1)
            for a in ans:
                tr.span(request_track(a.req.request_id), "answer",
                        t_dec0, t_dec1)

    def _flush_close_batch(self) -> None:
        """Move closing requests to the answer phase and flush every owed
        base-context token (accepted-step delimiters, budget-exhaustion
        </think> closers) in ONE merged base extend.  The small context is
        deliberately NOT closed: a closed request never drafts again, so
        the sequential controller's small-side </think> extend is dead
        work here (outputs are unaffected — tested)."""
        items: List[_Active] = []
        for a in self.active:
            if not a.alive:
                continue
            if a.state.phase == "close":
                if not a.state.done_thinking:
                    a.state.thinking += [tk.THINK_END]
                    self._commit(a, 1)
                    a.pending_base.append(tk.THINK_END)
                a.state.phase = "answer"
            if a.pending_base:
                items.append(a)
        if not items:
            return
        tr = self.tracer
        t0 = time.perf_counter() if tr is not None else 0.0
        self.base_be.extend_rows([a.base_row for a in items],
                                 [a.pending_base for a in items])
        if tr is not None:
            t1 = time.perf_counter()
            for a in items:
                tr.span(request_track(a.req.request_id), "close", t0, t1,
                        {"tokens": len(a.pending_base)})
        for a in items:
            self._grow(a, "base", len(a.pending_base))
            a.pending_base = []

    # ------------------------------------------------------------- stats
    def snapshot(self) -> SchedulerSnapshot:
        """One immutable copy of this tick's observable state for the
        admin plane (/status).  Built on the scheduler thread from plain
        scalars/strings — the admin thread never walks live scheduler
        objects (the snapshot locking contract, DESIGN.md
        §Observability)."""
        active = [{
            "request": a.req.request_id,
            "phase": a.state.phase,
            "cursor": a.cursor,
            "prompt_tokens": len(a.prompt),
            "status": a.req.status,
            "priority": a.req.priority,
            "steps": len(a.state.steps),
            "tokens": a.committed_tokens,
        } for a in self.active if a.alive]
        return SchedulerSnapshot(
            tick=self.ticks,
            time_s=time.perf_counter(),
            queue_depth=len(self.queue),
            active=active,
            pools={w: round(p.num_used / p.num_blocks, 4)
                   for w, p in self.pools.items()},
            pressure=round(self.res.pressure, 4),
            level=self.res.level,
            counts={
                "timeouts": self.timeouts,
                "shed": self.shed_requests,
                "quarantines": self.quarantines,
                "retries": self.retries,
                "failed": self.failures,
                "preemptions": self.preemptions,
                "stalled_ticks": self.stalled_ticks,
                "audit_violations": self.audit_violations,
                "done": len(self.done),
                "submitted": self._submitted,
                "committed_tokens": self.committed_tokens,
            },
            monitors=self.monitors.as_dict()
            if self.monitors is not None else None,
            memory=dict(self.last_memory)
            if self.last_memory is not None else None,
            compile=self.compile_watch.as_dict()
            if self.compile_watch is not None else None,
            mesh=self._mesh_section())

    def _mesh_section(self) -> Optional[Dict[str, object]]:
        """The snapshot's ``mesh`` block: axes/tp_size/devices plus — when
        a memory watch is attached — the per-device memory watermarks
        over the mesh's device set.  None when serving unsharded."""
        if self.tp is None:
            return None
        section = self.tp.describe()
        if self.memory_watch is not None:
            section["watermarks"] = self.memory_watch.per_device(
                list(self.tp.mesh.devices.flat))
        return section

    def resilience_stats(self) -> Dict[str, object]:
        """The run's failure-lifecycle and overload-control counters
        (the serve CLI's ``[resilience]`` line)."""
        out: Dict[str, object] = {
            "timeouts": self.timeouts,
            "shed": self.shed_requests,
            "quarantines": self.quarantines,
            "retries": self.retries,
            "failed": self.failures,
            "preemptions": self.preemptions,
            "stalled_ticks": self.stalled_ticks,
            "audit_violations": self.audit_violations,
        }
        out.update(self.res.as_dict())
        if self.faults is not None:
            out["faults"] = self.faults.as_dict()
        return out

    def pool_utilization(self) -> Dict[str, float]:
        """Fraction of each engine's KV block pool currently claimed
        (live sequences + snapshots + cached prefixes)."""
        return {w: p.num_used / p.num_blocks for w, p in self.pools.items()}

    def cache_stats(self) -> Dict[str, Dict[str, float]]:
        """Per-engine radix prefix-cache counters (empty when the cache
        is disabled)."""
        if self.caches is None:
            return {}
        return {w: c.stats.as_dict() for w, c in self.caches.items()}

    def clear_prefix_cache(self) -> int:
        """Drop every idle cached prefix (entries adopted by live
        sequences survive); returns the number of blocks freed.  After a
        full drain this returns the pools to empty — the cache's
        references are the only ones left."""
        if self.caches is None:
            return 0
        return sum(c.clear() for c in self.caches.values())
