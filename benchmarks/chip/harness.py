"""The chip benchmark's harness: load a cell by name, build the system
under test from seeded weights, warm it up with the cell's own traffic,
drive ``ContinuousScheduler.tick`` through a measured window, and keep
the records that the metric readers and the output check read.

Everything that belongs to one configuration, traffic mix, cell or
metric is a file of its own, found by the names in ``BENCHMARK.json``:

  configs/<config>.json     sizes as run, source, cuts, drafter recipe
  architectures/<arch>.py   what a configuration's architecture owns: its
                            sizes, program config, parameter layout,
                            drafter, FLOP count and device scopes (the
                            names in ``ARCH_NAMES``)
  references/<ref>.py       the plain reference a configuration names
  traffic/<mix>.json        parameters of the one generator (arrivals.py)
  cells/<workload>.json     serving settings of one cell and its limits
  metrics/<metric>.py       one reader per metric: ``read(rec)``
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

import arrivals
import weights

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
ARCHITECTURES = HERE / "architectures"
# what the harness, the check and the readers call in an architecture
ARCH_NAMES = ("dims", "model_config", "layout", "drafter_params",
              "tokens_flops", "SCOPES")

# The toy tokenizer's protocol ids the reference check needs (asserted
# against the program's tokenizer when the program is built).
EOS, THINK_END, STEP, SCORE = 2, 6, 7, 8
DIGIT_IDS = tuple(range(10, 20))
STOPS = (STEP, THINK_END, EOS)
STEP_TOKENS = 24          # the segmenter's max_step_tokens


def load_json(path: Path):
    return json.loads(Path(path).read_text())


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod          # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------------ cells

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    arch: ModuleType
    mix: Dict
    serve: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]

    @property
    def dims(self):
        return self.arch.dims(self.config)


def load_config(path: Path, architectures: Path = ARCHITECTURES
                ) -> Tuple[Dict, ModuleType]:
    """A configuration file and the architecture module it names
    (``"architecture": "<arch>"`` loads ``<architectures>/<arch>.py``)."""
    config = load_json(path)
    if "architecture" not in config:
        raise SystemExit(f"{path}: no \"architecture\" key; name the "
                         f"module under {architectures} that serves it")
    arch = config["architecture"]
    mod_path = Path(architectures) / f"{arch}.py"
    if not mod_path.is_file():
        raise SystemExit(f"{path}: architecture {arch!r} has no module "
                         f"{mod_path}")
    mod = load_module(mod_path, f"chipbench_architecture_{arch}")
    missing = [n for n in ARCH_NAMES if not hasattr(mod, n)]
    if missing:
        raise SystemExit(f"{mod_path} (named by {path}) lacks {missing}")
    return config, mod


def load_cell(workload: str, root: Path = ROOT,
              architectures: Path = ARCHITECTURES) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if workload not in by_name:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(by_name)}")
    entry = by_name[workload]
    cfg_file = {c["name"]: c["file"] for c in bench["configs"]}[
        entry["config"]]

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    config, arch = load_config(root / cfg_file, architectures)
    return Cell(name=workload, chips=entry["chips"], config=config,
                arch=arch,
                mix=load_json(HERE / "traffic" / f"{entry['traffic']}.json"),
                serve=load_json(HERE / "cells" / f"{workload}.json"),
                end_to_end=[m for m in bench["end_to_end"] if applies(m)],
                per_layer=[m for m in bench["per_layer"] if applies(m)])


def reader(metric: str) -> Callable:
    path = HERE / "metrics" / f"{metric}.py"
    return load_module(path, f"chipbench_metric_{metric}").read


def reference(config: Dict):
    path = HERE / "references" / f"{config['reference']}.py"
    return load_module(path, f"chipbench_reference_{config['reference']}")


# ---------------------------------------------------------- the program

class PinnedAcceptance:
    """Step acceptance pinned to a share: random weights make the
    verifier's score meaningless, so the k-th judged step is accepted
    where ``floor((k + 1) * share)`` passes ``floor(k * share)``: exactly
    that share of the judged steps, in the same pattern for every seed
    (a seeded coin would move the amount of regeneration, and so the
    work, from seed to seed).  The real verify and score passes still
    run; their scores are checked."""

    def __init__(self, share: float):
        from repro.core.policies import Verdict
        self._verdict = Verdict
        self.share = share
        self.k = 0

    def judge(self, utility: float):
        k, self.k = self.k, self.k + 1
        accept = math.floor((k + 1) * self.share) > math.floor(k * self.share)
        return self._verdict(accept, utility, f"pinned share={self.share}")

    def observe(self, verdict) -> None:
        pass


# The weights are drawn from this seed in every run, and ``--seed`` draws
# the traffic: a random decoder's step lengths and stops depend on its
# weights, so weights drawn from ``--seed`` would move the amount of work
# from seed to seed, where the seed should change only its order.
WEIGHTS_SEED = 0x5EED


def draw_params(cell: Cell) -> Dict:
    """The cell's weights, drawn on the device from ``WEIGHTS_SEED`` in
    its architecture's layout."""
    dims = cell.dims
    return weights.make_params(cell.arch.layout(dims), dims.jnp_dtype,
                               WEIGHTS_SEED)


def reference_params(rec: "Rec") -> Dict:
    """The reference's own draw of the weights, once the program's are
    freed: the same seed, nothing taken from the program."""
    return draw_params(rec.cell)


@dataclasses.dataclass
class Program:
    sched: object
    params: Dict
    driver: object = None


def _check_protocol() -> None:
    from repro.tokenizer import toy as tk
    have = (tk.EOS, tk.THINK_END, tk.STEP, tk.SCORE, tuple(tk.DIGIT_IDS))
    want = (EOS, THINK_END, STEP, SCORE, DIGIT_IDS)
    if have != want:
        raise RuntimeError(f"tokenizer protocol ids changed: {have} != "
                           f"{want}; update harness.py")


def build(cell: Cell, seed: int, tracer=None) -> Program:
    """The system under test: seeded weights, the architecture's drafter,
    SpecReason with token-level spec decode, one ContinuousScheduler."""
    from repro.core.controller import SpecReason, SpecReasonConfig
    from repro.models.model import Model
    from repro.sampling.sample import SamplingParams
    from repro.serving.engine import Engine
    from repro.serving.kv_manager import KVBudget, KVManager
    from repro.serving.scheduler import ContinuousScheduler

    _check_protocol()
    arch, dims, sv, mix = cell.arch, cell.dims, cell.serve, cell.mix
    params = draw_params(cell)
    mcfg = arch.model_config(dims)
    want = jax.tree.map(lambda s: (s.shape, str(s.dtype)),
                        Model(mcfg).abstract(dims.jnp_dtype))
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    if want != got:
        raise RuntimeError(f"{arch.__file__}: layout does not match the "
                           f"program's parameter tree")
    n_draft = cell.config["drafter"]["num_hidden_layers"]
    dcfg = arch.model_config(dims, n_draft, "-draft")
    dparams = arch.drafter_params(params, n_draft)
    slots = sv["slots"]
    base = Engine(Model(mcfg), params, max_len=slots, name=mcfg.name)
    small = Engine(Model(dcfg), dparams, max_len=slots, name=dcfg.name)
    budget = mix["token_budget"]
    ctrl = SpecReason(base, small, SpecReasonConfig(
        policy=PinnedAcceptance(sv["step_accept_share"]),
        token_budget=budget, max_steps=budget // STEP_TOKENS + 2,
        use_spec_decode=True, spec_gamma=sv["gamma"],
        sampling=SamplingParams(temperature=mix["temperature"])))
    kv = KVManager(mcfg, dcfg, KVBudget(total_bytes=sv["kv_budget_mb"] << 20))
    sched = ContinuousScheduler(
        ctrl, kv, max_batch=sv["rows"], context_capacity=slots,
        engine_capacity=slots, prefix_cache=True,
        cache_blocks=sv["prefix_cache_blocks"], chunked_prefill=True,
        max_prefill_tokens=sv["max_prefill_tokens"], tracer=tracer)
    return Program(sched, params)


# ---------------------------------------------------------------- records

@dataclasses.dataclass
class Req:
    spec: arrivals.Spec
    due: float                      # absolute perf_counter seconds
    submitted: Optional[float] = None
    handle: object = None

    @property
    def rid(self) -> Optional[str]:
        return None if self.handle is None else self.handle.request_id

    def out_tokens(self) -> int:
        res = self.handle.result
        return len(res.thinking_ids) + len(res.answer_ids)

    def finished_by(self, t: float) -> bool:
        h = self.handle
        return (h is not None and h.finished_at is not None
                and h.finished_at <= t)


@dataclasses.dataclass
class Rec:
    """What one run leaves for the metric readers and the check."""
    cell: Cell
    seed: int
    reqs: List[Req]
    t_start: float
    t_end: float
    ticks: List[tuple]              # (t0, t1) of every tick in the window
    snap_start: object
    snap_end: object
    setup_s: float
    compiles_in_window: int
    device_kind: str
    traces_in_window: int = 0
    trace: Optional[Dict] = None

    @property
    def window_s(self) -> float:
        return self.t_end - self.t_start

    @property
    def dims(self):
        return self.cell.dims

    # -- requests by what the window did with them
    def due_in_window(self) -> List[Req]:
        return [r for r in self.reqs if self.t_start <= r.due < self.t_end]

    def finished_in_window(self) -> List[Req]:
        return [r for r in self.reqs if r.finished_by(self.t_end)
                and not r.finished_by(self.t_start)
                and r.handle.status == "ok"]

    def attempted(self) -> List[Req]:
        """Requests the window offered (due in it) or worked on (admitted
        by its end and not finished before its start)."""
        due = set(id(r) for r in self.due_in_window())
        return [r for r in self.reqs if r.handle is not None and (
            id(r) in due or (r.handle.admitted_at is not None
                             and r.handle.admitted_at <= self.t_end
                             and not r.finished_by(self.t_start)))]

    def checkable(self) -> List[Req]:
        """Requests the window worked on that have finished with an
        answer, in the window or, for the check, after it."""
        return [r for r in self.attempted()
                if r.handle.finished_at is not None
                and r.handle.status == "ok"]

    def failed(self) -> List[Req]:
        return [r for r in self.attempted()
                if r.handle.status not in ("ok", "queued", "running")]

    # -- committed output tokens
    def tokens_per_record(self) -> float:
        """Output tokens per step record (accepted drafts, rejected
        drafts and base steps all leave one), over every finished
        request of the run: the conversion for requests in flight."""
        done = [r for r in self.reqs if r.finished_by(self.t_end)
                and r.handle.status == "ok"]
        recs = sum(len(r.handle.result.steps) for r in done)
        if not recs:
            return float(STEP_TOKENS)
        return sum(r.out_tokens() for r in done) / recs

    def tokens_at(self, req: Req, snap, t: float) -> float:
        """Output tokens ``req`` had committed at time ``t`` (the time of
        ``snap``): exact once finished, steps x tokens_per_record while
        in flight, 0 before its first step."""
        if req.finished_by(t):
            return float(req.out_tokens())
        steps = {a["request"]: a["steps"] for a in snap.active}
        return steps.get(req.rid, 0) * self.tokens_per_record()

    def committed(self, req: Req) -> float:
        return (self.tokens_at(req, self.snap_end, self.t_end)
                - self.tokens_at(req, self.snap_start, self.t_start))


def tick_ms(rec: Rec) -> Optional[float]:
    return 1e3 * rec.window_s / len(rec.ticks) if rec.ticks else None


def nearest_rank(values: Sequence[float], q: float) -> float:
    """The q-quantile by nearest rank (no interpolation: an infinite
    value stays infinite)."""
    v = sorted(values)
    if not v:
        return float("nan")
    return v[max(0, math.ceil(q * len(v)) - 1)]


# ------------------------------------------------------------------ drive

class CompileCount:
    """Backend compiles (persistent-cache loads included) and traces (a
    jit cache miss, even where the executable is then found), by program
    name."""

    def __init__(self):
        self.n = 0
        self.traces = 0
        self.names: Dict[str, int] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, fun_name: str = "?",
            **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.names[fun_name] = self.names.get(fun_name, 0) + 1
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1


class Driver:
    """Submits each request when it is due, then ticks: the benchmark's
    own loop around the one scheduler."""

    def __init__(self, prog: Program, cell: Cell, seed: int):
        from repro.data.tasks import Task, question_tokens
        self.sched = prog.sched
        self.key = jax.random.PRNGKey(0)
        self.t0 = time.perf_counter()
        specs = arrivals.generate(cell.mix, seed)
        self.tasks = [Task(start=s.start, ops=s.ops) for s in specs]
        if any(question_tokens(t) != s.prompt()
               for t, s in zip(self.tasks, specs)):
            raise RuntimeError("arrivals.Spec.prompt no longer renders "
                               "what data/tasks.question_tokens does")
        self.reqs = [Req(s, self.t0 + s.due_s) for s in specs]
        self.grouped = cell.mix.get("samples_per_session", 1) > 1
        self.next = 0
        self.ticks: List[tuple] = []

    def submit_due(self, now: float) -> None:
        while self.next < len(self.reqs) and self.reqs[self.next].due <= now:
            r = self.reqs[self.next]
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("chipbench.submit"):
                r.handle = self.sched.submit(
                    self.tasks[self.next],
                    group=f"s{r.spec.session}" if self.grouped else None)
            r.submitted = t0
            self.next += 1

    def step(self) -> None:
        """Submit what is due, then one tick (or, with nothing to do,
        sleep until the next request is due)."""
        now = time.perf_counter()
        self.submit_due(now)
        s = self.sched
        if not (s.active or s.queue):
            if self.next < len(self.reqs):
                time.sleep(max(0.0, min(self.reqs[self.next].due - now,
                                        0.05)))
            return
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench.tick"):
            s.tick(self.key)
        self.ticks.append((t0, time.perf_counter()))


def _caps(be) -> List[int]:
    """Every attended-cache slice width the engine can pick."""
    caps, b = {be.capacity}, 32
    while b < be.capacity:
        caps.add(b)
        b *= 2
    return sorted(caps)


def prime(prog: Program, cell: Cell) -> int:
    """Run, before any traffic, every engine program at every shape the
    window can reach, on one spare row: each extend bucket a prompt
    chunk, a scored step or a spec-decode verify can take, the feed, and
    the drafter's fused decode (drafting steps of every budget, and the
    spec-decode proposals), each at every cache slice width; then the
    step scoring at every step length.  A row's position is set so that
    the call picks the slice width wanted; its cache contents do not
    matter, and the row is freed after.  Traffic alone would reach a
    rare (bucket, width) pair only inside the window.  Returns the
    number of calls."""
    from repro.core.verifier import mean_body_logprob
    sched, ctrl = prog.sched, prog.sched.controller
    sp = ctrl.cfg.sampling
    longest = max(cell.serve["max_prefill_tokens"], STEP_TOKENS + 1)
    key = jax.random.PRNGKey(0)
    calls = 0
    for be in (sched.base_be, sched.small_be):
        row = be.alloc_row()
        pad = be.pad_id
        top = be._bucket(longest)
        for cap in _caps(be):
            for b in be.buckets:
                if b <= min(top, cap):
                    be.pos[row] = cap - b
                    be.extend_rows([row], [[pad] * b])
                    calls += 1
            be.pos[row] = cap - 1
            be.feed_rows([row], [pad])
            calls += 1
            if be is not sched.small_be:
                continue
            for m in (8, 16, STEP_TOKENS):    # decode buffers 8, 16, 32
                be.pos[row] = cap - m
                be.generate_rows([row], [m], ctrl.segmenter.stop_ids, sp,
                                 [key])
                calls += 1
            g = cell.serve["gamma"]
            be.pos[row] = cap - g
            be.generate_rows([row], [g], [], sp, keys=[key],
                             greedy_rows=[True], stop_ids_rows=[[]],
                             collect_probs=True)
            calls += 1
        be.free_row(row)
    calls += _prime_prefix_cache(prog, cell)
    vocab = sched.base_be.last_logits.shape[1]
    prev = np.zeros(vocab, np.float32)
    for n in range(1, STEP_TOKENS + 2):
        mean_body_logprob(prev, np.zeros((n, vocab), np.float32), [0] * n)
        calls += 1
    return calls


def _prime_prefix_cache(prog: Program, cell: Cell) -> int:
    """The radix prefix cache's programs: copying a prefill chunk's new
    blocks out of a row into the page store, at every chunk length; and,
    where the mix shares prompts, seeding up to one session's samples at
    once from a cached chain of every length its prompts can match.
    Spare rows and free store slots only; nothing enters the radix
    tree."""
    sched = prog.sched
    if sched.caches is None:
        return 0
    bs = sched.kv.block_size
    per = cell.mix.get("samples_per_session", 1)
    specs = arrivals.generate(cell.mix, 0)
    chains = set()
    if per > 1 or cell.mix.get("shared_prefix_tokens", 0):
        chains = {sched._cacheable_len(s.prompt_len) // bs for s in specs}
        a, b = specs[0].prompt(), specs[-1].prompt()
        common = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                      min(len(a), len(b)))
        chains.add(min(common // bs, sched._cacheable_len(len(a)) // bs))
        chains.discard(0)
    calls = 0
    for which, be in (("base", sched.base_be), ("small", sched.small_be)):
        store = sched.caches[which].store
        rows = [be.alloc_row() for _ in range(per)]
        top = min(cell.serve["max_prefill_tokens"] // bs, store.n_slots)
        for ns in range(1, top + 1):
            be.pos[rows[0]] = ns * bs
            k, v = be.export_prefix(rows[0], 0, ns * bs)
            store.write(list(range(ns)), k, v)
            calls += 1
        for nb in sorted(chains):
            for n in range(1, per + 1):
                for r in rows[:n]:
                    be.pos[r] = 0
                be.load_prefix_pages_rows(rows[:n], store.k_pages,
                                          store.v_pages, [[0] * nb] * n)
                calls += 1
        for r in rows:
            be.free_row(r)
    return calls


def warm_up(drv: Driver, cell: Cell) -> int:
    """Run the cell's own traffic until ``warmup_done`` requests have
    finished (every row once, for a backlog): the batch is then in steady
    state and every shape the window uses has compiled."""
    need = cell.serve["warmup_done"]
    while len(drv.sched.done) < need:
        drv.step()
        if drv.next >= len(drv.reqs) and not (drv.sched.active
                                              or drv.sched.queue):
            raise RuntimeError("the mix ran out before warm-up finished")
    return len(drv.ticks)


def device_info() -> Dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_note() -> str:
    """Device bytes in use and the limit on the first chip, for the
    set-up log ("" where the backend reports none)."""
    st = jax.devices()[0].memory_stats() or {}
    if "bytes_in_use" not in st:
        return ""
    return (f", device {st['bytes_in_use'] / 1e9:.2f} of "
            f"{st.get('bytes_limit', 0) / 1e9:.2f} GB in use, peak "
            f"{st.get('peak_bytes_in_use', 0) / 1e9:.2f}")


def memory_peak_bytes(n: int) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:n]]
    return int(max(peaks))


def run_window(cell: Cell, seed: int, seconds: float, trace: bool,
               t_proc: float, trace_dir: Optional[str] = None,
               log: Callable[[str], None] = print):
    """Build, warm up and measure; returns (rec, prog).  The program is
    still alive so that the caller reads memory before freeing it."""
    tracer = None
    if trace:
        from repro.serving.telemetry import Tracer
        tracer = Tracer(annotate=True)
    clock = CompileCount()
    prog = build(cell, seed, tracer)
    log(f"[setup] built{memory_note()}")
    t0 = time.perf_counter()
    calls = prime(prog, cell)
    log(f"[setup] primed {calls} calls in {time.perf_counter() - t0:.1f} "
        f"s, {clock.n} compiles so far{memory_note()}")
    drv = Driver(prog, cell, seed)
    warm = warm_up(drv, cell)
    log(f"[setup] warm-up: {warm} ticks, {len(drv.sched.done)} finished, "
        f"{clock.n} compiles so far{memory_note()}")
    if trace:
        jax.profiler.start_trace(trace_dir)
    n_ticks0 = len(drv.ticks)
    snap0 = drv.sched.snapshot()
    t_start = time.perf_counter()
    setup_s = t_start - t_proc
    compiles0, traces0, names0 = clock.n, clock.traces, dict(clock.names)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        while time.perf_counter() < t_start + seconds:
            drv.step()
    t_end = time.perf_counter()
    snap1 = drv.sched.snapshot()
    compiles = clock.n - compiles0
    traces = clock.traces - traces0
    if compiles:
        log("[window] compiled in the window: " + ", ".join(
            f"{k} x{v - names0.get(k, 0)}" for k, v in clock.names.items()
            if v > names0.get(k, 0)))
    if trace:
        jax.profiler.stop_trace()
    prog.driver = drv
    rec = Rec(cell=cell, seed=seed, reqs=drv.reqs, t_start=t_start,
              t_end=t_end, ticks=drv.ticks[n_ticks0:],
              snap_start=snap0, snap_end=snap1, setup_s=setup_s,
              compiles_in_window=compiles, traces_in_window=traces,
              device_kind=jax.devices()[0].device_kind)
    return rec, prog


SETTLE_S = 150.0


def settle(prog: Program, rec: Rec, n: int,
           cap_s: float = SETTLE_S) -> float:
    """After the window, untimed: drive the same loop on until ``n`` of
    the requests the window worked on have finished (all of them, where
    it worked on fewer), or ``cap_s`` has passed, so that the check has
    answers to compare where a request outlasts the window.  Returns the
    seconds spent."""
    t0 = time.perf_counter()
    want = min(n, len(rec.attempted()))
    while len(rec.checkable()) < want and time.perf_counter() - t0 < cap_s:
        prog.driver.step()
    return time.perf_counter() - t0


def free(prog: Program) -> None:
    """Drop every device buffer the program holds."""
    prog.sched = prog.params = prog.driver = None
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
