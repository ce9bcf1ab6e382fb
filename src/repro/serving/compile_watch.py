"""Compile- and device-plane observability: the recompilation sentinel,
device-memory accounting, and on-demand profiler capture.

**CompileWatch** (the sentinel) sits at ``BatchEngine._dispatch`` (and
the one jitted program BatchSpecEngine calls directly,
``core.spec_decode.acceptance_step``): every dispatch hashes the call's
abstract signature — the tuple of (shape, dtype) over the argument tree
leaves, which is exactly what decides whether XLA retraces — and a
first-seen signature is a compile event.  The engines' own jit caches
are keyed coarser than that (``_prefill_cache`` keys on the KV capacity
bucket only, while the token-array shape varies with the length bucket),
so counting cache misses there would undercount; the dispatch signature
is the ground truth.  On a compile event the sentinel:

* AOT-compiles a *twin* executable via ``fn.lower(*args).compile()`` to
  time the compile and read XLA's ``cost_analysis()`` FLOPs/bytes for
  the signature.  The twin never executes — the actual call still goes
  through the jitted function, so the execution path (and therefore
  token identity) is untouched; the extra compile lands only where a
  compile was already happening (warmup), keeping the steady-state
  overhead gate intact.
* emits a span on the ``compile`` tracer track, bumps the registry
  counters, and — past the warmup window (``tick > warmup_ticks``) —
  reports a post-warmup recompile to the monitors, where the hysteresis
  alarm feeds ``Monitors.pressure()`` and walks the degradation ladder.
  A steady-state serve runs with a handful of compiled programs (the
  bucketed-engine contract, serving/engine.py); sustained signature
  churn after warmup means bucket thrash, which degrading (shrinking
  gamma, capping decode) actively damps.

**Everything here is observation.**  ``observe`` never raises into the
dispatch path: a signature it cannot hash or a backend without
``cost_analysis`` degrades to counting only.  When the watch is absent
(``compile_watch=None``, the default everywhere) the serving plane is
bit-for-bit the PR 9 plane — the same zero-cost-when-off contract as
the tracer.

**MemoryWatch** samples ``device.memory_stats()`` per scheduler tick —
None-guarded: CPU backends return ``None`` — alongside host-side byte
*estimates* (model parameter bytes, dense-state bytes, paged-pool bytes
= num_blocks x block_bytes) so the memory picture exists even where the
backend keeps no allocator stats, and tracks a high-watermark across
the run.

**ProfilerCapture** wraps ``jax.profiler.start_trace``/``stop_trace``
for the admin ``/profile?seconds=S`` endpoint: a non-blocking latch
(concurrent captures are refused, not queued) and a ``finally`` stop so
a crash mid-capture still closes the trace file.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from .telemetry import TRACK_COMPILE

__all__ = [
    "CompileWatch",
    "MemoryWatch",
    "ProfilerBusyError",
    "ProfilerCapture",
    "call_signature",
]


# str(dtype) dominates the signature cost (~40us vs ~7us for the whole
# rest of a 12-leaf pytree); dtypes are a handful of interned objects,
# so memoize the rendering — observe() runs on every dispatch.
_DTYPE_STR: Dict[Any, str] = {}


def _dtype_str(dtype: Any) -> str:
    s = _DTYPE_STR.get(dtype)
    if s is None:
        s = _DTYPE_STR[dtype] = str(dtype)
    return s


def call_signature(args: Any) -> Tuple[Any, ...]:
    """The abstract signature of a dispatch: (shape, dtype) per array
    leaf of the argument tree, ``("static", repr)`` for non-array leaves
    (sampling params, python scalars).  Two calls with equal signatures
    hit the same XLA executable; a new signature forces a retrace."""
    out: List[Any] = []
    for leaf in jax.tree_util.tree_leaves(args):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            out.append((tuple(shape), _dtype_str(dtype)))
        else:
            out.append(("static", repr(leaf)))
    return tuple(out)


class CompileWatch:
    """Signature-keyed recompilation sentinel.

    One instance is shared by every engine of a scheduler (the engine
    name disambiguates).  Not thread-safe by design: all observation
    happens on the scheduler's tick thread, same as the tracer."""

    def __init__(self, tracer=None, metrics=None, monitors=None,
                 warmup_ticks: int = 8, keep_hlo: bool = False):
        if warmup_ticks < 0:
            raise ValueError("warmup_ticks must be >= 0")
        self.tracer = tracer
        self.metrics = metrics
        self.monitors = monitors
        self.warmup_ticks = int(warmup_ticks)
        self.keep_hlo = bool(keep_hlo)
        self.tick = 0
        self.compiles = 0
        self.post_warmup_compiles = 0
        # (engine, op) -> {signature -> cost dict or None}
        self._sigs: Dict[Tuple[str, str], Dict[Tuple[Any, ...],
                                               Optional[Dict[str, Any]]]] = {}
        # kept only under keep_hlo=True (tests join vs roofline.hlo_cost)
        self.hlo_text: Dict[Tuple[str, str],
                            Dict[Tuple[Any, ...], str]] = {}

    # -- scheduler hooks -------------------------------------------------

    def begin_tick(self, tick: int) -> None:
        """Called by the scheduler at the top of every tick; compiles
        observed while ``tick > warmup_ticks`` count as post-warmup."""
        self.tick = int(tick)

    # -- the sentinel ----------------------------------------------------

    def observe(self, engine: str, op: str, fn: Callable,
                args: Tuple[Any, ...]) -> Optional[Dict[str, Any]]:
        """Record one dispatch of ``fn(*args)`` by (engine, op).  Returns
        the signature's cost dict (``{"flops", "bytes"}``, values may be
        None), or None if the signature could not be hashed.  Never
        raises."""
        try:
            sig = call_signature(args)
        except Exception:
            return None
        per = self._sigs.setdefault((engine, op), {})
        if sig not in per:
            per[sig] = self._compile_event((engine, op), sig, fn, args)
        return per[sig]

    def _compile_event(self, key: Tuple[str, str], sig: Tuple[Any, ...],
                       fn: Callable, args: Tuple[Any, ...]
                       ) -> Optional[Dict[str, Any]]:
        engine, op = key
        t0 = time.perf_counter()
        flops: Optional[float] = None
        nbytes: Optional[float] = None
        try:
            lowered = fn.lower(*args)
            compiled = lowered.compile()
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else None
            if ca:
                if "flops" in ca:
                    flops = float(ca["flops"])
                if "bytes accessed" in ca:
                    nbytes = float(ca["bytes accessed"])
            if self.keep_hlo:
                self.hlo_text.setdefault(key, {})[sig] = compiled.as_text()
        except Exception:
            pass                 # counting still works without the twin
        t1 = time.perf_counter()
        post = self.tick > self.warmup_ticks
        self.compiles += 1
        if post:
            self.post_warmup_compiles += 1
            mon = self.monitors
            if mon is not None:
                try:
                    mon.observe_recompile()
                except Exception:
                    pass
        mt = self.metrics
        if mt is not None:
            labels = {"engine": engine, "op": op}
            mt.compiles.labels(**labels).inc()
            mt.compile_seconds.labels(**labels).inc(t1 - t0)
            if post:
                mt.post_warmup_compiles.labels(**labels).inc()
        tr = self.tracer
        if tr is not None:
            tr.span(TRACK_COMPILE, f"{engine}.{op}", t0, t1, {
                "signature": repr(sig),
                "flops": flops,
                "bytes": nbytes,
                "tick": self.tick,
                "post_warmup": post,
            })
        return {"flops": flops, "bytes": nbytes}

    # -- read side -------------------------------------------------------

    def as_dict(self) -> Dict[str, Any]:
        """The snapshot-sized summary (`/status` ``compile`` field)."""
        return {
            "programs": sum(len(v) for v in self._sigs.values()),
            "compiles": self.compiles,
            "post_warmup": self.post_warmup_compiles,
        }

    def signatures(self, engine: str, op: str) -> List[Tuple[Any, ...]]:
        """Distinct signatures seen for one op (test hook)."""
        return list(self._sigs.get((engine, op), {}).keys())

    def signature_costs(self, engine: str, op: str) -> Dict[Tuple[Any, ...],
                                                            Optional[Dict]]:
        """Per-signature cost dicts for one op (test hook — joins against
        the retained HLO under ``keep_hlo=True``)."""
        return dict(self._sigs.get((engine, op), {}))


class MemoryWatch:
    """Per-tick device-memory sampling + host-side byte accounting.

    ``device.memory_stats()`` is backend-dependent (None on CPU), so
    the watch always carries the host-computable estimates too: model
    parameter + dense-state bytes (``note_model``) and paged-pool bytes
    (``note_pool``).  ``sample()`` returns the `/status`-shaped dict and
    updates the gauges; the high-watermark is the max over samples of
    allocator bytes-in-use where available, else the accounted total."""

    def __init__(self, metrics=None, device=None):
        self.metrics = metrics
        if device is None:
            try:
                device = jax.devices()[0]
            except Exception:
                device = None
        self.device = device
        self.backend = getattr(device, "platform", None)
        self.model_bytes = 0
        self.pool_bytes: Dict[str, int] = {}
        self.peak_bytes = 0
        # per-device high watermarks (str(device) -> bytes) maintained by
        # ``per_device`` — the /status ``mesh.watermarks`` source when
        # serving is sharded over more devices than ``self.device``
        self._device_peaks: Dict[str, int] = {}

    def per_device(self, devices=None) -> List[Dict[str, Any]]:
        """Sample memory stats for EVERY given device (default: all
        ``jax.devices()``), maintaining a per-device high watermark.  On
        backends without allocator stats (CPU) ``bytes_in_use`` is None
        and the watermark falls back to the accounted total — each shard
        holds 1/tp of every sharded array, so the replicated-array bias
        makes this an upper bound per device."""
        if devices is None:
            try:
                devices = jax.devices()
            except Exception:
                devices = []
        out: List[Dict[str, Any]] = []
        accounted = self.model_bytes + sum(self.pool_bytes.values())
        for d in devices:
            try:
                stats = d.memory_stats()
            except Exception:
                stats = None
            stats = stats or {}
            in_use = stats.get("bytes_in_use")
            in_use = int(in_use) if in_use is not None else None
            peak = stats.get("peak_bytes_in_use")
            key = str(d)
            seen = in_use if in_use is not None else \
                accounted // max(len(devices), 1)
            if peak is not None:
                seen = max(seen, int(peak))
            self._device_peaks[key] = max(
                self._device_peaks.get(key, 0), seen)
            out.append({
                "device": key,
                "platform": getattr(d, "platform", None),
                "bytes_in_use": in_use,
                "peak_bytes": self._device_peaks[key],
            })
        return out

    def note_model(self, nbytes: int) -> None:
        self.model_bytes += int(nbytes)

    def note_pool(self, which: str, nbytes: int) -> None:
        self.pool_bytes[which] = int(nbytes)

    def sample(self) -> Dict[str, Any]:
        in_use: Optional[int] = None
        limit: Optional[int] = None
        stats = None
        if self.device is not None:
            try:
                stats = self.device.memory_stats()
            except Exception:
                stats = None
        if stats:                        # None on CPU backends
            if stats.get("bytes_in_use") is not None:
                in_use = int(stats["bytes_in_use"])
            if stats.get("bytes_limit") is not None:
                limit = int(stats["bytes_limit"])
            peak = stats.get("peak_bytes_in_use")
            if peak is not None:
                self.peak_bytes = max(self.peak_bytes, int(peak))
        accounted = self.model_bytes + sum(self.pool_bytes.values())
        self.peak_bytes = max(self.peak_bytes,
                              in_use if in_use is not None else accounted)
        snap = {
            "backend": self.backend,
            "model_bytes": self.model_bytes,
            "pool_bytes": dict(self.pool_bytes),
            "accounted_bytes": accounted,
            "device_bytes_in_use": in_use,
            "device_bytes_limit": limit,
            "peak_bytes": self.peak_bytes,
        }
        mt = self.metrics
        if mt is not None:
            mt.memory_bytes.labels(kind="model").set(float(self.model_bytes))
            for which, n in self.pool_bytes.items():
                mt.memory_bytes.labels(kind=f"kv_pool_{which}").set(float(n))
            mt.memory_bytes.labels(kind="accounted").set(float(accounted))
            if in_use is not None:
                mt.memory_bytes.labels(kind="device_in_use").set(
                    float(in_use))
            mt.memory_peak_bytes.set(float(self.peak_bytes))
        return snap


class ProfilerBusyError(RuntimeError):
    """A capture is already in flight (the latch is held)."""


class ProfilerCapture:
    """On-demand ``jax.profiler`` capture for the admin `/profile`
    endpoint.  One capture at a time (non-blocking latch — a second
    request gets :class:`ProfilerBusyError`, mapped to HTTP 409); the
    ``finally`` stop keeps the artifact readable if the sleep or the
    profiler itself raises mid-capture."""

    MAX_SECONDS = 60.0

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.captures = 0
        self._lock = threading.Lock()

    def capture(self, seconds: float) -> Dict[str, Any]:
        if not (0.0 < seconds <= self.MAX_SECONDS):
            raise ValueError(
                f"seconds must be in (0, {self.MAX_SECONDS:g}]")
        if not self._lock.acquire(blocking=False):
            raise ProfilerBusyError("a profiler capture is in flight")
        try:
            path = os.path.join(self.out_dir,
                                f"capture_{self.captures:03d}")
            os.makedirs(path, exist_ok=True)
            t0 = time.perf_counter()
            jax.profiler.start_trace(path)
            try:
                time.sleep(seconds)
            finally:
                jax.profiler.stop_trace()
            self.captures += 1
            return {"dir": path, "seconds": time.perf_counter() - t0,
                    "capture": self.captures - 1}
        finally:
            self._lock.release()
