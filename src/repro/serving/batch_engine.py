"""Multi-sequence batched engine: one model, B ragged rows, fused ops.

The single-request ``Engine`` runs one sequence per jitted call; under
concurrency that serializes every request's decode/prefill behind the
per-call dispatch overhead.  ``BatchEngine`` holds ONE batched
``DecodeState`` whose ``pos`` is a (B,) *vector* — every row sits at its
own context length — and advances any subset of rows with:

  * ``extend_rows``     — length-bucketed batched prefill: each involved
    row's chunk is scattered at its own offset (ragged), uninvolved rows
    process pad tokens whose cache writes land beyond their position
    (harmless: overwritten before becoming visible, same argument as the
    dense engine's trailing-pad buckets).
  * ``generate_rows``   — the fused multi-sequence decode step: ONE jitted
    ``jax.lax.while_loop`` advances every active row together with per-row
    stop flags, per-row token budgets, per-row PRNG keys and a per-row
    greedy override; exactly one host sync per call.

Greedy equivalence: when the batch capacity equals the sequential engine's
``max_len``, every per-row computation has the same reduction shapes as
the batch-1 engine, so a batched row reproduces the sequential engine's
tokens exactly (tested in tests/test_batch_engine.py) — that is what lets
the continuous-batching scheduler claim per-request equivalence with the
paper's sequential regime.

Attention-only families: ragged batching relies on position-masked caches
(pads invisible); recurrent SSM state would be polluted, so ssm/hybrid
models are rejected (they keep the sequential engine; see DESIGN.md).

Rollback: rows snapshot as (pos, last_logits row) — an O(1) truncate,
valid because attention caches mask by position.  Block-level accounting
for these rows lives in ``serving.paged_kv`` (the scheduler owns it).

In place: no snapshot holds a cache buffer, so every engine program
donates the state it is given and ``self.state`` is replaced by the one it
returns; each layer writes only its new tokens' slots and attends the
first ``cap_eff`` slots (DESIGN.md §Snapshot/rollback).
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.model import Model
from ..sampling.sample import SamplingParams, probs_from_logits, sample
from .engine import DEFAULT_BUCKETS, Meter, _STOP_SLOTS
from .telemetry import NO_REGION, Tracer, engine_track
from .tp import TPContext


@dataclasses.dataclass
class RowSnapshot:
    """O(1) per-row rollback point: position + the logits at it."""
    pos: int
    last_logits: np.ndarray           # (V,) float32


class BatchEngine:
    """One model, ``batch`` independent ragged rows over a single batched
    DecodeState.

    Contract: rows are allocated/freed by the scheduler (`alloc_row`/
    `free_row`); every multi-row method advances ONLY the rows it is
    given, in ONE jitted dispatch with ONE host sync, leaving uninvolved
    rows untouched (their pad writes land past their position — masked
    until overwritten).  When ``capacity`` equals the sequential engine's
    ``max_len``, each row's tokens are bit-identical to a sequential
    Engine session (greedy and sampled) — the foundation of every
    scheduler-level token-identity guarantee.  Rollback is O(1) per row
    (`snapshot_row`/`restore_row`/`truncate_row`); block-level accounting
    lives with the caller in ``serving.paged_kv``.  Every jitted call
    donates ``self.state``: a reference to an earlier state's ``k``/``v``
    is dead after the next call."""

    def __init__(self, model: Model, params, batch: int,
                 capacity: int = 1024,
                 buckets: Sequence[int] = DEFAULT_BUCKETS, name: str = "",
                 pad_id: int = 0, tracer: Optional[Tracer] = None,
                 compile_watch=None, tp: Optional[TPContext] = None,
                 role: str = "base"):
        if model.cfg.has_ssm:
            raise ValueError(
                "BatchEngine is attention-only: ragged batched rows rely on "
                "position-masked caches; SSM state would be polluted by "
                "pads.  Serve ssm/hybrid models through the sequential "
                "Engine.")
        self.model = model
        # tensor parallelism (serving/tp.py): params committed onto the
        # mesh under EXACT_TP_RULES, KV state sharded on kv-heads, every
        # dispatch traced under the mesh + exact-TP activation rules.
        # None (default) keeps the single-device path bit-identical —
        # and so does TP itself (the whole point; see TPContext).
        self.tp = tp
        if tp is not None:
            tp.check_model(model.cfg)
            params = tp.shard_params(model, params)
        self.params = params
        self.batch = batch
        self.capacity = capacity
        self.buckets = tuple(sorted(b for b in buckets if b <= capacity))
        self.name = name or f"batch-{model.cfg.name}"
        # ``role`` ("base" / "draft") names this engine's tracer regions,
        # so they read the same whatever model serves the role
        self.role = role
        self.pad_id = pad_id
        self.meter = Meter()
        # optional telemetry: one ``<role>.<op>`` region per engine call
        # on the tracer's ``engine:<name>`` track, tiled by its .put /
        # .dispatch / .wait / .pull phases (serving/telemetry.py).  Every
        # region site goes through ``_region``, which returns the shared
        # no-op context when ``tracer is None`` (the zero-cost-when-off
        # contract).
        self.tracer = tracer
        self._track = engine_track(self.name)
        # optional compile sentinel (serving/compile_watch.py): every
        # _dispatch reports its (op, abstract signature) so distinct XLA
        # compilations are counted per op and costed at compile time.
        # None (the default) leaves the dispatch path bit-identical to
        # the watch-less engine — same contract as the tracer.
        self.compile_watch = compile_watch
        # the KV cache takes the params' dtype: bf16 weights get a bf16
        # cache (what KVManager's 2-byte accounting assumes), the fp32
        # testbed an fp32 one
        state = model.init_state(batch, capacity,
                                 dtype=params["tok_embed"].dtype)
        state = dataclasses.replace(
            state, pos=jnp.zeros((batch,), jnp.int32))
        self.state = state if tp is None else tp.shard_state(state)
        # static per-token KV footprint (bytes across k+v, all layers) —
        # the cost annotation on engine-call spans (est. KV bytes
        # moved); zero for cache-less models
        k = self.state.k
        self._kv_token_bytes = 0 if k is None else (
            int(k.shape[0]) * 2 * int(k.shape[3]) * k.dtype.itemsize)
        vocab = model.cfg.vocab_size
        self.pos = np.zeros(batch, np.int64)          # host mirror of pos
        self.last_logits = np.zeros((batch, vocab), np.float32)
        self._free = list(range(batch - 1, -1, -1))
        self._live = [False] * batch
        self._prefill_cache: Dict[int, Callable] = {}
        self._fused_cache: Dict[Tuple[int, int, SamplingParams, bool],
                                Callable] = {}
        self._feed_cache: Dict[int, Callable] = {}
        self._import_cache: Dict[Tuple[int, int], Callable] = {}

    # ------------------------------------------------------------- rows
    def alloc_row(self) -> Optional[int]:
        """Claim a fresh row at position 0 (None when all rows are
        live).  The row's stale cache contents are invisible: attention
        masks by position and every write lands at the row's cursor."""
        if not self._free:
            return None
        r = self._free.pop()
        self._live[r] = True
        self.pos[r] = 0
        self.last_logits[r] = 0.0
        return r

    def free_row(self, row: int) -> None:
        """Return a live row to the free list (its cache is left in
        place — reclaimed lazily by the next occupant's writes)."""
        assert self._live[row], f"free of dead row {row}"
        self._live[row] = False
        self.pos[row] = 0
        self._free.append(row)

    @property
    def free_rows(self) -> int:
        """Rows currently available to `alloc_row`."""
        return len(self._free)

    def rows_finite(self, rows: Sequence[int]) -> List[bool]:
        """Whether each row's host-side ``last_logits`` are all finite —
        the scheduler's per-tick health scan: a NaN/Inf row (a corrupted
        engine step, or serving/faults.py's ``nan_logits`` injection)
        must be quarantined before anything samples from it."""
        if not rows:
            return []
        return np.isfinite(
            self.last_logits[list(rows)]).all(axis=1).tolist()

    def snapshot_row(self, row: int) -> RowSnapshot:
        """O(1) rollback point (position + its logits); restore with
        `restore_row`.  Valid as long as the row is not freed — the
        cache itself is never copied (attention-only masking makes the
        stale suffix invisible after restore)."""
        return RowSnapshot(int(self.pos[row]),
                           self.last_logits[row].copy())

    def restore_row(self, row: int, snap: RowSnapshot) -> None:
        """O(1) truncate: reset the position, restore its logits.  Stale
        cache entries past the position are masked out (attention-only)."""
        assert snap.pos <= self.pos[row]
        self.pos[row] = snap.pos
        self.last_logits[row] = snap.last_logits

    def truncate_row(self, row: int, pos: int) -> None:
        """O(1) position-only truncate (the spec-decode rollback): keep
        the row's cache, drop its logical length to ``pos``.  The row's
        last_logits become stale — the caller must refresh them (a feed
        or an extend) before anything samples from them."""
        assert self._live[row], f"truncate of dead row {row}"
        assert 0 <= pos <= self.pos[row], \
            f"row {row}: truncate to {pos} above position {self.pos[row]}"
        self.pos[row] = pos

    # ---------------------------------------------------------- helpers
    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"extend of {n} tokens exceeds bucket max "
                         f"{self.buckets[-1]}")

    def _put(self, x, dtype=None) -> jax.Array:
        """Host->device staging: committed replicated on the TP mesh (a
        jit call must not mix mesh-committed params with default-device
        operands), plain ``jnp.asarray`` otherwise."""
        if self.tp is None:
            return jnp.asarray(x, dtype)
        return self.tp.put(x, dtype)

    def _sync_pos(self) -> None:
        self.state = dataclasses.replace(
            self.state, pos=self._put(self.pos, jnp.int32))

    def _region(self, op: str, phase: str = ""):
        """The tracer region of one engine call, ``<role>.<op>``, or of
        one of its phases, ``<role>.<op>.<phase>`` (put / dispatch /
        wait / pull); the shared no-op context when tracing is off."""
        tr = self.tracer
        if tr is None:
            return NO_REGION
        name = f"{self.role}.{op}.{phase}" if phase else f"{self.role}.{op}"
        return tr.region(self._track, name)

    def _dispatch(self, op: str, fn: Callable, *args):
        """Run one jitted engine call in its ``.dispatch`` region.  With a
        compile watch attached, the call's abstract signature is recorded
        first (a first-seen signature is a compile event).  Under TP the
        whole body — the watch's lowering twin included — runs inside the
        mesh + exact-TP activation-rules context, so ``constrain``'s bare
        PartitionSpecs resolve and tracing matches execution."""
        tp_ctx = self.tp.context() if self.tp is not None \
            else contextlib.nullcontext()
        with self._region(op, "dispatch"), tp_ctx:
            cw = self.compile_watch
            if cw is not None:
                cw.observe(self.name, op, fn, args)
            return fn(*args)

    def _prefill_fn(self, cap_eff: int) -> Callable:
        """Batched prefill attending the first ``cap_eff`` cache slots —
        same occupied-prefix discipline as the decode loop.  The state is
        donated (see the class docstring)."""
        fn = self._prefill_cache.get(cap_eff)
        if fn is not None:
            return fn
        model = self.model

        def prefill(params, tokens, state):
            return model.prefill(params, tokens, state, width=cap_eff)

        fn = jax.jit(prefill, donate_argnums=2)
        self._prefill_cache[cap_eff] = fn
        return fn

    # ------------------------------------------------------------ extend
    def extend_rows(self, rows: Sequence[int],
                    token_lists: Sequence[Sequence[int]],
                    want_logits: bool = False, op: str = "extend"
                    ) -> Optional[List[np.ndarray]]:
        """Length-bucketed batched prefill: append ``token_lists[i]`` to
        row ``rows[i]``; all involved rows advance in ONE jitted call.
        With ``want_logits``, returns each involved row's (n_i, V) logits
        (the spec-decode/verifier scoring path).  ``op`` labels the
        call's tracer bracket (``prefill_rows`` relabels its delegated
        extends)."""
        assert len(rows) == len(token_lists)
        lens = [len(t) for t in token_lists]
        if not rows or max(lens, default=0) == 0:
            return [np.zeros((0, 0), np.float32) for _ in rows] \
                if want_logits else None
        with self._region(op) as rg:
            with self._region(op, "put"):
                bucket = self._bucket(max(lens))
                for r, n in zip(rows, lens):
                    # the whole padded bucket must fit: pad writes past
                    # capacity would clamp onto the last slot and race
                    # the real tail token
                    if self.pos[r] + bucket > self.capacity:
                        raise ValueError(
                            f"row {r} context overflow: {self.pos[r]}+{n} "
                            f"(bucket {bucket}) > {self.capacity}")
                toks = np.full((self.batch, bucket), self.pad_id, np.int32)
                for r, t in zip(rows, token_lists):
                    toks[r, :len(t)] = t
                # slice width: every live row's whole padded chunk must
                # land unclamped (uninvolved rows write their pads just
                # past their pos)
                live = [i for i in range(self.batch) if self._live[i]]
                need = max(int(self.pos[i]) for i in live) + bucket
                cap_eff = self._cap_bucket(need)
                fn = self._prefill_fn(cap_eff)
                self._sync_pos()
                t0 = time.perf_counter()
                toks = self._put(toks)
            logits, new_state = self._dispatch(op, fn, self.params, toks,
                                               self.state)
            with self._region(op, "wait"):
                logits = jax.block_until_ready(logits)   # the ONE host sync
            t1 = time.perf_counter()
            with self._region(op, "pull"):
                self.meter.prefill_time += t1 - t0
                self.meter.prefill_tokens += bucket * len(rows)
                self.meter.prefill_calls += 1
                # per-row position advance: involved rows by their REAL
                # length, uninvolved rows not at all (their pad chunk
                # wrote past pos only)
                for r, n in zip(rows, lens):
                    self.pos[r] += n
                self.state = dataclasses.replace(
                    new_state, pos=jnp.asarray(self.pos, jnp.int32))
                lg = np.asarray(logits, np.float32)
                out = []
                for r, n in zip(rows, lens):
                    if n > 0:
                        self.last_logits[r] = lg[r, n - 1]
                    if want_logits:
                        out.append(lg[r, :n])
            if rg is not None:
                # est. KV bytes: tokens newly written plus each involved
                # row's attended prefix window (static annotation, not a
                # measurement)
                rg.args.update(rows=len(rows), tokens=sum(lens),
                               bucket=bucket,
                               kv_bytes=self._kv_token_bytes
                               * (sum(lens) + len(rows) * cap_eff))
        return out if want_logits else None

    def prefill_rows(self, rows: Sequence[int],
                     chunks: Sequence[Sequence[int]],
                     starts: Sequence[int],
                     want_logits: bool = False
                     ) -> Optional[List[np.ndarray]]:
        """Multi-row CHUNKED prefill: append prompt chunk ``chunks[i]``
        to row ``rows[i]``, which must currently sit at token offset
        ``starts[i]`` — the row's prefill cursor.  A chunk continuation
        is exactly a ragged batched prefill at a nonzero per-row offset
        (the same path prefix-cache-seeded rows already take), so this
        delegates to :meth:`extend_rows` after checking the cursor
        contract: each row's position must equal its declared start, or
        the chunk would silently land at the wrong offsets and corrupt
        the prompt.  Partial-final-block handling lives in the paged
        pool's accounting (``PagedSeq.append`` fills a partially-filled
        tail block before claiming new ones); physically the batched
        rows are dense, so a chunk starting mid-block simply writes the
        next cache slots of its row."""
        assert len(rows) == len(chunks) == len(starts)
        for r, s in zip(rows, starts):
            assert self._live[r], f"chunked prefill into dead row {r}"
            assert self.pos[r] == s, \
                f"row {r}: chunk declared at offset {s} but the row " \
                f"sits at {self.pos[r]} — prefill cursor out of sync"
        return self.extend_rows(rows, chunks, want_logits, op="prefill")

    # ---------------------------------------------------------- generate
    def _decode_buf(self, max_tokens: int) -> int:
        b = 8
        while b < max_tokens:
            b *= 2
        return b

    def _cap_bucket(self, n: int) -> int:
        """Smallest power-of-two (capped at capacity) covering n context
        slots — the attended window (leading cache slots) of one call.
        Attending only the occupied prefix is the XLA analog of the paged
        kernel's block-table skip: per-token HBM traffic scales with the
        *live* context, not the provisioned capacity."""
        b = 32
        while b < n and b < self.capacity:
            b *= 2
        return min(b, self.capacity)

    def _fused_decode_fn(self, buf: int, cap_eff: int, sp: SamplingParams,
                         collect_probs: bool = False) -> Callable:
        """The fused multi-sequence decode step: one ``jax.lax.while_loop``
        advances every active row — per-row sample, per-row stop/budget
        flags, per-row key splits — with a single dispatch and a single
        host sync for the whole batched step.  Attention reads the first
        ``cap_eff`` cache slots; the state is donated.
        With ``collect_probs`` the per-step post-adjustment sampling
        distributions land in a (B, buf, V) buffer — the proposal
        distributions batched speculative decoding verifies against."""
        cache_key = (buf, cap_eff, sp, collect_probs)
        fn = self._fused_cache.get(cache_key)
        if fn is not None:
            return fn
        model = self.model
        pad_id = self.pad_id
        batch = self.batch

        def fused(params, state, last_logits, keys, stop_arr, stop_mask,
                  n_max, greedy_row):
            toks0 = jnp.full((batch, buf), -1, jnp.int32)
            vocab = last_logits.shape[-1]
            probs0 = (jnp.zeros((batch, buf, vocab), jnp.float32)
                      if collect_probs
                      else jnp.zeros((batch, 0, 0), jnp.float32))
            active0 = n_max > 0
            n0 = jnp.zeros((batch,), jnp.int32)

            def cond(carry):
                i, active = carry[0], carry[1]
                return jnp.logical_and(i < jnp.max(n_max), jnp.any(active))

            def body(carry):
                i, active, n, state, logits, keys, toks, probs = carry
                split = jax.vmap(jax.random.split)(keys)   # (B, 2, 2)
                keys_new, subs = split[:, 0], split[:, 1]
                tok_sp = jax.vmap(lambda l, k: sample(l, sp, k))(logits,
                                                                 subs)
                tok_gr = jnp.argmax(logits, axis=-1)
                tok = jnp.where(greedy_row, tok_gr, tok_sp).astype(jnp.int32)
                tok = jnp.where(active, tok, pad_id)
                toks = toks.at[:, i].set(jnp.where(active, tok, -1))
                if collect_probs:
                    # the distribution token i was sampled from (inactive
                    # rows write garbage; callers slice by their count)
                    probs = probs.at[:, i].set(
                        probs_from_logits(logits, sp).astype(jnp.float32))
                n = n + active.astype(jnp.int32)
                # per-row stop sets: a slot only stops the rows whose mask
                # covers it (lets one call mix e.g. step-bounded fallback
                # rows with eos-bounded answer rows)
                hit = jnp.any((tok[:, None] == stop_arr[None, :])
                              & stop_mask, axis=-1)
                old_pos = state.pos
                new_logits, new_state = model.decode_step(
                    params, state, tok[:, None], width=cap_eff)
                # inactive rows fed a pad: keep their position (the pad's
                # cache write landed beyond it — masked until overwritten)
                new_state = dataclasses.replace(
                    new_state,
                    pos=jnp.where(active, old_pos + 1, old_pos))
                logits = jnp.where(active[:, None], new_logits, logits)
                active = active & jnp.logical_not(hit) & (i + 1 < n_max)
                return (i + 1, active, n, new_state, logits, keys_new,
                        toks, probs)

            init = (jnp.asarray(0, jnp.int32), active0, n0, state,
                    last_logits, keys, toks0, probs0)
            _, _, n, state, logits, _, toks, probs = jax.lax.while_loop(
                cond, body, init)
            return toks, n, logits, state, probs

        fn = jax.jit(fused, donate_argnums=1)
        self._fused_cache[cache_key] = fn
        return fn

    def generate_rows(self, rows: Sequence[int], max_tokens,
                      stop_ids: Sequence[int], params: SamplingParams,
                      keys: Sequence[jax.Array],
                      greedy_rows: Optional[Sequence[bool]] = None,
                      stop_ids_rows: Optional[Sequence[Sequence[int]]] = None,
                      collect_probs: bool = False):
        """Decode every row in ``rows`` until its own stop/budget, all in
        one fused device call.  ``max_tokens`` is an int or a per-row list;
        ``keys`` one PRNG key per row (split on-device in the same order
        as the sequential loop, so sampled rows reproduce it);
        ``greedy_rows`` optionally forces argmax per row regardless of the
        shared sampling params (the per-row sampling override);
        ``stop_ids_rows`` optionally gives each row its OWN stop set
        (``stop_ids`` is then ignored) — what lets the scheduler run e.g.
        step-bounded fallback rows and eos-bounded answer rows as one
        call; with ``collect_probs`` also returns each involved row's
        (n_i, V) per-step sampling distributions (the batched
        spec-decode proposal path) as a second value."""
        if not rows:
            return ([], []) if collect_probs else []
        budgets = list(max_tokens) if not isinstance(max_tokens, int) \
            else [max_tokens] * len(rows)
        assert len(budgets) == len(rows) == len(keys)
        if stop_ids_rows is not None:
            assert len(stop_ids_rows) == len(rows)
            stop_ids = sorted(set(int(s) for row in stop_ids_rows
                                  for s in row))
        n_max = np.zeros(self.batch, np.int32)
        for r, m in zip(rows, budgets):
            # never decode past the cache; the write-at-pos scheme also
            # needs every live row to stay strictly below capacity
            n_max[r] = max(min(m, self.capacity - int(self.pos[r])), 0)
        live = [i for i in range(self.batch) if self._live[i]]
        assert all(self.pos[i] < self.capacity for i in live), \
            "a live row sits at full capacity; finish or preempt it first"
        if int(n_max.max()) == 0:
            empty = [[] for _ in rows]
            return (empty, [np.zeros((0, 0), np.float32) for _ in rows]) \
                if collect_probs else empty

        with self._region("decode") as rg:
            with self._region("decode", "put"):
                buf = self._decode_buf(int(n_max.max()))
                # attend only the occupied prefix: wide enough for every
                # involved row's worst-case end AND for every live row's
                # next write slot
                need = max(max(int(self.pos[i]) + 1 for i in live),
                           max(int(self.pos[r]) + int(n_max[r])
                               for r in rows))
                cap_eff = self._cap_bucket(need)
                stop = sorted(set(int(s) for s in stop_ids))
                n_slots = max(_STOP_SLOTS,
                              -(-len(stop) // _STOP_SLOTS) * _STOP_SLOTS)
                stop_arr = self._put(stop + [-1] * (n_slots - len(stop)),
                                     jnp.int32)
                stop_mask = np.zeros((self.batch, n_slots), bool)
                for i, r in enumerate(rows):
                    allowed = set(int(s) for s in stop_ids_rows[i]) \
                        if stop_ids_rows is not None else set(stop)
                    stop_mask[r] = [s in allowed for s in stop] \
                        + [False] * (n_slots - len(stop))
                key_mat = np.zeros((self.batch, 2), np.uint32)
                for r, k in zip(rows, keys):
                    key_mat[r] = np.asarray(k, np.uint32)
                greedy = np.zeros(self.batch, bool)
                if greedy_rows is not None:
                    for r, g in zip(rows, greedy_rows):
                        greedy[r] = g
                fn = self._fused_decode_fn(buf, cap_eff, params,
                                           collect_probs)
                self._sync_pos()
                t0 = time.perf_counter()
                args = (self.params, self.state, self._put(self.last_logits),
                        self._put(key_mat), stop_arr, self._put(stop_mask),
                        self._put(n_max), self._put(greedy))
            toks, n, logits, new_state, probs = self._dispatch(
                "decode", fn, *args)
            with self._region("decode", "wait"):
                toks = jax.block_until_ready(toks)      # the ONE host sync
            with self._region("decode", "pull"):
                toks = np.asarray(toks)
                n = np.asarray(n)
                t1 = time.perf_counter()
                self.meter.decode_time += t1 - t0
                self.meter.decode_tokens += int(n.sum())
                self.meter.decode_calls += 1
                lg = np.asarray(logits, np.float32)
                out: List[List[int]] = []
                probs_np = np.asarray(probs, np.float32) \
                    if collect_probs else None
                probs_out: List[np.ndarray] = []
                for r in rows:
                    k = int(n[r])
                    out.append([int(t) for t in toks[r, :k]])
                    if collect_probs:
                        probs_out.append(probs_np[r, :k])
                    if k > 0:
                        self.pos[r] += k
                        self.last_logits[r] = lg[r]
                self.state = dataclasses.replace(
                    new_state, pos=jnp.asarray(self.pos, jnp.int32))
            if rg is not None:
                ntok = int(n.sum())
                rg.args.update(rows=len(rows), tokens=ntok,
                               kv_bytes=self._kv_token_bytes
                               * (ntok + len(rows) * cap_eff))
        return (out, probs_out) if collect_probs else out

    # ------------------------------------------------------ prefix cache
    def kv_dims(self) -> Tuple[int, int, int]:
        """(n_layers, kv_heads, head_dim) of the attention cache — the
        page dimensions a PrefixKVStore for this engine needs."""
        ll, _, _, f = self.state.k.shape
        kh = self.model.cfg.n_kv_heads
        return ll, kh, f // kh

    def export_prefix(self, row: int, start: int, end: int
                      ) -> Tuple[jax.Array, jax.Array]:
        """Dense ``(L, end-start, kv, hd)`` K/V slices of one row's cache
        — the radix cache's insertion source.  Valid for token offsets
        the row has actually prefilled (``end <= pos[row]``)."""
        assert self._live[row], f"export from dead row {row}"
        assert 0 <= start <= end <= self.pos[row], \
            f"row {row}: export [{start}, {end}) outside prefilled " \
            f"[0, {self.pos[row]})"
        shape = (self.state.k.shape[0], end - start) + self.kv_dims()[1:]
        return (self.state.k[:, row, start:end].reshape(shape),
                self.state.v[:, row, start:end].reshape(shape))

    def load_prefix(self, row: int, k: jax.Array, v: jax.Array) -> None:
        """Seed a FRESH row's cache with ``n`` tokens of precomputed KV
        (a radix prefix-cache hit): writes ``k``/``v`` of shape
        ``(L, n, kv, hd)`` at offsets ``0..n-1`` and advances the row to
        position ``n``.  The row's ``last_logits`` stay stale — the
        caller must prefill at least one suffix token (the cache's
        block-aligned match rule guarantees one remains) before anything
        samples from the row."""
        assert self._live[row], f"load into dead row {row}"
        assert self.pos[row] == 0, \
            f"load_prefix onto non-fresh row {row} at pos {self.pos[row]}"
        ll, n = k.shape[:2]
        assert 0 < n <= self.capacity
        self.state = dataclasses.replace(
            self.state,
            k=self.state.k.at[:, row, :n].set(
                k.reshape(ll, n, -1).astype(self.state.k.dtype)),
            v=self.state.v.at[:, row, :n].set(
                v.reshape(ll, n, -1).astype(self.state.v.dtype)))
        self.pos[row] = n

    def _import_fn(self, shape: Tuple[int, int]) -> Callable:
        """One fused gather-pages-and-seed-rows program per
        (n_rows, max_chain_blocks): a whole tick's prefix-cache hits land
        in ONE device dispatch instead of a read + two writes per row."""
        fn = self._import_cache.get(shape)
        if fn is not None:
            return fn
        n_rows, nb = shape

        def imp(k_cache, v_cache, k_pages, v_pages, slots, rows):
            kg = k_pages[:, slots]            # (L, R, nb, bs, kv, hd)
            vg = v_pages[:, slots]
            ll, _, _, bs, kh, hd = kg.shape
            kg = kg.reshape(ll, n_rows, nb * bs, kh * hd)
            vg = vg.reshape(ll, n_rows, nb * bs, kh * hd)
            k_cache = k_cache.at[:, rows, :nb * bs].set(
                kg.astype(k_cache.dtype))
            v_cache = v_cache.at[:, rows, :nb * bs].set(
                vg.astype(v_cache.dtype))
            return k_cache, v_cache

        # donating the caches makes the seed an in-place page write, not
        # a full-cache copy, as in every engine program (DESIGN.md
        # §Snapshot/rollback): BatchEngine holds exactly one live state,
        # RowSnapshots carry no tensor references, and the caller
        # replaces self.state with the result immediately.
        fn = jax.jit(imp, donate_argnums=(0, 1))
        self._import_cache[shape] = fn
        return fn

    def load_prefix_pages(self, row: int, k_pages: jax.Array,
                          v_pages: jax.Array,
                          slots: Sequence[int]) -> None:
        """``load_prefix`` from a PrefixKVStore's page arrays: gather the
        cached chain's ``slots`` and seed the fresh row in one jitted
        dispatch.  Advances the row to ``len(slots) * block_size``; the
        caller still owes the suffix prefill (see ``load_prefix``)."""
        self.load_prefix_pages_rows([row], k_pages, v_pages, [slots])

    def load_prefix_pages_rows(self, rows: Sequence[int],
                               k_pages: jax.Array, v_pages: jax.Array,
                               slot_lists: Sequence[Sequence[int]]
                               ) -> None:
        """Seed EVERY row in ``rows`` from its cached chain in ONE jitted
        dispatch (the per-tick batched import: a tick admitting R cache
        hits costs one device call per engine, not R).  Ragged chains are
        padded to the longest with slot 0 — the padded blocks write
        garbage tokens past that row's position, invisible to attention
        and overwritten before ever becoming visible (the trailing-pad
        argument extend_rows already relies on)."""
        assert len(rows) == len(slot_lists)
        if not rows:
            return
        bs = k_pages.shape[2]
        max_nb = max(len(s) for s in slot_lists)
        assert max_nb > 0 and all(slot_lists), "empty chain in batched load"
        # the seed is deliberately not host-synced (it overlaps the
        # admission tick's later work): no .wait or .pull phase
        with self._region("cache_seed") as rg:
            with self._region("cache_seed", "put"):
                slot_mat = np.zeros((len(rows), max_nb), np.int32)
                for i, (row, slots) in enumerate(zip(rows, slot_lists)):
                    assert self._live[row], f"load into dead row {row}"
                    assert self.pos[row] == 0, \
                        f"load_prefix onto non-fresh row {row} at pos " \
                        f"{self.pos[row]}"
                    assert 0 < len(slots) * bs <= self.capacity
                    slot_mat[i, :len(slots)] = list(slots)
                fn = self._import_fn((len(rows), max_nb))
                args = (self.state.k, self.state.v, k_pages, v_pages,
                        self._put(slot_mat),
                        self._put(list(rows), jnp.int32))
            k, v = self._dispatch("cache_seed", fn, *args)
            self.state = dataclasses.replace(self.state, k=k, v=v)
            for row, slots in zip(rows, slot_lists):
                self.pos[row] = len(slots) * bs
            if rg is not None:
                tokens = sum(len(s) * bs for s in slot_lists)
                rg.args.update(rows=len(rows), tokens=tokens,
                               kv_bytes=2 * tokens * self._kv_token_bytes)

    # -------------------------------------------------------------- feed
    def _feed_fn(self, cap_eff: int) -> Callable:
        """One batched decode step over CHOSEN tokens (no sampling): the
        spec-decode reconcile op — feed each involved row its final
        suffix token, refreshing last_logits, in a single dispatch."""
        fn = self._feed_cache.get(cap_eff)
        if fn is not None:
            return fn
        model = self.model

        def feed(params, state, toks, active):
            old_pos = state.pos
            logits, new_state = model.decode_step(params, state,
                                                  toks[:, None],
                                                  width=cap_eff)
            # uninvolved rows fed a pad: keep their position (the pad's
            # cache write landed beyond it — masked until overwritten)
            return logits, dataclasses.replace(
                new_state, pos=jnp.where(active, old_pos + 1, old_pos))

        fn = jax.jit(feed, donate_argnums=1)
        self._feed_cache[cap_eff] = fn
        return fn

    def feed_rows(self, rows: Sequence[int],
                  tokens: Sequence[int]) -> None:
        """Append ``tokens[i]`` to row ``rows[i]`` with ONE batched decode
        step (the multi-row twin of ``Engine.decode_one``).  Used by the
        batched spec-decode reconcile: after the O(1) row truncate, the
        final suffix token is re-decoded to refresh the row's logits."""
        assert len(rows) == len(tokens)
        if not rows:
            return
        with self._region("feed") as rg:
            with self._region("feed", "put"):
                live = [i for i in range(self.batch) if self._live[i]]
                assert all(self.pos[r] < self.capacity for r in rows), \
                    "feed would write past capacity; truncate or preempt " \
                    "first"
                toks = np.full(self.batch, self.pad_id, np.int32)
                active = np.zeros(self.batch, bool)
                for r, t in zip(rows, tokens):
                    toks[r] = t
                    active[r] = True
                need = max(int(self.pos[i]) for i in live) + 1
                cap_eff = self._cap_bucket(need)
                fn = self._feed_fn(cap_eff)
                self._sync_pos()
                t0 = time.perf_counter()
                args = (self.params, self.state, self._put(toks),
                        self._put(active))
            logits, new_state = self._dispatch("feed", fn, *args)
            with self._region("feed", "wait"):
                logits = jax.block_until_ready(logits)   # the ONE host sync
            t1 = time.perf_counter()
            with self._region("feed", "pull"):
                self.meter.decode_time += t1 - t0
                self.meter.decode_tokens += len(rows)
                self.meter.decode_calls += 1
                lg = np.asarray(logits, np.float32)
                for r in rows:
                    self.pos[r] += 1
                    self.last_logits[r] = lg[r]
                self.state = dataclasses.replace(
                    new_state, pos=jnp.asarray(self.pos, jnp.int32))
            if rg is not None:
                rg.args.update(rows=len(rows), tokens=len(rows),
                               kv_bytes=self._kv_token_bytes
                               * len(rows) * (1 + cap_eff))
