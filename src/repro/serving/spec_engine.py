"""Hierarchical speculation in serving: *batched* token-level speculative
decoding over the continuous-batching engines (SpecReason+Decode, §4.2).

The sequential ``core.spec_decode`` routine drives two single-request
sessions; under concurrency every request would pay its own draft/verify
dispatches.  ``BatchSpecEngine`` runs ONE spec-decode round for every
in-flight row of a ``BatchEngine`` pair per iteration:

  1. **draft proposal** — one fused multi-sequence decode call proposes
     up to gamma tokens per row (per-row budgets, per-row PRNG keys,
     per-row proposal distributions collected on-device);
  2. **verification** — one base-model prefill over every row's chunk
     (``extend_rows(want_logits=True)``) yields the gamma+1 usable
     distributions per row.  On the paged TPU path this forward's
     attention is ``kernels.paged_append_attention``: span queries over
     scalar-prefetched block tables plus the in-flight draft K/V, causal
     within the appended span (validated in interpret mode against the
     gather-then-dense oracle and the dense prefill kernel);
  3. **acceptance** — ONE fused batched rejection-sampling/acceptance
     program (``core.spec_decode.acceptance_step`` — the same program the
     sequential routine runs with batch 1, so batched output is
     bit-identical per row to the sequential routine; tested);
  4. **reconcile** — rejected suffixes roll back with an O(1) per-row
     position truncate plus per-row block-table truncation in the paged
     pool (``PagedSeq.truncate`` — no copy, orphaned speculation blocks
     freed), then one batched ``feed_rows`` call per engine re-decodes
     each row's final suffix token (exactly the sequential reconcile,
     batched).

Rows finish at different rounds (stop hit, budget, capacity); finished
rows drop out and the round batch shrinks.  Block accounting and
preemption stay with the scheduler through a :class:`SpecLedger`: the
engine announces every in-flight grow (gamma draft tokens per row live in
the cache during verification — the admission headroom must cover them)
and every truncation; a ledger that preempts a row mid-round marks it
dead via ``alive`` and the engine drops it cleanly (regression-tested).

The draft engine's context is kept token-synchronized with the base
(every emitted token is fed to both), so the scheduler's later small-model
drafting resumes from a coherent prefix — same contract as the sequential
routine."""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.spec_decode import (SpecDecodeStats, acceptance_step,
                                build_stop_arrays)
from ..sampling.sample import SamplingParams
from .batch_engine import BatchEngine
from .telemetry import NO_REGION, TRACK_SPEC


@dataclasses.dataclass
class SpecRow:
    """One row's spec-decode work order: engine rows, token budget, stop
    set, PRNG key (the chain `spec_decode` would receive), greedy
    override."""
    base_row: int
    draft_row: int
    budget: int
    stop_ids: Sequence[int]
    key: jax.Array
    greedy: bool = False


class SpecLedger:
    """Block-accounting callbacks the scheduler supplies.  The default is
    a no-op ledger (standalone use: dense caches, no pool).

    ``grow``/``truncate`` report the *base*/"draft" context length changes
    as they happen — including the transient gamma in-flight draft tokens
    a verification pass writes; ``grow`` may preempt rows (pool pressure),
    which the engine observes through ``alive``.

    Shared-prefix contract: with the radix prefix cache on, a row's block
    table may hold blocks shared with the cache (and with the other
    best-of-N samples of the same prompt).  A ``truncate`` landing inside
    such a block copy-on-writes the kept partial tail
    (``PagedSeq.truncate`` emits the ``(src, dst)`` page copy), so the
    spec rollback never leaves a row with writable claim on slots its
    co-owners read; a ledger over dense rows drops the copy list (there
    is no physical page to copy), a fully-paged ledger must apply it."""

    def alive(self, i: int) -> bool:
        return True

    def grow(self, i: int, which: str, n_tokens: int) -> None:
        pass

    def truncate(self, i: int, which: str, length: int) -> None:
        pass


class BatchSpecEngine:
    """Batched token-level speculative decoding across BatchEngine rows.

    Per round, for every still-active row: ONE fused gamma-token draft
    proposal (draft engine), ONE base verification prefill over
    ``[pending] + chunk`` (deferred-feed layout), ONE fused batched
    acceptance program — rejected suffixes roll back by O(1) row
    truncate plus the ledger's block-table truncation.  Contract: each
    row's emitted tokens are bit-identical to the sequential
    ``core.spec_decode`` routine given the same key (greedy AND sampled,
    ragged budgets/stop sets, rows finishing at different rounds —
    tested in tests/test_spec_engine.py), and the engine owns BOTH
    engines' rows for the duration (the draft context is kept
    token-synchronized with the base)."""

    def __init__(self, base_be: BatchEngine, draft_be: BatchEngine,
                 gamma: int = 4):
        if gamma < 1:
            raise ValueError("gamma must be >= 1")
        if base_be.tp is not draft_be.tp:
            # one mesh for the whole spec round: a draft proposal feeding
            # a base verification must not hop between device sets (and a
            # half-sharded pair would silently break the per-row
            # bit-identity contract against the sequential routine)
            raise ValueError(
                "base and draft engines must share one TPContext "
                "(both None, or the same object)")
        self.base_be = base_be
        self.draft_be = draft_be
        self.gamma = gamma

    @property
    def tp_size(self) -> int:
        """Tensor-parallel degree of the engine pair (1 = unsharded)."""
        return 1 if self.base_be.tp is None else self.base_be.tp.tp_size

    def decode_rows(self, items: Sequence[SpecRow], params: SamplingParams,
                    ledger: Optional[SpecLedger] = None,
                    gamma: Optional[int] = None,
                    on_round: Optional[
                        Callable[[int, float, float,
                                  List[Tuple[int, int, int]]], None]] = None
                    ) -> Tuple[List[List[int]], List[SpecDecodeStats]]:
        """Run batched speculative decoding until every row hits its stop
        or budget.  Returns (emitted ids per row — bit-identical to the
        sequential ``spec_decode`` with the same key — and per-row
        SpecDecodeStats).  Rows the ledger preempts mid-flight keep their
        partial output (the caller requeues them anyway).  ``gamma``
        overrides the engine's configured draft length for THIS call —
        the degradation ladder's shrink-gamma rung (greedy outputs are
        gamma-invariant; sampled outputs are not bitwise, same as any
        gamma change).  ``on_round`` is the telemetry hook: after each
        round it receives ``(round_idx, t0, t1, infos)`` with ``infos``
        one ``(item_idx, proposed, accepted)`` per row the round judged
        (wall-clock bracket in ``time.perf_counter()`` seconds; pure
        observation — it must not touch engine state)."""
        ledger = ledger or SpecLedger()
        n = len(items)
        assert n <= self.base_be.batch
        out: List[List[int]] = [[] for _ in items]
        stats = [SpecDecodeStats() for _ in items]
        done = [False] * n
        keys: List[np.ndarray] = [np.asarray(it.key, np.uint32)
                                  for it in items]
        # deferred feed: each round's final suffix token stays pending —
        # its base logits ride the NEXT round's verification prefill
        # ([pending] + chunk); one base decode per ROW (not per round)
        # commits the last pending token when the row finishes
        pending: List[Optional[int]] = [None] * n
        stop_arr, stop_mask_items = build_stop_arrays(
            [it.stop_ids for it in items])
        gam = self.gamma if gamma is None else gamma
        if gam < 1:
            raise ValueError("gamma must be >= 1")
        if gam > self.gamma:
            raise ValueError("per-call gamma above the configured gamma "
                             "would exceed the admission headroom")
        rounds = 0

        while True:
            t_round0 = time.perf_counter() if on_round is not None else 0.0
            round_info: List[Tuple[int, int, int]] = []
            active = [i for i in range(n)
                      if not done[i] and ledger.alive(i)
                      and items[i].budget > len(out[i])]
            if not active and not any(
                    pending[i] is not None and ledger.alive(i)
                    for i in range(n)):
                break
            with self._region("spec.round"):
                g_want = {i: min(gam, items[i].budget - len(out[i]))
                          for i in active}

                # -- 1) one fused draft proposal for every active row
                with self._region("spec.draft"):
                    b_snap = {i: int(self.base_be.pos[items[i].base_row])
                              for i in active}
                    d_snap = {i: int(self.draft_be.pos[items[i].draft_row])
                              for i in active}
                    if active:
                        douts, dprobs = self.draft_be.generate_rows(
                            [items[i].draft_row for i in active],
                            [g_want[i] for i in active], [], params,
                            keys=[jnp.asarray(keys[i]) for i in active],
                            greedy_rows=[items[i].greedy for i in active],
                            stop_ids_rows=[[] for _ in active],
                            collect_probs=True)
                    else:
                        douts, dprobs = [], []
                    # no separate key-advance dispatch: acceptance_step
                    # performs the post-draft split internally from the
                    # same keys
                    chunks = {i: ids for i, ids in zip(active, douts)}
                    probs = {i: p for i, p in zip(active, dprobs)}
                    for i in active:
                        if not chunks[i]:
                            done[i] = True   # capacity exhausted: stop clean
                        else:
                            ledger.grow(i, "draft", len(chunks[i]))
                verify = [i for i in active if chunks[i] and ledger.alive(i)]

                if verify:
                    # -- 2) one base verification prefill: [pending] +
                    # chunk per row (the pending token's decode rides the
                    # prefill)
                    with self._region("spec.verify"):
                        prev = {i: self.base_be.last_logits[
                                    items[i].base_row].copy()
                                for i in verify if pending[i] is None}
                        ext = {i: ([pending[i]] if pending[i] is not None
                                   else []) + chunks[i] for i in verify}
                        all_l = self.base_be.extend_rows(
                            [items[i].base_row for i in verify],
                            [ext[i] for i in verify], want_logits=True)
                        chunk_l = {i: lg for i, lg in zip(verify, all_l)}
                        for i in verify:
                            ledger.grow(i, "base", len(ext[i]))
                judge = [i for i in verify if ledger.alive(i)]

                if judge:
                    # -- 3) the fused batched acceptance program (item i
                    # at slot i)
                    with self._region("spec.stage"):
                        big = self.base_be.batch
                        vocab = self.base_be.model.cfg.vocab_size
                        toks = np.zeros((big, gam), np.int32)
                        qprobs = np.zeros((big, gam, vocab), np.float32)
                        logits = np.zeros((big, gam, vocab), np.float32)
                        bonus = np.zeros((big, vocab), np.float32)
                        g_arr = np.zeros(big, np.int32)
                        key_mat = np.zeros((big, 2), np.uint32)
                        greedy = np.zeros(big, bool)
                        stop_mask = np.zeros((big, stop_arr.shape[0]), bool)
                        for i in judge:
                            ga = len(chunks[i])
                            p = 1 if pending[i] is not None else 0
                            toks[i, :ga] = chunks[i]
                            qprobs[i, :ga] = probs[i]
                            if p:
                                logits[i, :ga] = chunk_l[i][:ga]
                            else:
                                logits[i, 0] = prev[i]
                                if ga > 1:
                                    logits[i, 1:ga] = chunk_l[i][:ga - 1]
                            bonus[i] = chunk_l[i][p + ga - 1]
                            g_arr[i] = ga
                            key_mat[i] = keys[i]
                            greedy[i] = items[i].greedy
                            stop_mask[i] = stop_mask_items[i]
                        acc_args = (
                            jnp.asarray(toks), jnp.asarray(qprobs),
                            jnp.asarray(logits), jnp.asarray(bonus),
                            jnp.asarray(g_arr), jnp.asarray(key_mat),
                            jnp.asarray(stop_arr), jnp.asarray(stop_mask),
                            jnp.asarray(greedy), params)
                    with self._region("spec.accept") as rg:
                        suffix, m, n_acc, hit_stop, new_keys = \
                            self._accept(acc_args)
                        if rg is not None:
                            rg.args.update(rows=len(judge), gamma=gam)

                    # -- 4) reconcile: O(1) truncate + block-table
                    # truncation.  The base cache holds [pending] + chunk
                    # at the speculated positions and sfx[:-1] is a
                    # prefix of the chunk — keep p + m - 1 tokens, the new
                    # final suffix token becomes the pending one.  The
                    # draft context reconciles eagerly (ONE batched
                    # feed): the next proposal conditions on it.
                    with self._region("spec.reconcile"):
                        dfeed: List[Tuple[int, int]] = []   # (item, token)
                        for i in judge:
                            if not ledger.alive(i):
                                # an earlier row's grow preempted this
                                # one: its engine rows are freed — do not
                                # touch them
                                continue
                            ga, mi = len(chunks[i]), int(m[i])
                            p = 1 if pending[i] is not None else 0
                            sfx = [int(t) for t in suffix[i, :mi]]
                            out[i] += sfx
                            keys[i] = new_keys[i]
                            stats[i].proposed += ga
                            stats[i].accepted += int(n_acc[i])
                            stats[i].rounds += 1
                            if on_round is not None:
                                round_info.append((i, ga, int(n_acc[i])))
                            meter = self.base_be.meter
                            meter.spec_rounds += 1
                            meter.spec_proposed += ga
                            meter.spec_accepted += int(n_acc[i])
                            new_pos = b_snap[i] + p + mi - 1
                            self.base_be.truncate_row(items[i].base_row,
                                                      new_pos)
                            ledger.truncate(i, "base", new_pos)
                            pending[i] = sfx[-1]
                            self.draft_be.truncate_row(items[i].draft_row,
                                                       d_snap[i] + mi - 1)
                            ledger.truncate(i, "draft", d_snap[i] + mi - 1)
                            ledger.grow(i, "draft", 1)
                            if bool(hit_stop[i]) \
                                    or len(out[i]) >= items[i].budget:
                                done[i] = True
                            dfeed.append((i, sfx[-1]))
                        dfeed = [(i, t) for i, t in dfeed if ledger.alive(i)]
                        if dfeed:
                            self.draft_be.feed_rows(
                                [items[i].draft_row for i, _ in dfeed],
                                [t for _, t in dfeed])

            # -- 5) finish-feed: rows that just finished commit their
            # pending token with ONE batched base decode (refreshing the
            # row's last_logits for whatever the scheduler does next)
            fin = [i for i in range(n)
                   if done[i] and pending[i] is not None
                   and ledger.alive(i)]
            if fin:
                with self._region("spec.finish_feed"):
                    for i in fin:
                        ledger.grow(i, "base", 1)
                    fin = [i for i in fin if ledger.alive(i)]
                    if fin:
                        self.base_be.feed_rows(
                            [items[i].base_row for i in fin],
                            [pending[i] for i in fin])
                        for i in fin:
                            pending[i] = None
            if on_round is not None and round_info:
                on_round(rounds, t_round0, time.perf_counter(),
                         round_info)
            rounds += 1
        return out, stats

    def _region(self, name: str):
        """A tracer region on the ``spec`` track; the shared no-op
        context when tracing is off."""
        tr = self.base_be.tracer
        return NO_REGION if tr is None else tr.region(TRACK_SPEC, name)

    def _accept(self, acc_args: tuple) -> List[np.ndarray]:
        """Run the acceptance program on staged inputs and bring its
        verdicts to the host (the reconcile needs them there): suffix,
        m, n_accepted, hit_stop and the advanced keys."""
        with self._region("spec.accept.dispatch"):
            # the one jitted program this engine calls directly: the
            # compile sentinel covers it the same way the BatchEngine
            # dispatches are covered
            cw = self.base_be.compile_watch
            if cw is not None:
                cw.observe(self.base_be.name, "accept_prog",
                           acceptance_step, acc_args)
            res = acceptance_step(*acc_args)
        with self._region("spec.accept.wait"):
            res = jax.block_until_ready(res)
        with self._region("spec.accept.pull"):
            return [np.asarray(x) for x in res]
