"""What decides ``correct``: served tokens and scores against the plain
reference, run once the window has closed and the program is freed.

For each request of a sample drawn from the seed (the longest finished
one always in it), two token trees are rebuilt from what the program
returned (``result.steps``, ``thinking_ids``, ``answer_ids``):

  base tree   the base model's context: prompt, thinking, answer.  Each
              drafted step the base scored hangs a branch off it: the
              step's body (when rejected, so it never entered the
              context) and the ``<score>`` probe after it.
  draft tree  the drafter's context, which differs: it holds no
              delimiter after a draft that hit the step cap, and no
              ``</think>`` closer.  Rejected drafts hang as branches.

Three numbers are compared, each the widest over the sample:

  base_gap    for every token the base produced (regenerated steps and
              the answer, through token-level spec decode), how far its
              reference logit lies below the reference's best there;
  draft_gap   the same for every token the drafter produced, under the
              drafter's reference (the base's first layers);
  score_err   |program utility - reference utility| of every scored draft.

Decoding is greedy, so a served token is the program's argmax: a gap is
rounding where the two near-tie, and an O(1) jump where the program
computed something else.  The control reads the same trees with the
reference at float8 and takes the token that precision puts first.
"""

from __future__ import annotations

import dataclasses
import random
from typing import Dict, List

import numpy as np

from harness import (DIGIT_IDS, SCORE, STEP_TOKENS, STOPS, Rec, Req,
                     reference)

NAMES = ("base_gap", "draft_gap", "score_err")


class Inconsistent(ValueError):
    """A result whose step records do not add up to its token stream:
    it says the wrong thing, so the run is not correct."""


@dataclasses.dataclass
class Tree:
    tokens: List[int] = dataclasses.field(default_factory=list)
    parent: List[int] = dataclasses.field(default_factory=list)
    pos: List[int] = dataclasses.field(default_factory=list)
    checks: List[tuple] = dataclasses.field(default_factory=list)  # (row, tok)
    scores: List[tuple] = dataclasses.field(default_factory=list)  # (row, u)
    tip: int = -1                     # last token of the main chain

    def add(self, tok: int, parent: int, pos: int) -> int:
        self.tokens.append(int(tok))
        self.parent.append(parent)
        self.pos.append(pos)
        return len(self.tokens) - 1

    def extend(self, toks, check: bool = False) -> None:
        """Append ``toks`` to the main chain; with ``check``, each is a
        served token to compare at the row that predicted it."""
        for t in toks:
            prev = self.tip
            if check:
                self.checks.append((prev, int(t)))
            self.tip = self.add(t, prev, self.pos[prev] + 1 if prev >= 0
                                else 0)

    def branch(self, toks, check: bool = False) -> int:
        """Hang ``toks`` off the tip without moving it; returns the last
        branch token (or the tip for an empty branch)."""
        node = self.tip
        for t in toks:
            if check:
                self.checks.append((node, int(t)))
            node = self.add(t, node, self.pos[node] + 1)
        return node


def trees(prompt: List[int], res, budget: int):
    """(base tree, draft tree) of one finished request."""
    base, draft = Tree(), Tree()
    base.extend(prompt)
    draft.extend(prompt)
    think = list(res.thinking_ids)
    ti = 0
    for st in res.steps:
        cap = min(STEP_TOKENS, budget - ti)
        body = list(st.tokens)
        if st.source == "base":
            ids = body
            if len(body) < cap and ti + len(body) < len(think) \
                    and think[ti + len(body)] in STOPS:
                ids = body + [think[ti + len(body)]]
            if think[ti:ti + len(ids)] != ids:
                raise Inconsistent("a base step is not in the thinking")
            base.extend(ids, check=True)
            draft.extend(ids)
            ti += len(ids)
        elif st.accepted:
            if think[ti:ti + len(body)] != body \
                    or ti + len(body) >= len(think):
                raise Inconsistent("an accepted draft is not in the "
                                   "thinking, or lacks its delimiter")
            delim = think[ti + len(body)]
            draft.extend(body + ([delim] if len(body) < cap else []),
                         check=True)
            base.extend(body)
            base.scores.append((base.branch([SCORE]), st.utility))
            base.extend([delim])
            ti += len(body) + 1
        else:
            draft.branch(body, check=True)
            if st.utility != 0.0:      # 0.0: malformed, never scored
                base.scores.append((base.branch(body + [SCORE]),
                                    st.utility))
    base.extend(think[ti:])            # the </think> closer, if any
    base.extend(res.answer_ids, check=True)
    return base, draft


def sample(rec: Rec, n: int) -> List[Req]:
    """n requests the window worked on and that have finished, drawn
    from the seed, the longest among them."""
    done = sorted(rec.checkable(), key=lambda r: r.spec.index)
    if not done:
        return []
    longest = max(done, key=lambda r: (r.out_tokens(), -r.spec.index))
    rest = [r for r in done if r is not longest]
    random.Random(rec.seed ^ 0xC4EC).shuffle(rest)
    return [longest] + rest[:max(n - 1, 0)]


def _utility(row_logits: np.ndarray) -> float:
    d = row_logits[list(DIGIT_IDS)].astype(np.float64)
    p = np.exp(d - d.max())
    p /= p.sum()
    return float(np.dot(p, np.arange(10)))


def _read(ref, params, dims, tree: Tree, n_layers, precisions):
    """Per precision: (gaps of the checked tokens or of the control's
    own first choice, utility errors), all against the f32 reference."""
    out = {}
    rows = [r for r, _ in tree.checks] + [r for r, _ in tree.scores]
    if not rows:
        return {p: ([], []) for p in precisions}
    args = (np.asarray(tree.tokens), np.asarray(tree.parent),
            np.asarray(tree.pos), np.asarray(rows))
    want = ref.logits(params, dims, *args, n_layers=n_layers)
    nc = len(tree.checks)
    best = want[:nc].max(-1)
    u_ref = [_utility(want[nc + i]) for i in range(len(tree.scores))]
    for p in precisions:
        if p == "program":
            toks = np.asarray([t for _, t in tree.checks], np.int64)
            u = [u for _, u in tree.scores]
        else:
            got = ref.logits(params, dims, *args, n_layers=n_layers,
                             precision=p)
            toks = got[:nc].argmax(-1)
            u = [_utility(got[nc + i]) for i in range(len(tree.scores))]
        gaps = (best - want[np.arange(nc), toks]).tolist() if nc else []
        out[p] = (gaps, [abs(a - b) for a, b in zip(u, u_ref)])
    return out


def readings(rec: Rec, reqs: List[Req], params,
             precisions=("program",)) -> Dict[str, Dict[str, float]]:
    """The compared numbers for the program and for each control
    precision, over ``reqs``; ``params`` is the reference's own draw."""
    ref = reference(rec.cell.config)
    dims = rec.dims
    n_draft = rec.cell.config["drafter"]["num_hidden_layers"]
    budget = rec.cell.mix["token_budget"]
    acc = {p: {n: [] for n in NAMES} for p in precisions}
    for r in reqs:
        base, draft = trees(r.spec.prompt(), r.handle.result, budget)
        for tree, n_layers, gap in ((base, None, "base_gap"),
                                    (draft, n_draft, "draft_gap")):
            for p, (gaps, errs) in _read(ref, params, dims, tree, n_layers,
                                         precisions).items():
                acc[p][gap] += gaps
                acc[p]["score_err"] += errs
    return {p: {n: (max(v) if v else 0.0) for n, v in d.items()}
            for p, d in acc.items()}


def judge(values: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(values[n] <= limits[n] for n in NAMES)


def check_lines(values: Dict[str, float], limits: Dict[str, float]
                ) -> List[str]:
    return [f"check {n} {values[n]!r} limit {limits[n]!r}" for n in NAMES]


def as_key(values: Dict[str, float], limits: Dict[str, float]
           ) -> Dict[str, Dict[str, float]]:
    return {n: {"value": values[n], "limit": limits[n]} for n in NAMES}
