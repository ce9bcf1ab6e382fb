"""The one traffic generator: a mix file of parameters in, requests out.

A mix (``traffic/<name>.json``) states arrivals, prompt lengths, sharing
and the thinking budget; this module turns it and ``--seed`` into
requests with due times.  Every seed gets the same multiset of sizes and
gaps (quantiles of the stated distributions) in a different order, with
different token content, so that seeds change the order of the work and
not its amount.

Prompts are the chain-arithmetic questions of ``data/tasks``:
``<bos> <q> start D D`` then four tokens per operation, then
``</q> <think>``, so a prompt of ``L`` tokens carries ``(L - 7) / 4``
operations.  A shared template is a run of leading operations common to
every session; the samples of one session (best-of-N) share the whole
prompt.

Mix keys:
  arrival               "backlog" (all due at 0, queued before warm-up)
                        or "poisson" (open loop, sessions per second)
  sessions              sessions generated (a backlog larger than a run
                        can finish, or enough arrivals for the run)
  rate_sessions_per_s   poisson only
  samples_per_session   requests per session (best-of-N), default 1
  prompt_tokens         {"median", "sigma", "min", "max"}: lognormal
                        length of each session's own question
  shared_prefix_tokens  template length shared by all sessions, default 0
  token_budget          thinking-token budget of every request
  temperature           sampling temperature of every request
"""

from __future__ import annotations

import dataclasses
import math
import random
from statistics import NormalDist
from typing import Dict, List, Tuple

OPS = ("plus", "minus", "times")
# ids of the toy tokenizer's vocabulary (tokenizer/toy.py)
BOS, Q_OPEN, Q_CLOSE, THINK, START, SEMI = 1, 3, 4, 5, 20, 25
OP_IDS = {"plus": 21, "minus": 22, "times": 23}


def _digits(v: int) -> List[int]:
    return [10 + v // 10, 10 + v % 10]

HEAD_TOKENS = 7        # <bos> <q> start D D ... </q> <think>
OP_TOKENS = 4          # ; op D D


@dataclasses.dataclass
class Spec:
    """One request as generated: when it is due, its session and
    sample, and the task whose question is its prompt."""
    index: int
    session: int
    sample: int
    due_s: float
    start: int
    ops: List[Tuple[str, int]]

    @property
    def prompt_len(self) -> int:
        return HEAD_TOKENS + OP_TOKENS * len(self.ops)

    def prompt(self) -> List[int]:
        """The question's token ids, as ``data/tasks.question_tokens``
        renders them (the harness asserts that they agree)."""
        out = [BOS, Q_OPEN, START, *_digits(self.start)]
        for op, a in self.ops:
            out += [SEMI, OP_IDS[op], *_digits(a)]
        return out + [Q_CLOSE, THINK]


def _quantiles(n: int, inv_cdf) -> List[float]:
    return [inv_cdf((i + 0.5) / n) for i in range(n)]


def prompt_lengths(mix: Dict, n: int) -> List[int]:
    """The n session question lengths: lognormal quantiles, clipped."""
    p = mix["prompt_tokens"]
    mu, sigma = math.log(p["median"]), p["sigma"]
    norm = NormalDist()
    return [int(min(max(round(math.exp(mu + sigma * norm.inv_cdf(q))),
                        p["min"]), p["max"]))
            for q in _quantiles(n, lambda q: q)]


def gaps(mix: Dict, n: int) -> List[float]:
    """The n inter-arrival gaps (s): exponential quantiles, or all 0."""
    if mix["arrival"] == "backlog":
        return [0.0] * n
    rate = mix["rate_sessions_per_s"]
    return _quantiles(n, lambda q: -math.log(1.0 - q) / rate)


def _ops(rng: random.Random, n: int) -> List[Tuple[str, int]]:
    return [(rng.choice(OPS), rng.randint(0, 99)) for _ in range(n)]


def generate(mix: Dict, seed: int) -> List[Spec]:
    """Every request of the mix for ``seed``, in due order."""
    rng = random.Random(seed)
    n = mix["sessions"]
    lens = prompt_lengths(mix, n)
    gap = gaps(mix, n)
    rng.shuffle(lens)
    rng.shuffle(gap)
    start = rng.randint(0, 19)
    shared = mix.get("shared_prefix_tokens", 0)
    template = _ops(rng, round(max(shared - HEAD_TOKENS, 0) / OP_TOKENS))
    per = mix.get("samples_per_session", 1)
    out: List[Spec] = []
    t = 0.0
    for s in range(n):
        t += gap[s]
        own = _ops(rng, max(1, round(lens[s] / OP_TOKENS)))
        if not template:
            own = own[:max(1, round((lens[s] - HEAD_TOKENS) / OP_TOKENS))]
        for k in range(per):
            out.append(Spec(len(out), s, k, t, start, template + own))
    return out
