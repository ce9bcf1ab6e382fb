"""The traffic generator: deterministic per seed, the same sizes for
every seed, lengths as the mix states, prompts as the program renders."""

import statistics
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import arrivals  # noqa: E402
import harness  # noqa: E402

MIXES = sorted(p.stem for p in (HERE / "traffic").glob("*.json"))


def mix(name):
    return harness.load_json(HERE / "traffic" / f"{name}.json")


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a = arrivals.generate(mix(name), 2**31 + 99)
    b = arrivals.generate(mix(name), 2**31 + 99)
    c = arrivals.generate(mix(name), 12345)
    assert [(s.due_s, s.ops) for s in a] == [(s.due_s, s.ops) for s in b]
    assert [s.ops for s in a] != [s.ops for s in c]
    # every seed: the same multiset of sizes and gaps, in another order
    assert sorted(s.prompt_len for s in a) == sorted(s.prompt_len for s in c)
    assert a[-1].due_s == pytest.approx(c[-1].due_s)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_follow_the_mix(name):
    m = mix(name)
    specs = arrivals.generate(m, 7)
    per = m.get("samples_per_session", 1)
    assert len(specs) == m["sessions"] * per
    own = [s.prompt_len - m.get("shared_prefix_tokens", 0) for s in specs]
    p = m["prompt_tokens"]
    slack = arrivals.OP_TOKENS + arrivals.HEAD_TOKENS
    assert min(own) >= p["min"] - slack and max(own) <= p["max"] + slack
    assert abs(statistics.median(own) - p["median"]) <= 0.1 * p["median"] \
        + slack
    if m["arrival"] == "poisson":
        sessions = m["sessions"]
        span = specs[-1].due_s
        assert span == pytest.approx(sessions / m["rate_sessions_per_s"],
                                     rel=0.1)
    else:
        assert all(s.due_s == 0 for s in specs)


@pytest.mark.parametrize("name", MIXES)
def test_sessions_share_their_prompt_and_template(name):
    m = mix(name)
    specs = arrivals.generate(m, 3)
    by = {}
    for s in specs:
        by.setdefault(s.session, []).append(s.prompt())
    assert all(len({tuple(p) for p in ps}) == 1 for ps in by.values())
    shared = m.get("shared_prefix_tokens", 0)
    firsts = [ps[0] for ps in by.values()]
    if shared:
        assert len({tuple(p[:shared - 8]) for p in firsts}) == 1


def test_prompts_render_as_the_program_does():
    from repro.data.tasks import Task, question_tokens
    for s in arrivals.generate(mix(MIXES[0]), 5)[:20]:
        assert question_tokens(Task(s.start, s.ops)) == s.prompt()
