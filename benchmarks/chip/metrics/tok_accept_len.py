"""tok_accept_len: mean draft tokens accepted per token-level spec-decode
round (``result.spec_stats``), over every request finished by the
window's end: warm-up finishes count too, so that a window in which no
row happens to finish still reads."""


def read(rec):
    done = [r for r in rec.reqs if r.finished_by(rec.t_end)
            and r.handle.status == "ok"]
    rounds = sum(r.handle.result.spec_stats.rounds for r in done)
    if not rounds:
        return None
    return sum(r.handle.result.spec_stats.accepted for r in done) / rounds
