"""prefix_hit_share: prompt tokens served from the radix prefix cache
over prompt tokens, of the requests admitted in the window
(``Request.cache_hit_tokens`` / ``prompt_tokens``)."""


def read(rec):
    adm = [r.handle for r in rec.reqs if r.handle is not None
           and r.handle.admitted_at is not None
           and rec.t_start <= r.handle.admitted_at < rec.t_end]
    total = sum(h.prompt_tokens for h in adm)
    return 100.0 * sum(h.cache_hit_tokens for h in adm) / total \
        if total else None
