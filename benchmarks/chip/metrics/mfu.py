"""mfu: the forward FLOPs vanilla base decoding would spend on the output
tokens committed in the window (flops.py, from the shapes), over the
window times the chip's bf16 peak (peaks.py)."""

import flops
import peaks


def read(rec):
    total = 0
    for r in rec.reqs:
        if r.handle is None:
            continue
        a = rec.tokens_at(r, rec.snap_start, rec.t_start)
        b = rec.tokens_at(r, rec.snap_end, rec.t_end)
        p = r.spec.prompt_len
        total += flops.tokens_flops(rec.dims, p + round(a), p + round(b))
    if not total:
        return None
    return 100.0 * total / (rec.window_s
                            * peaks.peak(rec.device_kind)["bf16_flops"])
