#!/usr/bin/env python3
"""Readings behind a cell's limits and rate, many seeds in one process.

    python benchmarks/chip/calibrate.py --workload <name> \\
        --seeds <n> [<n> ...] --seconds <s> [--control] [--rates <r> ...]

On the chip, from the root of a checkout; not part of a benchmark run.
For each seed: build, warm up, run a window, then read the compared
numbers of ``check.py`` for the program and, with ``--control``, for the
float8 control on the same requests.  With ``--rates``, instead runs one
window per open-loop session rate and reports whether the queue grew:
the sweep that finds the knee.  One JSON line per reading on stdout.
"""

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import check  # noqa: E402
import harness  # noqa: E402
import run  # noqa: E402


def readings(cell, seed: int, seconds: float, control: bool) -> dict:
    t0 = time.perf_counter()
    rec, prog = harness.run_window(cell, seed, seconds, False, t0, None,
                                   run.log)
    out = {"seed": seed, "setup_s": rec.setup_s, "ticks": len(rec.ticks),
           "window_s": rec.window_s,
           "compiles_in_window": rec.compiles_in_window,
           "out_tok_s": harness.reader("out_tok_s")(rec),
           "memory_peak_bytes": harness.memory_peak_bytes(cell.chips)}
    out["settle_s"] = harness.settle(prog, rec,
                                     cell.serve["check_requests"])
    reqs = check.sample(rec, cell.serve["check_requests"])
    harness.free(prog)
    out["served_tokens"] = sum(r.out_tokens() for r in reqs)
    t1 = time.perf_counter()
    out.update(check.readings(rec, reqs, harness.reference_params(rec),
                              ("program", "fp8") if control else
                              ("program",)))
    out["reference_s"] = time.perf_counter() - t1
    limits = cell.serve["limits"]
    out["correct"] = {p: check.judge(out[p], limits)
                      for p in ("program", "fp8") if p in out}
    return out


def sweep(cell, seed: int, seconds: float, rate: float) -> dict:
    cell.mix = dict(cell.mix, rate_sessions_per_s=rate)
    rec, prog = harness.run_window(cell, seed, seconds, False,
                                   time.perf_counter(), None, run.log)
    out = {"rate": rate, "seed": seed, "window_s": rec.window_s,
           "queue_start": rec.snap_start.queue_depth,
           "queue_end": rec.snap_end.queue_depth,
           "due": len(rec.due_in_window()),
           "finished_per_s": len(rec.finished_in_window()) / rec.window_s}
    for m in ("ttft_p90_ms", "tpot_p90_ms", "tick_ms.bestof",
              "queue_wait_p90_ms", "prefix_hit_share", "gen_lag_p99_ms"):
        out[m] = harness.reader(m)(rec)
    harness.free(prog)
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--rates", type=float, nargs="*")
    args = ap.parse_args()
    run.log(f"[setup] compile cache {run.use_compile_cache()}")
    run.require_chips(1)
    cell = harness.load_cell(args.workload)
    for seed in args.seeds:
        harness.free(harness.Program(None, None))
        if args.rates:
            for rate in args.rates:
                print(json.dumps(sweep(cell, seed, args.seconds, rate)),
                      flush=True)
        else:
            print(json.dumps(readings(cell, seed, args.seconds,
                                      args.control)), flush=True)


if __name__ == "__main__":
    main()
