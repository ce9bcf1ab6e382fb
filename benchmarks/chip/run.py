#!/usr/bin/env python3
"""Chip benchmark: one cell, one run, one result line.

    python benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

From the root of a checkout.  Builds the cell named in BENCHMARK.json
from seeded weights, warms it up with its own traffic (set-up), drives
``ContinuousScheduler.tick`` for ``--seconds`` (the window), then checks
a sample of the requests the window worked on against the plain reference
(not counted in set-up or the window; requests the window worked on
that are still in flight at its close are driven on to their answers
first, untimed).  ``--trace 1`` also profiles the
window and reports the cell's per-layer metrics instead of its
end-to-end ones.  The last stdout line is one JSON object; the last
stderr lines are the compared numbers beside their limits.  With no
accelerator, or fewer chips than the cell asks for, it exits non-zero
and prints no result.
"""

import time

T_PROC = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import jax  # noqa: E402

import check  # noqa: E402
import harness  # noqa: E402
import peaks  # noqa: E402
import xplane  # noqa: E402


def use_compile_cache() -> str:
    """JAX's persistent compilation cache: the directory the environment
    names, else a fixed one in the checkout; every program is cached,
    however short its compile, so that a warm run compiles nothing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def require_chips(n: int) -> dict:
    dev = harness.device_info()
    if dev["platform"] == "cpu":
        log(f"no accelerator: JAX found only {dev['platform']}")
        sys.exit(2)
    if dev["count"] < n:
        log(f"the cell asks for {n} chips, JAX found {dev['count']}")
        sys.exit(3)
    return dev


def metrics(rec: harness.Rec, entries) -> dict:
    out = {}
    for m in entries:
        v = harness.reader(m["name"])(rec)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool,
        t_proc: float, require_chip: bool = True) -> dict:
    """One run of ``cell``; returns the result object."""
    dev = require_chips(cell.chips) if require_chip \
        else harness.device_info()
    if require_chip:
        peaks.peak(dev["kind"])          # an unknown chip is an error
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        rec, prog = harness.run_window(cell, seed, seconds, trace, t_proc,
                                       tdir, log)
        if trace:
            rec.trace = xplane.reduce(xplane.planes(xplane.find(tdir)))
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)
    dev["memory_peak_bytes"] = harness.memory_peak_bytes(cell.chips)
    log(f"[window] {rec.window_s:.3f} s, {len(rec.ticks)} ticks, "
        f"{len(rec.finished_in_window())} finished, compiles in window "
        f"{rec.compiles_in_window}, traces in window "
        f"{rec.traces_in_window}, set-up {rec.setup_s:.3f} s, "
        f"tokens/record {rec.tokens_per_record():.4f}")
    result = {
        "correct": False,
        "attempted": len(rec.attempted()),
        "failed": len(rec.failed()),
        "metrics": metrics(rec, cell.per_layer if trace else cell.end_to_end),
        "device": dev,
    }
    if trace and rec.trace is not None:
        dev["busy_s"] = rec.trace["busy_s"]
        dev["window_s"] = rec.trace["window_s"]
        result["breakdown"] = {"device_ops": rec.trace["device_ops"],
                               "idle_gaps": rec.trace["idle_gaps"]}
    # the check: after the window, with the program's state freed and the
    # reference's weights drawn anew from the seed
    waited = harness.settle(prog, rec, cell.serve["check_requests"])
    log(f"[check] waited {waited:.1f} s after the window for "
        f"{len(rec.checkable())} finished requests of the window's "
        f"{len(rec.attempted())}")
    sample = check.sample(rec, cell.serve["check_requests"])
    harness.free(prog)
    limits = cell.serve["limits"]
    t0 = time.perf_counter()
    if sample:
        try:
            values = check.readings(
                rec, sample, harness.reference_params(rec))[
                "program"]
            result["correct"] = check.judge(values, limits)
        except check.Inconsistent as e:
            values = {n: None for n in check.NAMES}
            log(f"check: {e}")
    else:
        values = {n: None for n in check.NAMES}
        log("check: no request of the window finished to compare")
    log(f"[check] {len(sample)} requests, "
        f"{sum(r.out_tokens() for r in sample)} served tokens, reference "
        f"{time.perf_counter() - t0:.1f} s")
    for line in check.check_lines(values, limits):
        log(line)
    result["check"] = check.as_key(values, limits)
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    log(f"[setup] compile cache {use_compile_cache()}")
    cell = harness.load_cell(args.workload, ROOT)
    result = run(cell, args.seed, args.seconds, bool(args.trace), T_PROC)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
