#!/usr/bin/env python3
"""The readings that a cell's limit on the logit gap is set from.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds <s>

For each seed, in one process: the cell is set up and served for a short
window at its own load, exactly as a run does; the engine is freed; then the
served tokens of the sampled requests are scored by the float32 reference
of the configuration's architecture (the program's reading) and its float8
network is put in the program's place at the same positions (the control's
reading). One JSON line per seed and rung. The benchmark's own runs never
run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(BENCH, "metrics"),
                os.path.join(os.path.dirname(BENCH), "src")]

import harness  # noqa: E402
import traffic  # noqa: E402


def readings(wl, cfg, mix, seed: int, seconds: float):
    """Serve one short window on ``seed``; yield (rung, gaps) per rung."""
    cell = harness.Cell(cfg, seed, trace=False)
    vocab = cfg["model"]["vocab_size"]
    cell.warm_up(vocab)
    plan = traffic.schedule(mix, cell.split, vocab, seed, seconds)
    out = cell.run_window(plan, seconds, drain_s=mix.get("drain_s", 0.0),
                          trace_s=0.0,
                          fill=bool(mix.get("fill_before_window")))
    samples = cell.samples(out, cfg["check"]["sample_per_rung"], seed)
    cell.release()
    key = cell.form.run_key(seed)
    for i, r in enumerate(cfg["rungs"]):
        smp = samples.get(r["name"], [])
        if smp:
            yield r["name"], cell.form.served_gaps(
                cell.arch[r["name"]], key, i, cfg["model"]["param_dtype"],
                smp, max_len=cell.prompt_len + cell.max_new,
                max_rows=cell.max_new, mode="fp8")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    wl, cfg, mix = harness.load_cell(args.workload)
    import jax
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    harness.require_chips(wl["chips"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        for rung, g in readings(wl, cfg, mix, seed, args.seconds):
            print(json.dumps({"workload": wl["name"], "seed": seed,
                              "rung": rung, **g,
                              "seconds": time.perf_counter() - t}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
