#!/usr/bin/env python3
"""Find the knee of an open-loop mix on a configuration, before a cell of
them is written: one process, one set-up, then one window per offered
rate, lowest first, each drained before the next.

    python3 bench/sweep.py --config <name> --traffic <mix> --seed <n> \
        --seconds <s> --rates 0.5,1,2,4 [--drain 60]

``bench/configs/<name>.json`` and ``bench/traffic/<mix>.json`` are read
directly; the configuration needs no entry in ``BENCHMARK.json``.

Per rate it prints the requests due, how many finished within the drain,
p90 time to first token and per-token time, and the engine's queue when the
window closed. A rate is sustained when every request due finishes and the
queue does not grow through the window. It stops at the first rate that
leaves requests unfinished. The knee, found once on the chip, fixes the
rate written in the mix file; the benchmark itself never searches.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(BENCH, "metrics"),
                os.path.join(os.path.dirname(BENCH), "src")]

import numpy as np  # noqa: E402

import harness  # noqa: E402
import traffic  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--drain", type=float, default=60.0)
    args = ap.parse_args()
    cfg = harness.load_json(os.path.join(BENCH, "configs",
                                         args.config + ".json"))
    mix = traffic.load(os.path.join(BENCH, "traffic", args.traffic + ".json"))
    import jax
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = harness.require_chips(1)
    cell = harness.Cell(cfg, args.seed, trace=False)
    vocab = cfg["model"]["vocab_size"]
    cell.warm_up(vocab)
    harness.log(f"sweep set-up {time.perf_counter() - T_START:.3f} s on "
                f"{devs[0].device_kind}")
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        m = copy.deepcopy(mix)
        m["arrival"]["rate_per_s"] = rate
        plan = traffic.schedule(m, cell.split, vocab, args.seed + i,
                                args.seconds)
        for p in plan:
            p.rid += 100_000 * (i + 1)
        out = cell.run_window(plan, args.seconds, drain_s=args.drain,
                              trace_s=0.0)
        st = list(out.stamps.values())
        done = [s for s in st if s.done is not None]
        ttft = [(s.first - s.due) * 1e3 for s in st if s.first is not None]
        tpot = [(s.done - s.first) * 1e3 / (s.n_out - 1) for s in done
                if s.n_out > 1]
        row = {"rate": rate, "due": len(st), "finished": len(done),
               "ttft_ms": sorted(round((s.first - s.due) * 1e3, 1)
                                 for s in st if s.first is not None),
               "refused": sum(s.refused for s in st),
               "ttft_p50_ms": float(np.median(ttft)) if ttft else None,
               "ttft_p90_ms": float(np.percentile(ttft, 90)) if ttft else None,
               "tpot_p90_ms": float(np.percentile(tpot, 90)) if tpot else None,
               "queued_at_close": int(cell.engine.backlog(out.t_end)),
               "drain_s": out.t_drained - out.t_end,
               "tokens_per_s": out.committed_in_window / (out.t_end - out.t0)}
        rows.append(row)
        print(json.dumps(row), flush=True)
        # empty the engine before the next rate
        while cell.engine.in_flight() or cell.engine.backlog(time.perf_counter()):
            cell.engine.step(time.perf_counter())
        cell.engine.flush_pending(time.perf_counter())
        cell.engine.done.clear()
        if len(done) < len(st):
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
